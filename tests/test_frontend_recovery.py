"""Deterministic crash recovery: a FaultInjector killing the engine
mid-trace must leave every non-rejected request token-for-token
identical to an unfaulted run — for the slotted-KV family (gemma3 gqa)
AND a recurrent family (rwkv6), whose per-slot recurrence cannot be
snapshotted from a KV cache and is instead rebuilt by replaying
prompt + committed tokens.

The contract under test: a failed engine step never commits (InjectedFault
fires before the dispatch; the NaN health bit trips before commit), the
frontend re-enqueues in-flight work as prompt+emitted with reduced
max_new_tokens, and greedy decode makes the continuation exact.
"""

import jax
import numpy as np
import pytest

import repro.configs as C
from repro.launch.mesh import make_local_mesh
from repro.launch.serve import merge_model
from repro.models.lm import LM
from repro.runtime import FaultInjector, InjectedFault
from repro.serving import (ContinuousEngine, EngineCorrupted, RequestStatus,
                           ServingFrontend, make_trace)


@pytest.fixture(scope="module")
def served_gqa():
    cfg = C.reduced("gemma3-1b")
    lm = LM(cfg)
    merged = merge_model(lm.init(jax.random.PRNGKey(0)), cfg.quant)
    return cfg, lm, merged


@pytest.fixture(scope="module")
def served_rwkv():
    cfg = C.reduced("rwkv6-7b")
    lm = LM(cfg)
    merged = merge_model(lm.init(jax.random.PRNGKey(0)), cfg.quant)
    return cfg, lm, merged


def _drain(served, *, injector=None, n_req=6, slots=2, **fe_kw):
    """Run a fixed mixed trace through a frontend; return ({rid: tokens}
    of FINISHED tickets, frontend)."""
    cfg, lm, merged = served
    trace = make_trace(n_req, cfg.vocab, seed=3,
                       prompt_lens=(3, 5, 8), gen_lens=(4, 9, 6))
    mesh = make_local_mesh()
    with jax.set_mesh(mesh):
        fe = ServingFrontend(lm, merged, n_slots=slots, max_len=24,
                             prefill_chunk=4, decode_burst=2,
                             queue_cap=n_req, injector=injector, **fe_kw)
        for r in trace:
            fe.submit(r.prompt, r.max_new_tokens, eos_id=r.eos_id, rid=r.rid)
        fe.run_until_drained()
    out = {t.rid: list(t.tokens) for t in fe.tickets.values()
           if t.status is RequestStatus.FINISHED}
    return out, fe


def _assert_recovered_identical(served, injector, *, want_kind):
    clean, _ = _drain(served)
    faulted, fe = _drain(served, injector=injector)
    assert fe.n_recoveries >= 1, "fault never fired"
    assert want_kind in {k for _, k in injector.log}
    assert faulted == clean, "recovery is not token-identical"
    assert all(t.status is RequestStatus.FINISHED
               for t in fe.tickets.values())
    # recovered tickets carry their rebuild count
    assert any(t.n_recoveries >= 1 for t in fe.tickets.values())


# ---------------------------------------------------------------------------
# recovery equivalence (the acceptance gate): gqa AND recurrent
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_crash_recovery_token_identical_gqa(served_gqa):
    """Engine killed at a seeded mid-trace dispatch: every request's
    stream matches the unfaulted run exactly (slotted-KV family)."""
    _assert_recovered_identical(
        served_gqa, FaultInjector(seed=0, crash_steps=(5,)),
        want_kind="crash")


@pytest.mark.slow
def test_crash_recovery_token_identical_recurrent(served_rwkv):
    """Same gate for a recurrent family: the per-slot RWKV6 recurrence is
    rebuilt by prompt+emitted replay, not cache snapshot, and must still
    be exact."""
    _assert_recovered_identical(
        served_rwkv, FaultInjector(seed=0, crash_steps=(5,)),
        want_kind="crash")


@pytest.mark.slow
def test_nan_corruption_recovery_token_identical_gqa(served_gqa):
    """NaN-poisoned decode state trips the in-graph health bit BEFORE the
    dispatch commits; the rebuilt engine continues token-identically."""
    _assert_recovered_identical(
        served_gqa, FaultInjector(seed=0, nan_steps=(5,)),
        want_kind="nan")


@pytest.mark.slow
def test_nan_corruption_recovery_token_identical_recurrent(served_rwkv):
    _assert_recovered_identical(
        served_rwkv, FaultInjector(seed=0, nan_steps=(5,)),
        want_kind="nan")


@pytest.mark.slow
def test_repeated_crashes_still_token_identical(served_gqa):
    """Several distinct crash points in one trace: each recovery replays
    from committed state only, so even crash->recover->crash chains stay
    exact."""
    clean, _ = _drain(served_gqa)
    inj = FaultInjector(seed=0, crash_steps=(3, 9, 14))
    faulted, fe = _drain(served_gqa, injector=inj)
    assert fe.n_recoveries == 3
    assert faulted == clean


@pytest.mark.slow
def test_straggler_injection_changes_latency_not_tokens(served_gqa):
    """Injected tail latency is an SLO problem, not a correctness one."""
    clean, _ = _drain(served_gqa)
    slept = []
    inj = FaultInjector(seed=0, straggle_steps=(2, 4, 6),
                        straggle_s=0.003, sleep=lambda s: slept.append(s))
    faulted, fe = _drain(served_gqa, injector=inj)
    assert faulted == clean
    assert fe.n_recoveries == 0
    assert slept == [0.003] * 3


# ---------------------------------------------------------------------------
# fast-lane smoke + unit-level recovery contracts
# ---------------------------------------------------------------------------


def test_recovery_smoke_single_crash(served_gqa):
    """NOT slow: one tiny request, one seeded crash — recovery happens
    and the request still finishes with the full token budget."""
    cfg, lm, merged = served_gqa
    mesh = make_local_mesh()
    with jax.set_mesh(mesh):
        fe = ServingFrontend(lm, merged, n_slots=1, max_len=12,
                             prefill_chunk=4, decode_burst=2,
                             injector=FaultInjector(seed=0, crash_steps=(1,)))
        t = fe.submit(np.array([5, 6, 7], np.int32), 5)
        fe.run_until_drained()
    assert fe.n_recoveries == 1
    assert t.status is RequestStatus.FINISHED
    assert len(t.tokens) == 5
    assert t.n_recoveries == 1
    assert fe.fault_log and "InjectedFault" in fe.fault_log[0][1]


def test_recovery_cap_goes_fatal_and_rejects(served_gqa):
    """Past max_recoveries the frontend fails loudly: live tickets become
    FAILED with the cause, and later submissions are REJECTED."""
    cfg, lm, merged = served_gqa
    mesh = make_local_mesh()
    with jax.set_mesh(mesh):
        fe = ServingFrontend(lm, merged, n_slots=1, max_len=12,
                             prefill_chunk=4, decode_burst=2,
                             max_recoveries=2,
                             injector=FaultInjector(seed=0, p_crash=1.0))
        t = fe.submit(np.array([5, 6, 7], np.int32), 5)
        fe.run_until_drained()
        late = fe.submit(np.array([5], np.int32), 2)
    assert t.status is RequestStatus.FAILED
    assert "unrecoverable" in t.error
    assert fe.fatal is not None
    assert late.status is RequestStatus.REJECTED
    assert "failed" in late.error


def test_failed_step_commits_nothing(served_gqa):
    """The invariant recovery rests on: a crashing dispatch leaves the
    scheduler's emitted streams exactly as they were."""
    cfg, lm, merged = served_gqa
    mesh = make_local_mesh()
    with jax.set_mesh(mesh):
        eng = ContinuousEngine(lm, merged, n_slots=1, max_len=12,
                               prefill_chunk=4, decode_burst=2,
                               step_hook=FaultInjector(seed=0,
                                                       crash_steps=(2,)))
        eng.submit(np.array([5, 6, 7], np.int32), 6, rid=0)
        eng.step_once()                       # 0: prefill
        eng.step_once()                       # 1: decode burst commits
        before = list(eng.sched.slots[0].emitted)
        assert before
        with pytest.raises(InjectedFault):
            eng.step_once()                   # 2: crash pre-dispatch
        assert list(eng.sched.slots[0].emitted) == before


def test_poisoned_cache_raises_before_commit(served_gqa):
    """engine.poison_cache() -> next dispatch's in-graph health bit trips
    (EngineCorrupted) and nothing commits from that dispatch."""
    cfg, lm, merged = served_gqa
    mesh = make_local_mesh()
    with jax.set_mesh(mesh):
        eng = ContinuousEngine(lm, merged, n_slots=1, max_len=12,
                               prefill_chunk=4, decode_burst=2)
        eng.submit(np.array([5, 6, 7], np.int32), 6, rid=0)
        eng.step_once()
        eng.step_once()
        before = list(eng.sched.slots[0].emitted)
        eng.poison_cache()
        with pytest.raises(EngineCorrupted):
            eng.step_once()
        assert list(eng.sched.slots[0].emitted) == before


def test_engine_counters_survive_a_rebuild(served_gqa):
    """``engine_stats`` sums every EngineStats field over the engines a
    recovery discards and the live one, the counters added for tracing
    among them."""
    import dataclasses

    cfg, lm, merged = served_gqa
    mesh = make_local_mesh()
    with jax.set_mesh(mesh):
        fe = ServingFrontend(lm, merged, n_slots=1, max_len=16,
                             prefill_chunk=4, decode_burst=2,
                             injector=FaultInjector(seed=0, crash_steps=(3,)))
        t = fe.submit(np.array([5, 6, 7], np.int32), 8)
        fe.run_until_drained()
    assert fe.n_recoveries == 1 and t.status is RequestStatus.FINISHED
    base, live, total = fe._stats_base, fe.engine.stats, fe.engine_stats
    assert base.forward_passes > 0 and base.dispatches > 0
    assert base.host_s > 0 and base.sync_s > 0
    for f in dataclasses.fields(total):
        assert getattr(total, f.name) == pytest.approx(
            getattr(base, f.name) + getattr(live, f.name)), f.name
