"""Continuous-batching engine: token-for-token equivalence with the
static per-request path, per-request termination (EOS / max-len), slot
eviction + refill, and host-side scheduler bookkeeping."""

import jax
import numpy as np
import pytest

import repro.configs as C
from repro.launch.mesh import make_local_mesh
from repro.launch.serve import merge_model, generate_scan
from repro.models.lm import LM
from repro.serving import ContinuousEngine, Request, Scheduler, make_trace


@pytest.fixture(scope="module")
def served():
    cfg = C.reduced("gemma3-1b")
    lm = LM(cfg)
    merged = merge_model(lm.init(jax.random.PRNGKey(0)), cfg.quant)
    return cfg, lm, merged


def _reference(lm, merged, req, gen_len=None):
    """One request alone through the static prefill+scan path."""
    gen_len = req.max_new_tokens if gen_len is None else gen_len
    mesh = make_local_mesh()
    with jax.set_mesh(mesh):
        toks, _ = generate_scan(lm, mesh, merged, req.prompt[None, :],
                                gen_len, len(req.prompt) + gen_len)
    return [int(t) for t in toks[0]]


# ---------------------------------------------------------------------------
# equivalence
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_engine_matches_per_request_scan_on_mixed_trace(served):
    """The tentpole gate: a mixed-length trace with more requests than
    slots (so eviction + refill and chunked prefill all trigger) emits
    per-request token streams identical to running each request alone
    through ``generate_scan``."""
    cfg, lm, merged = served
    trace = make_trace(7, cfg.vocab, seed=3,
                       prompt_lens=(3, 6, 11), gen_lens=(2, 9, 4))
    eng = ContinuousEngine(lm, merged, n_slots=3, max_len=24,
                           prefill_chunk=4, decode_burst=3)
    for r in trace:
        eng.submit(r.prompt, r.max_new_tokens, r.eos_id, rid=r.rid)
    out = eng.run()
    assert sorted(out) == [r.rid for r in trace]
    for r in trace:
        assert out[r.rid] == _reference(lm, merged, r), f"rid {r.rid}"
    st = eng.stats
    assert st.tokens_out == sum(r.max_new_tokens for r in trace)
    assert 0.0 < st.occupancy <= 1.0


@pytest.mark.slow
def test_engine_invariant_to_chunk_and_burst(served):
    """prefill_chunk / decode_burst are pure scheduling knobs: any setting
    produces the identical token streams."""
    cfg, lm, merged = served
    trace = make_trace(5, cfg.vocab, seed=11,
                       prompt_lens=(2, 7), gen_lens=(3, 8))
    outs = []
    for chunk, burst in ((1, 1), (4, 2), (8, 8)):
        eng = ContinuousEngine(lm, merged, n_slots=2, max_len=20,
                               prefill_chunk=chunk, decode_burst=burst)
        for r in trace:
            eng.submit(r.prompt, r.max_new_tokens, r.eos_id, rid=r.rid)
        outs.append(eng.run())
    assert outs[0] == outs[1] == outs[2]


@pytest.mark.slow
def test_engine_eos_truncates_and_frees_slot(served):
    """A request with an EOS id stops at the first emitted EOS (inclusive)
    and its slot is refilled — the trailing requests still complete."""
    cfg, lm, merged = served
    trace = make_trace(4, cfg.vocab, seed=5, prompt_lens=(4,), gen_lens=(10,))
    ref = _reference(lm, merged, trace[0])
    eos = ref[3]  # stop request 0 four tokens in (on its own stream)
    trace[0].eos_id = eos
    cut = ref.index(eos) + 1  # first occurrence may be even earlier

    eng = ContinuousEngine(lm, merged, n_slots=2, max_len=16,
                           prefill_chunk=4, decode_burst=4)
    for r in trace:
        eng.submit(r.prompt, r.max_new_tokens, r.eos_id, rid=r.rid)
    out = eng.run()
    assert out[0] == ref[:cut]
    for r in trace[1:]:
        assert len(out[r.rid]) == r.max_new_tokens


# ---------------------------------------------------------------------------
# stats / construction invariants
# ---------------------------------------------------------------------------


def test_engine_decode_burst_clamped_to_power_of_two(served):
    """The compile-bound invariant: burst lengths are powers of two, so a
    non-power-of-two --decode-burst must clamp DOWN at construction (6
    would otherwise compile a k=6 scan program alongside k in {1,2,4})."""
    cfg, lm, merged = served
    for asked, want in ((1, 1), (2, 2), (6, 4), (8, 8), (13, 8), (0, 1)):
        eng = ContinuousEngine(lm, merged, n_slots=1, max_len=8,
                               decode_burst=asked)
        assert eng.decode_burst == want, (asked, want)


def test_burst_ladder_compiles_within_guard_budget(served):
    """The O(log decode_burst) compile invariant, ENFORCED: under a
    CompileGuard the engine declares bit_length(decode_burst) scan
    programs for `_JIT_BURST`, and a mixed-length trace whose shortest-
    request-driven burst lengths walk the k in {1, 2, 4, 8} ladder must
    stay within that budget (the engine's own per-step guard.check()
    raises CompileBudgetExceeded the moment an off-ladder k compiles)."""
    from repro.runtime.compile_guard import CompileGuard
    cfg, lm, merged = served
    trace = make_trace(6, cfg.vocab, seed=23, prompt_lens=(2, 5),
                       gen_lens=(1, 3, 6, 9))
    with CompileGuard("burst-pin") as g:
        # max_len=18 is unique to this test so the burst programs'
        # cache shapes are fresh in this process: the guard must see
        # >= 1 real compile, not an already-warm cache
        eng = ContinuousEngine(lm, merged, n_slots=3, max_len=18,
                               prefill_chunk=4, decode_burst=8)
        for r in trace:
            eng.submit(r.prompt, r.max_new_tokens, r.eos_id, rid=r.rid)
        out = eng.run()
        assert sorted(len(v) for v in out.values()) == sorted(
            r.max_new_tokens for r in trace)
        g.check()
        count, budget = g.counts()["engine._JIT_BURST"]
        assert budget == 4, budget  # k ladder {1, 2, 4, 8}
        assert 1 <= count <= budget, (count, budget)


def test_engine_occupancy_pinned_on_hand_computed_trace(served):
    """EngineStats counts slot/busy steps in MODEL-STEP units on both the
    ragged and burst paths.  Hand trace: slots=2, prefill_chunk=4,
    requests (prompt 2, gen 2) and (prompt 4, gen 2).

      step 1 (ragged, C=4): 2*4 = 8 slot rows, 2+4 = 6 consumed,
                            both slots finish their prompt -> 2 tokens
      step 2 (burst, k=1):  2*1 = 2 slot rows, 2 consumed, 2 tokens

    -> slot_steps 10, busy 8, occupancy 0.8 (the old per-dispatch unit
    on the ragged path would have claimed 4/4 = 100%)."""
    cfg, lm, merged = served
    eng = ContinuousEngine(lm, merged, n_slots=2, max_len=8,
                           prefill_chunk=4, decode_burst=4)
    eng.submit(np.arange(4, 6, dtype=np.int32), 2)
    eng.submit(np.arange(4, 8, dtype=np.int32), 2)
    out = eng.run()
    assert sorted(len(v) for v in out.values()) == [2, 2]
    st = eng.stats
    assert (st.dispatches, st.model_steps) == (2, 5)
    assert (st.slot_steps, st.busy_slot_steps) == (10, 8)
    assert st.occupancy == pytest.approx(0.8)
    assert st.tokens_out == 4


def test_stats_seconds_accrue_per_step_for_external_drivers(served):
    """Wall clock lives in :meth:`step_once`, not :meth:`run` — an
    externally-driven loop (the frontend's) must still report seconds
    and a finite tok_per_s.  Regression: timing used to wrap only run(),
    so frontend-served engines claimed 0 s and absurd tok/s."""
    cfg, lm, merged = served
    eng = ContinuousEngine(lm, merged, n_slots=2, max_len=8,
                           prefill_chunk=4, decode_burst=4)
    eng.submit(np.arange(4, 6, dtype=np.int32), 2)
    while eng.sched.has_work:   # drive per-step, never calling run()
        eng.step_once()
    st = eng.stats
    assert st.tokens_out == 2
    assert st.seconds > 0.0
    assert st.tok_per_s == st.tokens_out / st.seconds


@pytest.mark.slow
def test_burst_path_eos_matches_ragged_token_for_token(served):
    """EOS hit INSIDE a fused decode burst: the emitted stream includes
    the EOS, the slot idles (-1 rows) for the burst's remaining steps,
    and commit_burst folds back exactly the tokens the per-step ragged
    path (decode_burst=1) produces."""
    cfg, lm, merged = served
    trace = make_trace(2, cfg.vocab, seed=13, prompt_lens=(4,),
                       gen_lens=(12,))
    ref = _reference(lm, merged, trace[0])
    trace[0].eos_id = ref[5]  # stops mid-burst on the 8-step burst path
    cut = ref.index(trace[0].eos_id) + 1

    outs = []
    for burst in (1, 8):  # ragged per-step vs fused scan
        eng = ContinuousEngine(lm, merged, n_slots=2, max_len=16,
                               prefill_chunk=4, decode_burst=burst)
        for r in trace:
            eng.submit(r.prompt, r.max_new_tokens, r.eos_id, rid=r.rid)
        outs.append(eng.run())
    assert outs[0] == outs[1]
    assert outs[1][0] == ref[:cut]          # EOS inclusive, then stopped
    assert outs[1][0][-1] == trace[0].eos_id
    assert len(outs[1][1]) == trace[1].max_new_tokens


def test_make_trace_rejects_tiny_vocab():
    """vocab <= 4 would make rng.integers(4, vocab) crash (or sample an
    empty range) deep inside numpy; fail loudly at the API instead."""
    with pytest.raises(ValueError, match="vocab > 4"):
        make_trace(2, 4)
    with pytest.raises(ValueError, match="vocab > 4"):
        make_trace(2, 3)
    assert len(make_trace(2, 5)) == 2  # smallest legal vocab still works


# ---------------------------------------------------------------------------
# scheduler (host-side, no model)
# ---------------------------------------------------------------------------


def _req(p, n, eos=None):
    return Request(prompt=np.arange(4, 4 + p, dtype=np.int32),
                   max_new_tokens=n, eos_id=eos)


def test_scheduler_fifo_admission_and_refill():
    s = Scheduler(n_slots=2, max_len=32, prefill_chunk=4)
    rids = [s.submit(_req(3, 2)) for _ in range(3)]
    assert s.admit() == [0, 1] and s.queue  # third request waits
    # drain both slots: one prompt chunk, then two decode commits
    tokens, n_new = s.plan()
    assert n_new.tolist() == [3, 3] and tokens.shape == (2, 4)
    assert s.commit(np.array([7, 8])) == []          # first gen tokens
    tokens, n_new = s.plan()
    assert n_new.tolist() == [1, 1] and tokens[0, 0] == 7
    done = s.commit(np.array([9, 9]))
    assert sorted(done) == rids[:2] and s.outputs[rids[0]] == [7, 9]
    assert s.admit() == [0]                          # refill, FIFO
    assert s.slots[0].req.rid == rids[2]


def test_scheduler_rid_assignment_never_collides():
    """Auto-assigned rids skip past pre-assigned ones (make_trace pins
    rid=0..n-1), and a duplicate pre-assigned rid fails loudly instead of
    silently overwriting the earlier request's output."""
    s = Scheduler(n_slots=1, max_len=32, prefill_chunk=2)
    assert s.submit(_req(2, 1)) == 0
    pre = _req(2, 1)
    pre.rid = 5
    assert s.submit(pre) == 5
    assert s.submit(_req(2, 1)) == 6  # auto continues past the pin
    dup = _req(2, 1)
    dup.rid = 0
    with pytest.raises(ValueError):
        s.submit(dup)


def test_scheduler_rejects_oversized_request():
    s = Scheduler(n_slots=1, max_len=8, prefill_chunk=4)
    with pytest.raises(ValueError):
        s.submit(_req(6, 4))  # 6 + 4 > 8


def test_scheduler_chunked_prefill_rides_with_decode():
    """A decoding slot keeps consuming one token per step while a fresh
    slot streams its long prompt in chunks."""
    s = Scheduler(n_slots=2, max_len=32, prefill_chunk=4)
    a = s.submit(_req(2, 4))
    s.admit()
    s.plan()
    s.commit(np.array([5, 0]))                       # a: first token
    b = s.submit(_req(10, 2))
    assert s.admit() == [1]
    tokens, n_new = s.plan()                         # mixed step
    assert n_new.tolist() == [1, 4] and tokens.shape == (2, 4)
    s.commit(np.array([6, 0]))                       # b still mid-prompt
    assert s.outputs.get(b) is None
    _, n_new = s.plan()
    assert n_new.tolist() == [1, 4] and s.slots[1].pp == 8
    s.commit(np.array([7, 0]))


def test_scheduler_eos_mid_burst_commit():
    s = Scheduler(n_slots=1, max_len=32, prefill_chunk=2)
    rid = s.submit(_req(2, 5, eos=9))
    s.admit()
    s.plan()
    s.commit(np.array([4]))
    tok, remaining, eos = s.burst_state()
    assert tok.tolist() == [4] and remaining.tolist() == [4]
    assert eos.tolist() == [9]
    # device emitted [5, 9] then idled (-1): eos inclusive, slot evicted
    done = s.commit_burst(np.array([[5], [9], [-1]]), np.array([9]),
                          np.array([0]))
    assert done == [rid] and s.outputs[rid] == [4, 5, 9]
    assert s.slots[0] is None
