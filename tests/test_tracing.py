"""The program's own tracing: host spans around every engine dispatch,
device scopes in the compiled programs' ``op_name`` metadata, and the
counters and stamps the engine and frontend keep (``EngineStats``
host_s / sync_s / forward_passes / compiles, ``Ticket.t_admit``)."""

import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

import repro.configs as C
from repro.launch import steps as St
from repro.launch.mesh import make_local_mesh
from repro.launch.serve import merge_model
from repro.models.lm import LM
from repro.optim import AdamWConfig, adamw_init, split_params
from repro.runtime import tracing
from repro.serving import ContinuousEngine, RequestStatus, ServingFrontend
from repro.serving import engine as E

ENGINE_SPANS = [tracing.PLAN, tracing.DISPATCH, tracing.SYNC, tracing.COMMIT]


@pytest.fixture(scope="module")
def model():
    cfg = C.reduced("starcoder2-7b")
    lm = LM(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    return lm, params, merge_model(params, cfg.quant)


def _prompts(n, lo=3):
    return [np.arange(lo + i, dtype=np.int32) % 50 + 5 for i in range(n)]


def _repro_spans(logdir):
    path, = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    return sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                  for p in ProfileData.from_file(path).planes
                  for ln in p.lines for e in ln.events
                  if e.name.startswith(tracing.PREFIX))


def test_profiled_run_records_engine_spans_per_dispatch(model, tmp_path):
    """Under the profiler every dispatch leaves plan, dispatch, sync and
    commit in that order, inside one frontend.step, after its intake and
    before its harvest; every span name is one of tracing's."""
    lm, _, merged = model
    fe = ServingFrontend(lm, merged, n_slots=2, max_len=32, prefill_chunk=4,
                         decode_burst=4)
    for p in _prompts(3):
        fe.submit(p, 6)
    fe.run_until_drained()                      # compiles outside the trace
    d0 = fe.engine_stats.dispatches
    jax.profiler.start_trace(str(tmp_path))
    for p in _prompts(3):
        fe.submit(p, 6)
    fe.run_until_drained()
    jax.profiler.stop_trace()
    spans = _repro_spans(str(tmp_path))
    known = {v for k, v in vars(tracing).items()
             if k.isupper() and isinstance(v, str)
             and v.startswith(tracing.PREFIX)}
    assert {n for _, _, n in spans} <= known
    steps = [(a, b) for a, b, n in spans if n == tracing.FRONTEND_STEP]
    dispatched = 0
    for a, b in steps:
        inner = [n for s, e, n in spans
                 if a <= s and e <= b and n != tracing.FRONTEND_STEP]
        assert inner[0] == tracing.INTAKE
        engine = [n for n in inner if n in ENGINE_SPANS]
        if engine:
            dispatched += 1
            assert engine == ENGINE_SPANS
            assert inner[-1] == tracing.HARVEST
            assert inner.index(tracing.ADMIT) < inner.index(tracing.PLAN)
    assert dispatched == fe.engine_stats.dispatches - d0 > 0


def _scopes_in(text):
    names = re.findall(r'op_name="([^"]*)"', text)
    return {s for s in tracing.SCOPES
            if any(re.search(rf"(?<![\w-]){s}(?![\w-])", n) for n in names)}


def test_compiled_programs_carry_the_scopes(model):
    """The ragged step and the burst name their projections, attention
    and cache inserts; the train step its projections (forward and
    backward), attention and optimizer."""
    lm, params, merged = model
    eng = ContinuousEngine(lm, merged, n_slots=2, max_len=32,
                           prefill_chunk=4, decode_burst=2)
    zi = jnp.zeros((2,), jnp.int32)
    args = (lm, eng.params, eng.aux, eng.cache)
    step = E._JIT_STEP.lower(*args, jnp.zeros((2, 4), jnp.int32), zi)
    burst = E._JIT_BURST.lower(*args, zi, zi, zi, k_steps=2)
    serve = {tracing.LINEAR, tracing.ATTENTION, tracing.KV_INSERT}
    assert _scopes_in(step.compile().as_text()) == serve
    assert _scopes_in(burst.compile().as_text()) == serve
    mesh = make_local_mesh()
    with jax.set_mesh(mesh):
        tr, fr = split_params(params)
        jit_for, _ = St.make_train_step(lm, mesh, AdamWConfig(lr=1e-3))
        shape = jax.ShapeDtypeStruct((2, 16), jnp.int32)
        fn, _ = jit_for({"tokens": shape, "labels": shape})
        batch = {"tokens": jnp.ones((2, 16), jnp.int32),
                 "labels": jnp.ones((2, 16), jnp.int32)}
        text = fn.lower(tr, fr, adamw_init(tr), batch).compile().as_text()
    assert _scopes_in(text) == {tracing.LINEAR, tracing.ATTENTION,
                                tracing.OPTIMIZER}
    assert re.search(r'op_name="[^"]*transpose\([^"]*linear', text)


def test_forward_passes_count_burst_lengths_and_ragged_steps(
        model, monkeypatch):
    lm, _, merged = model
    bursts, ragged = [], []
    step, burst = E._JIT_STEP, E._JIT_BURST

    def spy_step(*a, **k):
        ragged.append(1)
        return step(*a, **k)

    def spy_burst(*a, k_steps):
        bursts.append(k_steps)
        return burst(*a, k_steps=k_steps)

    monkeypatch.setattr(E, "_JIT_STEP", spy_step)
    monkeypatch.setattr(E, "_JIT_BURST", spy_burst)
    eng = ContinuousEngine(lm, merged, n_slots=2, max_len=32,
                           prefill_chunk=4, decode_burst=4)
    for p, n in zip(_prompts(3), (7, 3, 5)):
        eng.submit(p, n)
    eng.run()
    st = eng.stats
    assert bursts and ragged
    assert st.forward_passes == sum(bursts) + len(ragged)
    assert st.dispatches == len(bursts) + len(ragged)


def test_compiles_count_new_programs_and_none_when_warm(model, monkeypatch):
    """A burst length first used mid-run adds to ``compiles`` on that
    iteration; the same traffic again, on a fresh engine of the same
    shapes, compiles nothing.  (A slot count and capacity no other test
    uses, so every program here is new to the process.)"""
    lm, _, merged = model
    ks = []
    burst = E._JIT_BURST

    def spy_burst(*a, k_steps):
        ks.append(k_steps)
        return burst(*a, k_steps=k_steps)

    monkeypatch.setattr(E, "_JIT_BURST", spy_burst)

    def serve(lengths):
        eng = ContinuousEngine(lm, merged, n_slots=3, max_len=37,
                               prefill_chunk=4, decode_burst=4)
        per_step = []
        for n in lengths:
            eng.submit(np.array([5, 6, 7], np.int32), n)
            while eng.sched.has_work:
                before, n_ks = eng.stats.compiles, len(ks)
                eng.step_once()
                per_step.append((ks[n_ks:], eng.stats.compiles - before))
        return eng.stats.compiles, per_step

    total, per_step = serve([5, 3])     # bursts of 4, then first of 2
    first_two = next(c for k, c in per_step if k == [2])
    assert first_two >= 1 and total >= 3
    again, _ = serve([5, 3])
    assert again == 0


def test_host_and_sync_seconds_make_up_seconds(model):
    lm, _, merged = model
    fe = ServingFrontend(lm, merged, n_slots=2, max_len=32, prefill_chunk=4,
                         decode_burst=4)
    for p in _prompts(4):
        fe.submit(p, 5)
    fe.run_until_drained()
    st = fe.engine_stats
    assert st.host_s > 0 and st.sync_s > 0
    assert st.host_s + st.sync_s <= st.seconds + 1e-9
    assert st.host_s + st.sync_s == pytest.approx(st.seconds, rel=1e-9)


def test_t_admit_lies_between_submit_and_first_token(model):
    """More requests than slots, so some wait for a slot; one request
    finishes in the iteration that admits it."""
    lm, _, merged = model
    fe = ServingFrontend(lm, merged, n_slots=2, max_len=32, prefill_chunk=4,
                         decode_burst=4)
    ts = [fe.submit(p, 6) for p in _prompts(5)]
    ts.append(fe.submit(np.array([9, 8], np.int32), 1))
    fe.run_until_drained()
    assert all(t.status is RequestStatus.FINISHED for t in ts)
    for t in ts:
        assert t.t_submit <= t.t_admit <= t.t_first
    waited = [t for t in ts if t.t_admit > ts[0].t_admit]
    assert waited
