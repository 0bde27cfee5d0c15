"""ServingFrontend lifecycle: typed terminal statuses, loud
backpressure, deadline enforcement (injected clock), cancellation, and
graceful / preemption-style drain.

Everything here drives step()/run_until_drained() synchronously (except
the one threaded live-intake test), so the tests are deterministic; the
recovery-equivalence gates live in tests/test_frontend_recovery.py.
"""

import threading

import jax
import numpy as np
import pytest

import repro.configs as C
from repro.launch.mesh import make_local_mesh
from repro.launch.serve import merge_model
from repro.models.lm import LM
from repro.runtime import PreemptionGuard
from repro.serving import (ContinuousEngine, RequestStatus, ServingFrontend,
                           TERMINAL_STATUSES, make_trace, slo_summary)


@pytest.fixture(scope="module")
def served():
    cfg = C.reduced("gemma3-1b")
    lm = LM(cfg)
    merged = merge_model(lm.init(jax.random.PRNGKey(0)), cfg.quant)
    return cfg, lm, merged


@pytest.fixture(scope="module")
def mesh():
    return make_local_mesh()


class FakeClock:
    """Deterministic injectable clock: time moves only via advance()."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def _frontend(served, **kw):
    cfg, lm, merged = served
    kw.setdefault("n_slots", 2)
    kw.setdefault("max_len", 20)
    kw.setdefault("prefill_chunk", 4)
    kw.setdefault("decode_burst", 2)
    return ServingFrontend(lm, merged, **kw)


# ---------------------------------------------------------------------------
# lifecycle / equivalence with the raw engine
# ---------------------------------------------------------------------------


def test_drained_tokens_match_raw_engine(served, mesh):
    """The frontend is a lifecycle layer, not a decode layer: a drained
    clean run yields exactly the raw ContinuousEngine's token streams,
    every ticket FINISHED with timing stamps and a set done-event."""
    cfg, lm, merged = served
    trace = make_trace(5, cfg.vocab, seed=2, prompt_lens=(3, 5),
                       gen_lens=(2, 6))
    with jax.set_mesh(mesh):
        eng = ContinuousEngine(lm, merged, n_slots=2, max_len=20,
                               prefill_chunk=4, decode_burst=2)
        for r in trace:
            eng.submit(r.prompt, r.max_new_tokens, r.eos_id, rid=r.rid)
        ref = eng.run()

        fe = _frontend(served)
        tickets = [fe.submit(r.prompt, r.max_new_tokens, eos_id=r.eos_id,
                             rid=r.rid) for r in trace]
        counts = fe.run_until_drained()
    assert counts == {"FINISHED": len(trace)}
    for t in tickets:
        assert t.status is RequestStatus.FINISHED
        assert t.tokens == ref[t.rid]
        assert t.done.is_set()
        assert t.t_first is not None and t.t_done is not None
        assert t.ttft is not None and t.ttft >= 0.0
    s = slo_summary(fe)
    assert s["finished"] == len(trace) and s["reject_rate"] == 0.0


def test_result_blocks_until_terminal(served, mesh):
    cfg, lm, merged = served
    with jax.set_mesh(mesh):
        fe = _frontend(served)
        t = fe.submit(np.array([5, 6, 7], np.int32), 3)
        assert t.status is RequestStatus.QUEUED
        assert fe.result(t.rid, timeout=0.0).status is RequestStatus.QUEUED
        fe.run_until_drained()
    assert fe.result(t.rid).status is RequestStatus.FINISHED
    assert len(t.tokens) == 3


# ---------------------------------------------------------------------------
# admission control / backpressure
# ---------------------------------------------------------------------------


def test_backpressure_rejects_loudly_with_queue_depth(served, mesh):
    """Overload must reject at submit time with the depth in the error —
    never silently queue past queue_cap — and the accepted requests must
    still finish."""
    cfg, lm, merged = served
    with jax.set_mesh(mesh):
        fe = _frontend(served, queue_cap=3)
        ts = [fe.submit(np.array([4 + i, 9], np.int32), 2) for i in range(6)]
        assert [t.status is RequestStatus.REJECTED for t in ts] \
            == [False] * 3 + [True] * 3
        for t in ts[3:]:
            assert "backpressure" in t.error and "3/3" in t.error
            assert t.done.is_set()
        fe.run_until_drained()
    assert fe.status_counts() == {"FINISHED": 3, "REJECTED": 3}
    assert slo_summary(fe)["reject_rate"] == 0.5


def test_invalid_requests_reject_not_raise(served, mesh):
    cfg, lm, merged = served
    with jax.set_mesh(mesh):
        fe = _frontend(served, max_len=10)
        empty = fe.submit(np.array([], np.int32), 4)
        zero = fe.submit(np.array([5], np.int32), 0)
        huge = fe.submit(np.array([5, 6, 7], np.int32), 99)
        for t, frag in ((empty, "empty prompt"), (zero, "max_new_tokens"),
                        (huge, "cache positions")):
            assert t.status is RequestStatus.REJECTED and frag in t.error
        ok = fe.submit(np.array([5, 6, 7], np.int32), 4)
        with pytest.raises(ValueError, match="duplicate rid"):
            fe.submit(np.array([5], np.int32), 2, rid=ok.rid)
        fe.run_until_drained()
    assert ok.status is RequestStatus.FINISHED


# ---------------------------------------------------------------------------
# deadlines (injected clock)
# ---------------------------------------------------------------------------


def test_total_deadline_evicts_in_flight_slot(served, mesh):
    """A running request whose total deadline expires is evicted at plan
    time like an EOS slot: TIMED_OUT, partial tokens kept, and the freed
    slot still serves the deadline-free request to completion."""
    cfg, lm, merged = served
    clk = FakeClock()
    with jax.set_mesh(mesh):
        fe = _frontend(served, n_slots=1, clock=clk)
        doomed = fe.submit(np.array([5, 6, 7], np.int32), 12, deadline_s=5.0)
        free = fe.submit(np.array([8, 9], np.int32), 3)
        fe.step()          # prefill dispatch
        fe.step()          # first decode burst commits tokens
        assert doomed.status is RequestStatus.RUNNING
        assert 0 < len(doomed.tokens) < doomed.max_new_tokens
        clk.advance(6.0)   # past the total deadline
        fe.run_until_drained()
    assert doomed.status is RequestStatus.TIMED_OUT
    assert "total deadline" in doomed.error
    assert 0 < len(doomed.tokens) < doomed.max_new_tokens
    assert free.status is RequestStatus.FINISHED
    assert len(free.tokens) == 3


def test_ttft_deadline_times_out_queued_request(served, mesh):
    """A request that never got a first token past its TTFT deadline
    times out while queued, before ever reaching a slot."""
    cfg, lm, merged = served
    clk = FakeClock()
    with jax.set_mesh(mesh):
        fe = _frontend(served, clock=clk,
                       default_ttft_deadline_s=1.0)
        stale = fe.submit(np.array([5, 6], np.int32), 4)
        clk.advance(2.0)   # expires in the intake queue, pre-dispatch
        fresh = fe.submit(np.array([7, 8], np.int32), 4)
        fe.run_until_drained()
    assert stale.status is RequestStatus.TIMED_OUT
    assert "TTFT deadline" in stale.error and "queued" in stale.error
    assert stale.tokens == []
    assert fresh.status is RequestStatus.FINISHED


def test_deadline_defaults_apply_per_request_override(served):
    cfg, lm, merged = served
    clk = FakeClock()
    fe = _frontend(served, clock=clk, default_deadline_s=7.0)
    a = fe.submit(np.array([5], np.int32), 2)
    b = fe.submit(np.array([5], np.int32), 2, deadline_s=99.0)
    assert a.deadline_s == 7.0 and b.deadline_s == 99.0


# ---------------------------------------------------------------------------
# cancellation
# ---------------------------------------------------------------------------


def test_cancel_queued_and_in_flight(served, mesh):
    cfg, lm, merged = served
    with jax.set_mesh(mesh):
        fe = _frontend(served, n_slots=1)
        running = fe.submit(np.array([5, 6, 7], np.int32), 10)
        queued = fe.submit(np.array([8, 9], np.int32), 5)
        assert fe.cancel(queued.rid)      # still in intake: no dispatch yet
        fe.step()
        fe.step()
        assert running.status is RequestStatus.RUNNING
        assert fe.cancel(running.rid)
        fe.run_until_drained()
    assert queued.status is RequestStatus.CANCELLED
    assert "queued" in queued.error and queued.tokens == []
    assert running.status is RequestStatus.CANCELLED
    assert "in flight" in running.error
    assert 0 < len(running.tokens) < running.max_new_tokens
    assert not fe.cancel(running.rid)     # already terminal


# ---------------------------------------------------------------------------
# drain: stop() and SIGTERM via PreemptionGuard
# ---------------------------------------------------------------------------


def test_stop_finishes_accepted_queue_and_rejects_new(served, mesh):
    cfg, lm, merged = served
    with jax.set_mesh(mesh):
        fe = _frontend(served, n_slots=1)
        accepted = [fe.submit(np.array([5 + i, 6], np.int32), 2)
                    for i in range(3)]
        counts = fe.stop()                # graceful: drains the queue too
        late = fe.submit(np.array([9], np.int32), 2)
    assert counts == {"FINISHED": 3}
    assert all(t.status is RequestStatus.FINISHED for t in accepted)
    assert late.status is RequestStatus.REJECTED
    assert "draining" in late.error


def test_preemption_guard_drain_cancels_undispatched(served, mesh):
    """SIGTERM-style drain (guard.requested): in-flight slots finish,
    accepted-but-undispatched requests are CANCELLED, new submissions
    are REJECTED — the serving analogue of the training loop's
    checkpoint-and-exit contract."""
    cfg, lm, merged = served
    guard = PreemptionGuard()
    with jax.set_mesh(mesh):
        fe = _frontend(served, n_slots=1, guard=guard)
        inflight = fe.submit(np.array([5, 6, 7], np.int32), 4)
        waiting = fe.submit(np.array([8, 9], np.int32), 4)
        fe.step()                          # inflight reaches the slot
        guard.requested = True             # what the SIGTERM handler flips
        fe.run_until_drained()
        late = fe.submit(np.array([10], np.int32), 2)
    assert inflight.status is RequestStatus.FINISHED
    assert len(inflight.tokens) == 4
    assert waiting.status is RequestStatus.CANCELLED
    assert "preemption" in waiting.error
    assert late.status is RequestStatus.REJECTED


# ---------------------------------------------------------------------------
# threaded live intake
# ---------------------------------------------------------------------------


def test_threaded_live_intake_drains_clean(served, mesh):
    """start()/stop() with submissions from a feeder thread: every
    accepted request reaches a terminal status and the serve thread
    joins."""
    cfg, lm, merged = served
    trace = make_trace(6, cfg.vocab, seed=4, prompt_lens=(3,), gen_lens=(3,))
    with jax.set_mesh(mesh):
        fe = _frontend(served, queue_cap=len(trace)).start()
        with pytest.raises(RuntimeError, match="already started"):
            fe.start()

        def feed():
            for r in trace:
                fe.submit(r.prompt, r.max_new_tokens, rid=r.rid)

        th = threading.Thread(target=feed)
        th.start()
        th.join()
        counts = fe.stop()
    assert counts == {"FINISHED": len(trace)}
    assert all(t.status in TERMINAL_STATUSES for t in fe.tickets.values())
    assert fe.wall_s > 0.0
    assert fe.engine_stats.tokens_out == sum(r.max_new_tokens for r in trace)
