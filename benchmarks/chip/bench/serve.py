"""Serving cells: the merged INT4 model behind ``ServingFrontend``.

Set-up draws the QA-LoRA model from the seed, merges it with
``schemes.merge_tree`` and runs every program the cell's traffic uses once
(the ragged step at the cell's chunk width, the burst ladder, the slot
reset).  The window then drives ``ServingFrontend.step`` on one thread:
the load generator submits what is due, the frontend runs one engine
dispatch, and the client side stamps every token it finds committed.

  closed  ``clients`` clients, one request each in flight; a client sends
          its next request when its last one finishes.  All slots are
          filled before the window opens.
  poisson requests due at seeded Poisson times, from ``lead_s`` before
          the window; after it closes, traffic goes on until every request
          due in the window has its first token (at most ``drain_s``).

Times come from this process's clock: a token's time is when the client
side first sees it committed, so the tokens a burst returns together are
0 apart.  TTFT runs from the due time.
"""

from __future__ import annotations

import dataclasses
import gc
import json
import time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import spans as S
from . import traffic as T
from . import weights as W
from . import work

MISSING_S = 3600.0  # a request that never answered counts as one hour


@dataclasses.dataclass
class Served:
    due: float
    sent: float
    prompt: np.ndarray
    max_new: int
    ticket: object
    times: List[float] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class StepRecord:
    t0: float
    t1: float
    model_steps: int
    rows: list          # [(cache length before, rows consumed)] per slot


def _cache_len(slot) -> int:
    """Rows a slot's cache holds: its prompt so far and every token but
    the last, which is the next step's input."""
    return slot.pp + max(len(slot.emitted) - 1, 0)


def _lengths(fe) -> dict:
    return {s.req.rid: _cache_len(s) for s in fe.engine.sched.slots
            if s is not None}


class Server:
    def __init__(self, c: dict, seed: int):
        from repro.core.schemes import merge_tree
        from repro.models.lm import LM

        self.seed = seed
        self.cfg, self.mix, self.eng = c["config"], c["traffic"], c["cell"]["engine"]
        if self.eng["prefill_chunk"] <= self.eng["decode_burst"]:
            # a dispatch's model steps tell a ragged step (the chunk
            # width) from a burst (at most decode_burst)
            raise ValueError("a serving cell needs prefill_chunk > "
                             "decode_burst")
        self.lm = LM(W.arch_config(self.cfg))
        self.params = merge_tree(W.make_program(self.lm, self.cfg, seed))
        self.fe = None

    def frontend(self):
        from repro.serving.frontend import ServingFrontend

        e = self.eng
        return ServingFrontend(
            self.lm, self.params, n_slots=e["n_slots"], max_len=e["max_len"],
            prefill_chunk=e["prefill_chunk"], decode_burst=e["decode_burst"],
            queue_cap=e["queue_cap"],
            cache_dtype=getattr(jnp, self.cfg["kv_dtype"]))

    def warm_up(self):
        """Compile what the window runs: two rounds of full slots, each
        request two prompt chunks and 2 x decode_burst tokens long, so the
        ragged step and slot reset see both the fresh and the committed
        cache and the bursts run every length of their ladder."""
        e = self.eng
        fe = self.frontend()
        r = T.rng(self.seed, 9)
        for _ in range(2 * e["n_slots"]):
            fe.submit(r.integers(T.FIRST_TOKEN, self.cfg["vocab"],
                                 e["prefill_chunk"] + 1).astype(np.int32),
                      2 * e["decode_burst"])
        fe.run_until_drained()
        del fe
        gc.collect()

    def free(self):
        self.fe = self.params = None
        gc.collect()


def serve(server: Server, seconds: float, tracer) -> dict:
    """Run the window; returns what the metrics and the check read."""
    mix = server.mix
    fe = server.fe = server.frontend()
    reqs = T.Requests(mix, server.cfg["vocab"], server.seed)
    recs: List[Served] = []
    live: List[Served] = []
    steps: List[StepRecord] = []
    closed = mix["kind"] == "closed"

    def submit(due: float):
        prompt, out = reqs(len(recs))
        r = Served(due, time.perf_counter(), prompt, out, None)
        r.ticket = fe.submit(prompt, out)
        recs.append(r)
        live.append(r)
        return r

    clients: List[Optional[Served]] = []
    arrivals, n_due = None, 0
    if closed:
        clients = [submit(time.perf_counter()) for _ in range(mix["clients"])]
    else:
        horizon = mix["lead_s"] + seconds + mix["drain_s"]
        arrivals = T.stratified_poisson_arrivals(
            int(mix["rate"] * horizon * 1.5) + 16, mix["rate"],
            T.order_seed(mix, server.seed), mix.get("pool", T.POOL))

    def harvest(now: float) -> list:
        done = []
        for r in list(live):
            toks = r.ticket.tokens
            while len(r.times) < len(toks):
                r.times.append(now)
            if r.ticket.done.is_set():
                live.remove(r)
                done.append(r)
        return done

    def one_step(record: bool):
        before = _lengths(fe) if record else None
        st0 = fe.engine_stats.model_steps
        t0 = time.perf_counter()
        with S.span(S.STEP):
            busy = fe.step()
        t1 = time.perf_counter()
        with S.span(S.COMMIT):
            done = harvest(t1)
        if record:
            after = _lengths(fe)
            for r in done:
                after[r.ticket.rid] = len(r.prompt) + len(r.ticket.tokens) - 1
            rows = [(before.get(rid, 0), n - before.get(rid, 0))
                    for rid, n in after.items() if n > before.get(rid, 0)]
            steps.append(StepRecord(t0, t1, fe.engine_stats.model_steps - st0,
                                    rows))
        return busy, t1

    def refill(now: float):
        for j, r in enumerate(clients):
            if r.ticket.done.is_set():
                clients[j] = submit(r.times[-1] if r.times else now)

    if closed:
        # fill every slot before the window opens
        while not (fe.engine.sched.all_decoding
                   and fe.engine.sched.n_active == mix["clients"]):
            one_step(False)
            refill(time.perf_counter())
        tracer.start()
        t_open = time.perf_counter()
    else:
        tracer.start()
        t_start = time.perf_counter()
        t_open = t_start + mix["lead_s"]
    stats0 = None
    t_close = None
    window = S.span(S.WINDOW)
    while True:
        now = time.perf_counter()
        if stats0 is None and now >= t_open:
            if not closed:
                t_open = now
            stats0 = dataclasses.replace(fe.engine_stats)
            window.__enter__()
        with S.span(S.SUBMIT):
            if closed:
                refill(now)
            else:
                due = T.replay_due(arrivals, t_start, now, n_due)
                for i in range(n_due, due):
                    submit(t_start + arrivals[i])
                n_due = due
        busy, t1 = one_step(stats0 is not None and t_close is None)
        if not busy and not closed and n_due < len(arrivals):
            # nothing to run: wait for the next arrival, not in a spin
            time.sleep(min(max(t_start + arrivals[n_due] - t1, 0), 0.05))
        if stats0 is not None and t_close is None and t1 >= t_open + seconds:
            t_close = t1
            stats1 = dataclasses.replace(fe.engine_stats)
            window.__exit__(None, None, None)
        if t_close is not None:
            if closed:
                break
            pending = [r for r in recs if t_open <= r.due < t_close
                       and not r.times and not r.ticket.done.is_set()]
            if not pending or t1 > t_close + mix["drain_s"]:
                break
    tracer.stop()
    return {"recs": recs, "steps": steps, "t_open": t_open,
            "t_close": t_close, "stats0": stats0,
            "stats1": stats1, "closed": closed}


def end_to_end(run: dict) -> dict:
    """The user-facing numbers of one window, under the names of the
    end-to-end metrics they are, with the counts behind them."""
    t_open, t_close = run["t_open"], run["t_close"]
    inside = lambda t: t_open < t <= t_close  # noqa: E731
    tokens, gaps, ttft = 0, [], []
    for r in run["recs"]:
        tokens += sum(1 for t in r.times if inside(t))
        gaps += [b - a for a, b in zip(r.times, r.times[1:])
                 if inside(a) and inside(b)]
        if t_open <= r.due < t_close:
            ttft.append(r.times[0] - r.due if r.times else MISSING_S)
    def waiting(t):
        return sum(1 for r in run["recs"] if r.due <= t
                   and not (r.times and r.times[0] <= t))
    return {"tokens": tokens, "window_s": t_close - t_open,
            "waiting_open": waiting(t_open), "waiting_close": waiting(t_close),
            "tok_s": tokens / (t_close - t_open),
            "itl_p95_ms": 1e3 * T.nearest_rank(gaps, 0.95) if gaps else None,
            "n_gaps": len(gaps),
            "ttft_p90_ms": 1e3 * T.nearest_rank(ttft, 0.90) if ttft else None,
            "n_due": len(ttft)}


def accounting(run: dict):
    """(attempted, failed): for an open loop the requests due in the
    window, for a closed loop those in flight in it; failed are those
    that ended in any state but FINISHED or never answered."""
    from repro.serving.frontend import RequestStatus

    t_open, t_close = run["t_open"], run["t_close"]
    if run["closed"]:
        mine = [r for r in run["recs"] if r.sent < t_close
                and not (r.times and r.ticket.done.is_set()
                         and r.times[-1] <= t_open)]
    else:
        mine = [r for r in run["recs"] if t_open <= r.due < t_close]
    bad = [r for r in mine
           if (r.ticket.done.is_set()
               and r.ticket.status is not RequestStatus.FINISHED)
           or (not run["closed"] and not r.times)]
    return len(mine), len(bad)


def passes(run: dict, prefill_chunk: int):
    """The window's forward passes, each as its rows [(cache length,
    rows consumed)]: a ragged dispatch is one pass over its chunk, a
    burst of k model steps is k passes of one row per slot still
    running."""
    out = []
    for s in run["steps"]:
        if s.model_steps == 0:
            continue
        if s.model_steps == prefill_chunk:
            out.append((s, [s.rows]))
        else:
            out.append((s, [[(a + j, 1) for a, n in s.rows if n > j]
                            for j in range(s.model_steps)]))
    return out


def layer_context(run: dict, server: Server, peak: dict) -> dict:
    """What the per-layer readers of a serving cell read."""
    cfg, chunk = server.cfg, server.eng["prefill_chunk"]
    t_open, t_close = run["t_open"], run["t_close"]
    s0, s1 = run["stats0"], run["stats1"]
    span_s, n_pass, least = 0.0, 0, 0.0
    for step, ps in passes(run, chunk):
        span_s += step.t1 - step.t0
        n_pass += len(ps)
        least += sum(work.least_seconds(*work.serve_pass(cfg, rows), peak)
                     for rows in ps)
    return {
        "slot_steps": s1.slot_steps - s0.slot_steps,
        "busy_slot_steps": s1.busy_slot_steps - s0.busy_slot_steps,
        "step_span_s": span_s, "forward_passes": n_pass,
        "least_s": least, "window_s": t_close - t_open,
    }


# ---------------------------------------------------------------------------
# correctness: served tokens against the plain reference
# ---------------------------------------------------------------------------


def sample(run: dict, seed: int, want_tokens: int, max_requests: int):
    """Requests that emitted tokens in the window, drawn from the seed:
    the one with most tokens first, then others until ``want_tokens``."""
    t_open, t_close = run["t_open"], run["t_close"]
    cand = [r for r in run["recs"]
            if any(t_open < t <= t_close for t in r.times)]
    if not cand:
        return []
    cand.sort(key=lambda r: -len(r.ticket.tokens))
    first, rest = cand[0], cand[1:]
    order = T.rng(seed, 8).permutation(len(rest))
    out, n = [first], len(first.ticket.tokens)
    for i in order:
        if n >= want_tokens or len(out) >= max_requests:
            break
        out.append(rest[i])
        n += len(rest[i].ticket.tokens)
    return out


def _bucket(n: int) -> int:
    b = 256
    while b < n:
        b *= 2
    return b


_REF_JITS: dict = {}


def reference_gaps(ref, cfg: dict, raw: dict, prompt, served, lower=False):
    """Teacher-forced over ``prompt + served[:-1]``: per served token, the
    reference's best logit minus its logit for that token (0 where the
    reference agrees).  With ``lower``, the gaps of the tokens the
    lower-precision control puts first instead."""
    seq = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
    n = len(seq)
    t = _bucket(n)
    toks = np.zeros(t, np.int32)
    toks[:n] = seq
    m = len(served)
    pos = np.zeros(t, np.int32)
    pos[:m] = np.arange(len(prompt) - 1, n)
    tgt = np.zeros(t, np.int32)
    tgt[:m] = served
    key = (id(ref), json.dumps(cfg, sort_keys=True), t, lower)
    if key not in _REF_JITS:
        def fn(raw, toks, pos, served):
            lg = ref.logits(raw, cfg, toks)[pos]
            pick = served
            if lower:
                pick = jnp.argmax(ref.logits(raw, cfg, toks, lower=True)[pos],
                                  -1)
            best = lg.max(-1)
            return best - jnp.take_along_axis(lg, pick[:, None], -1)[:, 0]
        _REF_JITS[key] = jax.jit(fn)
    return np.asarray(_REF_JITS[key](raw, jnp.asarray(toks), jnp.asarray(pos),
                                     jnp.asarray(tgt)))[:m]


def check(run: dict, server_cfg: dict, cell: dict, seed: int, ref) -> dict:
    """The numbers compared, each with its limit.  Runs after the program
    is freed; draws the reference's weights anew from the seed."""
    lim = cell["limits"]
    picked = sample(run, seed, lim["checked_tokens"], lim["max_requests"])
    raw = W.make_raw(server_cfg, seed)
    gaps = [reference_gaps(ref, server_cfg, raw, r.prompt,
                           list(r.ticket.tokens)) for r in picked]
    n = sum(len(g) for g in gaps)
    from repro.serving.frontend import RequestStatus
    bad = sum(1 for r in run["recs"]
              if r.ticket.status is RequestStatus.FINISHED
              and len(r.ticket.tokens) != r.max_new)
    return {
        "logit_gap_max": {"value": float(max((g.max() for g in gaps),
                                             default=float("inf"))),
                          "limit": lim["logit_gap_max"]},
        "wrong_lengths": {"value": bad, "limit": 0},
        "checked_tokens": {"value": n, "limit": lim["checked_tokens"]},
    }


def passed(checks: dict) -> bool:
    return (checks["logit_gap_max"]["value"] <= checks["logit_gap_max"]["limit"]
            and checks["wrong_lengths"]["value"] == 0
            and checks["checked_tokens"]["value"]
            >= checks["checked_tokens"]["limit"])
