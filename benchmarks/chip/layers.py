"""Per-layer readings from the program's own spans, scopes and counters.

Not run by the benchmark.  One traced window of one cell, set up as
``run.py`` sets it up (without the check against the reference), read
through what the program records about itself:

    python3 benchmarks/chip/layers.py --workload danube-1.8b.chat \
        --seed 7 --seconds 30

One JSON line on standard output:

  host_ms          host seconds per dispatch outside the sync span
                   (EngineStats host_s / dispatches over the window)
  sync_ms          seconds per dispatch the host waited for results
  prefill_ms       nearest-rank p90, over the requests due in the window
                   that got a first token, of t_first - t_admit
  compiles         executables the engine's jits (the train step's jit)
                   added in the window; trace_compiles: compile events
                   the trace holds inside the window
  linear_roofline  least time of the window's linear work
                   (bench/linear_work.py) over the device seconds under
                   the ``linear`` scope, in %
  device_scopes    busy seconds by scope (bench/scopes.py), ``other`` the
                   rest; unmapped_s: device seconds of operations found
                   in no compiled program
  idle_gaps        the longest gaps, each named by the innermost span
                   covering most of it, and gap_parts: each one's seconds
                   by the innermost span open at each instant;
                   idle_by_span: the same over all the window's gaps
"""

import argparse
import dataclasses
import json
import os
import shutil
import sys
import time

T_START = time.perf_counter()

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)), "src"))


def _texts(compiled) -> dict:
    """{module name: [compiled text]} of compiled programs."""
    out = {}
    for c in compiled:
        text = c.as_text()
        name = text.split(None, 2)[1].rstrip(",")  # "HloModule <name>, ..."
        out.setdefault(name, []).append(text)
    return out


def serve_programs(engine) -> dict:
    """The compiled text of every program the engine's window runs: the
    ragged step at its chunk width, each burst length of its ladder, the
    slot reset; lowered with the engine's own (committed) state."""
    import jax.numpy as jnp

    from repro.serving import engine as E

    b, c = engine.n_slots, engine.prefill_chunk
    zi = jnp.zeros((b,), jnp.int32)
    args = (engine.lm, engine.params, engine.aux, engine.cache)
    progs = [E._JIT_STEP.lower(*args, jnp.zeros((b, c), jnp.int32), zi),
             E._JIT_RESET.lower(engine.slot_state, engine.cache,
                                jnp.zeros((b,), bool))]
    k = 1
    while k <= engine.decode_burst:
        progs.append(E._JIT_BURST.lower(*args, zi, zi, zi, k_steps=k))
        k *= 2
    return _texts(p.compile() for p in progs)


def serve_readings(c, seed, seconds, peak, tracer):
    from bench import linear_work as LW, serve as SV, traffic as T, work

    prog = SV.Server(c, seed)
    prog.warm_up()
    run = SV.serve(prog, seconds, tracer)
    s0, s1 = run["stats0"], run["stats1"]
    d = {f.name: getattr(s1, f.name) - getattr(s0, f.name)
         for f in dataclasses.fields(s1)}
    n = max(d["dispatches"], 1)
    t_open, t_close = run["t_open"], run["t_close"]
    pre = [r.ticket.t_first - r.ticket.t_admit for r in run["recs"]
           if t_open <= r.due < t_close and r.ticket.t_first is not None]
    least = sum(work.least_seconds(*LW.serve_pass(prog.cfg, rows), peak)
                for _, ps in SV.passes(run, prog.eng["prefill_chunk"])
                for rows in ps)
    texts = serve_programs(prog.fe.engine)
    prog.free()
    return {"e2e": SV.end_to_end(run), "counters": d,
            "host_ms": 1e3 * d["host_s"] / n, "sync_ms": 1e3 * d["sync_s"] / n,
            "prefill_ms": (1e3 * T.nearest_rank(pre, 0.90) if pre else None),
            "n_prefill": len(pre), "compiles": d["compiles"],
            "linear_least_s": least}, texts


def train_readings(c, seed, seconds, peak, tracer):
    import jax
    import jax.numpy as jnp

    from bench import linear_work as LW, train as TN, work

    prog = TN.Trainer(c, seed)
    prog.first_steps()
    n0 = prog.step_fn._cache_size()
    run = TN.train(prog, seconds, tracer)
    compiles = prog.step_fn._cache_size() - n0
    b, s = prog.mix["batch"], prog.mix["seq"]
    least = len(run["losses"]) * work.least_seconds(
        *LW.train_step(prog.cfg, b, s), peak)
    shape = jnp.zeros((b, s), jnp.int32)
    with jax.set_mesh(prog.mesh):
        texts = _texts([prog.step_fn.lower(
            *prog.state, {"tokens": shape, "labels": shape}).compile()])
    prog.free()
    return {"e2e": TN.end_to_end(run, prog), "compiles": compiles,
            "linear_least_s": least}, texts


def readings(workload: str, seed: int, seconds: float, *,
             allow_cpu: bool = False, stand_in: dict = None,
             peak_kind: str = None) -> dict:
    import jax

    from bench import manifest as M, scopes as SC, trace as TR, work
    from bench.cell import Tracer, devices, enable_cache

    # the compile cache's key leaves out op_name metadata by default, so an
    # executable that another tree compiled from the same program would
    # come back with that tree's metadata and scopes
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    c = M.cell(M.load(), workload)
    for part, over in (stand_in or {}).items():
        c[part] = {**c[part], **over}
    dev = devices(c["workload"]["chips"], allow_cpu)[0]
    if not allow_cpu:
        enable_cache()
    peak = work.peaks(peak_kind or dev.device_kind)
    tracer = Tracer(True, cpu=dev.platform == "cpu")
    read = (train_readings if c["traffic"]["kind"] == "train"
            else serve_readings)
    out, texts = read(c, seed, seconds, peak, tracer)
    try:
        events = SC.extract(TR.find_xplane(tracer.dir), cpu=tracer.cpu)
    finally:
        shutil.rmtree(tracer.dir, ignore_errors=True)
    tr = SC.reduce(events, SC.Programs(texts))
    lin = tr["device_scopes"]["linear"]
    out.update(workload=workload, seed=seed, device=dev.device_kind,
               linear_roofline=(100.0 * out["linear_least_s"] / lin
                                if lin else None),
               trace_compiles=tr.pop("compiles"), **tr)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    args = ap.parse_args(argv)
    out = readings(args.workload, args.seed, args.seconds)
    out["wall_s"] = time.perf_counter() - T_START
    print(json.dumps(out, default=float), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
