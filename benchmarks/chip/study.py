"""Readings that set the benchmark's correctness limits and its rates.

Not run by the benchmark.  One process serves many seeds, so the
program's set-up and compiles are paid once:

    # the program's numbers on a dozen seeds, the float8 control's on three
    python3 benchmarks/chip/study.py --workload sc2-7b.decode \
        --seeds 11,12,13,14,15,16,17,18,19,20,21,22 --control 11,12,13

    # a fine-tune step that drops half its batch, on three seeds
    python3 benchmarks/chip/study.py --workload sc2-7b.finetune \
        --seeds 31,32,33 --fault half_batch

    # offered load against TTFT and backlog, one window per rate
    python3 benchmarks/chip/study.py --workload danube-1.8b.chat \
        --seeds 41 --rates 2,3,4,5,6

Each reading is one JSON line on standard output.  The control is the
plain reference computed in float8 (``configs/gqa.py``, ``lower=True``)
in the program's place: for a served cell, at every served position of
the same requests, the reference's gap for the token the control puts
first; for a fine-tune cell, the control's losses, gradients and moves
compared as the program's are.
"""

import argparse
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)), "src"))


def serve_readings(c, seed, seconds, control, warm):
    from bench import manifest as M, serve as SV, weights as W
    from bench.cell import Tracer

    prog = SV.Server(c, seed)
    if warm:
        prog.warm_up()
    run = SV.serve(prog, seconds, Tracer(False))
    e2e = SV.end_to_end(run)
    waiting = sum(1 for r in run["recs"] if not r.ticket.done.is_set())
    prog.free()
    ref = M.reference(c["config"])
    out = {"seed": seed, "e2e": e2e, "unfinished": waiting,
           "checks": SV.check(run, c["config"], c["cell"], seed, ref)}
    lim = c["cell"]["limits"]
    picked = SV.sample(run, seed, lim["checked_tokens"], lim["max_requests"])
    raw = W.make_raw(c["config"], seed)
    for who, lower in (("program", False), ("control", True)):
        if lower and not control:
            continue
        gaps = np.concatenate([SV.reference_gaps(
            ref, c["config"], raw, r.prompt, list(r.ticket.tokens),
            lower=lower) for r in picked])
        out[who] = {"logit_gap_max": float(gaps.max()),
                    "logit_gap_mean": float(gaps.mean()),
                    "flipped": float((gaps > 0).mean())}
    return out


def train_readings(c, seed, control, fault=""):
    from bench import manifest as M, train as TN

    prog = TN.Trainer(c, seed)
    first = prog.first_steps()
    prog.free()
    ref = M.reference(c["config"])
    refs = TN.reference_steps(ref, c["config"], c["traffic"], c["cell"], seed)
    lim = c["cell"]["limits"]
    out = {"seed": seed, "fault": fault, "losses": first["losses"],
           "ref_losses": refs["losses"],
           "checks": TN.compare(first, refs, lim)}
    if control:
        low = TN.reference_steps(ref, c["config"], c["traffic"], c["cell"],
                                 seed, lower=True)
        out["control"] = TN.compare(low, refs, lim)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", default="")
    ap.add_argument("--seconds", type=float, default=30)
    ap.add_argument("--rates", default="")
    ap.add_argument("--fault", default="")
    args = ap.parse_args(argv)
    from bench import manifest as M
    from bench.cell import devices, enable_cache

    c = M.cell(M.load(), args.workload)
    devices(c["workload"]["chips"], allow_cpu=False)
    enable_cache()
    seeds = [int(s) for s in args.seeds.split(",")]
    control = {int(s) for s in args.control.split(",") if s}
    serving = c["traffic"]["kind"] != "train"
    if args.fault:
        from bench.faults import broken_train_fn
        from repro.launch import steps
        steps.make_train_fn = broken_train_fn(steps.make_train_fn, args.fault)
    warm = True
    for seed in seeds:
        for rate in [float(r) for r in args.rates.split(",") if r] or [None]:
            t0 = time.perf_counter()
            if rate is not None:
                c["traffic"] = {**c["traffic"], "rate": rate}
            if serving:
                out = serve_readings(c, seed, args.seconds, seed in control,
                                     warm)
                warm = False
            else:
                out = train_readings(c, seed, seed in control, args.fault)
            out.update(rate=rate, seconds=time.perf_counter() - t0)
            print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
