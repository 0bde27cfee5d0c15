"""Chip benchmark of the QA-LoRA serving and fine-tuning stack: one run of
one cell of ``BENCHMARK.json``.

    python3 benchmarks/chip/run.py --workload sc2-7b.decode --seed 7 \
        --seconds 30 --trace 0

Run from the repository root, on a machine that holds the chips the cell
asks for.  Set-up (weights from the seed, merge, compile or cache load,
warm-up) is timed as ``setup_s``; the window then runs for ``--seconds``;
after it the outputs are compared with the plain reference.  The last line
on standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (with ``--trace 1`` also
``breakdown``), and last ``checks``, each compared number beside its
limit; the same checks end standard error.  Without a TPU, or with fewer
chips than the cell asks for, it prints no result and exits with 3.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)), "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="also write the traced run's extracted events "
                         "(gzipped JSON) to this file")
    args = ap.parse_args(argv)
    try:
        from bench.cell import run_cell

        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), T_START,
                          keep_trace=args.keep_trace)
    except Exception as e:  # noqa: BLE001
        if type(e).__name__ == "NoChip":
            print(f"[chip-bench] {e}", file=sys.stderr, flush=True)
            return 3
        traceback.print_exc()
        print("[chip-bench] FAILED: no result", file=sys.stderr, flush=True)
        return 1
    emit(result)
    return 0


def emit(result: dict):
    """The checks as the last lines of standard error, then the result
    as the last line of standard output, ``checks`` its last key."""
    checks = result.pop("checks")
    for name, v in checks.items():
        print(f"check {name}: {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps({**result, "checks": checks}), flush=True)


if __name__ == "__main__":
    sys.exit(main())
