"""One run of one cell: device check, set-up, window, metrics, check.

``run_cell`` returns the result the command prints as its last line.  Its
keyword arguments after ``trace`` exist for the CPU tests only (the
command line offers none of them): ``allow_cpu`` lets the run proceed
without a TPU, ``stand_in`` replaces entries of the cell's configuration,
traffic and cell files (``{"config": {...}, "traffic": {...}, "cell":
{...}}``) with a tiny stand-in's, and ``peak_kind`` names the row of the
peak table to divide by.
"""

from __future__ import annotations

import gzip
import json
import math
import shutil
import sys
import tempfile
import time

from . import manifest as M
from . import serve as SV
from . import trace as TR
from . import traffic as T
from . import train as TN
from . import work


class NoChip(RuntimeError):
    """No TPU, or fewer chips than the cell asks for."""


class Tracer:
    """The profiler around the window, when the run is traced."""

    def __init__(self, on: bool, cpu: bool = False):
        self.on, self.cpu, self.dir, self.stopped = on, cpu, None, False

    def start(self):
        import jax

        if self.on and self.dir is None:
            self.dir = tempfile.mkdtemp(prefix="chipbench-trace-")
            jax.profiler.start_trace(self.dir)

    def stop(self):
        import jax

        if self.dir is not None and not self.stopped:
            jax.profiler.stop_trace()
            self.stopped = True

    def events(self) -> dict:
        try:
            return TR.extract(TR.find_xplane(self.dir), cpu=self.cpu)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


def devices(chips: int, allow_cpu: bool):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" and not allow_cpu:
        raise NoChip(f"JAX finds no TPU (devices: {devs[0].platform}); "
                     f"this benchmark measures the chip and never falls "
                     f"back to the CPU")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips; JAX finds "
                     f"{len(devs)}")
    return devs[:chips]


def enable_cache():
    """JAX's persistent compilation cache at the checkout's fixed
    ``.jax_cache`` (or where JAX_COMPILATION_CACHE_DIR says), every
    program kept, so only a checkout's first run of a cell compiles."""
    import jax

    from repro.runtime.compile_cache import enable_compile_cache

    where = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return where


def _metrics(declared, read) -> dict:
    out = {}
    for m in declared:
        v = read(m["name"])
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             t_start: float, *, allow_cpu: bool = False,
             stand_in: dict = None, peak_kind: str = None,
             keep_trace: str = None) -> dict:
    c = M.cell(M.load(), workload)
    for part, over in (stand_in or {}).items():
        c[part] = {**c[part], **over}
    devs = devices(c["workload"]["chips"], allow_cpu)
    if not allow_cpu:
        log(f"[cache] {enable_cache()}")
    dev = devs[0]
    peak = work.peaks(peak_kind or dev.device_kind)
    log(f"[device] {len(devs)} x {dev.platform} {dev.device_kind}")
    ref = M.reference(c["config"])
    tracer = Tracer(trace, cpu=dev.platform == "cpu")
    serving = c["traffic"]["kind"] != "train"

    if serving:
        prog = SV.Server(c, seed)
        prog.warm_up()
        run = SV.serve(prog, seconds, tracer)
        e2e = SV.end_to_end(run)
        attempted, failed = SV.accounting(run)
        layer = SV.layer_context(run, prog, peak)
        recs = run["recs"]
        late = T.lateness([r.due for r in recs], [r.sent for r in recs])
        log(f"[generator] lateness {json.dumps(late)}")
    else:
        prog = TN.Trainer(c, seed)
        first = prog.first_steps()
        run = TN.train(prog, seconds, tracer)
        e2e = TN.end_to_end(run, prog)
        attempted = len(run["losses"])
        failed = sum(1 for x in run["losses"] if not math.isfinite(x))
        layer = TN.layer_context(run, prog, peak)
    e2e["setup_s"] = run["t_open"] - t_start
    log(f"[window] {json.dumps(e2e)}")
    stats = dev.memory_stats() or {}
    memory = int(stats.get("peak_bytes_in_use", 0))
    prog.free()

    result = {"correct": None, "attempted": attempted, "failed": failed,
              "metrics": _metrics(c["end_to_end"], e2e.get),
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(devs), "memory_peak_bytes": memory}}
    if trace:
        events = tracer.events()
        if keep_trace:
            with gzip.open(keep_trace, "wt") as f:
                json.dump(events, f)
        tr = TR.reduce(events)
        ctx = {"layer": layer, "trace": tr}
        result["metrics"].update(_metrics(
            c["per_layer"], lambda n: M.metric_reader(n).read(ctx)))
        result["device"].update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        result["breakdown"] = {"device_ops": tr["device_ops"],
                               "idle_gaps": tr["idle_gaps"]}

    t_check = time.perf_counter()
    if serving:
        checks = SV.check(run, c["config"], c["cell"], seed, ref)
        result["correct"] = SV.passed(checks)
    else:
        refs = TN.reference_steps(ref, c["config"], c["traffic"], c["cell"],
                                  seed)
        checks = TN.compare(first, refs, c["cell"]["limits"])
        result["correct"] = TN.passed(checks)
    log(f"[check] {time.perf_counter() - t_check:.1f} s against the plain "
        f"reference")
    result["checks"] = checks
    return result
