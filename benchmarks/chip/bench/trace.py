"""Profiler trace -> device busy time, idle gaps and top operations.

Two stages, so the second can be tested on a small recorded trace:

  extract(path)   the ``.xplane.pb`` the JAX profiler wrote -> plain
                  events: per TPU device the operations it ran, and the
                  benchmark's own host spans (``bench.*``), as
                  (name, start_ns, duration_ns)
  reduce(events)  busy = the union of each device's operation intervals
                  inside the ``bench.window`` span, averaged over the
                  devices; idle share = 1 - busy / window; the operations
                  that took most time, by the names the trace gives; and
                  the longest gaps with no operation, each labelled by the
                  host span that covers most of it
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Tuple

from . import spans as S

Event = Tuple[str, float, float]
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINES = ("XLA Ops",)
# control flow spans the operations of its body on the same line: it
# counts toward busy time, not among the operations that took most time
CONTAINERS = ("while", "conditional", "call")
MIN_GAP_NS = 1000
TOP = 10


def find_xplane(logdir: str) -> str:
    found = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return found[-1]


def op_name(text: str) -> str:
    """The instruction's name from a TPU op event, which carries the whole
    HLO line (``%fusion.3 = bf16[...] fusion(...), ...``)."""
    return text.split(" = ", 1)[0].lstrip("%")


def extract(path: str, cpu: bool = False) -> dict:
    """``cpu``: a CPU run has no device plane; its XLA operations, on the
    host's XLA client threads, stand in for one (tests only)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = []
            for line in plane.lines:
                if line.name in OP_LINES:
                    ops += [(op_name(e.name), e.start_ns, e.duration_ns)
                            for e in line.events]
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(e.name, e.start_ns, e.duration_ns)
                         for e in line.events
                         if e.name.startswith(S.PREFIX)]
                if cpu and line.name.startswith("tf_XLA"):
                    devices.setdefault("cpu", []).extend(
                        (e.name, e.start_ns, e.duration_ns)
                        for e in line.events if "hlo_op" in dict(e.stats))
    return {"devices": devices, "host": host}


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _label(a: float, b: float, host: List[Event]) -> str:
    best, label = 0.0, "no host span"
    for name, s, d in host:
        if name == S.WINDOW:
            continue
        cover = min(b, s + d) - max(a, s)
        if cover > best:
            best, label = cover, name[len(S.PREFIX):]
    return label


def reduce(events: dict, top: int = TOP) -> dict:
    """window_s, busy_s (mean over devices), idle_share, device_ops
    [[name, seconds]] and idle_gaps [[host span, seconds]]."""
    wins = [(s, s + d) for n, s, d in events["host"] if n == S.WINDOW]
    if not wins:
        raise ValueError("the trace holds no bench.window span")
    w0, w1 = wins[0]
    busy, ops, gaps = [], defaultdict(float), []
    for name in sorted(events["devices"]):
        spans = []
        for op, s, d in events["devices"][name]:
            a, b = max(s, w0), min(s + d, w1)
            if b > a:
                spans.append((a, b))
                if not op.startswith(CONTAINERS):
                    ops[op] += (b - a) * 1e-9
        u = _union(spans)
        busy.append(sum(b - a for a, b in u) * 1e-9)
        edges = [w0] + [x for iv in u for x in iv] + [w1]
        gaps += [(b - a, a, b) for a, b in zip(edges[::2], edges[1::2])
                 if b - a >= MIN_GAP_NS]
    if not busy:
        raise ValueError("the trace holds no TPU device plane")
    window = (w1 - w0) * 1e-9
    busy_s = sum(busy) / len(busy)
    gaps.sort(reverse=True)
    return {
        "window_s": window, "busy_s": busy_s,
        "idle_share": 1 - busy_s / window,
        "device_ops": [[n, s] for n, s in sorted(ops.items(),
                                                  key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[_label(a, b, events["host"]), g * 1e-9]
                      for g, a, b in gaps[:top]],
    }
