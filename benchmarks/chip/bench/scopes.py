"""Profiler trace -> device seconds per named scope, and idle gaps named by
the program's own spans.

The program writes host spans (``repro.*``, ``repro.runtime.tracing``)
and names its device work with scopes (``linear``, ``attention``,
``kv_insert``, ``optimizer``).  A TPU trace gives each operation only its
HLO instruction name, so the scope of an operation is read from the
``op_name`` metadata of that instruction in the compiled program of the
module running it (``XLA Modules`` line); the compiled programs' text is
passed in.  Two stages, as in ``trace.py``:

  extract(path)          the ``.xplane.pb`` -> plain events: per device
                         its operations as (name, start_ns, duration_ns,
                         module), and the host spans of the benchmark
                         (``bench.*``), of the program (``repro.*``) and
                         jax's compile events
  reduce(events, hlo)    the window's busy seconds split by scope (the
                         rest as ``other``), idle gaps each named by the
                         innermost span covering most of it, idle seconds
                         per innermost span, and compiles in the window
"""

from __future__ import annotations

import bisect
import re
from collections import defaultdict
from typing import Dict, List, Tuple

from repro.runtime import tracing

from . import spans as S
from . import trace as TR

# jax's host events of a compile or a persistent-cache load: the python
# tracer's frames (file:line function) and the TPU client's own
COMPILE = re.compile(r"(compile_or_get_cached|PJRT_Client_Compile"
                     r"|TpuCompiler::Compile)$")
COMPILE_LABEL = "jax.compile"
MODULE = re.compile(r"^(.*)\((\d+)\)$")      # jit__burst_steps(7589...)
NAME = re.compile(r"^\s*(?:ROOT )?%([\w.-]+) = ")
INSTR = re.compile(NAME.pattern + r".*?metadata=\{op_name=\"([^\"]*)\"")
SCOPE_RE = {s: re.compile(rf"(?<![\w-]){re.escape(s)}(?![\w-])")
            for s in tracing.SCOPES}
OTHER = "other"


def _host_name(name: str):
    """The name a host event is kept under, or None."""
    if name.startswith((S.PREFIX, tracing.PREFIX)):
        return name
    if COMPILE.search(name):
        return COMPILE_LABEL
    return None


def extract(path: str, cpu: bool = False) -> dict:
    """``cpu``: a CPU run has no device plane; its XLA operations, on the
    host's XLA client threads, stand in for one (tests only)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: Dict[str, list] = {}
    host: List[tuple] = []
    for plane in pd.planes:
        if TR.DEVICE_PLANE.match(plane.name):
            lines = {ln.name: list(ln.events) for ln in plane.lines}
            mods = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                          for e in lines.get("XLA Modules", ()))
            ops = []
            for ln in TR.OP_LINES:
                for e in lines.get(ln, ()):
                    ops.append((TR.op_name(e.name), e.start_ns,
                                e.duration_ns, _module_at(mods, e.start_ns)))
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    n = _host_name(e.name)
                    if n is not None:
                        host.append((n, e.start_ns, e.duration_ns))
                if cpu and line.name.startswith("tf_XLA"):
                    for e in line.events:
                        st = dict(e.stats)
                        if "hlo_op" in st:
                            mod = f"{st['hlo_module']}({st['program_id']})"
                            devices.setdefault("cpu", []).append(
                                (e.name, e.start_ns, e.duration_ns, mod))
    return {"devices": devices, "host": host}


def _module_at(mods, t) -> str:
    """The module event (sorted [(start, end, name)]) running at ``t``."""
    i = bisect.bisect_right(mods, (t, float("inf"), "")) - 1
    return mods[i][2] if i >= 0 and t < mods[i][1] else ""


def op_scopes(hlo_text: str) -> Dict[str, str]:
    """{instruction name: scope} of one compiled module's text, for the
    instructions whose ``op_name`` names one of the program's scopes (the
    innermost, where several nest)."""
    out = {}
    for line in hlo_text.splitlines():
        m = INSTR.match(line)
        if not m:
            continue
        name, path = m.groups()
        best, at = None, -1
        for s, rx in SCOPE_RE.items():
            for hit in rx.finditer(path):
                if hit.start() > at:
                    best, at = s, hit.start()
        if best is not None:
            out[name] = best
    return out


def instructions(hlo_text: str) -> set:
    return {m.group(1) for m in map(NAME.match, hlo_text.splitlines()) if m}


class Programs:
    """The compiled programs a trace ran, by module name: each module
    event's program is the text of that name whose instructions cover
    most of the operations the event ran."""

    def __init__(self, texts: Dict[str, List[str]]):
        self.cands = {name: [(instructions(t), op_scopes(t)) for t in ts]
                      for name, ts in texts.items()}
        self.picked: Dict[str, Tuple[set, dict]] = {}

    def pick(self, module: str, ops: List[str]):
        if module not in self.picked:
            m = MODULE.match(module)
            cands = self.cands.get(m.group(1) if m else module, [])
            self.picked[module] = max(
                cands, key=lambda c: sum(o in c[0] for o in ops),
                default=(set(), {}))
        return self.picked[module]


def _window(events: dict):
    wins = [(s, s + d) for n, s, d in events["host"] if n == S.WINDOW]
    if not wins:
        raise ValueError("the trace holds no bench.window span")
    return wins[0]


def label(a: float, b: float, host: List[tuple]) -> str:
    """The span covering most of [a, b]; of spans covering equally much,
    the shortest (the innermost)."""
    best, label_ = (0.0, 0.0), "no host span"
    for name, s, d in host:
        if name == S.WINDOW:
            continue
        cover = min(b, s + d) - max(a, s)
        if cover > 0 and (cover, -d) > best:
            best, label_ = (cover, -d), _short(name)
    return label_


def _short(name: str) -> str:
    for p in (S.PREFIX, tracing.PREFIX):
        if name.startswith(p):
            return name[len(p):]
    return name


def idle_by_span(gaps, host) -> Dict[str, float]:
    """Idle seconds by the innermost (shortest) span open at each instant
    of the gaps (``no host span`` where none is): one sweep over the
    spans' and gaps' ends."""
    ev = []
    for i, (name, s, d) in enumerate(host):
        if name != S.WINDOW:
            ev += [(s, 1, i), (s + d, 0, i)]
    for a, b in gaps:
        ev += [(a, 1, -1), (b, 0, -1)]
    ev.sort()
    active, in_gap, prev = {}, 0, None
    out: Dict[str, float] = defaultdict(float)
    for t, start, i in ev:
        if in_gap and t > prev:
            inner = min(active.values())[1] if active else "no host span"
            out[inner] += (t - prev) * 1e-9
        prev = t
        if i < 0:
            in_gap += 1 if start else -1
        elif start:
            active[i] = (host[i][2], _short(host[i][0]))
        else:
            active.pop(i, None)
    return dict(out)


def compiles(host, w0: float, w1: float) -> int:
    """Compiles or cache loads that began in [w0, w1): the compile events
    there, nested ones counted once."""
    return len(TR._union([(s, s + d) for n, s, d in host
                          if n == COMPILE_LABEL and w0 <= s < w1]))


def reduce(events: dict, programs: Programs, top: int = TR.TOP) -> dict:
    """busy_s, device seconds per scope (``other`` the rest of busy),
    device seconds of operations found in no program (``unmapped_s``),
    the longest idle gaps [[label, seconds]] and each one's seconds by
    innermost span (``gap_parts``), idle seconds by innermost span over
    all gaps, and the compile events that began inside the window."""
    w0, w1 = _window(events)
    scoped = defaultdict(float)
    busy, unmapped, gaps = [], 0.0, []
    for dev in sorted(events["devices"]):
        ops = events["devices"][dev]
        by_mod = defaultdict(list)
        for op, _, _, mod in ops:
            by_mod[mod].append(op)
        spans = []
        for op, s, d, mod in ops:
            a, b = max(s, w0), min(s + d, w1)
            if b <= a:
                continue
            spans.append((a, b))
            if op.startswith(TR.CONTAINERS):
                continue
            names, scopes = programs.pick(mod, by_mod[mod])
            if op not in names:
                unmapped += (b - a) * 1e-9
            sc = scopes.get(op)
            if sc is not None:
                scoped[sc] += (b - a) * 1e-9
        u = TR._union(spans)
        busy.append(sum(b - a for a, b in u) * 1e-9)
        edges = [w0] + [x for iv in u for x in iv] + [w1]
        gaps += [(a, b) for a, b in zip(edges[::2], edges[1::2])
                 if b - a >= TR.MIN_GAP_NS]
    if not busy:
        raise ValueError("the trace holds no device plane")
    n = len(busy)
    busy_s = sum(busy) / n
    scopes = {s: scoped[s] / n for s in tracing.SCOPES}
    scopes[OTHER] = busy_s - sum(scopes.values())
    host = events["host"]
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    return {
        "window_s": (w1 - w0) * 1e-9, "busy_s": busy_s,
        "device_scopes": scopes, "unmapped_s": unmapped / n,
        "idle_gaps": [[label(a, b, host), (b - a) * 1e-9] for a, b in longest],
        "gap_parts": [idle_by_span([g], host) for g in longest],
        "idle_by_span": {k: v / n for k, v in
                         idle_by_span(gaps, host).items()},
        "compiles": compiles(host, w0, w1),
    }
