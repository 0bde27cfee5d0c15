"""Benchmark-side host spans.

Each span is written into the profiler's trace (``TraceAnnotation``)
while a run is traced, so the trace reduction can say what the host was
doing in a device gap; untraced, a span costs next to nothing.
"""

from __future__ import annotations

import jax

SUBMIT = "bench.submit"            # the load generator submits requests
STEP = "bench.frontend_step"       # one ServingFrontend.step (engine dispatch)
COMMIT = "bench.host_commit"       # the client side reads committed tokens
TRAIN = "bench.train_step"         # dispatch one train step and read its loss
WINDOW = "bench.window"            # the measured window
PREFIX = "bench."


def span(name: str):
    return jax.profiler.TraceAnnotation(name)
