"""Names of the program's own host spans and device scopes.

Host spans (:func:`span`) are ``jax.profiler.TraceAnnotation`` objects.
While a profiler runs (``jax.profiler.start_trace`` or
``jax.profiler.start_server``) each span lands in the trace on the same
clock as the device's operations, so a gap in which the device waits can
be named by what the host was doing.  With no profiler running a span
costs about a microsecond.

Device scopes (:func:`scope`) are ``jax.named_scope``: every operation
traced inside one carries the scope's name in its ``op_name`` metadata in
the compiled program.  They change nothing the compiler fuses or runs.

The profiler keeps what these record; this module holds no state.
"""

from __future__ import annotations

import jax

PREFIX = "repro."

# host spans of one ServingFrontend.step, outermost first
FRONTEND_STEP = "repro.frontend.step"   # the whole frontend iteration
INTAKE = "repro.frontend.intake"        # drain, cancels, deadlines, intake
ADMIT = "repro.engine.admit"            # slot admission, reset, pages
PLAN = "repro.engine.plan"              # the plan and its input arrays
DISPATCH = "repro.engine.dispatch"      # enqueue the jitted call(s)
SYNC = "repro.engine.sync"              # the host waits for the device
COMMIT = "repro.engine.commit"          # commit tokens, count
HARVEST = "repro.frontend.harvest"      # fold results into tickets

# device scopes
LINEAR = "linear"         # every projection (schemes.linear_apply)
ATTENTION = "attention"   # the attention cores
KV_INSERT = "kv_insert"   # writing new keys and values into the cache
OPTIMIZER = "optimizer"   # the AdamW update of a train step
SCOPES = (LINEAR, ATTENTION, KV_INSERT, OPTIMIZER)


def span(name: str):
    """A host span named ``name`` (a context manager)."""
    return jax.profiler.TraceAnnotation(name)


def scope(name: str):
    """A device scope named ``name`` (a context manager)."""
    return jax.named_scope(name)
