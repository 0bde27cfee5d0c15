"""Device meshes.

Single pod: 16x16 = 256 chips, axes ("data", "model").
Multi-pod:  2x16x16 = 512 chips, axes ("pod", "data", "model") — the
"pod" axis crosses the inter-pod DCI links, so only data-parallel traffic
(adapter-gradient all-reduce, periodic compressed sync) rides it.
Local: a (data, model) mesh over this host's first ``data * model``
devices — 1x1 is the one-chip (or one-CPU) mesh the drivers serve on.

Every axis is ``AxisType.Auto``: the models place activations with
``with_sharding_constraint`` (``repro.models.common.constrain``), which
only Auto axes accept.  Callers enter a mesh with ``jax.set_mesh``.

Functions, not module constants: importing this module never touches JAX
device state (the dry-run must set XLA_FLAGS before first device init).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1):
    """("data", "model") mesh over the first ``data * model`` devices of
    ``jax.devices()`` — a TPU chip on the accelerator, a CPU device in
    tests."""
    return auto_mesh((data, model), ("data", "model"),
                     devices=jax.devices()[:data * model])
