"""Shared building blocks for the model zoo.

No flax — modules are (init_fn, apply_fn) pairs over plain pytrees, which
keeps them trivially `scan`-able over layers and `eval_shape`-able for
allocation-free dry-runs.

Every projection matrix goes through :func:`linear_init` /
:func:`linear_apply` from :mod:`repro.core.schemes` — the registered
LinearScheme API (fp / lora / qlora / qalora / intq, plus any scheme a
downstream registers).  Params are tagged :class:`LinearParams`
containers carrying their scheme + resolved :class:`QuantPolicy`, so the
paper's technique is a first-class per-layer policy: pass a uniform
``QuantPolicy`` or a glob-pattern ``PolicyTree`` as ``cfg.quant`` and
thread it through the inits with ``pol.at("name")``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.schemes import (  # noqa: F401  (re-exported API)
    FP, LinearParams, PolicyTree, QuantPolicy, dense_view, linear_apply,
    linear_init, merge_linear)


# ---------------------------------------------------------------------------
# norms / activations / rope
# ---------------------------------------------------------------------------


def rmsnorm_init(d: int, dtype=jnp.float32):
    return {"g": jnp.ones((d,), dtype)}


def rmsnorm(p, x, eps: float = 1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    y = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (y * p["g"].astype(jnp.float32)).astype(x.dtype)


def act_fn(name: str):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu, "relu": jax.nn.relu}[name]


def rope(x, positions, theta: float = 1e4):
    """Rotary embedding. x: [..., seq, n_heads, head_dim]; positions: [..., seq]."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions[..., :, None].astype(jnp.float32) * freqs  # [..., seq, half]
    cos = jnp.cos(ang)[..., :, None, :]  # [..., seq, 1, half]
    sin = jnp.sin(ang)[..., :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [x1f * cos - x2f * sin, x2f * cos + x1f * sin], axis=-1
    ).astype(x.dtype)


# ---------------------------------------------------------------------------
# sharding helpers
# ---------------------------------------------------------------------------


def active_mesh():
    """The mesh entered with ``jax.set_mesh``, or None outside one."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


def mesh_axis_size(*names: str) -> int:
    """Product of the active mesh's sizes along ``names`` (absent axes and
    no active mesh count 1)."""
    mesh = active_mesh()
    n = 1
    if mesh is not None:
        for a in names:
            n *= mesh.shape.get(a, 1)
    return n


def constrain_first(x, candidates):
    """Apply the first candidate spec whose *every* named axis group exists
    on the mesh and divides its dim — unlike :func:`constrain`, which drops
    non-dividing axes per-dim, this treats each candidate atomically (used
    where fallbacks need to re-shard a *different* dim, e.g. MoE dispatch
    buffers: expert-dim EP if it divides, else token-dim DP)."""
    mesh = active_mesh()
    if mesh is None:
        return x
    for spec in candidates:
        spec_t = tuple(spec)
        if len(spec_t) != x.ndim:
            continue
        ok = True
        for dim, names in enumerate(spec_t):
            if names is None:
                continue
            group = names if isinstance(names, tuple) else (names,)
            if any(n not in mesh.shape for n in group):
                ok = False
                break
            size = 1
            for n in group:
                size *= mesh.shape[n]
            if x.shape[dim] % size != 0:
                ok = False
                break
        if ok:
            return constrain(x, spec_t)
    return x


def constrain(x, spec):
    """with_sharding_constraint that is a no-op outside a mesh context."""
    from jax.sharding import PartitionSpec as P
    mesh = active_mesh()
    if mesh is None or spec is None:
        return x
    # right-align the spec against x's rank
    spec = tuple(spec)
    if len(spec) > x.ndim:
        spec = spec[-x.ndim:]
    elif len(spec) < x.ndim:
        spec = (None,) * (x.ndim - len(spec)) + spec
    # drop mesh axes that don't exist on this mesh or don't divide the dim
    axes = []
    for dim, names in enumerate(spec):
        if names is None:
            axes.append(None)
            continue
        group = tuple(n for n in (names if isinstance(names, tuple) else (names,))
                      if n in mesh.shape)
        size = 1
        for n in group:
            size *= mesh.shape[n]
        ok = group and x.shape[dim] % size == 0
        axes.append((group if len(group) > 1 else group[0]) if ok else None)
    return jax.lax.with_sharding_constraint(x, P(*axes))
