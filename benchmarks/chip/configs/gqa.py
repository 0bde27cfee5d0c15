"""Plain reference of the gqa family: a pre-norm decoder with grouped-query
attention, rotary positions and a quantized, QA-LoRA-adapted MLP and
attention, written from the published description in float32.

It reads only the raw arrays the benchmark drew from the seed (INT4 codes,
scales, zero points, adapters A and B, norms, embedding, head) and imports
nothing of the program.  Every projection is

    y = x @ W + s * pool(x) @ A @ B,   W[i, j] = code[i, j] * scale[g, j]
                                               + zero[g, j],  g = i // 32

with ``pool`` the sum over each group of input features (QA-LoRA,
arXiv:2309.14717, Algorithm 1).  The reference keeps the adapters apart
from the zero points, so it also checks the merge the program serves.

``lower=True`` computes the same one precision step down, float8 e4m3
(one scale per tensor) wherever the configuration states bfloat16: the
dequantized weights, adapters, embedding and head, and every activation
the program holds in bfloat16 (the residual stream, norm outputs, each
projection's output, queries, keys and values, the attention output, the
MLP's hidden layer, the logits).  Gradients through those points are
rounded the same way, each with its own scale.  It is the control the
comparison must fail.

Departures of the program's model from the published ones, which the
reference follows: RMSNorm everywhere and no biases (starcoder2 uses
LayerNorm and biases), and no sliding window below 4096 positions (where
it never binds).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


def _round8(x):
    # e4m3fn has no infinity: a value past 448 would become NaN, so the
    # scaled tensor is clipped to the range it was scaled to
    s = F8_MAX / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    y = jnp.clip(x * s, -F8_MAX, F8_MAX)
    return y.astype(jnp.float8_e4m3fn).astype(jnp.float32) / s


@jax.custom_vjp
def _f8(x):
    return _round8(x)


_f8.defvjp(lambda x: (_round8(x), None), lambda _, g: (_round8(g),))


def _lo(x, lower):
    """``x`` as float32, held in float8 where ``lower``."""
    x = x.astype(jnp.float32)
    return _f8(x) if lower else x


def _mm(spec, a, b, lower):
    return jnp.einsum(spec, _lo(a, lower), _lo(b, lower), precision=HIGHEST)


def dequantize(codes, scale, zero, group_size):
    """INT4 codes, two to a byte (even row in the low nibble) -> float32."""
    lo, hi = codes & 15, codes >> 4
    q = jnp.stack([lo, hi], axis=-2).reshape(
        codes.shape[:-2] + (2 * codes.shape[-2], codes.shape[-1]))
    d_in, d_out = q.shape[-2:]
    w = (q.reshape(d_in // group_size, group_size, d_out).astype(jnp.float32)
         * scale.astype(jnp.float32)[:, None, :]
         + zero.astype(jnp.float32)[:, None, :])
    return w.reshape(d_in, d_out)


def linear(x, p, cfg, lower=False):
    q = cfg["quant"]
    g = q["group_size"]
    w = dequantize(p["codes"], p["scale"], p["zero"], g)
    pooled = x.reshape(x.shape[:-1] + (x.shape[-1] // g, g)).sum(-1)
    side = _mm("...g,gr->...r", pooled, p["a"], lower)
    return _lo(_mm("...i,io->...o", x, w, lower)
               + q["s"] * _mm("...r,ro->...o", _lo(side, lower), p["b"],
                              lower), lower)


def rmsnorm(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def rope(x, pos, theta):
    half = x.shape[-1] // 2
    freq = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None, None].astype(jnp.float32) * freq
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def gelu_tanh(x):
    return 0.5 * x * (1 + jnp.tanh(math.sqrt(2 / math.pi)
                                   * (x + 0.044715 * x ** 3)))


def block(x, lyr, cfg, lower=False):
    """One decoder layer over one sequence x [T, d]."""
    t = x.shape[0]
    h, kvh, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    pos = jnp.arange(t)
    a = _lo(rmsnorm(x, lyr["ln1"], cfg["norm_eps"]), lower)
    q = _lo(rope(linear(a, lyr["wq"], cfg, lower).reshape(t, h, hd), pos,
                 cfg["rope_theta"]), lower)
    k = _lo(rope(linear(a, lyr["wk"], cfg, lower).reshape(t, kvh, hd), pos,
                 cfg["rope_theta"]), lower)
    v = linear(a, lyr["wv"], cfg, lower).reshape(t, kvh, hd)
    q = q.reshape(t, kvh, h // kvh, hd)
    scores = _mm("qkgd,skd->kgqs", q, k, lower) / math.sqrt(hd)
    mask = pos[None, :] <= pos[:, None]
    if cfg.get("window"):
        mask &= pos[None, :] > pos[:, None] - cfg["window"]
    p = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("kgqs,skd->qkgd", p, v, precision=HIGHEST)
    o = _lo(o.reshape(t, h * hd), lower)
    x = _lo(x + linear(o, lyr["wo"], cfg, lower), lower)
    m = _lo(rmsnorm(x, lyr["ln2"], cfg["norm_eps"]), lower)
    if cfg["gated_mlp"]:
        u = jax.nn.silu(linear(m, lyr["gate"], cfg, lower)) \
            * linear(m, lyr["up"], cfg, lower)
    else:
        u = gelu_tanh(linear(m, lyr["up"], cfg, lower))
    return _lo(x + linear(_lo(u, lower), lyr["down"], cfg, lower), lower)


def _layers(raw, adapters=None):
    """Per-layer arrays stacked on axis 0, for a scan over layers;
    ``adapters`` ({name: {"a", "b"}}) replaces the raw A and B."""
    lin = {n: dict(p) for n, p in raw["lin"].items()}
    for n, ab in (adapters or {}).items():
        lin[n].update(ab)
    return {"ln1": raw["ln1"], "ln2": raw["ln2"], **lin}


def hidden(raw, cfg, tokens, adapters=None, lower=False, remat=False):
    """Final-norm hidden states [T, d] of one sequence of ids [T]."""
    x = _lo(raw["embed"][tokens], lower)
    body = lambda x, lyr: (block(x, lyr, cfg, lower), None)  # noqa: E731
    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, _layers(raw, adapters))
    return _lo(rmsnorm(x, raw["final_ln"], cfg["norm_eps"]), lower)


def _head(h, raw, lower):
    return _lo(_mm("td,dv->tv", h, raw["head"], lower), lower)


def logits(raw, cfg, tokens, lower=False):
    """Logits [T, vocab] of one sequence."""
    return _head(hidden(raw, cfg, tokens, lower=lower), raw, lower)


def loss_sum(adapters, raw, cfg, tokens, labels, lower=False):
    """Sum of next-token cross-entropies over labels >= 0, and their count,
    for one row; ``adapters`` are the trainable A and B."""
    h = hidden(raw, cfg, tokens, adapters, lower=lower, remat=True)
    lg = _head(h, raw, lower)
    lse = jax.nn.logsumexp(lg, -1)
    ll = jnp.take_along_axis(lg, jnp.clip(labels, 0)[:, None], -1)[:, 0]
    ok = (labels >= 0).astype(jnp.float32)
    return jnp.sum((lse - ll) * ok), jnp.sum(ok)
