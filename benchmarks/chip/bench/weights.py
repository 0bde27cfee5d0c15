"""Weights from the seed, made on the device in one jitted call.

The configuration's family (``families/<family>.py``) draws every array
its plain reference reads, in the types they are served in, from one key
(``raw_weights``), and puts the same arrays into the program's parameter
containers (``fill_program``).  What every family shares is here:
``quant_linear`` draws one quantized projection with its QA-LoRA pair:
INT4 codes as random packed bytes (two codes a byte, the row with the
even index in the low nibble), a bf16 scale and zero point per (group of
``group_size`` input rows, column), and the adapter pair (A: groups x
rank, B: rank x columns, B drawn nonzero so that the adapters move the
logits); ``program_tree`` checks the filled tree against the program's
own init.

Scales are set so that a dequantized weight has standard deviation
1/sqrt(d_in), as a normal initialization would: 16 equally likely codes
have standard deviation sqrt(255/12) in code units.
"""

from __future__ import annotations

import dataclasses
import json
import math

import jax
import jax.numpy as jnp

from . import manifest as M

CODE_STD = math.sqrt((16**2 - 1) / 12)


def key_of(seed: int):
    """A PRNG key for any non-negative seed, including ones past 32 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def _codes(key, shape):
    """Uniform packed bytes [*lead, rows, cols] (cols a multiple of 4)."""
    *lead, cols = shape
    words = jax.random.bits(key, (*lead, cols // 4), jnp.uint32)
    return jax.lax.bitcast_convert_type(words, jnp.uint8).reshape(shape)


def quant_linear(key, cfg: dict, lead: tuple, d_in: int, d_out: int) -> dict:
    """One quantized projection with its QA-LoRA pair, stacked over
    ``lead`` (layers, or layers and experts): packed codes, scale and zero
    point per (group, column), A and B."""
    q, init = cfg["quant"], cfg["init"]
    dt, sdt = getattr(jnp, cfg["dtype"]), getattr(jnp, q["scale_dtype"])
    g, r = q["group_size"], q["rank"]
    n_groups = d_in // g
    base = 1.0 / (CODE_STD * math.sqrt(d_in))
    k = jax.random.split(key, 5)
    scale = base * (1 + 0.25 * jax.random.uniform(
        k[1], (*lead, n_groups, d_out), jnp.float32, -1, 1))
    zero = -scale * (7.5 + 0.5 * jax.random.normal(
        k[2], (*lead, n_groups, d_out), jnp.float32))
    return {
        "codes": _codes(k[0], (*lead, d_in * q["bits"] // 8, d_out)),
        "scale": scale.astype(sdt),
        "zero": zero.astype(sdt),
        "a": normal(k[3], (*lead, n_groups, r), 1 / math.sqrt(n_groups), dt),
        "b": normal(k[4], (*lead, r, d_out), init["adapter_b_std"], dt),
    }


def program_linear(lp, cfg: dict, r: dict):
    """The program's QA-LoRA linear ``lp`` holding the arrays of ``r``
    (as ``quant_linear`` draws them)."""
    from repro.core.qalora import QALoRAParams
    from repro.core.quant import QuantizedLinear

    q = cfg["quant"]
    qt = QuantizedLinear(r["codes"], r["scale"], r["zero"], q["bits"],
                         q["group_size"])
    return dataclasses.replace(lp, data={"q": qt,
                                         "ad": QALoRAParams(r["a"], r["b"])})


def arch_config(cfg: dict):
    """The program's ``ArchConfig`` for ``cfg``: its registered config
    with every size, dtype and quantization setting taken from ``cfg``
    (the keys its family's ``PROGRAM_KEYS`` name)."""
    import repro.configs as C
    from repro.core.schemes import QuantPolicy

    q = cfg["quant"]
    pol = QuantPolicy(mode="qalora", bits=q["bits"], group_size=q["group_size"],
                      rank=q["rank"], s=q["s"], dtype=getattr(jnp, cfg["dtype"]),
                      scale_dtype=getattr(jnp, q["scale_dtype"]))
    return C.get(cfg["program_config"]).scaled(
        quant=pol, **{k: cfg[k] for k in M.family(cfg).PROGRAM_KEYS})


def program_tree(lm, cfg: dict, raw: dict) -> dict:
    """The same arrays in the program's parameter tree (its containers,
    read from ``lm.init``'s shapes, filled by the family).  Raises where a
    leaf is left over or has another shape or type than the program's own
    init gives."""
    want = jax.eval_shape(lm.init, jax.random.PRNGKey(0))
    tree = M.family(cfg).fill_program(want, cfg, raw)
    got_s = jax.tree.map(lambda x: (x.shape, x.dtype), tree)
    want_s = jax.tree.map(lambda x: (x.shape, x.dtype), want)
    if got_s != want_s:
        raise ValueError(f"weights do not match the program's tree for "
                         f"{cfg['name']}")
    return tree


_JITS: dict = {}


def _jit(kind: str, cfg: dict, fn):
    k = (kind, json.dumps(cfg, sort_keys=True))
    if k not in _JITS:
        _JITS[k] = jax.jit(fn)
    return _JITS[k]


def make_raw(cfg: dict, seed: int) -> dict:
    """Every array the plain reference reads, drawn on the device in one
    call by the family's ``raw_weights``."""
    draw = M.family(cfg).raw_weights
    return _jit("raw", cfg, lambda key: draw(cfg, key))(key_of(seed))


def make_program(lm, cfg: dict, seed: int) -> dict:
    """The program's parameter tree, drawn on the device in one call."""
    draw = M.family(cfg).raw_weights
    return _jit("program", cfg, lambda key: program_tree(
        lm, cfg, draw(cfg, key)))(key_of(seed))
