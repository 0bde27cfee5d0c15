"""Weights from the seed, made on the device in one jitted call.

``raw_weights`` draws every array the configuration names, in the types
they are served in, from one key: INT4 codes as random packed bytes
(two codes a byte, the row with the even index in the low nibble), a
bf16 scale and zero point per (group of ``group_size`` input rows,
column), and the QA-LoRA adapter pair (A: groups x rank, B: rank x
columns, B drawn nonzero so that the adapters move the logits).  The
plain reference reads these arrays; ``program_tree`` only wraps the same
arrays in the program's parameter containers.

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

from . import work

CODE_STD = math.sqrt((16**2 - 1) / 12)


def key_of(seed: int):
    """A PRNG key for any non-negative seed, including ones past 32 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def _normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def _codes(key, shape):
    """Uniform packed bytes [*lead, rows, cols] (cols a multiple of 4)."""
    *lead, cols = shape
    words = jax.random.bits(key, (*lead, cols // 4), jnp.uint32)
    return jax.lax.bitcast_convert_type(words, jnp.uint8).reshape(shape)


def raw_weights(cfg: dict, key) -> dict:
    L, d, v = cfg["n_layers"], cfg["d_model"], cfg["vocab"]
    q, init = cfg["quant"], cfg["init"]
    dt, sdt = getattr(jnp, cfg["dtype"]), getattr(jnp, q["scale_dtype"])
    g, r = q["group_size"], q["rank"]
    ks = iter(jax.random.split(key, 64))
    nj = init["norm_jitter"]
    raw = {
        "embed": _normal(next(ks), (v, d), init["embed_std"], dt),
        "head": _normal(next(ks), (d, v), init["head_std"], dt),
        "final_ln": 1 + _normal(next(ks), (d,), nj, jnp.float32),
        "ln1": 1 + _normal(next(ks), (L, d), nj, jnp.float32),
        "ln2": 1 + _normal(next(ks), (L, d), nj, jnp.float32),
        "lin": {},
    }
    for name, d_in, d_out in work.linears(cfg):
        n_groups = d_in // g
        base = 1.0 / (CODE_STD * math.sqrt(d_in))
        k = jax.random.split(next(ks), 5)
        scale = base * (1 + 0.25 * jax.random.uniform(
            k[1], (L, n_groups, d_out), jnp.float32, -1, 1))
        zero = -scale * (7.5 + 0.5 * jax.random.normal(
            k[2], (L, n_groups, d_out), jnp.float32))
        raw["lin"][name] = {
            "codes": _codes(k[0], (L, d_in * q["bits"] // 8, d_out)),
            "scale": scale.astype(sdt),
            "zero": zero.astype(sdt),
            "a": _normal(k[3], (L, n_groups, r), 1 / math.sqrt(n_groups), dt),
            "b": _normal(k[4], (L, r, d_out), init["adapter_b_std"], dt),
        }
    return raw


def arch_config(cfg: dict):
    """The program's ``ArchConfig`` for ``cfg``: its registered config
    with every size, dtype and quantization setting taken from ``cfg``."""
    import repro.configs as C
    from repro.core.schemes import QuantPolicy

    q = cfg["quant"]
    pol = QuantPolicy(mode="qalora", bits=q["bits"], group_size=q["group_size"],
                      rank=q["rank"], s=q["s"], dtype=getattr(jnp, cfg["dtype"]),
                      scale_dtype=getattr(jnp, q["scale_dtype"]))
    keys = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
            "d_ff", "vocab", "rope_theta", "window", "gated_mlp", "act",
            "norm_eps")
    return C.get(cfg["program_config"]).scaled(
        quant=pol, **{k: cfg[k] for k in keys})


def program_tree(lm, cfg: dict, raw: dict) -> dict:
    """The same arrays in the program's parameter tree (its containers,
    read from ``lm.init``'s shapes).  Raises where a leaf is left over or
    has another shape or type than the program's own init gives."""
    from repro.core.qalora import QALoRAParams
    from repro.core.quant import QuantizedLinear
    from repro.core.schemes import map_linears

    q = cfg["quant"]
    want = jax.eval_shape(lm.init, jax.random.PRNGKey(0))

    def fill(path, lp):
        if lp.scheme == "fp":
            return dataclasses.replace(lp, data={"w": raw["head"]})
        r = raw["lin"][path.rsplit("/", 1)[-1]]
        qt = QuantizedLinear(r["codes"], r["scale"], r["zero"], q["bits"],
                             q["group_size"])
        return dataclasses.replace(lp, data={"q": qt,
                                             "ad": QALoRAParams(r["a"], r["b"])})

    tree = map_linears(want, fill)
    tree["embed"] = raw["embed"]
    tree["final_ln"] = {"g": raw["final_ln"]}
    tree["blocks"]["ln1"] = {"g": raw["ln1"]}
    tree["blocks"]["ln2"] = {"g": raw["ln2"]}
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
    return _jit("raw", cfg, lambda key: raw_weights(cfg, key))(key_of(seed))


def make_program(lm, cfg: dict, seed: int) -> dict:
    """The program's parameter tree, drawn on the device in one call."""
    return _jit("program", cfg, lambda key: program_tree(
        lm, cfg, raw_weights(cfg, key)))(key_of(seed))
