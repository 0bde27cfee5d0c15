"""The gqa family: a pre-norm decoder with grouped-query attention and a
plain or gated MLP (starcoder2, h2o-danube).

Its weights from the seed, how they fill the program's parameter tree,
and the work its forward passes and QA-LoRA steps require, counted from
the configuration and the traffic, never from a compiled program, so the
counts do not move when an implementation does.  ``configs/gqa.py`` is
its plain reference; it reads the arrays ``raw_weights`` draws.
"""

from __future__ import annotations

import dataclasses
from typing import Iterable, Tuple

import jax
import jax.numpy as jnp

from bench import weights as W
from bench.work import DTYPE_BYTES

# the configuration's keys the program's ArchConfig takes over
PROGRAM_KEYS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
                "d_ff", "vocab", "rope_theta", "window", "gated_mlp", "act",
                "norm_eps")

# CPU stand-ins: the cell tests' sizes, and a wider one for the control
# test, so that a run serves enough tokens for near-ties, where a lower
# precision flips the top token
_QUANT = {"bits": 4, "group_size": 32, "rank": 8, "s": 2.0,
          "scale_dtype": "bfloat16"}
_INIT = {"embed_std": 0.02, "head_std": 0.1, "norm_jitter": 0.1,
         "adapter_b_std": 0.01}
TINY = {"n_layers": 2, "d_model": 128, "n_heads": 4, "n_kv_heads": 2,
        "head_dim": 32, "d_ff": 256, "vocab": 512,
        "quant": _QUANT, "init": _INIT}
TINY_CONTROL = {"n_layers": 4, "d_model": 256, "n_heads": 8, "n_kv_heads": 2,
                "head_dim": 32, "d_ff": 512, "vocab": 1024,
                "quant": _QUANT, "init": _INIT}


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------


def raw_weights(cfg: dict, key) -> dict:
    """Embedding, head, norms and every layer's projections, each stacked
    over the layers, drawn from ``key``."""
    L, d, v = cfg["n_layers"], cfg["d_model"], cfg["vocab"]
    init, dt = cfg["init"], getattr(jnp, cfg["dtype"])
    ks = iter(jax.random.split(key, 64))
    nj = init["norm_jitter"]
    raw = {
        "embed": W.normal(next(ks), (v, d), init["embed_std"], dt),
        "head": W.normal(next(ks), (d, v), init["head_std"], dt),
        "final_ln": 1 + W.normal(next(ks), (d,), nj, jnp.float32),
        "ln1": 1 + W.normal(next(ks), (L, d), nj, jnp.float32),
        "ln2": 1 + W.normal(next(ks), (L, d), nj, jnp.float32),
        "lin": {},
    }
    for name, d_in, d_out in linears(cfg):
        raw["lin"][name] = W.quant_linear(next(ks), cfg, (L,), d_in, d_out)
    return raw


def fill_program(tree: dict, cfg: dict, raw: dict) -> dict:
    """``raw`` in the program's tree ``tree`` (shapes from ``lm.init``): a
    projection is found by the last part of its path."""
    from repro.core.schemes import map_linears

    def fill(path, lp):
        if lp.scheme == "fp":
            return dataclasses.replace(lp, data={"w": raw["head"]})
        return W.program_linear(lp, cfg, raw["lin"][path.rsplit("/", 1)[-1]])

    tree = map_linears(tree, fill)
    tree["embed"] = raw["embed"]
    tree["final_ln"] = {"g": raw["final_ln"]}
    tree["blocks"]["ln1"] = {"g": raw["ln1"]}
    tree["blocks"]["ln2"] = {"g": raw["ln2"]}
    return tree


def adapters(raw: dict) -> dict:
    """The trainable adapters of ``raw``: {projection: {"a", "b"}}."""
    return {n: {"a": p["a"], "b": p["b"]} for n, p in raw["lin"].items()}


# ---------------------------------------------------------------------------
# required work
# ---------------------------------------------------------------------------


def linears(cfg: dict) -> Iterable[Tuple[str, int, int]]:
    """(name, d_in, d_out) of one layer's projections."""
    d, hd = cfg["d_model"], cfg["head_dim"]
    q, kv = cfg["n_heads"] * hd, cfg["n_kv_heads"] * hd
    yield "wq", d, q
    yield "wk", d, kv
    yield "wv", d, kv
    yield "wo", q, d
    if cfg["gated_mlp"]:
        yield "gate", d, cfg["d_ff"]
    yield "up", d, cfg["d_ff"]
    yield "down", cfg["d_ff"], d


def linear_params(cfg: dict) -> int:
    """Weights of all quantized projections, every layer."""
    return cfg["n_layers"] * sum(i * o for _, i, o in linears(cfg))


def quantized_bytes(cfg: dict) -> int:
    """INT-N codes plus one scale and one zero point per (group, column)
    of every projection, every layer."""
    q = cfg["quant"]
    sb = DTYPE_BYTES[q["scale_dtype"]]
    per_layer = sum(i * o * q["bits"] // 8 + 2 * (i // q["group_size"]) * o * sb
                    for _, i, o in linears(cfg))
    return cfg["n_layers"] * per_layer


def head_bytes(cfg: dict) -> int:
    return cfg["d_model"] * cfg["vocab"] * DTYPE_BYTES[cfg["dtype"]]


def kv_bytes_per_token(cfg: dict) -> int:
    return (cfg["n_layers"] * 2 * cfg["n_kv_heads"] * cfg["head_dim"]
            * DTYPE_BYTES[cfg["kv_dtype"]])


def _attn_span(cfg: dict, pos: int) -> int:
    """Keys a query at position ``pos`` attends to."""
    w = cfg.get("window") or 0
    return min(pos + 1, w) if w else pos + 1


def attention_flops(cfg: dict, start: int, n: int) -> int:
    """Scores plus weighted values for ``n`` query rows at positions
    ``start..start+n-1`` of one sequence, every layer (causal)."""
    keys = sum(_attn_span(cfg, p) for p in range(start, start + n))
    return cfg["n_layers"] * 4 * cfg["n_heads"] * cfg["head_dim"] * keys


def serve_pass(cfg: dict, rows: Iterable[Tuple[int, int]]):
    """(flops, bytes) one forward pass over a slotted batch requires.

    ``rows`` holds, per slot that consumes tokens in this pass, its
    cache length before the pass and the rows it consumes.  The pass
    reads every quantized weight and the head once, reads each such
    slot's live keys and values and writes its new ones, and takes the
    logits of each slot's last row."""
    rows = [(s, n) for s, n in rows if n > 0]
    tokens = sum(n for _, n in rows)
    flops = (2 * tokens * linear_params(cfg)
             + sum(attention_flops(cfg, s, n) for s, n in rows)
             + 2 * len(rows) * cfg["d_model"] * cfg["vocab"])
    kv = kv_bytes_per_token(cfg)
    nbytes = (quantized_bytes(cfg) + head_bytes(cfg)
              + sum(kv * (s + n) for s, n in rows)
              + tokens * cfg["d_model"] * DTYPE_BYTES[cfg["dtype"]])
    return flops, nbytes


def adapter_params(cfg: dict) -> int:
    q = cfg["quant"]
    return cfg["n_layers"] * sum(
        q["rank"] * (i // q["group_size"] + o) for _, i, o in linears(cfg))


def train_step(cfg: dict, batch: int, seq: int):
    """(flops, bytes) one QA-LoRA step requires: the forward pass and the
    gradient with respect to every activation through the frozen base
    (2 + 2 flops per weight and token, no weight gradient), the adapters'
    forward, input and weight gradients (3 x 2 per adapter weight and
    token), causal attention forward and backward (3 x forward), and the
    head's forward and input gradient.  Recomputation is not counted.
    Bytes: the quantized base, head and adapters read once each."""
    tokens = batch * seq
    d, v = cfg["d_model"], cfg["vocab"]
    flops = (tokens * (4 * linear_params(cfg) + 6 * adapter_params(cfg)
                       + 4 * d * v)
             + batch * 3 * attention_flops(cfg, 0, seq))
    nbytes = (quantized_bytes(cfg) + head_bytes(cfg)
              + adapter_params(cfg) * DTYPE_BYTES[cfg["dtype"]])
    return flops, nbytes


# The ``linear`` scope covers every call of ``schemes.linear_apply``: the
# quantized projections, and in serving the tagged head, which
# ``LM._logits`` applies through ``linear_apply``.  Training applies the
# head through a dense view outside the scope, so its count leaves the
# head out.


def linear_serve_pass(cfg: dict, rows: Iterable[Tuple[int, int]]):
    """(flops, bytes) of one serving pass's linear work: 2 flops per
    quantized weight per consumed row, and the head for each consuming
    slot's last row; every quantized weight and the head read once, and
    the consumed rows' activations."""
    rows = [(s, n) for s, n in rows if n > 0]
    tokens = sum(n for _, n in rows)
    d = cfg["d_model"]
    flops = (2 * tokens * linear_params(cfg)
             + 2 * len(rows) * d * cfg["vocab"])
    nbytes = (quantized_bytes(cfg) + head_bytes(cfg)
              + tokens * d * DTYPE_BYTES[cfg["dtype"]])
    return flops, nbytes


def linear_train_step(cfg: dict, batch: int, seq: int):
    """(flops, bytes) of one QA-LoRA step's linear work: 4 flops per base
    weight and token (forward and input gradient), 6 per adapter weight
    and token; the base and the adapters read once.  Recomputation is not
    counted."""
    tokens = batch * seq
    ad = adapter_params(cfg)
    flops = tokens * (4 * linear_params(cfg) + 6 * ad)
    nbytes = quantized_bytes(cfg) + ad * DTYPE_BYTES[cfg["dtype"]]
    return flops, nbytes
