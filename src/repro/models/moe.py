"""Mixture-of-experts with capacity-based dispatch (EP-shardable).

Routing styles:
  * "softmax"  — Mixtral: softmax over experts, top-k, renormalize.
  * "sigmoid"  — DeepSeek-V3: sigmoid affinity + learned per-expert bias
                 used *only for selection* (aux-loss-free balancing);
                 gates are the normalized sigmoid scores of the selected
                 experts.  Optional shared expert(s) run densely.

Dispatch is sort-free-scatter: positions-within-expert come from a stable
argsort rank (O(Tk log Tk), no [Tk, E] one-hot), token *ids* (int32) are
scattered into an ``[E, C]`` slot table with mode="drop" for capacity
overflow, and the expert compute buffer ``[E, C, d]`` is a gather.  The
expert dim shards over ("data","model") when divisible (expert parallel
across the whole pod); otherwise d_ff shards on "model" (TP inside each
expert).  Sequence chunking (``moe_chunk``) bounds the transient
[T*k, d] combine tensors.

Routers stay fp (tiny, accuracy-critical — standard practice in the
quantization literature, DESIGN.md §Arch-applicability).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .common import (QuantPolicy, linear_init, linear_apply, act_fn,
                     constrain_first, mesh_axis_size)
from .scan_utils import cscan

# dispatch-buffer sharding candidates [E, C, d]: full-mesh EP when the
# expert count divides, else capacity-dim DP (PERF: without the DP
# fallback, every data shard redundantly computes ALL capacity slots —
# found 16x FLOPs waste on mixtral train_4k, see EXPERIMENTS.md §Perf)
_BUF_SPECS = (
    (("pod", "data", "model"), None, None),
    (("data", "model"), None, None),
    ("model", None, None),
    (None, ("pod", "data"), None),
    (None, "data", None),
)

# combine-side sharding for out_buf [E, C, d]: shard the FEATURE dim so the
# token gather is device-local (PERF: gathering from an expert-sharded
# buffer made GSPMD emit a full [T, d] f32 all-reduce per layer-chunk —
# 27.9 TB/device on deepseek-v3 train_4k; resharding E->d first replaces it
# with a small buffer all-to-all).  "model"-only sharding comes FIRST:
# full-mesh feature sharding forced an involuntary-remat reshard back to
# the (dp, model-seq) residual layout (measured: memory term 367s vs 259s
# on deepseek-v3 train_4k).  See EXPERIMENTS.md §Perf.
_COMBINE_SPECS = (
    (None, None, "model"),
    (None, None, ("data", "model")),
    (None, None, ("pod", "data", "model")),
)


def moe_init(key, d_model: int, d_ff: int, n_experts: int, pol: QuantPolicy,
             n_shared: int = 0, shared_d_ff: int = 0, routing: str = "softmax"):
    ks = jax.random.split(key, 5)
    def expert_mat(k, d_in, d_out, name):
        # one stacked init per expert: vmap the linear initializer
        return jax.vmap(lambda kk: linear_init(kk, d_in, d_out, pol.at(name)))(
            jax.random.split(k, n_experts))
    p = {
        "router": linear_init(ks[0], d_model, n_experts, pol.at("router"),
                              quantize_policy=False),
        "gate": expert_mat(ks[1], d_model, d_ff, "gate"),
        "up": expert_mat(ks[2], d_model, d_ff, "up"),
        "down": expert_mat(ks[3], d_ff, d_model, "down"),
    }
    if routing == "sigmoid":
        p["bias"] = jnp.zeros((n_experts,), jnp.float32)  # aux-free balancing bias
    if n_shared:
        from .mlp import mlp_init
        p["shared"] = mlp_init(ks[4], d_model, shared_d_ff * n_shared,
                               pol.at("shared"))
    return p


def _route(p, x2, n_experts: int, top_k: int, routing: str, pol):
    logits = linear_apply(p["router"], x2.astype(jnp.float32), pol)  # [T, E]
    if routing == "softmax":
        probs = jax.nn.softmax(logits, axis=-1)
        gates, idx = jax.lax.top_k(probs, top_k)
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    else:  # sigmoid, aux-loss-free (DeepSeek-V3)
        scores = jax.nn.sigmoid(logits)
        _, idx = jax.lax.top_k(scores + p["bias"][None, :], top_k)
        gates = jnp.take_along_axis(scores, idx, axis=-1)
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balance aux (coefficient applied by caller)
    me = jax.nn.softmax(logits, axis=-1).mean(0)
    ce = jnp.zeros((n_experts,), jnp.float32).at[idx.reshape(-1)].add(1.0)
    ce = ce / jnp.maximum(ce.sum(), 1.0)
    aux = n_experts * jnp.sum(me * ce)
    return gates.astype(x2.dtype), idx, aux


def _positions_in_expert(flat_idx, n_experts: int):
    """Rank of each assignment within its expert, without a [Tk,E] one-hot."""
    tk = flat_idx.shape[0]
    order = jnp.argsort(flat_idx, stable=True)
    ranks = jnp.zeros((tk,), jnp.int32).at[order].set(jnp.arange(tk, dtype=jnp.int32))
    sorted_flat = flat_idx[order]
    first = jnp.searchsorted(sorted_flat, jnp.arange(n_experts), side="left")
    return ranks - first[flat_idx].astype(jnp.int32)


def _expert_ffn(p, buf, pol: QuantPolicy, act: str):
    """buf: [E, C, d] -> [E, C, d], vmapped over the expert dim."""
    def one(gate, up, down, xb):
        h = act_fn(act)(linear_apply(gate, xb, pol)) * linear_apply(up, xb, pol)
        return linear_apply(down, h, pol)
    return jax.vmap(one)(p["gate"], p["up"], p["down"], buf)


def moe_apply(p, x, pol: QuantPolicy, *, n_experts: int, top_k: int,
              capacity_factor: float = 1.25, routing: str = "softmax",
              act: str = "silu", moe_chunk: int = 0):
    """x: [B, S, d] -> (y, aux_loss)."""
    b, s, d = x.shape
    if moe_chunk and s > moe_chunk:
        assert s % moe_chunk == 0
        nc = s // moe_chunk
        xs = x.reshape(b, nc, moe_chunk, d).transpose(1, 0, 2, 3)

        def step(aux, xc):
            yc, a = _moe_tokens(p, xc, pol, n_experts, top_k, capacity_factor,
                                routing, act)
            return aux + a, yc

        aux, ys = cscan(step, jnp.float32(0.0), xs, name="moe_chunk")
        return ys.transpose(1, 0, 2, 3).reshape(b, s, d), aux / nc
    return _moe_tokens(p, x, pol, n_experts, top_k, capacity_factor, routing, act)


def _moe_tokens(p, x, pol, n_experts, top_k, capacity_factor, routing, act):
    b, s, d = x.shape
    t = b * s
    x2 = x.reshape(t, d)
    gates, idx, aux = _route(p, x2, n_experts, top_k, routing, pol)

    cap = int(math.ceil(top_k * t / n_experts * capacity_factor))
    cap = max(cap, 1)
    flat = idx.reshape(-1)  # [T*k]
    pos = _positions_in_expert(flat, n_experts)
    tok = jnp.repeat(jnp.arange(t, dtype=jnp.int32), top_k)
    keep = pos < cap
    # OOB rows (dropped tokens) -> scatter mode="drop"
    e_ix = jnp.where(keep, flat, n_experts)
    p_ix = jnp.where(keep, pos, cap)

    slot_tok = jnp.full((n_experts, cap), t, jnp.int32)  # t == "no token"
    slot_tok = slot_tok.at[e_ix, p_ix].set(tok, mode="drop")
    x2p = jnp.concatenate([x2, jnp.zeros((1, d), x2.dtype)], 0)  # pad row
    # Full-mesh EP (expert count divides the whole mesh): feature-shard the
    # token table and the combine buffer so both gathers are device-local
    # (otherwise GSPMD emits full [T, d] all-gathers/all-reduces — measured
    # 20x collective cut on deepseek-v3).  In the TP-fallback regime this
    # resharding HURTS (measured on mixtral: useful 0.74 -> 0.20), so it is
    # gated on divisibility.  EXPERIMENTS.md §Perf records both runs.
    ep = n_experts % mesh_axis_size("pod", "data", "model") == 0
    if ep:
        x2p = constrain_first(x2p, [s[1:] for s in _COMBINE_SPECS])
    buf = x2p[slot_tok]  # [E, C, d] gather
    buf = constrain_first(buf, _BUF_SPECS)

    out_buf = _expert_ffn(p, buf, pol, act)
    out_buf = out_buf.astype(x.dtype)
    out_buf = constrain_first(out_buf, _COMBINE_SPECS if ep else _BUF_SPECS)

    # combine: gather each assignment's row, weight by gate, sum over k
    # (feature-sharded buffer -> the gather is local per device)
    rows = out_buf[e_ix.clip(0, n_experts - 1), p_ix.clip(0, cap - 1)]  # [Tk, d]
    rows = jnp.where(keep[:, None], rows, 0)
    y = (rows.reshape(t, top_k, d) * gates[..., None].astype(x.dtype)).sum(axis=1)

    if "shared" in p:
        from .mlp import mlp_apply
        y = y + mlp_apply(p["shared"], x2, pol, act)
    return y.reshape(b, s, d).astype(x.dtype), aux
