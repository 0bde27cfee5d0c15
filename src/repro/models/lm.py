"""Model assembly: every assigned architecture as a scan-over-layers LM.

Families
--------
  gqa          dense decoder (pixtral/gemma3/starcoder2/h2o-danube/deepseek-67b)
  gqa_moe      Mixtral (GQA + top-k MoE FFN)
  mla_moe      DeepSeek-V3 (MLA + 256-expert MoE + shared expert + MTP)
  mamba_hybrid Zamba2 (Mamba2 stack + periodic shared attention block)
  rwkv         RWKV6 (time-mix + channel-mix)
  encdec       Seamless-M4T (audio-frontend encoder + causal decoder)

Params are plain pytrees; layer stacks are leading-axis-stacked and applied
with :func:`cscan` (roofline-countable).  Every projection routes through
the QuantPolicy (fp / lora / qlora / qalora), so the paper's technique is a
config switch across all ten architectures.

Batch format: {"tokens": [B,St] int32, "labels": [B,St] int32 (-1 = pad)}
plus "frontend" [B,F,d] for vlm and "src" [B,Ss,d] for audio enc-dec —
modality frontends are stubs per the assignment (precomputed embeddings).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core import schemes
from .common import QuantPolicy, linear_init, linear_apply, rmsnorm, rmsnorm_init, constrain
from .attention import (_kv_up_split, gqa_init, gqa_apply,
                        gqa_prefill_chunk, mla_init, mla_apply,
                        mla_prefill_chunk, cross_init, cross_kv,
                        cross_apply, cross_chunk)
from .mlp import mlp_init, mlp_apply
from .moe import moe_init, moe_apply
from .ssm import (mamba2_init, mamba2_mix, mamba2_chunk_step, rwkv6_init,
                  rwkv6_time_mix, rwkv6_channel_mix, rwkv6_time_mix_ragged,
                  rwkv6_channel_mix_ragged)
from .slot_state import (SlotState, attn_cfg as _attn_cfg,
                         mla_cfg as _mla_cfg, mamba_cfg as _mamba_cfg,
                         rwkv_cfg as _rwkv_cfg, hybrid_layout)
from .scan_utils import cscan


# ---------------------------------------------------------------------------
# per-family transformer blocks
# ---------------------------------------------------------------------------


def _gqa_block_init(key, cfg: ArchConfig, pol: QuantPolicy, moe: bool = False):
    ks = jax.random.split(key, 4)
    p = {"ln1": rmsnorm_init(cfg.d_model), "ln2": rmsnorm_init(cfg.d_model),
         "attn": gqa_init(ks[0], _attn_cfg(cfg), pol.at("attn"))}
    if moe:
        p["moe"] = moe_init(ks[1], cfg.d_model, cfg.moe_d_ff or cfg.d_ff,
                            cfg.n_experts, pol.at("moe"),
                            n_shared=cfg.n_shared_experts,
                            shared_d_ff=cfg.moe_d_ff or cfg.d_ff,
                            routing=cfg.routing)
    else:
        p["mlp"] = mlp_init(ks[1], cfg.d_model, cfg.d_ff, pol.at("mlp"),
                            cfg.gated_mlp)
    return p


def _gqa_block(p, x, cfg: ArchConfig, pol, *, window=None, theta=None,
               positions=None, moe=False):
    a, kv = gqa_apply(p["attn"], rmsnorm(p["ln1"], x, cfg.norm_eps),
                      _attn_cfg(cfg), pol, positions=positions,
                      window=window, theta=theta,
                      chunk_q=cfg.chunk_q, chunk_k=cfg.chunk_k)
    x = x + a
    aux = jnp.float32(0.0)
    h = rmsnorm(p["ln2"], x, cfg.norm_eps)
    if moe:
        m, aux = moe_apply(p["moe"], h, pol, n_experts=cfg.n_experts,
                           top_k=cfg.top_k, capacity_factor=cfg.capacity_factor,
                           routing=cfg.routing, act=cfg.act,
                           moe_chunk=cfg.moe_chunk)
    else:
        m = mlp_apply(p["mlp"], h, pol, cfg.act)
    return x + m, kv, aux


def _gqa_block_chunk(p, x, cache, cur_len, n_new, cfg: ArchConfig, pol, *,
                     window=None, theta=None, moe=False, pages=None):
    """Ragged chunk through one block: x [B,C,d], per-slot n_new consumed.
    ``pages`` ([B,P] int32) switches the KV leaves to paged pools."""
    a, cache = gqa_prefill_chunk(p["attn"], rmsnorm(p["ln1"], x, cfg.norm_eps),
                                 cache, cur_len, n_new, _attn_cfg(cfg), pol,
                                 window=window, theta=theta, pages=pages)
    x = x + a
    h = rmsnorm(p["ln2"], x, cfg.norm_eps)
    if moe:
        m, _ = moe_apply(p["moe"], h, pol, n_experts=cfg.n_experts,
                         top_k=cfg.top_k, capacity_factor=cfg.capacity_factor,
                         routing=cfg.routing, act=cfg.act, moe_chunk=0)
    else:
        m = mlp_apply(p["mlp"], h, pol, cfg.act)
    return x + m, cache


def _mla_block_init(key, cfg: ArchConfig, pol, moe: bool):
    ks = jax.random.split(key, 2)
    p = {"ln1": rmsnorm_init(cfg.d_model), "ln2": rmsnorm_init(cfg.d_model),
         "attn": mla_init(ks[0], _mla_cfg(cfg), pol.at("attn"))}
    if moe:
        p["moe"] = moe_init(ks[1], cfg.d_model, cfg.moe_d_ff, cfg.n_experts,
                            pol.at("moe"), n_shared=cfg.n_shared_experts,
                            shared_d_ff=cfg.moe_d_ff, routing=cfg.routing)
    else:
        p["mlp"] = mlp_init(ks[1], cfg.d_model, cfg.d_ff, pol.at("mlp"),
                            cfg.gated_mlp)
    return p


def _mla_block(p, x, cfg, pol, *, positions=None, moe=False):
    a, _ = mla_apply(p["attn"], rmsnorm(p["ln1"], x, cfg.norm_eps),
                     _mla_cfg(cfg), pol, positions=positions)
    x = x + a
    aux = jnp.float32(0.0)
    h = rmsnorm(p["ln2"], x, cfg.norm_eps)
    if moe:
        m, aux = moe_apply(p["moe"], h, pol, n_experts=cfg.n_experts,
                           top_k=cfg.top_k, capacity_factor=cfg.capacity_factor,
                           routing=cfg.routing, act=cfg.act,
                           moe_chunk=cfg.moe_chunk)
    else:
        m = mlp_apply(p["mlp"], h, pol, cfg.act)
    return x + m, aux


def _mla_block_prefill(p, x, cfg, pol, moe=False):
    """Like _mla_block but returns the compressed cache."""
    a, ckv = mla_apply(p["attn"], rmsnorm(p["ln1"], x, cfg.norm_eps),
                       _mla_cfg(cfg), pol)
    x = x + a
    h = rmsnorm(p["ln2"], x, cfg.norm_eps)
    if moe:
        m, _ = moe_apply(p["moe"], h, pol, n_experts=cfg.n_experts,
                         top_k=cfg.top_k, capacity_factor=cfg.capacity_factor,
                         routing=cfg.routing, act=cfg.act,
                         moe_chunk=cfg.moe_chunk)
    else:
        m = mlp_apply(p["mlp"], h, pol, cfg.act)
    return x + m, ckv


def _mla_block_chunk(p, x, cache, cur_len, n_new, cfg, pol, *, moe=False,
                     w_kv=None, pages=None):
    """Ragged chunk through one MLA block: x [B,C,d], per-slot n_new
    consumed.  ``w_kv`` optionally carries this layer's precomputed
    absorbed (W_uk, W_uv) so no dequant runs in the step graph; ``pages``
    ([B,P] int32) switches the compressed cache to paged pools."""
    a, cache = mla_prefill_chunk(p["attn"], rmsnorm(p["ln1"], x, cfg.norm_eps),
                                 cache, cur_len, n_new, _mla_cfg(cfg), pol,
                                 w_kv=w_kv, pages=pages)
    x = x + a
    h = rmsnorm(p["ln2"], x, cfg.norm_eps)
    if moe:
        m, _ = moe_apply(p["moe"], h, pol, n_experts=cfg.n_experts,
                         top_k=cfg.top_k, capacity_factor=cfg.capacity_factor,
                         routing=cfg.routing, act=cfg.act, moe_chunk=0)
    else:
        m = mlp_apply(p["mlp"], h, pol, cfg.act)
    return x + m, cache


# ---------------------------------------------------------------------------
# LM
# ---------------------------------------------------------------------------



def _sp(x, cfg=None):
    """Sequence-parallel residual constraint between layers (PERF: without
    it the rematted per-layer residual stack is replicated over the model
    axis — 95 x 1.07GB/device on deepseek-67b train_4k; with SP it shards
    seq over "model" for a 16x cut.  Gated per-arch: it pessimizes
    chunked-recurrence mixers.  See EXPERIMENTS.md §Perf)."""
    if cfg is not None and not cfg.seq_parallel:
        return x
    return constrain(x, (("pod", "data"), "model", None))

def _layer_map(fn, keys):
    """Initialize a stack of layers one key at a time.  ``lax.map`` holds
    a single layer's f32 weight draw; ``vmap`` would draw every layer's
    at once (32 x [4608, 18432] f32 = 10.9 GB for one starcoder2-7b MLP
    matrix — more than a 16 GB chip holds next to the model)."""
    return jax.lax.map(fn, keys)


def _maybe_remat(fn, cfg: ArchConfig):
    return jax.checkpoint(fn) if cfg.remat else fn


@dataclasses.dataclass(frozen=True)
class LM:
    cfg: ArchConfig

    # ---------------- init ----------------

    def init(self, key) -> Dict[str, Any]:
        cfg, pol = self.cfg, self.cfg.quant
        ks = jax.random.split(key, 8)
        d = cfg.d_model
        params: Dict[str, Any] = {
            "embed": jax.random.normal(ks[0], (cfg.vocab, d), pol.dtype) * 0.02,
            "final_ln": rmsnorm_init(d),
        }
        if not cfg.tie_embeddings:
            # lm_head is exempt from catch-all quantization rules; an
            # explicit "lm_head=..." policy rule opts it in.
            w = jax.random.normal(ks[1], (d, cfg.vocab), pol.dtype) * 0.02
            hpol = schemes.resolve_path(pol, "lm_head")
            params["head"] = (schemes.dense_linear(w, hpol)
                              if hpol.mode == "fp"
                              else schemes.from_dense_linear(
                                  jax.random.fold_in(ks[1], 1), w, hpol))

        fam = cfg.family
        if fam in ("gqa", "gqa_moe"):
            moe = fam == "gqa_moe"
            bpol = pol.at("blocks")
            params["blocks"] = _layer_map(
                lambda k: _gqa_block_init(k, cfg, bpol, moe),
                jax.random.split(ks[2], cfg.n_layers))
        elif fam == "mla_moe":
            nd = cfg.n_dense_layers
            dpol, mpol = pol.at("dense_blocks"), pol.at("moe_blocks")
            params["dense_blocks"] = _layer_map(
                lambda k: _mla_block_init(k, cfg, dpol, False),
                jax.random.split(ks[2], nd))
            params["moe_blocks"] = _layer_map(
                lambda k: _mla_block_init(k, cfg, mpol, True),
                jax.random.split(ks[3], cfg.n_layers - nd))
            if cfg.mtp:
                params["mtp_proj"] = linear_init(ks[4], 2 * d, d,
                                                 pol.at("mtp_proj"),
                                                 quantize_policy=False)
                params["mtp_block"] = _mla_block_init(ks[5], cfg,
                                                      pol.at("mtp_block"), False)
                params["mtp_ln"] = rmsnorm_init(d)
        elif fam == "mamba_hybrid":
            n_groups, per, tail = self._hybrid_layout()
            mcfg = _mamba_cfg(cfg)
            gpol, tpol = pol.at("mamba_groups"), pol.at("mamba_tail")
            params["mamba_groups"] = _layer_map(
                lambda kg: _layer_map(lambda k: mamba2_init(k, mcfg, gpol), kg),
                jax.random.split(ks[2], n_groups * per).reshape(n_groups, per, 2))
            params["mamba_tail"] = _layer_map(
                lambda k: mamba2_init(k, mcfg, tpol), jax.random.split(ks[3], tail))
            params["shared_attn"] = _gqa_block_init(ks[4], cfg,
                                                    pol.at("shared_attn"), False)
        elif fam == "rwkv":
            rcfg = _rwkv_cfg(cfg)
            bpol = pol.at("blocks")
            def blk(k):
                k1, k2 = jax.random.split(k)
                return {"ln1": rmsnorm_init(d), "ln2": rmsnorm_init(d),
                        "mix": rwkv6_init(k1, rcfg, bpol.at("mix"))}
            params["blocks"] = _layer_map(blk, jax.random.split(ks[2], cfg.n_layers))
        elif fam == "encdec":
            epol, dpol = pol.at("enc_blocks"), pol.at("dec_blocks")
            params["enc_blocks"] = _layer_map(
                lambda k: self._enc_block_init(k, epol),
                jax.random.split(ks[2], cfg.n_enc_layers))
            params["dec_blocks"] = _layer_map(
                lambda k: self._dec_block_init(k, dpol),
                jax.random.split(ks[3], cfg.n_layers))
            params["enc_ln"] = rmsnorm_init(d)
        else:
            raise ValueError(fam)
        return params

    def _hybrid_layout(self):
        return hybrid_layout(self.cfg)

    def _enc_block_init(self, key, pol):
        cfg = self.cfg
        ks = jax.random.split(key, 2)
        return {"ln1": rmsnorm_init(cfg.d_model), "ln2": rmsnorm_init(cfg.d_model),
                "attn": gqa_init(ks[0], _attn_cfg(cfg), pol.at("attn")),
                "mlp": mlp_init(ks[1], cfg.d_model, cfg.d_ff, pol.at("mlp"),
                                cfg.gated_mlp)}

    def _dec_block_init(self, key, pol):
        cfg = self.cfg
        ks = jax.random.split(key, 3)
        return {"ln1": rmsnorm_init(cfg.d_model), "ln2": rmsnorm_init(cfg.d_model),
                "ln3": rmsnorm_init(cfg.d_model),
                "attn": gqa_init(ks[0], _attn_cfg(cfg), pol.at("attn")),
                "cross": cross_init(ks[1], _attn_cfg(cfg), pol.at("cross")),
                "mlp": mlp_init(ks[2], cfg.d_model, cfg.d_ff, pol.at("mlp"),
                                cfg.gated_mlp)}

    # ---------------- shared pieces ----------------

    def _layer_extras(self):
        """Per-layer scanned (window, rope_theta) arrays (gemma3 interleave)."""
        cfg = self.cfg
        L = cfg.n_layers
        if cfg.global_every:
            is_global = (jnp.arange(L) % cfg.global_every) == (cfg.global_every - 1)
            window = jnp.where(is_global, 0, cfg.window or 0)
            theta = jnp.where(is_global, cfg.global_rope_theta, cfg.rope_theta)
            return window.astype(jnp.int32), theta.astype(jnp.float32)
        w = cfg.window if cfg.window else 0
        return (jnp.full((L,), w, jnp.int32),
                jnp.full((L,), cfg.rope_theta, jnp.float32))

    def _embed(self, params, tokens):
        x = params["embed"][tokens]  # gather; vocab sharded on model
        return constrain(x, (("pod", "data"), None, None))

    def _head_w(self, params):
        if self.cfg.tie_embeddings:
            return params["embed"].T
        h = params["head"]
        # tagged linear (possibly quantized via an explicit lm_head policy
        # rule) or a legacy raw array from an old checkpoint
        return h if hasattr(h, "ndim") else schemes.dense_view(h)

    def _logits(self, params, h):
        if not self.cfg.tie_embeddings and schemes.is_linear(params.get("head")):
            # tagged head: scheme apply (kernel-routed when quantized via an
            # explicit lm_head policy rule) instead of densify-then-matmul
            return schemes.linear_apply(params["head"], h).astype(jnp.float32)
        return (h @ self._head_w(params).astype(h.dtype)).astype(jnp.float32)

    def _xent(self, params, h, labels):
        """Chunked softmax cross-entropy (never materializes [B,S,V])."""
        cfg = self.cfg
        b, s, d = h.shape
        c = min(cfg.xent_chunk, s)
        assert s % c == 0
        nc = s // c
        hs = h.reshape(b, nc, c, d).swapaxes(0, 1)
        ys = labels.reshape(b, nc, c).swapaxes(0, 1)
        w = self._head_w(params)

        def body(carry, xs):
            hc, yc = xs
            logits = (hc @ w.astype(hc.dtype)).astype(jnp.float32)
            logits = constrain(logits, (("pod", "data"), None, "model"))
            lse = jax.nn.logsumexp(logits, axis=-1)
            ll = jnp.take_along_axis(
                logits, yc.clip(0)[..., None], axis=-1)[..., 0]
            mask = (yc >= 0).astype(jnp.float32)
            loss_sum, n = carry
            return (loss_sum + (((lse - ll) * mask).sum()),
                    n + mask.sum()), None

        (loss_sum, n), _ = cscan(body, (jnp.float32(0.0), jnp.float32(0.0)),
                                 (hs, ys), name="xent_chunk")
        return loss_sum / jnp.maximum(n, 1.0)

    def _inputs_to_x(self, params, batch):
        """Token embeds, with vlm patch embeds prepended (frontend stub)."""
        x = self._embed(params, batch["tokens"])
        if self.cfg.frontend == "vision" and "frontend" in batch:
            x = jnp.concatenate([batch["frontend"].astype(x.dtype), x], axis=1)
        return x

    # ---------------- forward (train/prefill trunk) ----------------

    def _trunk(self, params, x, collect_cache: bool = False):
        """Runs the layer stack. Returns (h, aux, caches or None)."""
        cfg, pol = self.cfg, self.cfg.quant
        fam = cfg.family
        x = constrain(x, (("pod", "data"), None, None))

        if fam in ("gqa", "gqa_moe"):
            moe = fam == "gqa_moe"
            window, theta = self._layer_extras()

            def body(carry, xs):
                xc, aux = carry
                blk, w_, t_ = xs
                fn = _maybe_remat(
                    lambda b_, x_: _gqa_block(b_, x_, cfg, pol, window=w_,
                                              theta=t_, moe=moe), cfg)
                y, kv, a = fn(blk, xc)
                out = kv if collect_cache else None
                return (_sp(y, cfg), aux + a), out

            (x, aux), caches = cscan(body, (x, jnp.float32(0.0)),
                                     (params["blocks"], window, theta),
                                     name="layers")
            cache = None
            if collect_cache:
                cache = {"k": caches[0], "v": caches[1]}
            return x, aux, cache

        if fam == "mla_moe":
            aux = jnp.float32(0.0)
            caches = []
            for name, moe in (("dense_blocks", False), ("moe_blocks", True)):
                if collect_cache:
                    def body(xc, blk):
                        y, ckv = _maybe_remat(
                            lambda b_, x_: _mla_block_prefill(b_, x_, cfg, pol, moe),
                            cfg)(blk, xc)
                        return _sp(y, cfg), ckv
                    x, ckv = cscan(body, x, params[name], name=name)
                    caches.append(ckv)
                else:
                    def body(carry, blk):
                        xc, a = carry
                        y, a2 = _maybe_remat(
                            lambda b_, x_: _mla_block(b_, x_, cfg, pol, moe=moe),
                            cfg)(blk, xc)
                        return (_sp(y, cfg), a + a2), None
                    (x, aux), _ = cscan(body, (x, aux), params[name], name=name)
            cache = None
            if collect_cache:
                cache = {"dense": {"c": caches[0][0], "kr": caches[0][1]},
                         "moe": {"c": caches[1][0], "kr": caches[1][1]}}
            return x, aux, cache

        if fam == "mamba_hybrid":
            mcfg = _mamba_cfg(cfg)
            shared = params["shared_attn"]

            def mamba_body(xc, blk):
                def fn(b_, x_):
                    y, st = mamba2_mix(b_, x_, mcfg, pol, return_state=True)
                    return x_ + y, st
                y, st = _maybe_remat(fn, cfg)(blk, xc)
                return _sp(y, cfg), st if collect_cache else None

            def group_body(xc, gblk):
                xc, sts = cscan(mamba_body, xc, gblk, name="mamba_inner")
                y, kv, _ = _maybe_remat(
                    lambda b_, x_: _gqa_block(b_, x_, cfg, pol), cfg)(shared, xc)
                return _sp(y, cfg), (sts, kv) if collect_cache else None

            x, gout = cscan(group_body, x, params["mamba_groups"], name="groups")
            x, tsts = cscan(mamba_body, x, params["mamba_tail"], name="mamba_tail")
            cache = None
            if collect_cache:
                sts, kvs = gout
                cache = {"groups": sts, "tail": tsts,
                         "k": kvs[0], "v": kvs[1]}
            return x, jnp.float32(0.0), cache

        if fam == "rwkv":
            rcfg = _rwkv_cfg(cfg)

            def body(xc, blk):
                def fn(b_, x_):
                    y, (tp, wkv) = rwkv6_time_mix(
                        b_["mix"], rmsnorm(b_["ln1"], x_), rcfg, pol)
                    x_ = x_ + y
                    y, cp = rwkv6_channel_mix(
                        b_["mix"], rmsnorm(b_["ln2"], x_), rcfg, pol)
                    return x_ + y, {"tm_prev": tp, "wkv": wkv, "cm_prev": cp}
                y, st = _maybe_remat(fn, cfg)(blk, xc)
                return _sp(y, cfg), st if collect_cache else None

            x, sts = cscan(body, x, params["blocks"], name="layers")
            return x, jnp.float32(0.0), sts if collect_cache else None

        raise ValueError(fam)

    # ---------------- encoder (enc-dec) ----------------

    def _encode(self, params, src, src_len=None):
        """``src_len`` ([B] traced int32, optional): valid frame count per
        row.  The encoder is bidirectional, so zero-padded frames WOULD
        leak into every valid output — masking keys >= src_len[b] keeps
        valid rows equal to the unpadded call up to reduction order
        (padded output rows are garbage-but-finite; callers slice them
        away).  This is what
        lets the serving engine bucket source lengths to a bounded set of
        compiled shapes."""
        cfg, pol = self.cfg, self.cfg.quant
        x = constrain(src, (("pod", "data"), None, None))

        def body(xc, blk):
            def fn(b_, x_):
                a, _ = gqa_apply(b_["attn"], rmsnorm(b_["ln1"], x_), _attn_cfg(cfg),
                                 pol, causal=False, kv_len=src_len,
                                 chunk_q=cfg.chunk_q, chunk_k=cfg.chunk_k)
                x_ = x_ + a
                return x_ + mlp_apply(b_["mlp"], rmsnorm(b_["ln2"], x_), pol, cfg.act)
            return _maybe_remat(fn, cfg)(blk, xc), None

        x, _ = cscan(body, x, params["enc_blocks"], name="enc_layers")
        return rmsnorm(params["enc_ln"], x)

    def _decode_trunk(self, params, x, memory, collect_cache=False):
        cfg, pol = self.cfg, self.cfg.quant

        def body(xc, blk):
            def fn(b_, x_):
                a, kv = gqa_apply(b_["attn"], rmsnorm(b_["ln1"], x_),
                                  _attn_cfg(cfg), pol,
                                  chunk_q=cfg.chunk_q, chunk_k=cfg.chunk_k)
                x_ = x_ + a
                km, vm = cross_kv(b_["cross"], memory, _attn_cfg(cfg), pol)
                x_ = x_ + cross_apply(b_["cross"], rmsnorm(b_["ln2"], x_), km, vm,
                                      _attn_cfg(cfg), pol,
                                      chunk_q=cfg.chunk_q, chunk_k=cfg.chunk_k)
                x_ = x_ + mlp_apply(b_["mlp"], rmsnorm(b_["ln3"], x_), pol, cfg.act)
                return x_, (kv, (km, vm))
            fn = _maybe_remat(fn, cfg) if not collect_cache else fn
            y, caches = fn(blk, xc)
            return _sp(y, cfg), caches if collect_cache else None

        x, caches = cscan(body, x, params["dec_blocks"], name="dec_layers")
        return x, caches

    def encode_cross(self, params, src, src_len=None):
        """Run the encoder over ``src`` [B,Ss,d] and precompute every
        decoder layer's cross K/V from the memory: returns (k, v), each
        [L,B,Ss,KvH,hd].  The continuous engine calls this ONCE per
        admitted encdec request and pins the result into the slot's
        frozen cross cache — cross K/V never recompute during decode.

        ``src_len`` ([B] traced int32, optional) marks the valid frames
        of a zero-padded ``src``: rows >= src_len[b] are masked out of
        the (bidirectional) encoder attention, so valid memory rows —
        and the cross K/V derived from them — match encoding the
        unpadded source up to reduction order.  Callers pin only the first
        src_len rows (padded rows carry garbage-but-finite K/V)."""
        cfg, pol = self.cfg, self.cfg.quant
        memory = self._encode(params, src, src_len)

        def body(carry, blk):
            km, vm = cross_kv(blk["cross"], memory, _attn_cfg(cfg), pol)
            return carry, (km, vm)

        _, (ks, vs) = cscan(body, jnp.float32(0.0), params["dec_blocks"],
                            name="cross_kv")
        return ks, vs

    # ---------------- public API ----------------

    def loss(self, params, batch):
        cfg = self.cfg
        if cfg.family == "encdec":
            memory = self._encode(params, batch["src"])
            x = self._embed(params, batch["tokens"])
            h, _ = self._decode_trunk(params, x, memory)
            aux = jnp.float32(0.0)
        else:
            x = self._inputs_to_x(params, batch)
            h, aux, _ = self._trunk(params, x)
        h = rmsnorm(params["final_ln"], h, cfg.norm_eps)
        labels = batch["labels"]
        if cfg.frontend == "vision" and "frontend" in batch:
            f = batch["frontend"].shape[1]
            labels = jnp.concatenate(
                [jnp.full((labels.shape[0], f), -1, labels.dtype), labels], axis=1)
        loss = self._xent(params, h, labels)
        metrics = {"xent": loss, "aux": aux}
        if cfg.family == "mla_moe" and cfg.mtp and "mtp_block" in params:
            loss = loss + 0.3 * self._mtp_loss(params, h, batch, labels)
        loss = loss + cfg.aux_coef * aux
        return loss, metrics

    def _mtp_loss(self, params, h, batch, labels):
        """DeepSeek-V3 multi-token prediction: one extra depth predicting t+2."""
        cfg, pol = self.cfg, self.cfg.quant
        emb_next = self._inputs_to_x(params, batch)
        cat = jnp.concatenate([rmsnorm(params["mtp_ln"], h),
                               jnp.roll(emb_next, -1, axis=1)], axis=-1)
        x = linear_apply(params["mtp_proj"], cat, pol)
        x, _ = _mla_block(params["mtp_block"], x, cfg, pol, moe=False)
        labels2 = jnp.roll(labels, -1, axis=1).at[:, -1].set(-1)
        return self._xent(params, x, labels2)

    def prefill(self, params, batch):
        """Returns (last-token logits [B, V], cache dict)."""
        cfg = self.cfg
        if cfg.family == "encdec":
            memory = self._encode(params, batch["src"])
            x = self._embed(params, batch["tokens"])
            h, caches = self._decode_trunk(params, x, memory, collect_cache=True)
            # cross "len" records the true memory length so decode masks
            # exactly the rows the prefill attention saw (the decode cache
            # zero-pads cross beyond it; see merge_prefill_cache)
            src_len = jnp.full((x.shape[0],), memory.shape[1], jnp.int32)
            cache = {"self": {"k": caches[0][0], "v": caches[0][1]},
                     "cross": {"k": caches[1][0], "v": caches[1][1],
                               "len": src_len}}
        else:
            x = self._inputs_to_x(params, batch)
            h, _, cache = self._trunk(params, x, collect_cache=True)
        h = rmsnorm(params["final_ln"], h[:, -1:], cfg.norm_eps)
        logits = self._logits(params, h)[:, 0]
        seq = (batch["tokens"].shape[1]
               + (batch.get("frontend").shape[1]
                  if cfg.frontend == "vision" and "frontend" in batch else 0))
        length = jnp.full((h.shape[0],), seq, jnp.int32)
        return logits, {"layers": cache, "len": length}

    def slot_state(self, page_size: int = 0, n_pages: int = 0) -> SlotState:
        """The per-slot decode-state layout/lifecycle for this config
        (init / snapshot / reset / advance; see models/slot_state.py).
        ``page_size > 0`` selects the paged-pool CACHE layout (``n_pages``
        shared pages; page 0 reserved null)."""
        return SlotState(self.cfg, page_size=page_size, n_pages=n_pages)

    def supports_ragged(self) -> bool:
        """True when :meth:`step_ragged` covers ``cfg.family`` — the
        single source of truth the continuous engine's family guard
        derives from (no separate supported-families constant to drift)."""
        return self.cfg.family in ("gqa", "gqa_moe", "mla_moe",
                                   "mamba_hybrid", "rwkv", "encdec")

    def init_cache(self, batch: int, seq: int, dtype=jnp.bfloat16):
        """Fresh decode cache (see :class:`SlotState` for the layout and
        the eviction/reset contract).  For encdec, ``seq`` is split into
        source/target capacities via ``cfg.source_frac`` (the engine
        passes an explicit ``src_cap`` through :meth:`slot_state`)."""
        return self.slot_state().init(batch, seq, dtype=dtype)

    def decode_step(self, params, cache, tokens, aux=None):
        """tokens: [B,1] -> (logits [B,V], updated cache). One serve step:
        the C=1 always-active special case of :meth:`step_ragged` for
        EVERY family — one implementation of the decode math, so the
        static and continuous engines cannot silently diverge.

        ``aux`` optionally carries :meth:`absorbed_weights` output so the
        MLA absorbed-weight dequant stays out of the per-step graph."""
        return self.step_ragged(params, cache, tokens,
                                jnp.ones_like(cache["len"]), aux=aux)

    def absorbed_weights(self, params):
        """Precompute the per-layer effective (adapter-merged, dequantized)
        absorbed MLA weights — the step-invariant piece of the absorbed
        decode path.  Returns ``{"dense": (W_uk, W_uv), "moe": ...}`` with
        leading layer axes for ``mla_moe`` (``None`` for every other
        family).  Serving loops compute this ONCE and thread it through
        :meth:`step_ragged` / :meth:`decode_step` as ``aux``, so the
        rank-512 ``kv_up`` dequant never re-runs inside a per-token step
        (per step per layer it is pure hot-path waste)."""
        if self.cfg.family != "mla_moe":
            return None
        mcfg = _mla_cfg(self.cfg)
        dt = self.cfg.quant.dtype
        return {"dense": _kv_up_split(params["dense_blocks"]["attn"], mcfg, dt),
                "moe": _kv_up_split(params["moe_blocks"]["attn"], mcfg, dt)}

    def step_ragged(self, params, cache, tokens, n_new, aux=None):
        """Ragged serve step for continuous batching — every family.

        ``tokens`` [B, C] int32, ``n_new`` [B] in [0, C]: slot b consumes
        ``tokens[b, :n_new[b]]`` at positions ``len[b]..len[b]+n_new[b]-1``
        of its private slot state and advances only by ``n_new[b]``.
        One compiled program therefore serves any mix of slot states —
        chunked prefill (n_new == C), in-flight decode (n_new == 1) and
        free/finished slots (n_new == 0, state and length untouched) —
        which is what lets the engine admit requests mid-flight.

        Per-slot state follows the family (:class:`SlotState`): slotted
        KV for gqa/gqa_moe, slotted compressed latent + rope key for
        mla_moe, running Mamba2/RWKV6 recurrences for mamba_hybrid/rwkv
        (masked rows are IDENTITY in the recurrence, so idle slots freeze
        bit-exactly; the hybrid family's shared-attention blocks ride the
        slotted-KV chunk path), and for encdec a slotted self-KV plus a
        frozen per-slot cross cache written at admission (masked to each
        slot's own cross ``len``).

        ``aux`` optionally carries :meth:`absorbed_weights` output; when
        given, the MLA absorbed-weight dequant stays OUT of this graph.

        Returns (logits [B, V] at each slot's LAST consumed row — garbage
        for n_new == 0 slots, callers must mask — and the updated cache).

        Per-slot results are independent of the other slots' content for
        dense attention (gqa, and mla_moe layers without MoE); for MoE
        layers, finite expert capacity routes over ALL B*C rows (idle and
        padding rows included), so logits depend on batch composition —
        the same batch-dependence the static path has between
        whole-prompt prefill and per-token decode.
        """
        h, layers, n_new = self._ragged_trunk(params, cache, tokens, n_new,
                                              aux)
        last = jnp.clip(n_new - 1, 0, tokens.shape[1] - 1)
        h_last = jnp.take_along_axis(h, last[:, None, None], axis=1)
        logits = self._logits(params, h_last)[:, 0]
        return logits, self.slot_state().advance(cache, layers, n_new)

    def verify_ragged(self, params, cache, tokens, n_new, aux=None):
        """Per-POSITION serve step for draft-and-verify speculative
        decoding: the same ragged contract as :meth:`step_ragged` (slot
        b consumes ``tokens[b, :n_new[b]]``), but logits come back for
        EVERY consumed position — ``logits[b, i]`` predicts the token
        AFTER ``tokens[b, i]`` — together with the post-final-norm
        hidden states (the MTP drafter's input).  Rows at
        ``i >= n_new[b]`` are garbage (callers mask).  Returns
        ``(logits [B, C, V], h [B, C, d], cache)``.  The cache advances
        by the FULL ``n_new``; a caller rejecting a draft suffix rolls
        it back by shrinking ``len`` — sound exactly when
        ``SlotState.supports_rollback()`` (every read mask is bounded by
        the slot's own length, so the stale tail is never read)."""
        h, layers, n_new = self._ragged_trunk(params, cache, tokens, n_new,
                                              aux)
        logits = self._logits(params, h)
        return logits, h, self.slot_state().advance(cache, layers, n_new)

    def mtp_draft_logits(self, params, h, next_tokens):
        """DeepSeek-V3's trained MTP head as a DRAFTER: from
        :meth:`verify_ragged` hidden states ``h`` [B, C, d] and the
        accepted next token at each position (the verify argmax),
        predict one token further out — ``logits[b, i]`` drafts position
        i+2.  Mirrors :meth:`_mtp_loss` exactly (mtp_ln(h) concatenated
        with the NEXT token's embedding -> mtp_proj -> one dense MLA
        block -> shared head), except the next-token embedding comes
        from the decode-time argmax instead of a rolled teacher-forcing
        batch.  The MLA block runs positionless self-attention over the
        C-token window only — a drafter-quality approximation; the
        verify step guards correctness."""
        cfg, pol = self.cfg, self.cfg.quant
        cat = jnp.concatenate([rmsnorm(params["mtp_ln"], h),
                               self._embed(params, next_tokens)], axis=-1)
        x = linear_apply(params["mtp_proj"], cat, pol)
        x, _ = _mla_block(params["mtp_block"], x, cfg, pol, moe=False)
        return self._logits(params, x)

    def _ragged_trunk(self, params, cache, tokens, n_new, aux=None):
        """Shared ragged layer stack (contract: :meth:`step_ragged`).
        Returns (post-final-norm hidden states [B, C, d], updated layer
        state, int32 ``n_new``)."""
        cfg, pol = self.cfg, self.cfg.quant
        fam = cfg.family
        if not self.supports_ragged():
            raise NotImplementedError(
                f"step_ragged has no {fam!r} support "
                f"(LM.supports_ragged() is False)")
        cur = cache["len"]
        n_new = n_new.astype(jnp.int32)
        # paged CACHE layout: the per-slot page map rides in the pytree as
        # values, so remaps never retrace this program
        pages = cache.get("pages")
        x = self._embed(params, tokens)

        if fam == "mla_moe":
            def mk_body(moe):
                def body(xc, xs):
                    blk, cc, w_kv = xs
                    y, cc = _mla_block_chunk(blk, xc, cc, cur, n_new, cfg,
                                             pol, moe=moe, w_kv=w_kv,
                                             pages=pages)
                    return y, cc
                return body
            wkv_d = aux["dense"] if aux is not None else None
            wkv_m = aux["moe"] if aux is not None else None
            x, dc = cscan(mk_body(False), x,
                          (params["dense_blocks"], cache["layers"]["dense"],
                           wkv_d), name="dense_blocks")
            x, mc = cscan(mk_body(True), x,
                          (params["moe_blocks"], cache["layers"]["moe"],
                           wkv_m), name="moe_blocks")
            layers = {"dense": dc, "moe": mc}
        elif fam == "mamba_hybrid":
            mcfg = _mamba_cfg(cfg)
            shared = params["shared_attn"]
            lay = cache["layers"]

            def mamba_body(xc, xs):
                blk, st = xs
                y, st = mamba2_chunk_step(blk, xc, st, n_new, mcfg, pol)
                return xc + y, st

            def group_body(xc, xs):
                gblk, gst, kvc = xs
                xc, gst = cscan(mamba_body, xc, (gblk, gst),
                                name="mamba_inner")
                y, kvc = _gqa_block_chunk(shared, xc, kvc, cur, n_new,
                                          cfg, pol, pages=pages)
                return y, (gst, kvc)

            x, (gstates, kvs) = cscan(
                group_body, x,
                (params["mamba_groups"], lay["groups"],
                 {"k": lay["k"], "v": lay["v"]}), name="groups")
            x, tstates = cscan(mamba_body, x,
                               (params["mamba_tail"], lay["tail"]),
                               name="mamba_tail")
            layers = {"groups": gstates, "tail": tstates,
                      "k": kvs["k"], "v": kvs["v"]}
        elif fam == "rwkv":
            rcfg = _rwkv_cfg(cfg)

            def body(xc, xs):
                blk, st = xs
                y, (tp, wkv) = rwkv6_time_mix_ragged(
                    blk["mix"], rmsnorm(blk["ln1"], xc),
                    (st["tm_prev"], st["wkv"]), n_new, rcfg, pol)
                xc = xc + y
                y, cp = rwkv6_channel_mix_ragged(
                    blk["mix"], rmsnorm(blk["ln2"], xc), st["cm_prev"],
                    n_new, rcfg, pol)
                return xc + y, {"tm_prev": tp, "wkv": wkv, "cm_prev": cp}

            x, layers = cscan(body, x, (params["blocks"], cache["layers"]),
                              name="layers")
        elif fam == "encdec":
            acfg = _attn_cfg(cfg)
            crossc = cache["layers"]["cross"]
            # legacy caches without a cross "len" behave as before:
            # every memory row (zero-padded or not) is attended
            clen = crossc.get("len")
            if clen is None:
                clen = jnp.full((x.shape[0],), crossc["k"].shape[2],
                                jnp.int32)

            def body(xc, xs):
                blk, selfc, ck, cv = xs
                a, selfc = gqa_prefill_chunk(
                    blk["attn"], rmsnorm(blk["ln1"], xc), selfc, cur,
                    n_new, acfg, pol, pages=pages)
                xc = xc + a
                xc = xc + cross_chunk(blk["cross"],
                                      rmsnorm(blk["ln2"], xc), ck, cv,
                                      clen, acfg, pol)
                xc = xc + mlp_apply(blk["mlp"], rmsnorm(blk["ln3"], xc),
                                    pol, cfg.act)
                return xc, selfc

            x, selfc = cscan(body, x,
                             (params["dec_blocks"], cache["layers"]["self"],
                              crossc["k"], crossc["v"]), name="dec_layers")
            layers = {"self": selfc, "cross": crossc}
        else:
            moe = fam == "gqa_moe"
            window, theta = self._layer_extras()

            def body(xc, xs):
                blk, kvc, w_, t_ = xs
                y, kvc = _gqa_block_chunk(blk, xc, kvc, cur, n_new, cfg, pol,
                                          window=w_, theta=t_, moe=moe,
                                          pages=pages)
                return y, kvc

            x, layers = cscan(body, x, (params["blocks"], cache["layers"],
                                        window, theta), name="layers")
        return rmsnorm(params["final_ln"], x, cfg.norm_eps), layers, n_new

    # ---------------- serving: prefill + scan decode ----------------

    def merge_prefill_cache(self, prefill_cache, decode_cache):
        """Embed a :meth:`prefill` cache into a full-capacity decode cache.

        ``prefill`` materializes per-layer caches sized to the prompt;
        :meth:`init_cache` allocates them at max generation length.  Leaves
        with identical shapes carry over (recurrent states, lengths); any
        leaf that is smaller along some axes (KV / compressed-KV seq dims)
        is zero-padded up to the decode layout, which matches what
        ``init_cache`` would have held there.  Family-agnostic: works for
        gqa / mla / hybrid / rwkv / encdec alike.
        """
        def pad(p, c):
            p = p.astype(c.dtype)
            if p.shape == c.shape:
                return p
            assert p.ndim == c.ndim, (p.shape, c.shape)
            widths = [(0, cs - ps) for ps, cs in zip(p.shape, c.shape)]
            assert all(w >= 0 for _, w in widths), (p.shape, c.shape)
            return jnp.pad(p, widths)

        return jax.tree.map(pad, prefill_cache, decode_cache)

    def generate(self, params, cache, logits, gen_len: int):
        """Greedy scan decode: one compiled program for the whole generation.

        ``logits`` are the last-position logits from :meth:`prefill` (or a
        prior :meth:`decode_step`); token ``t+1`` = argmax of step ``t``'s
        logits, so the sequence is token-identical to a per-step Python
        loop — without ``gen_len`` dispatches and host syncs.  Returns
        (tokens [B, gen_len], final cache).
        """
        tok0 = jnp.argmax(logits, -1).astype(jnp.int32)  # [B]
        # step-invariant absorbed weights: computed once OUTSIDE the scan
        # body, so the MLA kv_up dequant does not re-run every token
        aux = self.absorbed_weights(params)

        def body(carry, _):
            cache, tok = carry
            lg, cache = self.decode_step(params, cache, tok[:, None], aux=aux)
            return (cache, jnp.argmax(lg, -1).astype(jnp.int32)), tok

        (cache, last), toks = jax.lax.scan(
            body, (cache, tok0), None, length=max(gen_len - 1, 0))
        if gen_len <= 0:
            return jnp.zeros((tok0.shape[0], 0), jnp.int32), cache
        toks = jnp.concatenate([toks, last[None]], axis=0)
        return toks.swapaxes(0, 1), cache
