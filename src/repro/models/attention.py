"""Attention variants: GQA (+RoPE, sliding window), chunked flash for long
sequences, decode with KV cache, and DeepSeek-style MLA with the absorbed
decode path.

All projections route through ``linear_apply`` so QA-LoRA (or any baseline
mode) applies uniformly.  Long-sequence memory is kept sub-quadratic with a
two-level scan (q-chunks x kv-chunks, running-softmax) — the jnp analogue
of flash attention; on TPU this stays in VMEM-sized tiles after XLA fusion.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp

from .common import (QuantPolicy, dense_view, linear_init, linear_apply,
                     rmsnorm, rmsnorm_init, rope, constrain, mesh_axis_size)
from .scan_utils import cscan, cmap
from ..runtime import tracing

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# chunked (flash-style) attention core
# ---------------------------------------------------------------------------


def _mask(qpos, kpos, causal: bool, window):
    """window: None (full), python int, or traced scalar (0 = full attention
    — lets a scanned per-layer window drive gemma3's local:global pattern)."""
    m = jnp.ones((qpos.shape[0], kpos.shape[0]), bool)
    if causal:
        m &= kpos[None, :] <= qpos[:, None]
    if window is not None:
        active = window > 0
        wm = kpos[None, :] > (qpos[:, None] - window)
        m &= wm | ~active
    return m


def flash_attention(q, k, v, *, causal=True, window=None, q_offset=0,
                    chunk_q=256, chunk_k=1024, scale=None, kv_len=None):
    """q: [B,Sq,H,Dq]  k: [B,Sk,KvH,Dq]  v: [B,Sk,KvH,Dv] -> [B,Sq,H,Dv].

    H must be a multiple of KvH (GQA).  Memory: O(chunk_q * chunk_k) scores.

    ``kv_len`` ([B] traced int32, optional) masks keys at positions >=
    kv_len[b] — the ragged-source hook that lets a BIDIRECTIONAL caller
    (the encdec encoder) zero-pad Sk to a bucketed shape without padding
    rows leaking into valid outputs.  Query rows >= kv_len[b] still
    attend (to the valid keys), producing garbage-but-finite output the
    caller must slice away; masked keys hit exp(NEG_INF) == 0 exactly,
    so valid rows match the unpadded call up to reduction order.

    PERF: when the kv-head count can't shard over the model axis but the
    full head count can, the GQA [H]->[KvH,G] grouping strands the score
    tensors replicated (found 16x attention-byte waste on deepseek-67b
    train_4k — EXPERIMENTS.md §Perf).  Expanding KV to H heads costs one
    O(B*S*H*hd) broadcast but lets every score/context tensor shard.
    """
    with tracing.scope(tracing.ATTENTION):
        return _flash(q, k, v, causal, window, q_offset, chunk_q, chunk_k,
                      scale, kv_len)


def _flash(q, k, v, causal, window, q_offset, chunk_q, chunk_k, scale,
           kv_len):
    b, sq, h, dq = q.shape
    _, sk, kvh, _ = k.shape
    tp = mesh_axis_size("model")
    if kvh < h and kvh % tp != 0 and h % tp == 0:
        g_exp = h // kvh
        k = jnp.repeat(k, g_exp, axis=2)
        v = jnp.repeat(v, g_exp, axis=2)
        k = constrain(k, ("data", None, "model", None))
        v = constrain(v, ("data", None, "model", None))
        kvh = h
    dv = v.shape[-1]
    g = h // kvh
    scale = scale if scale is not None else 1.0 / math.sqrt(dq)

    chunk_q = min(chunk_q, sq)
    chunk_k = min(chunk_k, sk)
    assert sq % chunk_q == 0 and sk % chunk_k == 0, (sq, chunk_q, sk, chunk_k)
    nq, nk = sq // chunk_q, sk // chunk_k

    qc = q.reshape(b, nq, chunk_q, kvh, g, dq).transpose(1, 0, 3, 4, 2, 5)
    # [nq, B, KvH, G, cq, Dq]
    kc = k.reshape(b, nk, chunk_k, kvh, dq).transpose(1, 0, 3, 2, 4)
    vc = v.reshape(b, nk, chunk_k, kvh, dv).transpose(1, 0, 3, 2, 4)
    # [nk, B, KvH, ck, D*]

    def q_step(qi, q_blk):
        qpos = q_offset + qi * chunk_q + jnp.arange(chunk_q)

        def kv_step(carry, xs):
            m_run, l_run, acc = carry
            ki, k_blk, v_blk = xs
            kpos = ki * chunk_k + jnp.arange(chunk_k)
            s = jnp.einsum("bhgqd,bhkd->bhgqk", q_blk.astype(jnp.float32),
                           k_blk.astype(jnp.float32)) * scale
            msk = _mask(qpos, kpos, causal, window)
            s = jnp.where(msk[None, None, None], s, NEG_INF)
            if kv_len is not None:
                km = kpos[None, :] < kv_len[:, None]          # [B, ck]
                s = jnp.where(km[:, None, None, None, :], s, NEG_INF)
            m_new = jnp.maximum(m_run, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m_run - m_new)
            l_new = l_run * corr + p.sum(axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bhgqk,bhkd->bhgqd", p, v_blk.astype(jnp.float32))
            return (m_new, l_new, acc), None

        init = (jnp.full((b, kvh, g, chunk_q), NEG_INF, jnp.float32),
                jnp.zeros((b, kvh, g, chunk_q), jnp.float32),
                jnp.zeros((b, kvh, g, chunk_q, dv), jnp.float32))
        (m_run, l_run, acc), _ = cscan(
            kv_step, init, (jnp.arange(nk), kc, vc), name="flash_kv")
        out = acc / jnp.maximum(l_run[..., None], 1e-37)
        return out  # [B, KvH, G, cq, Dv]

    outs = cmap(lambda xs: q_step(*xs), (jnp.arange(nq), qc), name="flash_q")
    # [nq, B, KvH, G, cq, Dv] -> [B, Sq, H, Dv]
    out = outs.transpose(1, 0, 4, 2, 3, 5).reshape(b, sq, h, dv)
    return out.astype(q.dtype)


def chunk_attention(q, k_cache, v_cache, qpos, *, window=None):
    """Ragged-chunk attention against a slotted cache.

    q: [B,C,H,Dq]; caches: [B,S,KvH,D*]; qpos: [B,C] absolute position of
    each query row (per-slot ragged — row i of slot b attends to cache
    positions <= qpos[b, i]).  Masked cache entries hit exp(NEG_INF) == 0
    exactly, so results are independent of the cache capacity S and of
    whatever stale KV a previous slot occupant left beyond qpos.
    """
    with tracing.scope(tracing.ATTENTION):
        b, c, h, dq = q.shape
        _, s, kvh, _ = k_cache.shape
        g = h // kvh
        qg = q.reshape(b, c, kvh, g, dq)
        scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32),
                            k_cache.astype(jnp.float32)) / math.sqrt(dq)
        kpos = jnp.arange(s)
        valid = kpos[None, None, :] <= qpos[:, :, None]  # [B, C, S]
        if window is not None:
            active = window > 0
            valid &= (kpos[None, None, :] > (qpos[:, :, None] - window)) | ~active
        scores = jnp.where(valid[:, None, None, :, :], scores, NEG_INF)
        p = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bhgqk,bkhd->bqhgd", p, v_cache.astype(jnp.float32))
        return out.reshape(b, c, h, -1).astype(q.dtype)


def decode_attention(q, k_cache, v_cache, cur_len, *, window=None):
    """One-token attention. q: [B,1,H,Dq]; caches: [B,S,KvH,D*].

    ``cur_len`` counts valid cache entries INCLUDING the just-inserted
    token, so the query row sits at absolute position cur_len - 1."""
    return chunk_attention(q, k_cache, v_cache, (cur_len - 1)[:, None],
                           window=window)


# ---------------------------------------------------------------------------
# GQA attention block
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class AttnConfig:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    rope_theta: float = 1e4
    window: Optional[int] = None  # sliding window; None = full causal
    qk_norm: bool = False


def gqa_init(key, cfg: AttnConfig, pol: QuantPolicy):
    ks = jax.random.split(key, 4)
    h, kvh, hd, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_model
    p = {
        "wq": linear_init(ks[0], d, h * hd, pol.at("wq")),
        "wk": linear_init(ks[1], d, kvh * hd, pol.at("wk")),
        "wv": linear_init(ks[2], d, kvh * hd, pol.at("wv")),
        "wo": linear_init(ks[3], h * hd, d, pol.at("wo")),
    }
    if cfg.qk_norm:
        p["qn"] = rmsnorm_init(hd)
        p["kn"] = rmsnorm_init(hd)
    return p


def _qkv(p, x, cfg: AttnConfig, pol, positions, theta=None):
    b, s, _ = x.shape
    theta = cfg.rope_theta if theta is None else theta
    q = linear_apply(p["wq"], x, pol).reshape(b, s, cfg.n_heads, cfg.head_dim)
    k = linear_apply(p["wk"], x, pol).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    v = linear_apply(p["wv"], x, pol).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    if "qn" in p:
        q, k = rmsnorm(p["qn"], q), rmsnorm(p["kn"], k)
    q = rope(q, positions, theta)
    k = rope(k, positions, theta)
    q = constrain(q, ("data", None, "model", None))
    k = constrain(k, ("data", None, "model", None))
    v = constrain(v, ("data", None, "model", None))
    return q, k, v


def gqa_apply(p, x, cfg: AttnConfig, pol: QuantPolicy, positions=None,
              window=None, theta=None, causal=True, chunk_q=256, chunk_k=1024,
              kv_len=None):
    """Training / prefill self-attention; returns (out, new_kv).

    ``window``/``theta`` override cfg (may be traced per-layer scalars).
    ``kv_len`` ([B], optional) masks keys >= kv_len[b] — see
    :func:`flash_attention` (ragged padded sources)."""
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    q, k, v = _qkv(p, x, cfg, pol, positions, theta)
    window = cfg.window if window is None else window
    o = flash_attention(q, k, v, causal=causal, window=window,
                        chunk_q=chunk_q, chunk_k=chunk_k, kv_len=kv_len)
    out = linear_apply(p["wo"], o.reshape(b, s, -1), pol)
    return out, (k, v)


def gqa_decode(p, x, cache, cur_len, cfg: AttnConfig, pol: QuantPolicy,
               window=None, theta=None):
    """x: [B,1,d]; cache: dict(k,v: [B,S,KvH,hd]); cur_len: [B] tokens so
    far.  The C=1 always-active special case of :func:`gqa_prefill_chunk`
    — one copy of the decode math for every serve path."""
    return gqa_prefill_chunk(p, x, cache, cur_len,
                             jnp.ones_like(cur_len), cfg, pol,
                             window=window, theta=theta)


def _insert_token(cache, new, cur_len):
    """cache [B,S,...], new [B,1,...]: write new at position cur_len[b]."""
    s = cache.shape[1]
    onehot = (jnp.arange(s)[None, :] == cur_len[:, None])
    oh = onehot.reshape(onehot.shape + (1,) * (cache.ndim - 2))
    return jnp.where(oh, new.astype(cache.dtype), cache)


def _insert_tokens(cache, new, cur_len, n_new):
    """Ragged multi-token insert: write new[b, i] at position cur_len[b] + i
    for i < n_new[b]; rows i >= n_new[b] are dropped (cache [B,S,...],
    new [B,C,...], cur_len / n_new [B]).  Generalizes :func:`_insert_token`
    to per-slot chunk lengths — the continuous-batching prefill path."""
    s, c = cache.shape[1], new.shape[1]
    pos = cur_len[:, None] + jnp.arange(c)[None, :]           # [B, C]
    pos = jnp.where(jnp.arange(c)[None, :] < n_new[:, None], pos, s)
    oh = (jnp.arange(s)[None, :, None] == pos[:, None, :])    # [B, S, C]
    # contract over C (einsum, not broadcast-then-sum: no [B,S,C,...]
    # transient — at serving S that would be C x the cache per layer)
    ins = jnp.einsum("bsc,bc...->bs...", oh.astype(cache.dtype),
                     new.astype(cache.dtype))
    hit = oh.any(axis=2).reshape(oh.shape[:2] + (1,) * (cache.ndim - 2))
    return jnp.where(hit, ins, cache)


def paged_view(pool, pages):
    """Gather a slot-contiguous view of a paged cache pool.

    pool: [n_pages, ps, ...]; pages: [B, P] int32 page indices (entry k of
    slot b maps logical positions [k*ps, (k+1)*ps) — unmapped entries point
    at the null page 0).  Returns [B, P*ps, ...], drop-in for the slotted
    [B, S, ...] cache the attention cores expect.  Null-page rows surface
    at positions past the slot's allocation, which qpos masking already
    excludes, so results never depend on null-page content.
    """
    b, p = pages.shape
    ps = pool.shape[1]
    return pool[pages].reshape((b, p * ps) + pool.shape[2:])


def _insert_tokens_paged(pool, new, cur_len, n_new, pages):
    """Paged counterpart of :func:`_insert_tokens`: scatter new[b, i] into
    the pool page holding logical position cur_len[b] + i (i < n_new[b]).
    pool: [n_pages, ps, ...]; new: [B, C, ...]; pages: [B, P].  Rows
    i >= n_new[b] are dumped into the null page (page 0, position 0) —
    never read, exactly as contiguous masked inserts drop them.  Live
    slots hold disjoint page sets past their (read-only) shared prefix,
    so flat scatter indices never collide across slots."""
    n_pages, ps = pool.shape[0], pool.shape[1]
    b, c = new.shape[0], new.shape[1]
    pos = cur_len[:, None] + jnp.arange(c)[None, :]           # [B, C]
    valid = jnp.arange(c)[None, :] < n_new[:, None]
    pidx = jnp.take_along_axis(
        pages, jnp.clip(pos // ps, 0, pages.shape[1] - 1), axis=1)
    dest = jnp.where(valid, pidx * ps + pos % ps, 0)          # [B, C]
    flat = pool.reshape((n_pages * ps,) + pool.shape[2:])
    flat = flat.at[dest.reshape(-1)].set(
        new.reshape((b * c,) + new.shape[2:]).astype(pool.dtype))
    return flat.reshape(pool.shape)


def _cache_insert(cache_leaf, new, cur_len, n_new, pages):
    """Insert dispatch: contiguous slotted leaf when pages is None, paged
    pool otherwise."""
    with tracing.scope(tracing.KV_INSERT):
        if pages is None:
            return _insert_tokens(cache_leaf, new, cur_len, n_new)
        return _insert_tokens_paged(cache_leaf, new, cur_len, n_new, pages)


def _cache_view(cache_leaf, pages):
    """Read dispatch: the leaf itself when contiguous, gathered view when
    paged."""
    return cache_leaf if pages is None else paged_view(cache_leaf, pages)


def gqa_prefill_chunk(p, x, cache, cur_len, n_new, cfg: AttnConfig,
                      pol: QuantPolicy, window=None, theta=None, pages=None):
    """Ragged chunk step: x [B,C,d]; slot b consumes rows [:n_new[b]] at
    positions cur_len[b].. (per-slot rotary offsets), inserts their K/V
    into the slotted cache, and attends causally against it.  C == 1 with
    n_new in {0,1} is masked decode; larger C is chunked prefill.  Rows
    i >= n_new[b] compute garbage but never touch the cache.

    ``pages`` ([B, P] int32, optional) switches the cache leaves from
    per-slot [B, S, ...] to paged pools [n_pages, ps, ...] — inserts
    scatter through the page map and attention runs on the gathered
    per-slot view.  Identical math either way."""
    b, c, _ = x.shape
    positions = cur_len[:, None] + jnp.arange(c)[None, :]  # [B, C]
    q, k, v = _qkv(p, x, cfg, pol, positions, theta)
    kc = _cache_insert(cache["k"], k, cur_len, n_new, pages)
    vc = _cache_insert(cache["v"], v, cur_len, n_new, pages)
    window = cfg.window if window is None else window
    o = chunk_attention(q, _cache_view(kc, pages), _cache_view(vc, pages),
                        positions, window=window)
    out = linear_apply(p["wo"], o.reshape(b, c, -1), pol)
    return out, {"k": kc, "v": vc}


def gqa_init_cache(batch: int, seq: int, cfg: AttnConfig, dtype=jnp.bfloat16):
    """Slotted KV cache: each of the ``batch`` slots owns a private [seq]
    ragged region (its valid prefix is tracked per-slot by the caller's
    ``len`` vector; see :meth:`repro.models.lm.LM.init_cache`)."""
    shape = (batch, seq, cfg.n_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


# ---------------------------------------------------------------------------
# cross-attention (enc-dec)
# ---------------------------------------------------------------------------


def cross_init(key, cfg: AttnConfig, pol: QuantPolicy):
    return gqa_init(key, cfg, pol)


def cross_kv(p, mem, cfg: AttnConfig, pol: QuantPolicy):
    """Precompute K/V from encoder memory (reused across decode steps)."""
    b, s, _ = mem.shape
    k = linear_apply(p["wk"], mem, pol).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    v = linear_apply(p["wv"], mem, pol).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    return k, v


def cross_apply(p, x, k_mem, v_mem, cfg: AttnConfig, pol: QuantPolicy,
                chunk_q=256, chunk_k=1024):
    """No rope, no causality: queries attend to the full encoder memory.
    Single-token calls are the C=1 full-memory special case of
    :func:`cross_chunk` (one copy of the cross decode math)."""
    b, s, _ = x.shape
    if s == 1:
        return cross_chunk(p, x, k_mem, v_mem,
                           jnp.full((b,), k_mem.shape[1], jnp.int32),
                           cfg, pol)
    q = linear_apply(p["wq"], x, pol).reshape(b, s, cfg.n_heads, cfg.head_dim)
    o = flash_attention(q, k_mem, v_mem, causal=False,
                        chunk_q=chunk_q, chunk_k=chunk_k)
    return linear_apply(p["wo"], o.reshape(b, s, -1), pol)


def cross_chunk(p, x, k_mem, v_mem, mem_len, cfg: AttnConfig,
                pol: QuantPolicy):
    """Ragged cross-attention against a per-slot frozen memory cache.

    x: [B,C,d]; k_mem/v_mem: [B,Ss,KvH,hd] (the slotted cross cache,
    written once at admission); mem_len: [B] valid source rows per slot.
    Every query row of slot b attends to memory positions < mem_len[b] —
    no rope, no causality, no dependence on the slot's decode position.
    mem_len == 0 (a src-less slot) degenerates to a uniform average over
    the slot's zeroed cross cache, i.e. a zero context — identical to
    attending over an all-zero memory, which is what the static loop
    path does."""
    b, c, _ = x.shape
    q = linear_apply(p["wq"], x, pol).reshape(b, c, cfg.n_heads, cfg.head_dim)
    mem_pos = jnp.broadcast_to((mem_len - 1)[:, None], (b, c))
    o = chunk_attention(q, k_mem, v_mem, mem_pos)
    return linear_apply(p["wo"], o.reshape(b, c, -1), pol)


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2/V3 multi-head latent attention)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    d_model: int
    n_heads: int
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 1e4


def mla_init(key, cfg: MLAConfig, pol: QuantPolicy):
    ks = jax.random.split(key, 6)
    h = cfg.n_heads
    qk = cfg.qk_nope_dim + cfg.qk_rope_dim
    return {
        "q_down": linear_init(ks[0], cfg.d_model, cfg.q_lora_rank, pol.at("q_down")),
        "q_up": linear_init(ks[1], cfg.q_lora_rank, h * qk, pol.at("q_up")),
        "kv_down": linear_init(ks[2], cfg.d_model, cfg.kv_lora_rank + cfg.qk_rope_dim,
                               pol.at("kv_down")),
        "kv_up": linear_init(ks[3], cfg.kv_lora_rank,
                             h * (cfg.qk_nope_dim + cfg.v_head_dim), pol.at("kv_up")),
        "wo": linear_init(ks[4], h * cfg.v_head_dim, cfg.d_model, pol.at("wo")),
        "qn": rmsnorm_init(cfg.q_lora_rank),
        "kvn": rmsnorm_init(cfg.kv_lora_rank),
    }


def _mla_q(p, x, cfg: MLAConfig, pol, positions):
    b, s, _ = x.shape
    h = cfg.n_heads
    qc = rmsnorm(p["qn"], linear_apply(p["q_down"], x, pol))
    q = linear_apply(p["q_up"], qc, pol).reshape(
        b, s, h, cfg.qk_nope_dim + cfg.qk_rope_dim)
    q_nope, q_rope = q[..., : cfg.qk_nope_dim], q[..., cfg.qk_nope_dim:]
    q_rope = rope(q_rope, positions, cfg.rope_theta)
    return q_nope, q_rope


def _mla_ckv(p, x, cfg: MLAConfig, pol, positions):
    ckv = linear_apply(p["kv_down"], x, pol)
    c, k_rope = ckv[..., : cfg.kv_lora_rank], ckv[..., cfg.kv_lora_rank:]
    c = rmsnorm(p["kvn"], c)
    k_rope = rope(k_rope[:, :, None, :], positions, cfg.rope_theta)[:, :, 0]
    return c, k_rope  # [B,S,rank], [B,S,rope]


def mla_apply(p, x, cfg: MLAConfig, pol: QuantPolicy, positions=None):
    """Training / prefill. Materializes per-head K/V chunk-wise via flash."""
    b, s, _ = x.shape
    h = cfg.n_heads
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    q_nope, q_rope = _mla_q(p, x, cfg, pol, positions)
    c, k_rope = _mla_ckv(p, x, cfg, pol, positions)
    kv = linear_apply(p["kv_up"], c, pol).reshape(
        b, s, h, cfg.qk_nope_dim + cfg.v_head_dim)
    k_nope, v = kv[..., : cfg.qk_nope_dim], kv[..., cfg.qk_nope_dim:]
    k_nope = constrain(k_nope, ("data", None, "model", None))
    v = constrain(v, ("data", None, "model", None))
    q = jnp.concatenate([q_nope, q_rope], -1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (b, s, h, cfg.qk_rope_dim))], -1)
    q = constrain(q, ("data", None, "model", None))
    o = flash_attention(q, k, v, causal=True)
    out = linear_apply(p["wo"], o.reshape(b, s, -1), pol)
    return out, (c, k_rope)


def mla_chunk_attention(q_c, q_rope, c_cache, kr_cache, qpos, *, scale):
    """Absorbed ragged-chunk attention over the slotted compressed cache.

    The MLA analogue of :func:`chunk_attention`: attention runs entirely
    in the compressed (rank) space — ``c_cache`` [B,S,rank] +
    ``kr_cache`` [B,S,rope] are the slotted cache, never expanded per
    head.  ``q_c`` [B,C,H,rank] is the nope query pre-absorbed through
    W_uk; ``q_rope`` [B,C,H,rope]; ``qpos`` [B,C] absolute position of
    each query row (per-slot ragged — row i of slot b attends to cache
    positions <= qpos[b, i]).  Returns the context still in compressed
    space, [B,C,H,rank] float32 (callers up-project through W_uv).

    Masked cache entries hit exp(NEG_INF) == 0 exactly, so results are
    independent of the cache capacity S and of stale compressed KV a
    previous slot occupant left beyond qpos; a fully-masked row (qpos <
    0, an idle slot) degenerates to a uniform-weight average — garbage
    but FINITE, so idle slots can never poison a batch with NaN.
    """
    s_c = jnp.einsum("bqhr,bkr->bhqk", q_c.astype(jnp.float32),
                     c_cache.astype(jnp.float32))
    s_r = jnp.einsum("bqhd,bkd->bhqk", q_rope.astype(jnp.float32),
                     kr_cache.astype(jnp.float32))
    scores = (s_c + s_r) * scale
    kpos = jnp.arange(c_cache.shape[1])
    valid = kpos[None, None, :] <= qpos[:, :, None]  # [B, C, S]
    scores = jnp.where(valid[:, None, :, :], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkr->bqhr", p, c_cache.astype(jnp.float32))


def mla_prefill_chunk(p, x, cache, cur_len, n_new, cfg: MLAConfig,
                      pol: QuantPolicy, w_kv=None, pages=None):
    """Ragged chunk step through MLA: x [B,C,d]; slot b consumes rows
    [:n_new[b]] at positions cur_len[b].. (per-slot rotary offsets),
    inserts their compressed latent / rope key into the slotted cache,
    and runs absorbed attention against it.  C == 1 with n_new in {0,1}
    is masked decode; larger C is chunked prefill.  Rows i >= n_new[b]
    compute garbage but never touch the cache.

    ``w_kv`` optionally supplies the precomputed effective (W_uk, W_uv)
    pair ([rank,H,nope], [rank,H,vdim]) so the absorbed-weight dequant
    runs OUTSIDE the per-step graph (the serving engine computes it once
    per run); when None it is derived here via :func:`_kv_up_split`.

    ``pages`` ([B, P] int32, optional) switches the compressed cache from
    per-slot [B, S, ...] leaves to paged pools [n_pages, ps, ...] — see
    :func:`gqa_prefill_chunk`.
    """
    b, c, _ = x.shape
    positions = cur_len[:, None] + jnp.arange(c)[None, :]  # [B, C]
    q_nope, q_rope = _mla_q(p, x, cfg, pol, positions)     # [B,C,H,*]
    c_new, kr_new = _mla_ckv(p, x, cfg, pol, positions)
    cc = _cache_insert(cache["c"], c_new, cur_len, n_new, pages)
    krc = _cache_insert(cache["kr"], kr_new, cur_len, n_new, pages)

    # absorb kv_up's K-half into q  (W_uk: rank -> H*nope)
    w_uk, w_uv = w_kv if w_kv is not None else _kv_up_split(p, cfg, x.dtype)
    q_c = jnp.einsum("bqhn,rhn->bqhr", q_nope.astype(jnp.float32),
                     w_uk.astype(jnp.float32))             # [B,C,H,rank]
    ctx_c = mla_chunk_attention(
        q_c, q_rope, _cache_view(cc, pages), _cache_view(krc, pages),
        positions,
        scale=1.0 / math.sqrt(cfg.qk_nope_dim + cfg.qk_rope_dim))
    o = jnp.einsum("bqhr,rhv->bqhv", ctx_c, w_uv.astype(jnp.float32))
    out = linear_apply(p["wo"], o.reshape(b, c, -1).astype(x.dtype), pol)
    return out, {"c": cc, "kr": krc}


def mla_decode(p, x, cache, cur_len, cfg: MLAConfig, pol: QuantPolicy,
               w_kv=None):
    """Absorbed one-token decode — the C=1 always-active special case of
    :func:`mla_prefill_chunk`, so the static and continuous engines share
    one copy of the absorbed math."""
    return mla_prefill_chunk(p, x, cache, cur_len, jnp.ones_like(cur_len),
                             cfg, pol, w_kv=w_kv)


def _kv_up_split(p, cfg: MLAConfig, dtype):
    """Effective (adapter-included) kv_up weight, split into K and V halves,
    dequantized in the *activation* dtype (not the storage default).
    Handles leading stack dims (scanned layers): [..., rank, H, nope/vdim].
    """
    w = dense_view(p["kv_up"], dtype=dtype)
    h = cfg.n_heads
    w = w.reshape(w.shape[:-2] + (cfg.kv_lora_rank, h,
                                  cfg.qk_nope_dim + cfg.v_head_dim))
    return w[..., : cfg.qk_nope_dim], w[..., cfg.qk_nope_dim:]


def mla_init_cache(batch: int, seq: int, cfg: MLAConfig, dtype=jnp.bfloat16):
    return {"c": jnp.zeros((batch, seq, cfg.kv_lora_rank), dtype),
            "kr": jnp.zeros((batch, seq, cfg.qk_rope_dim), dtype)}
