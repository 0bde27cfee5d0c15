"""Pallas TPU kernel: small-M fused dequant GEMV  y = x @ dequant(W_q).

Decode-shape specialization of :mod:`repro.kernels.qmatmul` (DESIGN: the
serve hot path).  At M <= 8 the matmul grid would still tile M to an
MXU-aligned block — padding a single decode token up to 128 rows and
burning ~128x the MXU work for the same HBM traffic.  Here the whole
activation strip [m, bk] rides in VMEM, the grid runs over (N, K) only,
and the [bk, bn] dequantized weight tile is contracted against all m rows
at once: the kernel stays bandwidth-bound on the packed INT-N weight
stream, which is the QA-LoRA deployment win (paper Sec. 3.2 / App. B).
The plain GEMV (`qmatvec_pallas`) dequantizes one code plane of the packed
bytes at a time and contracts it with the matching plane of x, split
outside the kernel, so the codes are never shuffled back into row order.

A fused QA-LoRA variant (`qalora_matvec_pallas`) carries the group-pooled
rank-r adapter epilogue in a second tiny VMEM scratch, mirroring
`qalora_fused.py`: pool_sum(x) @ A accumulates across K steps and the
`@ B` epilogue lands once per N tile on the last K step.

The multi-tenant variant (`qalora_slot_matvec_pallas`) is the punica-style
batched segmented-rank epilogue: `(A, B)` live in stacked device-resident
banks `[n_adapters, ...]` and each row of x carries an adapter index
(SMEM scalars), gathered with `pl.ds` inside the kernel — one dispatch
applies a DIFFERENT adapter per decode slot over the shared INT-N base.

Grid = (N/bn, K/bk), K innermost; f32 accumulation in VMEM scratch.
Constraints (asserted below, so a stale/hand-edited autotune cache entry
fails loudly instead of silently dropping K/N tail blocks): bk | K,
bn | N, group_size | bk, codes_per_byte | bk.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quant import codes_per_byte

from .qmatmul import _dequant_block, group_pool

# Above this M the padded-matmul path wins (MXU utilization catches up);
# below it the GEMV grid avoids the pad-to-block_m waste entirely.  The
# fused QA-LoRA variants keep this bound (the banked one unrolls a loop
# over the rows).
GEMV_MAX_M = 8
# The merged intq route's own bound (`repro.kernels.ops.intq_matmul`): on
# TPU, flattened row counts up to here run the plain GEMV.  Decode bursts
# (one row per slot: 16 or 32) sit under it and ragged prefill steps (4096
# rows) far above; the crossover against XLA's dequantize-then-dot is in
# PERF.md.
INTQ_GEMV_MAX_M = 128


def _qmatvec_kernel(x_ref, qw_ref, scale_ref, zero_ref, o_ref, acc_ref, *,
                    bits: int, group_size: int, n_k: int):
    """One [bk, bn] tile, code plane by code plane: plane t of the packed
    bytes holds input rows ``r * cpb + t`` (:func:`repro.core.quant.pack`)
    and meets x's plane t (``x[:, t::cpb]``, split outside the kernel),
    so the codes are never interleaved back into row order."""
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    cpb = codes_per_byte(bits)
    q = qw_ref[...].astype(jnp.int32)
    rows, bn = q.shape
    per_group = group_size // cpb  # plane rows a scale row covers
    scale = scale_ref[...].astype(jnp.float32)[:, None, :]
    zero = zero_ref[...].astype(jnp.float32)[:, None, :]
    mask = jnp.int32(2**bits - 1)
    for t in range(cpb):
        codes = (q >> (bits * t)) & mask if cpb > 1 else q
        w = codes.astype(jnp.float32).reshape(rows // per_group, per_group, bn)
        w = (w * scale + zero).reshape(rows, bn).astype(x_ref.dtype)
        acc_ref[...] += jax.lax.dot_general(
            x_ref[t], w, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def qmatvec_pallas(x, qweight, scale, zero, *, bits: int, group_size: int,
                   block_n: int, block_k: int,
                   out_dtype=None, interpret: bool = False):
    """Raw pallas_call; use :mod:`repro.kernels.ops` for the dispatching
    wrapper.  ``x: [m, K]`` with m <= INTQ_GEMV_MAX_M (no M tiling)."""
    m, k_dim = x.shape
    n = qweight.shape[1]
    assert m <= INTQ_GEMV_MAX_M, (m, INTQ_GEMV_MAX_M)
    cpb = codes_per_byte(bits)
    assert k_dim % block_k == 0 and n % block_n == 0, (k_dim, n, block_k, block_n)
    assert block_k % group_size == 0 and group_size % cpb == 0, (block_k, group_size, cpb)
    n_k = k_dim // block_k
    grid = (n // block_n, n_k)
    out_dtype = out_dtype or x.dtype
    # [cpb, m, K / cpb]: plane t holds x[:, t::cpb]
    planes = x.reshape(m, k_dim // cpb, cpb).transpose(2, 0, 1)

    kernel = functools.partial(
        _qmatvec_kernel, bits=bits, group_size=group_size, n_k=n_k)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((cpb, m, block_k // cpb), lambda j, k: (0, 0, k)),
            pl.BlockSpec((block_k // cpb, block_n), lambda j, k: (k, j)),
            pl.BlockSpec((block_k // group_size, block_n), lambda j, k: (k, j)),
            pl.BlockSpec((block_k // group_size, block_n), lambda j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((m, block_n), lambda j, k: (0, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((m, block_n), jnp.float32)],
        interpret=interpret,
    )(planes, qweight, scale, zero)


def _qalora_matvec_kernel(x_ref, qw_ref, scale_ref, zero_ref, a_ref, b_ref,
                          o_ref, acc_ref, lacc_ref, *, bits: int,
                          group_size: int, n_k: int, s: float):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        lacc_ref[...] = jnp.zeros_like(lacc_ref)

    x = x_ref[...]
    m, bk = x.shape
    w = _dequant_block(qw_ref[...], scale_ref[...], zero_ref[...],
                       bits, bk, group_size, dtype=x.dtype)
    acc_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    # adapter: pool x over quantization groups, contract with A's K-slice
    pooled = group_pool(x, group_size)
    lacc_ref[...] += jax.lax.dot_general(
        pooled, a_ref[...].astype(x.dtype), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _done():
        adapter = jax.lax.dot_general(
            lacc_ref[...].astype(b_ref.dtype), b_ref[...],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        o_ref[...] = (acc_ref[...] + s * adapter).astype(o_ref.dtype)


def qalora_matvec_pallas(x, qweight, scale, zero, a, b, *, s: float,
                         bits: int, group_size: int,
                         block_n: int, block_k: int,
                         out_dtype=None, interpret: bool = False):
    """Fused y = x @ dequant(W_q) + s * pool_sum(x) @ A @ B at decode M."""
    m, k_dim = x.shape
    n = qweight.shape[1]
    assert m <= GEMV_MAX_M, (m, GEMV_MAX_M)
    rank = a.shape[1]
    cpb = codes_per_byte(bits)
    assert k_dim % block_k == 0 and n % block_n == 0, (k_dim, n, block_k, block_n)
    assert block_k % group_size == 0 and block_k % cpb == 0, (block_k, group_size, cpb)
    n_k = k_dim // block_k
    grid = (n // block_n, n_k)
    out_dtype = out_dtype or x.dtype

    kernel = functools.partial(
        _qalora_matvec_kernel, bits=bits, group_size=group_size, n_k=n_k, s=s)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((m, block_k), lambda j, k: (0, k)),
            pl.BlockSpec((block_k // cpb, block_n), lambda j, k: (k, j)),
            pl.BlockSpec((block_k // group_size, block_n), lambda j, k: (k, j)),
            pl.BlockSpec((block_k // group_size, block_n), lambda j, k: (k, j)),
            pl.BlockSpec((block_k // group_size, rank), lambda j, k: (k, 0)),
            pl.BlockSpec((rank, block_n), lambda j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((m, block_n), lambda j, k: (0, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((m, block_n), jnp.float32),
            pltpu.VMEM((m, rank), jnp.float32),
        ],
        interpret=interpret,
    )(x, qweight, scale, zero, a, b)


def _qalora_slot_matvec_kernel(ids_ref, x_ref, qw_ref, scale_ref, zero_ref,
                               a_ref, b_ref, o_ref, acc_ref, lacc_ref, *,
                               bits: int, group_size: int, n_k: int,
                               s: float, m: int):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        lacc_ref[...] = jnp.zeros_like(lacc_ref)

    x = x_ref[...]
    _, bk = x.shape
    w = _dequant_block(qw_ref[...], scale_ref[...], zero_ref[...],
                       bits, bk, group_size, dtype=x.dtype)
    acc_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    # per-row adapter gather: each row contracts its pooled activations
    # with ITS OWN adapter's A-slice from the bank (dynamic leading-axis
    # slice; m <= GEMV_MAX_M keeps this a tiny unrolled loop)
    pooled = group_pool(x, group_size)
    for i in range(m):
        a_i = a_ref[pl.ds(ids_ref[i], 1)][0].astype(x.dtype)  # [bk/g, r]
        lacc_ref[i:i + 1, :] += jax.lax.dot_general(
            pooled[i:i + 1, :], a_i, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _done():
        for i in range(m):
            b_i = b_ref[pl.ds(ids_ref[i], 1)][0]  # [r, bn]
            adapter = jax.lax.dot_general(
                lacc_ref[i:i + 1, :].astype(b_i.dtype), b_i,
                (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
            o_ref[i:i + 1, :] = (acc_ref[i:i + 1, :]
                                 + s * adapter).astype(o_ref.dtype)


def qalora_slot_matvec_pallas(x, qweight, scale, zero, a_bank, b_bank, ids,
                              *, s: float, bits: int, group_size: int,
                              block_n: int, block_k: int,
                              out_dtype=None, interpret: bool = False):
    """Fused y[i] = x[i] @ dequant(W_q) + s * pool(x[i]) @ A[ids[i]] @
    B[ids[i]]: one dispatch, a different adapter per row (decode slot).

    ``a_bank [N, L, r]`` / ``b_bank [N, r, D_out]`` ride whole in VMEM
    (adapter banks are tiny next to the packed base); ``ids [m]`` int32
    lives in SMEM for the in-kernel gather."""
    m, k_dim = x.shape
    n = qweight.shape[1]
    assert m <= GEMV_MAX_M, (m, GEMV_MAX_M)
    assert ids.shape == (m,), (ids.shape, m)
    n_adapters, _, rank = a_bank.shape
    assert b_bank.shape[:2] == (n_adapters, rank), (a_bank.shape, b_bank.shape)
    cpb = codes_per_byte(bits)
    assert k_dim % block_k == 0 and n % block_n == 0, (k_dim, n, block_k, block_n)
    assert block_k % group_size == 0 and block_k % cpb == 0, (block_k, group_size, cpb)
    n_k = k_dim // block_k
    grid = (n // block_n, n_k)
    out_dtype = out_dtype or x.dtype

    kernel = functools.partial(
        _qalora_slot_matvec_kernel, bits=bits, group_size=group_size,
        n_k=n_k, s=s, m=m)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # ids [m]
            pl.BlockSpec((m, block_k), lambda j, k: (0, k)),
            pl.BlockSpec((block_k // cpb, block_n), lambda j, k: (k, j)),
            pl.BlockSpec((block_k // group_size, block_n), lambda j, k: (k, j)),
            pl.BlockSpec((block_k // group_size, block_n), lambda j, k: (k, j)),
            pl.BlockSpec((n_adapters, block_k // group_size, rank),
                         lambda j, k: (0, k, 0)),
            pl.BlockSpec((n_adapters, rank, block_n), lambda j, k: (0, 0, j)),
        ],
        out_specs=pl.BlockSpec((m, block_n), lambda j, k: (0, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((m, block_n), jnp.float32),
            pltpu.VMEM((m, rank), jnp.float32),
        ],
        interpret=interpret,
    )(ids.astype(jnp.int32), x, qweight, scale, zero, a_bank, b_bank)
