"""Pallas TPU kernel: group-wise-quantized matmul  y = x @ dequant(W_q).

TPU adaptation of the paper's CUDA INT4 matmul (DESIGN.md Sec. 2): packed
codes stream HBM->VMEM tile-by-tile (BlockSpec), the VPU unpacks nibbles
and applies the per-(group, column) affine dequant, and bf16 tiles feed
the MXU.  The win is HBM bandwidth: INT4 moves ~3.6x fewer weight bytes
than bf16, which is the dominant roofline term for decode / long-context.

Grid = (M/bm, N/bn, K/bk), K innermost; partial products accumulate in an
f32 VMEM scratch and are written out once on the last K step.

Constraints: bk % group_size == 0 and bk % codes_per_byte == 0 (asserted
below), and the TPU (8, 128) block tiling that
:func:`repro.kernels.autotune.block_k_sizes` encodes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quant import codes_per_byte


def _unpack_block(qw_blk, bits: int, bk: int):
    """uint8 packed [bk/cpb, bn] -> int32 codes [bk, bn].

    Code t of byte row r sits at logical row r*cpb + t, matching
    :func:`repro.core.quant.pack` (reshape-interleave, axis 0).  The
    shifts run on int32: Mosaic casts uint8 to int32 and int32 to float,
    but refuses uint8 -> float32 directly.
    """
    cpb = codes_per_byte(bits)
    qw_blk = qw_blk.astype(jnp.int32)
    if cpb == 1:
        return qw_blk
    mask = jnp.int32(2**bits - 1)
    parts = [(qw_blk >> (bits * t)) & mask for t in range(cpb)]
    stacked = jnp.stack(parts, axis=1)  # [bk/cpb, cpb, bn]
    return stacked.reshape(bk, qw_blk.shape[-1])


def _dequant_block(qw_blk, scale_blk, zero_blk, bits: int, bk: int, group_size: int,
                   dtype=jnp.bfloat16):
    """Affine-dequantize one [bk, bn] weight tile (scale/zero are [bk/g, bn])."""
    codes = _unpack_block(qw_blk, bits, bk).astype(jnp.float32)
    g = group_size
    bn = codes.shape[-1]
    grouped = codes.reshape(bk // g, g, bn)
    w = grouped * scale_blk.astype(jnp.float32)[:, None, :] + zero_blk.astype(jnp.float32)[:, None, :]
    return w.reshape(bk, bn).astype(dtype)


def group_pool(x, group_size: int):
    """Sum each run of ``group_size`` lanes: x [m, bk] -> [m, bk/g] in
    x.dtype (the QA-LoRA adapter input).  A matmul with a 0/1 pooling
    matrix, because Mosaic refuses the lane-splitting reshape a
    reshape-sum would need; the sums accumulate in f32."""
    bk = x.shape[-1]
    shape = (bk, bk // group_size)
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    lo = jax.lax.broadcasted_iota(jnp.int32, shape, 1) * group_size
    pool = ((row >= lo) & (row < lo + group_size)).astype(x.dtype)
    return jax.lax.dot_general(
        x, pool, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(x.dtype)


def _qmatmul_kernel(x_ref, qw_ref, scale_ref, zero_ref, o_ref, acc_ref, *,
                    bits: int, group_size: int, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    bk = x_ref.shape[-1]
    w = _dequant_block(qw_ref[...], scale_ref[...], zero_ref[...],
                       bits, bk, group_size, dtype=x_ref.dtype)
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == n_k - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def qmatmul_pallas(x, qweight, scale, zero, *, bits: int, group_size: int,
                   block_m: int, block_n: int, block_k: int,
                   out_dtype=None, interpret: bool = False):
    """Raw pallas_call; use :mod:`repro.kernels.ops` for the padded wrapper."""
    m, k_dim = x.shape
    n = qweight.shape[1]
    cpb = codes_per_byte(bits)
    assert m % block_m == 0 and k_dim % block_k == 0 and n % block_n == 0, \
        (m, k_dim, n, block_m, block_n, block_k)
    assert block_k % group_size == 0 and block_k % cpb == 0, (block_k, group_size, cpb)
    n_k = k_dim // block_k
    grid = (m // block_m, n // block_n, n_k)
    out_dtype = out_dtype or x.dtype

    kernel = functools.partial(
        _qmatmul_kernel, bits=bits, group_size=group_size, n_k=n_k)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_k // cpb, block_n), lambda i, j, k: (k, j)),
            pl.BlockSpec((block_k // group_size, block_n), lambda i, j, k: (k, j)),
            pl.BlockSpec((block_k // group_size, block_n), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        # f32 accumulator lives in VMEM across the K loop
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        interpret=interpret,
    )(x, qweight, scale, zero)
