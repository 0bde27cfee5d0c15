"""Pallas TPU kernel: fused QA-LoRA matmul.

    y = x @ dequant(W_q)  +  s * pool_sum(x) @ A @ B

Beyond-paper optimization (DESIGN.md Sec. 2): the paper computes the
adapter path as a separate AvgPool1d + two matmuls, i.e. a second pass
over the activations.  Here the x tile is already resident in VMEM for
the base matmul, so group-pooling it (reshape-sum over lanes of size
``group_size``) and the rank-r contraction ride along for free; the
adapter accumulator ``[bm, r]`` is a tiny second VMEM scratch, and the
``@ B`` epilogue happens once per (i, j) tile on the last K step.

This removes one full activation read (2*M*K bytes) per layer versus the
unfused schedule — material for the memory-bound decode shapes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quant import codes_per_byte

from .qmatmul import _dequant_block, group_pool


def _qalora_kernel(x_ref, qw_ref, scale_ref, zero_ref, a_ref, b_ref, o_ref,
                   acc_ref, lacc_ref, *, bits: int, group_size: int, n_k: int,
                   s: float):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        lacc_ref[...] = jnp.zeros_like(lacc_ref)

    x = x_ref[...]
    bm, bk = x.shape
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


def qalora_matmul_pallas(x, qweight, scale, zero, a, b, *, s: float,
                         bits: int, group_size: int,
                         block_m: int, block_n: int, block_k: int,
                         out_dtype=None, interpret: bool = False):
    """Raw pallas_call; use :mod:`repro.kernels.ops` for the padded wrapper."""
    m, k_dim = x.shape
    n = qweight.shape[1]
    rank = a.shape[1]
    cpb = codes_per_byte(bits)
    assert m % block_m == 0 and k_dim % block_k == 0 and n % block_n == 0, \
        (m, k_dim, n, block_m, block_n, block_k)
    assert block_k % group_size == 0 and block_k % cpb == 0, (block_k, group_size, cpb)
    n_k = k_dim // block_k
    grid = (m // block_m, n // block_n, n_k)
    out_dtype = out_dtype or x.dtype

    kernel = functools.partial(
        _qalora_kernel, bits=bits, group_size=group_size, n_k=n_k, s=s)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_k // cpb, block_n), lambda i, j, k: (k, j)),
            pl.BlockSpec((block_k // group_size, block_n), lambda i, j, k: (k, j)),
            pl.BlockSpec((block_k // group_size, block_n), lambda i, j, k: (k, j)),
            pl.BlockSpec((block_k // group_size, rank), lambda i, j, k: (k, 0)),
            pl.BlockSpec((rank, block_n), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((block_m, block_n), jnp.float32),
            pltpu.VMEM((block_m, rank), jnp.float32),
        ],
        interpret=interpret,
    )(x, qweight, scale, zero, a, b)
