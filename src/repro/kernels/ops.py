"""Jit'd public wrappers around the Pallas kernels.

Handles leading-dim flattening, M-padding to the block size, block-shape
resolution (autotune cache -> measurement -> MXU-aligned heuristic — see
:mod:`repro.kernels.autotune`), shape-based dispatch between the matmul
and decode-GEMV kernels, and the CPU fallback: ``interpret=True``
executes the kernel body in Python on CPU so correctness is testable
everywhere; on TPU the same code lowers to Mosaic.

Dispatch: after flattening the leading dims, calls with M <=
``GEMV_MAX_M`` (= 8) route to :mod:`repro.kernels.qmatvec`, whose grid
runs over (N, K) only — no M tiling, no padding a single decode token up
to an MXU block.  Larger M takes the (M, N, K)-tiled matmul.

The merged intq linear has its own route, :func:`intq_matmul`, chosen from
the backend and the row count alone: on TPU, up to ``INTQ_GEMV_MAX_M``
rows run the GEMV with :func:`decode_blocks`; everything else, and every
call off TPU, runs ``x @ dequantize(q)`` in XLA.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.core.quant import QuantizedLinear, dequantize
from repro.core.qalora import QALoRAParams

from . import autotune
from .qmatmul import qmatmul_pallas
from .qalora_fused import qalora_matmul_pallas
from .qmatvec import (GEMV_MAX_M, INTQ_GEMV_MAX_M, qmatvec_pallas,
                      qalora_matvec_pallas, qalora_slot_matvec_pallas)


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _on_tpu() -> bool:
    """The intq route's backend probe, apart from the interpret default so
    a CPU test can take the TPU path with the kernel in interpret mode."""
    return jax.default_backend() == "tpu"


def heuristic_blocks(m: int, k: int, n: int, bits: int, group_size: int,
                     rank: int = 0):
    """Static VMEM-budgeted block shapes that meet the TPU tiling rule
    (see :func:`repro.kernels.autotune.block_k_sizes`; no measurement)."""
    block_k = autotune.block_k_sizes(k, bits, group_size, 512)[0]
    block_n = autotune.block_n_sizes(n, 256)[0]
    block_m = min(128, m)
    # x + unpacked w + acc must fit VMEM comfortably (<2MB at defaults)
    return block_m, block_n, block_k


# Decode GEMV blocks (merged intq route): Pallas pays a fixed cost per
# grid step, so few, deep steps whose VMEM working set stays at half of
# v5e's 16 MiB default scoped limit.  Blocks past 512 columns gained
# nothing on the chip, and full-depth K blocks lost (PERF.md).
DECODE_BLOCK_K, DECODE_BLOCK_N = 2048, 512
DECODE_VMEM_BYTES = 8 * 2**20


def gemv_vmem_bytes(m: int, block_k: int, block_n: int, bits: int,
                    group_size: int) -> int:
    """VMEM one GEMV grid step holds: double-buffered bf16 x planes,
    packed codes, bf16 scale and zero and the output, the f32 accumulator,
    and the int32 codes plus the f32 and bf16 weights of one code plane."""
    from repro.core.quant import codes_per_byte

    plane = block_k // codes_per_byte(bits) * block_n
    inputs = (2 * m * block_k + plane + 2 * 2 * block_k // group_size * block_n
              + 2 * m * block_n)
    return 2 * inputs + 4 * m * block_n + (4 + 4 + 2) * plane


def decode_blocks(m: int, k: int, n: int, bits: int,
                  group_size: int) -> tuple:
    """(block_n, block_k) of the decode GEMV: the widest legal N block up
    to DECODE_BLOCK_N, then the deepest legal K block up to DECODE_BLOCK_K
    whose step fits DECODE_VMEM_BYTES."""
    bn = autotune.block_n_sizes(n, DECODE_BLOCK_N)[0]
    bks = autotune.block_k_sizes(k, bits, group_size, DECODE_BLOCK_K)
    fits = [bk for bk in bks if gemv_vmem_bytes(m, bk, bn, bits, group_size)
            <= DECODE_VMEM_BYTES]
    return bn, (fits or bks[-1:])[0]


def intq_matmul(x, qt: QuantizedLinear):
    """y = x @ dequant(qt) for a merged INT-N linear, any leading dims.
    On TPU, decode-shaped calls (flattened rows <= INTQ_GEMV_MAX_M) run
    the fused dequant-GEMV; every other call dequantizes in XLA."""
    *lead, k = x.shape
    m = math.prod(lead)
    if not (_on_tpu() and m <= INTQ_GEMV_MAX_M):
        return x @ dequantize(qt, x.dtype)
    n = qt.d_out
    bn, bk = decode_blocks(m, k, n, qt.bits, qt.group_size)
    y = qmatvec_pallas(
        x.reshape(m, k), qt.qweight, qt.scale, qt.zero, bits=qt.bits,
        group_size=qt.group_size, block_n=bn, block_k=bk,
        interpret=_default_interpret())
    return y.reshape(*lead, n)


def pick_blocks(m: int, k: int, n: int, bits: int, group_size: int,
                rank: int = 0, measure: bool = None):
    """Resolve block shapes: autotune cache hit -> (optional) measurement
    -> static heuristic.  Measurement runs only when ``measure=True`` or
    ``REPRO_AUTOTUNE=1`` — it times real kernels (see autotune.py)."""
    backend = jax.default_backend()
    key = autotune.cache_key(m, k, n, bits, group_size, rank, backend)
    hit = autotune.lookup(key)
    if hit is not None:
        return hit
    if measure or (measure is None and autotune.measure_enabled()):
        return autotune.measure_qmatmul(m, k, n, bits, group_size, rank)
    return heuristic_blocks(m, k, n, bits, group_size, rank)


def _flatten_pad(x, block_m_cap: int = 128):
    *lead, k = x.shape
    m = int(math.prod(lead)) if lead else 1
    x2 = x.reshape(m, k)
    bm = min(block_m_cap, m)
    pad = (-m) % bm
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    return x2, lead, m, bm


def _dispatch(x):
    """Flatten leading dims; returns (lead, m, use_gemv)."""
    *lead, _ = x.shape
    m = int(math.prod(lead)) if lead else 1
    return lead, m, m <= GEMV_MAX_M


@functools.partial(jax.jit, static_argnames=("s", "out_dtype", "interpret"))
def qmatmul(x, qt: QuantizedLinear, s=None, out_dtype=None, interpret=None):
    """y = x @ dequant(qt); any leading dims on x.  Small-M calls (decode)
    dispatch to the GEMV kernel automatically."""
    interpret = _default_interpret() if interpret is None else interpret
    k, n = qt.d_in, qt.d_out
    lead, m, use_gemv = _dispatch(x)
    if use_gemv:
        _, bn, bk = pick_blocks(m, k, n, qt.bits, qt.group_size)
        y = qmatvec_pallas(
            x.reshape(m, k), qt.qweight, qt.scale, qt.zero, bits=qt.bits,
            group_size=qt.group_size, block_n=bn, block_k=bk,
            out_dtype=out_dtype or x.dtype, interpret=interpret)
        return y.reshape(*lead, n)
    x2, lead, m, bm = _flatten_pad(x)
    _, bn, bk = pick_blocks(x2.shape[0], k, n, qt.bits, qt.group_size)
    y = qmatmul_pallas(
        x2, qt.qweight, qt.scale, qt.zero, bits=qt.bits,
        group_size=qt.group_size, block_m=bm, block_n=bn, block_k=bk,
        out_dtype=out_dtype or x.dtype, interpret=interpret)
    return y[:m].reshape(*lead, n)


@functools.partial(jax.jit, static_argnames=("causal", "window", "interpret",
                                              "block_q", "block_k"))
def flash_mha(q, k, v, causal=True, window=0, interpret=None,
              block_q=128, block_k=128):
    """Flash attention, q/k/v: [B, S, H, d] (MHA; expand GQA kv first).

    Kernel path for TPU; interpret=True (default off-TPU) for validation.
    """
    interpret = _default_interpret() if interpret is None else interpret
    from .flash import flash_mha_pallas
    b, sq, h, d = q.shape
    fold = lambda t: t.transpose(0, 2, 1, 3).reshape(b * h, t.shape[1], d)
    o = flash_mha_pallas(fold(q), fold(k), fold(v), causal=causal,
                         window=window, block_q=min(block_q, sq),
                         block_k=min(block_k, k.shape[1]),
                         interpret=interpret)
    return o.reshape(b, h, sq, d).transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=("s", "out_dtype", "interpret"))
def qalora_matmul(x, qt: QuantizedLinear, p: QALoRAParams, s: float = 1.0,
                  out_dtype=None, interpret=None):
    """Fused y = x @ dequant(qt) + s * pool_sum(x) @ A @ B.  Small-M calls
    (decode) dispatch to the fused GEMV kernel automatically."""
    interpret = _default_interpret() if interpret is None else interpret
    k, n = qt.d_in, qt.d_out
    rank = p.a.shape[1]
    lead, m, use_gemv = _dispatch(x)
    if use_gemv:
        _, bn, bk = pick_blocks(m, k, n, qt.bits, qt.group_size, rank)
        y = qalora_matvec_pallas(
            x.reshape(m, k), qt.qweight, qt.scale, qt.zero, p.a, p.b,
            s=float(s), bits=qt.bits, group_size=qt.group_size,
            block_n=bn, block_k=bk,
            out_dtype=out_dtype or x.dtype, interpret=interpret)
        return y.reshape(*lead, n)
    x2, lead, m, bm = _flatten_pad(x)
    _, bn, bk = pick_blocks(x2.shape[0], k, n, qt.bits, qt.group_size, rank)
    y = qalora_matmul_pallas(
        x2, qt.qweight, qt.scale, qt.zero, p.a, p.b, s=float(s),
        bits=qt.bits, group_size=qt.group_size,
        block_m=bm, block_n=bn, block_k=bk,
        out_dtype=out_dtype or x.dtype, interpret=interpret)
    return y[:m].reshape(*lead, n)


@functools.partial(jax.jit, static_argnames=("s", "out_dtype", "interpret"))
def qalora_slot_matmul(x, qt: QuantizedLinear, a_bank, b_bank, ids,
                       s: float = 1.0, out_dtype=None, interpret=None):
    """Multi-tenant fused forward: y[i] = x[i] @ dequant(qt) +
    s * pool(x[i]) @ A[ids[i]] @ B[ids[i]].

    ``a_bank [N, L, r]`` / ``b_bank [N, r, D_out]`` stack N adapters;
    ``ids`` carries one adapter index per leading row of x and must have
    shape ``x.shape[:-1]`` (broadcast per-slot ids over ride-along dims
    before calling).  Decode shapes (flattened M <= GEMV_MAX_M) run the
    fused per-slot gather kernel in ONE dispatch; larger M (prefill)
    takes the base matmul kernel plus the einsum-gather adapter
    reference — at prefill M the adapter epilogue is a rounding error
    next to the base GEMM, so the gather kernel's VMEM bank residency is
    not worth a second matmul variant."""
    interpret = _default_interpret() if interpret is None else interpret
    k, n = qt.d_in, qt.d_out
    rank = a_bank.shape[-1]
    assert ids.shape == x.shape[:-1], (ids.shape, x.shape)
    lead, m, use_gemv = _dispatch(x)
    if use_gemv:
        _, bn, bk = pick_blocks(m, k, n, qt.bits, qt.group_size, rank)
        y = qalora_slot_matvec_pallas(
            x.reshape(m, k), qt.qweight, qt.scale, qt.zero,
            a_bank, b_bank, ids.reshape(m), s=float(s), bits=qt.bits,
            group_size=qt.group_size, block_n=bn, block_k=bk,
            out_dtype=out_dtype or x.dtype, interpret=interpret)
        return y.reshape(*lead, n)
    from repro.core.qalora import bank_adapter_delta
    base = qmatmul(x, qt, out_dtype=out_dtype, interpret=interpret)
    delta = bank_adapter_delta(x.reshape(m, k), a_bank, b_bank,
                               ids.reshape(m), float(s), qt.group_size)
    return base + delta.reshape(base.shape).astype(base.dtype)
