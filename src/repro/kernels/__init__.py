"""Pallas TPU kernels for the quantized compute hot-spots.

``qmatmul``            — group-wise WxA16 dequant matmul (x @ dequant(W_q))
``qalora_matmul``      — fused base matmul + group-pooled LoRA adapter
``qalora_slot_matmul`` — multi-tenant variant: per-row adapter index
                         gathers (A, B) from stacked device banks inside
                         one dispatch (punica-style segmented rank)

The wrappers dispatch on shape: flattened M <= ``GEMV_MAX_M`` routes to
the decode-optimized GEMV kernels in :mod:`repro.kernels.qmatvec` (grid
over (N, K) only — no M tiling/padding).  Block shapes come from the
autotune cache when present (:mod:`repro.kernels.autotune`), else a
static heuristic.

Each has a pure-jnp oracle in :mod:`repro.kernels.ref` (the slot variant's
oracle is ``repro.core.qalora.bank_adapter_delta``); CPU validation runs
with ``interpret=True``.
"""

from .ops import (qmatmul, intq_matmul, qalora_matmul,  # noqa: F401
                  qalora_slot_matmul,
                  flash_mha, pick_blocks, heuristic_blocks)
from .qmatvec import GEMV_MAX_M, INTQ_GEMV_MAX_M  # noqa: F401
from .ref import qmatmul_ref, qalora_matmul_ref  # noqa: F401
from . import autotune  # noqa: F401
