"""Required work of the ``linear`` scope, from shapes (as ``work.py``).

The scope covers every call of ``schemes.linear_apply``: the quantized
projections, and in serving the tagged head, which ``LM._logits`` applies
through ``linear_apply``.  Training applies the head through a dense view
outside the scope, so its count leaves the head out.  The least time of
the counted work over the device seconds under the scope is the linear
layer's share of its roofline.
"""

from __future__ import annotations

from typing import Iterable, Tuple

from . import work


def serve_pass(cfg: dict, rows: Iterable[Tuple[int, int]]):
    """(flops, bytes) of one serving pass's linear work: 2 flops per
    quantized weight per consumed row, and the head for each consuming
    slot's last row; every quantized weight and the head read once, and
    the consumed rows' activations."""
    rows = [(s, n) for s, n in rows if n > 0]
    tokens = sum(n for _, n in rows)
    d = cfg["d_model"]
    flops = (2 * tokens * work.linear_params(cfg)
             + 2 * len(rows) * d * cfg["vocab"])
    nbytes = (work.quantized_bytes(cfg) + work.head_bytes(cfg)
              + tokens * d * work.DTYPE_BYTES[cfg["dtype"]])
    return flops, nbytes


def train_step(cfg: dict, batch: int, seq: int):
    """(flops, bytes) of one QA-LoRA step's linear work: 4 flops per base
    weight and token (forward and input gradient), 6 per adapter weight
    and token; the base and the adapters read once.  Recomputation is not
    counted."""
    tokens = batch * seq
    ad = work.adapter_params(cfg)
    flops = tokens * (4 * work.linear_params(cfg) + 6 * ad)
    nbytes = work.quantized_bytes(cfg) + ad * work.DTYPE_BYTES[cfg["dtype"]]
    return flops, nbytes
