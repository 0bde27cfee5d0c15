"""Required work from shapes, and the chip's peaks.

What a step *requires*, counted from the configuration and the traffic,
never from a compiled program: the counts must not move when an
implementation does.  A share of the roofline is then

    least time = max(flops / peak_flops, bytes / peak_bytes_per_s)

summed over the steps of the window and divided by its length.
"""

from __future__ import annotations

from typing import Iterable, Tuple

# Google Cloud documentation, "TPU v5e": per chip 197 TFLOP/s bf16,
# 393 TOP/s int8, 16 GB HBM at 819 GB/s.  JAX names the chip "TPU v5 lite".
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                    "source": "cloud.google.com/tpu/docs/v5e"},
}

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(PEAKS)}")
    return PEAKS[device_kind]


def linears(cfg: dict) -> Iterable[Tuple[str, int, int]]:
    """(name, d_in, d_out) of one layer's projections (gqa family)."""
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


def least_seconds(flops: float, nbytes: float, peak: dict) -> float:
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])
