"""The chip's peaks, and the least time a counted amount of work takes.

What a step *requires* is counted by the configuration's family
(``families/<family>.py``) from the configuration and the traffic.  A
share of the roofline is then

    least time = max(flops / peak_flops, bytes / peak_bytes_per_s)

summed over the steps of the window and divided by its length.
"""

from __future__ import annotations

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


def least_seconds(flops: float, nbytes: float, peak: dict) -> float:
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])
