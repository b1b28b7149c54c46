"""Published peaks of each accelerator, keyed by JAX's ``device_kind``.

A device that is not in the table is an error, never a default: a
utilization against a guessed peak is no measurement.
"""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of
    # HBM at 819 GB/s per chip. JAX names this chip "TPU v5 lite".
    "TPU v5 lite": {"bf16_flops_per_s": 197e12,
                    "hbm_bytes_per_s": 819e9,
                    "source": "Google Cloud, TPU v5e"},
}


def peak(device_kind: str) -> dict:
    """The peaks of ``device_kind``; ``KeyError`` for a kind not listed."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
