"""Published peaks of each chip, keyed by the ``device_kind`` JAX reports.

A device missing from the table is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s
    # int8, 16 GB of HBM at 819 GB/s per chip.
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_s": 819e9, "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(PEAKS)}")
    return PEAKS[device_kind]
