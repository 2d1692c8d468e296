"""Published peaks of each chip the benchmark may run on, keyed by JAX's
``device_kind``.  A device that is not in the table is an error, never a
default.

TPU v5e (``device_kind`` "TPU v5 lite"): Google Cloud documentation, "TPU
v5e" (cloud.google.com/tpu/docs/v5e): 197 TFLOP/s bf16, 393 TOP/s int8,
16 GB HBM at 819 GB/s per chip.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,     # FLOP/s per chip
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"device_kind {device_kind!r} is not in the "
                       f"benchmark's peaks table ({sorted(PEAKS)})")
    return PEAKS[device_kind]
