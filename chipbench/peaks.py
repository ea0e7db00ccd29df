"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports. A device that is not here is an error.

TPU v5e: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s in bf16 and 16 GB of HBM at 819 GB/s per chip.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
}


def peak(kind: str) -> dict:
    if kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {kind!r}")
    return PEAKS[kind]
