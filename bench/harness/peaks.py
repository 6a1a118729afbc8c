"""Published peaks of each accelerator, keyed by JAX's ``device_kind``.

TPU v5e: 197 TFLOP/s in bfloat16 and 819 GB/s of HBM bandwidth per
chip (Google Cloud documentation, "TPU v5e").  A device that is not in
the table is an error: a roofline share needs the chip's own peaks.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "bytes_per_s": 819e9},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to bench/harness/"
                       f"peaks.py with their source") from None
