"""Published per-chip peaks, keyed by ``device_kind`` as JAX reports it.

Source: Google Cloud documentation, "TPU v5e" (Cloud TPU system
architecture): per chip 197 TFLOP/s bf16, 394 TOP/s int8, 16 GB of HBM at
819 GB/s.

A device that is not in the table is an error: add its published numbers
here, with their source, rather than borrowing another chip's.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 394e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
    },
}


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind``."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; add them to bench/peaks.py "
                         f"with their source") from None
