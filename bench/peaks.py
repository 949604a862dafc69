"""Published per-chip peaks, keyed by the ``device_kind`` JAX reports.

Source: Google Cloud documentation, "TPU v5e" (system architecture): one
v5e chip peaks at 197 TFLOP/s in bf16 and 393 TOP/s in int8, with 16 GB
of HBM at 819 GB/s and 1,600 Gbit/s of chip-to-chip interconnect. JAX
names the chip "TPU v5 lite". A copy of ``benchmarks/roofline.py``
``PEAKS``. A device that is not in the table is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                    "ici_bytes_per_s": 200e9},
}


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; raises for a kind with none here."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None
