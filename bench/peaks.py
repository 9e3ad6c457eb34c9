"""Published peaks of the accelerators the benchmark runs on.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.
JAX reports the chip as ``TPU v5 lite``; ``TPU v5e`` is the product name.
A device kind that is not in the table is an error, never a default.
"""
from __future__ import annotations

SOURCE = "Google Cloud documentation, 'TPU v5e'"

_V5E = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9}

PEAKS = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None
