"""Peaks of one chip, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bfloat16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s per chip.
A kind that is not here is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise SystemExit(f"no peaks for device kind {device_kind!r}; "
                         f"known: {sorted(PEAKS)}") from None
