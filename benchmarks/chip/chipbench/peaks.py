"""Published peaks per chip, keyed by ``device_kind`` as JAX reports it.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.
A kind that is not listed is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "ops_int8": 393e12,
                    "hbm_bytes": 16e9, "hbm_bytes_per_s": 819e9},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       "add them to chipbench/peaks.py with their source") from None
