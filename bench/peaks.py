"""Published peak rates of each accelerator, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s per chip.

A device that is not in the table is an error, never a default: a share of
a peak computed against the wrong chip's peak would be a wrong number.
"""
from __future__ import annotations

SOURCE = "Google Cloud documentation, 'TPU v5e' (cloud.google.com/tpu/docs/v5e)"

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}


def peaks(device_kind: str) -> dict:
    """→ the peak table of ``device_kind``; raises ``KeyError`` for a kind
    the table does not hold."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks known for device kind {device_kind!r}; "
                       f"add them to bench/peaks.py with their source "
                       f"(known: {sorted(PEAKS)})") from None
