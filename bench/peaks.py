"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports.  A device that is not in the table is an
error, never a default: a share of a peak needs the peak of the chip that
ran.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2 at 819 GB/s per chip.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud, TPU v5e: 197 TFLOP/s bf16, 819 GB/s, 16 GB",
    },
}


def peaks_for(device_kind: str) -> dict:
    """The table's row for ``device_kind``; raises for an unknown chip."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}: add a row to bench/peaks.py with "
                       f"its source") from None


def least_seconds(flops: float, nbytes: float, peaks: dict):
    """The least time the chip could take for this work, and which of the
    two peaks bounds it."""
    t_flops = flops / peaks["flops_bf16"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")
