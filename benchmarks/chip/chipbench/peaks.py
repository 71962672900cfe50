"""Published peaks of one chip, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 16 GB HBM at 819 GB/s per chip.  A device missing from
the table is an error, never a default.
"""
from __future__ import annotations

from typing import NamedTuple


class Peak(NamedTuple):
    flops: float        # bf16 FLOP/s
    hbm_bytes: float    # bytes/s
    hbm_capacity: float  # bytes
    source: str


PEAKS = {
    "TPU v5 lite": Peak(197e12, 819e9, 16e9,
                        "Google Cloud, TPU v5e system architecture"),
}


def peak(device_kind: str) -> Peak:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       "add them, with their source, to chipbench/peaks.py")
    return PEAKS[device_kind]
