"""Published peaks of each accelerator, keyed by JAX's ``device_kind``.

A device that is not in the table is an error, never a default: a roofline
or utilisation share against the wrong peak is a wrong number.
"""
from __future__ import annotations

from typing import NamedTuple


class Peak(NamedTuple):
    flops: float        # dense bfloat16 FLOP/s per chip
    hbm_bytes_s: float  # HBM bandwidth per chip, bytes/s
    source: str


PEAKS = {
    "TPU v5 lite": Peak(197e12, 819e9,
                        "Google Cloud TPU documentation, 'TPU v5e': 197 "
                        "TFLOP/s bf16, 819 GB/s HBM per chip"),
}


def peak_for(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peak for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
