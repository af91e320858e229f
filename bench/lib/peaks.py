"""Published peaks of each accelerator, keyed by ``device_kind`` as JAX
reports it.  A kind that is not in the table is an error, not a default."""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peaks:
    flops: float          # bf16 FLOP/s per chip
    hbm_bytes_per_s: float
    hbm_bytes: float
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(
        flops=197e12, hbm_bytes_per_s=819e9, hbm_bytes=16e9,
        source='Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
               '819 GB/s HBM, 16 GB HBM per chip'),
}


def peaks(device_kind: str) -> Peaks:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r};"
                       f" known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
