"""Production mesh construction and per-chip peak rates.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state.  ``PEAKS`` holds the
published peaks of each chip, keyed by ``device_kind`` as JAX reports it
(used by the roofline report, repro.roofline, and the resource handler's
napkin math); a kind that is not in the table is an error, not a default.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax


@dataclass(frozen=True)
class HardwareSpec:
    peak_flops: float                 # bf16 FLOP/s per chip
    hbm_bw: float                     # bytes/s per chip
    ici_bw: float                     # bytes/s per link
    hbm_bytes: float                  # HBM capacity per chip
    source: str


PEAKS = {
    # 1,600 Gbit/s of interconnect per chip over 4 links = 50 GB/s a link
    "TPU v5 lite": HardwareSpec(
        peak_flops=197e12, hbm_bw=819e9, ici_bw=50e9, hbm_bytes=16e9,
        source='Google Cloud documentation, "TPU v5e"'),
}


def peaks(device_kind: str) -> HardwareSpec:
    """Published peaks of ``device_kind`` (``jax.Device.device_kind``)."""
    if device_kind not in PEAKS:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


def _auto(axes):
    """Auto axis types: the program places arrays with ``in_shardings``
    and ``with_sharding_constraint`` and lets the partitioner propagate,
    which ``jax.make_mesh``'s default Explicit axes refuse."""
    return (jax.sharding.AxisType.Auto,) * len(axes)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(axes))


def make_host_mesh(shape=None, axes=("data", "model")):
    """Small mesh over whatever devices exist (tests / local runs)."""
    n = len(jax.devices())
    if shape is None:
        shape = (1, n)
    return jax.make_mesh(shape, axes, axis_types=_auto(axes))
