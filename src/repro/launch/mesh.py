"""Production meshes, and the per-chip peaks the rooflines divide by.

A function, not a module constant: importing this module must never touch
jax device state (smoke tests see 1 CPU device; only dryrun.py forces 512).
"""
from __future__ import annotations

from typing import NamedTuple

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    # automatic axes: the model pins activations with with_sharding_constraint
    return jax.make_mesh(shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


class ChipPeaks(NamedTuple):
    flops_bf16: float        # FLOP/s
    hbm_bytes_s: float       # B/s
    hbm_bytes: float         # B
    ici_bytes_s_per_link: float  # B/s, one link of the chip-to-chip ring


#: Published peaks of one chip, keyed by the ``device_kind`` JAX reports.
#: "TPU v5 lite" is the TPU v5e.  Source: Google Cloud documentation,
#: "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of
#: chip-to-chip interconnect over 4 links (50 GB/s per link).
CHIP_PEAKS: dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(flops_bf16=197e12, hbm_bytes_s=819e9,
                             hbm_bytes=16e9, ici_bytes_s_per_link=50e9),
}

#: the chip the dry-run's production meshes model
DRYRUN_DEVICE_KIND = "TPU v5 lite"


def chip_peaks(device_kind: str) -> ChipPeaks:
    """Peaks of ``device_kind``; a chip missing from the table is an error."""
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(CHIP_PEAKS)}") from None
