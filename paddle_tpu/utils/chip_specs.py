"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

One table for every script that turns a measured rate into a
utilization.  A device that is not in the table is an error, never a
default: a rate divided by another chip's peak is not a utilization.
Sources: Google Cloud TPU documentation, system-architecture pages
"TPU v5e", "TPU v5p", "TPU v4" (bf16 peak per chip, HBM capacity and
bandwidth per chip).
"""
from __future__ import annotations

from typing import NamedTuple


class ChipSpec(NamedTuple):
    bf16_flops: float       # peak bf16 FLOP/s per chip
    hbm_bytes: float        # HBM capacity per chip
    hbm_bytes_per_s: float  # HBM bandwidth per chip


# device_kind strings are what jax reports, not marketing names: a v5e
# is "TPU v5 lite", a v5p is "TPU v5"
CHIP_SPECS = {
    "TPU v5 lite": ChipSpec(197e12, 16e9, 819e9),
    "TPU v5": ChipSpec(459e12, 95e9, 2765e9),
    "TPU v4": ChipSpec(275e12, 32e9, 1228e9),
}


def chip_spec(device_kind: str) -> ChipSpec:
    try:
        return CHIP_SPECS[device_kind]
    except KeyError:
        raise ValueError(
            f"unknown device_kind {device_kind!r}: no published peak in "
            f"paddle_tpu.utils.chip_specs (known: {sorted(CHIP_SPECS)})"
        ) from None
