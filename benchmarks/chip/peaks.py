"""Published peaks per chip, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16,
393 TOP/s in int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of
chip-to-chip interconnect.  A device that is not in the table is an
error, never a default.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    flops_bf16: float       # FLOP/s
    hbm_bytes_per_s: float  # bytes/s


CHIP_PEAKS = {
    "TPU v5 lite": ChipPeaks(flops_bf16=197e12, hbm_bytes_per_s=819e9),
}


def peaks_of(device_kind: str) -> ChipPeaks:
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks recorded for device kind {device_kind!r};"
                       f" known: {sorted(CHIP_PEAKS)}") from None
