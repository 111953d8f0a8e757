"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bfloat16,
393 TOP/s in int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip
interconnect.  A device kind that is not in the table is an error, never
a default.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    flops: float  # bfloat16 FLOP/s per chip
    hbm_bytes_per_s: float
    hbm_bytes: float


PEAKS = {
    "TPU v5 lite": ChipPeaks(flops=197e12, hbm_bytes_per_s=819e9, hbm_bytes=16e9),
}


def peaks_for(device_kind: str) -> ChipPeaks:
    """The published peaks of ``device_kind``; raises on an unknown kind."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; add them to "
            "chipbench/peaks.py with their source"
        ) from None
