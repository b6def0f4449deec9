"""Published per-chip peaks, keyed by JAX's ``device_kind``.

A device that is not in the table is an error, never a default.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops: float  # bf16 FLOP/s
    hbm_bw: float  # bytes/s
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(
        flops=197e12,
        hbm_bw=819e9,
        source='Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM',
    ),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r} in bench/peaks.py "
            f"(known: {sorted(PEAKS)})"
        ) from None
