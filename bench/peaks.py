"""Published peaks of the chips the benchmark may run on, keyed by the
``device_kind`` that JAX reports.  A device that is not in the table is an
error: no peak is ever assumed."""

from __future__ import annotations

import dataclasses

SOURCE = "Google Cloud documentation, TPU v5e"


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float        # FLOP/s, dense bf16 matrix multiplication
    hbm_bytes_per_s: float   # HBM bandwidth
    hbm_bytes: int           # HBM capacity
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(bf16_flops=197e12, hbm_bytes_per_s=819e9,
                         hbm_bytes=16 << 30, source=SOURCE),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
