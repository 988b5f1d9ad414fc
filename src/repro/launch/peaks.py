"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

One table for every roofline in the repo (the dry-run's analysis, the
decode-kernel benchmark).  A kind that is not in the table is an error,
never a default: a roofline against the wrong chip's peaks is wrong.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float        # FLOP/s
    hbm_bytes: float         # bytes/s
    ici_link_bytes: float    # bytes/s per chip-to-chip link


PEAKS: dict[str, Peaks] = {
    # Google Cloud documentation, "TPU v5e".  1,600 Gbit/s of interconnect
    # per chip = 200 GB/s over 4 links.
    "TPU v5 lite": Peaks(bf16_flops=197e12, hbm_bytes=819e9,
                         ici_link_bytes=50e9),
}

# The chip the dry-run and the decode-kernel roofline model.
V5E = "TPU v5 lite"


def peaks(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
