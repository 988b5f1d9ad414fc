"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

The benchmark's own copy, so that the yardstick does not move with the
program.  A kind that is not in the table is an error, never a default.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float        # FLOP/s
    hbm_bytes: float         # bytes/s
    ici_link_bytes: float    # bytes/s per chip-to-chip link


PEAKS: dict[str, Peaks] = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of HBM
    # at 819 GB/s, 1,600 Gbit/s of interconnect per chip (4 links).
    "TPU v5 lite": Peaks(bf16_flops=197e12, hbm_bytes=819e9,
                         ici_link_bytes=50e9),
}


def peaks(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
