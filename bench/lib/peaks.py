"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports.  A kind missing from the table is an error:
a roofline or utilization against a guessed peak is no number at all.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2 at 819 GB/s per chip.
"""
from __future__ import annotations

import dataclasses
from typing import Dict


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float          # FLOP/s
    int8_ops: float            # OP/s
    hbm_bytes_per_s: float     # B/s
    hbm_bytes: float           # B


TABLE: Dict[str, Peaks] = {
    "TPU v5 lite": Peaks(bf16_flops=197e12, int8_ops=393e12,
                         hbm_bytes_per_s=819e9, hbm_bytes=16e9),
}


class UnknownDevice(KeyError):
    pass


def for_kind(device_kind: str) -> Peaks:
    try:
        return TABLE[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(TABLE)}") from None


def roofline_s(flops: float, nbytes: float, peaks: Peaks) -> float:
    """The least time the chip could take for this work."""
    return max(flops / peaks.bf16_flops, nbytes / peaks.hbm_bytes_per_s)
