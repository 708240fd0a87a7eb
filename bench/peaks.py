"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` that JAX reports.  A device that is not here is an error."""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peak:
    bf16_flops: float       # FLOP/s per chip, dense bf16
    hbm_bytes_per_s: float  # per chip
    source: str


_V5E = Peak(bf16_flops=197e12, hbm_bytes_per_s=819e9,
            source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
                   "16 GB HBM at 819 GB/s per chip")

PEAKS = {
    "TPU v5 lite": _V5E,
}


class UnknownDevice(LookupError):
    pass


def peak(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device kind {device_kind!r}; add them "
            f"to bench/peaks.py with their source") from None
