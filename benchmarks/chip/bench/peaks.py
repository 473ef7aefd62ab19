"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` that JAX reports.  A kind missing here is an error: a
roofline share or an MFU against a guessed peak would be a guess."""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops_bf16: float       # FLOP/s
    hbm_bytes_per_s: float
    hbm_bytes: float
    source: str


_V5E = Peaks(
    flops_bf16=197e12,
    hbm_bytes_per_s=819e9,
    hbm_bytes=16 * 2 ** 30,
    source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
           "819 GB/s HBM, 16 GiB HBM per chip",
)

TABLE = {
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
}


def lookup(device_kind: str) -> Peaks:
    try:
        return TABLE[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add them "
            f"to {__name__}.TABLE with their source") from None
