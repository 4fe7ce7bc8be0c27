"""Published peak rates of one chip, keyed by the ``device_kind`` JAX
reports. A kind that is not here is an error, never a default."""

from __future__ import annotations

from typing import NamedTuple


class Peaks(NamedTuple):
    flops: float       # operations per second (bf16 matrix unit)
    hbm_bytes: float   # bytes per second of device memory
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(
        flops=197e12, hbm_bytes=819e9,
        source='Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
               '16 GB HBM at 819 GB/s'),
}


def peaks(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peak rates for device kind {device_kind!r}; "
                       "add it to chipbench/peaks.py with its source"
                       ) from None


def least_seconds(ops: float, nbytes: float, device_kind: str) -> float:
    """The least time the chip could take for ``ops`` operations and
    ``nbytes`` bytes: the larger of the two bounds."""
    pk = peaks(device_kind)
    return max(ops / pk.flops, nbytes / pk.hbm_bytes)
