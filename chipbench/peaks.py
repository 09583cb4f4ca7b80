"""Published peaks of each accelerator the benchmark may run on, keyed by
the ``device_kind`` JAX reports.  A kind that is not here is an error.

TPU v5e: 197 TFLOP/s bf16 (MXU), 393 TOP/s int8, 16 GB HBM at 819 GB/s
(Google Cloud documentation, "TPU v5e").  The chip publishes no f32 or
vector-unit peak, so the bf16 figure is the only compute peak: a share of
it taken by f32 vector work reads low, never high.
"""
from __future__ import annotations

from typing import Dict, NamedTuple


class Peaks(NamedTuple):
    flops_per_s: float      # bf16 matrix-unit peak
    bytes_per_s: float      # HBM bandwidth
    hbm_bytes: float
    source: str


PEAKS: Dict[str, Peaks] = {
    "TPU v5 lite": Peaks(197e12, 819e9, 16e9,
                         'Google Cloud documentation, "TPU v5e"'),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


def least_seconds(flops: float, nbytes: float, peaks: Peaks) -> float:
    """The least time the chip could take: the larger of the compute bound
    and the memory bound."""
    return max(flops / peaks.flops_per_s, nbytes / peaks.bytes_per_s)
