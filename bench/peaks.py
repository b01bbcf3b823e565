"""Peak rates of each accelerator the benchmark runs on, keyed by the
``device_kind`` JAX reports.

Source: Google Cloud documentation, "TPU v5e" (system architecture table):
197 TFLOP/s bf16 and 16 GB of HBM at 819 GB/s per chip.  A device kind
that is not in the table is an error, never a default.
"""

from __future__ import annotations

from typing import NamedTuple


class Peak(NamedTuple):
    flops_bf16: float      # FLOP/s
    hbm_bytes_per_s: float  # B/s
    hbm_bytes: int         # B of device memory
    source: str


PEAKS = {
    "TPU v5 lite": Peak(
        flops_bf16=197e12, hbm_bytes_per_s=819e9, hbm_bytes=16 * 10**9,
        source='Google Cloud documentation, "TPU v5e"',
    ),
}


def peak_of(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peak rates for device kind {device_kind!r}; add it to "
            f"bench/peaks.py with its source (known: {sorted(PEAKS)})"
        ) from None


def roofline_s(flops: float, nbytes: float, peak: Peak) -> float:
    """Least time the chip could take for this work: the larger of the
    compute bound and the memory bound."""
    return max(flops / peak.flops_bf16, nbytes / peak.hbm_bytes_per_s)
