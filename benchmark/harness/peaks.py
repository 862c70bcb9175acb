"""Published peaks of the devices the benchmark knows, keyed by the
`device_kind` JAX reports. A kind that is not here is an error, never a
default, and nothing in the environment overrides the table: a utilisation is
only comparable between PRs if its denominator cannot move."""

from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peaks:
    bf16_flops: float      # FLOP/s, dense bf16, one multiply-add = 2 FLOPs
    hbm_bytes_per_s: float
    ici_bits_per_s: float  # chip-to-chip interconnect, per chip
    hbm_bytes: int
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(
        bf16_flops=197e12, hbm_bytes_per_s=819e9, ici_bits_per_s=1600e9,
        hbm_bytes=16 * 10**9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "16 GB HBM2e at 819 GB/s, 1,600 Gbit/s interchip interconnect"),
}


def for_kind(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}: add a row "
            f"with its source to {__name__}.PEAKS (known: {sorted(PEAKS)})"
        ) from None
