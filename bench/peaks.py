"""Published peaks of the chips the benchmark runs on, keyed by ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2 at 819 GB/s per chip, 1,600 Gbit/s
of inter-chip interconnect per chip.  JAX reports the v5e as "TPU v5 lite".

A device missing from the table is an error: a share of a peak that was not
looked up is not reported.
"""

from __future__ import annotations

from dataclasses import dataclass

__all__ = ["Peak", "PEAKS", "peak_for"]


@dataclass(frozen=True)
class Peak:
    bf16_flops: float   # dense bf16 FLOP/s per chip
    hbm_bytes: float    # HBM bytes/s per chip
    source: str


_V5E = Peak(
    bf16_flops=197e12,
    hbm_bytes=819e9,
    source='Google Cloud documentation, "TPU v5e"',
)

PEAKS: dict[str, Peak] = {
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
}


def peak_for(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peak for device kind {device_kind!r}; add it to bench/peaks.py "
            f"with its source"
        ) from None
