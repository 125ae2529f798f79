"""Published peaks of the chips the benchmark runs on, keyed by ``device_kind``.

A device kind that is not in the table is an error, never a default: a
roofline share against an assumed peak is not a measurement.
"""
from __future__ import annotations

# Google Cloud documentation, "TPU v5e" (system architecture table):
# 197 TFLOP/s bf16, 16 GB HBM2 at 819 GB/s per chip.
PEAKS = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


class UnknownDevice(KeyError):
    """The device kind has no row in :data:`PEAKS`."""


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}") from None


def least_time_s(floor_bytes: float, floor_flops: float, device_kind: str,
                 chips: int) -> float:
    """The least time ``chips`` such chips need for the floor work:
    the larger of bytes over bandwidth and operations over peak."""
    p = peaks(device_kind)
    return max(floor_bytes / (chips * p["hbm_bytes_per_s"]),
               floor_flops / (chips * p["flops_per_s"]))
