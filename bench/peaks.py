"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip
interconnect (50 GB/s on each of a chip's four ICI links).

Kept with the benchmark so that the yardstick does not move with the
program; a device that is not in the table is an error, never a default.
"""
from __future__ import annotations

CHIP_PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9,
                    "hbm_bytes": 16e9},
}


def chip_peaks(device_kind: str) -> dict:
    """Peaks of one chip of ``device_kind``; raises KeyError for a kind with
    no published entry."""
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"(known: {sorted(CHIP_PEAKS)})") from None


def least_time_s(flops: float, nbytes: float, device_kind: str) -> tuple:
    """Roofline bound: (seconds, "compute" | "memory") — the larger of the
    operations over peak FLOP/s and the bytes over peak bandwidth."""
    p = chip_peaks(device_kind)
    t_c = flops / p["flops_bf16"]
    t_m = nbytes / p["hbm_bw"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
