"""Published peaks of one chip, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16, 16 GB of HBM
at 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect. JAX reports the v5e as
"TPU v5 lite". A kind that is not in the table is an error, never a default:
a share of another chip's peak is a wrong number under a device metric's name.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_s": 819e9,
                    "ici_bits_s": 1600e9, "hbm_bytes": 16e9},
}


def peak(device_kind: str, what: str) -> float:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device_kind {device_kind!r} "
                       f"(known: {sorted(PEAKS)}); add it with its source")
    return PEAKS[device_kind][what]
