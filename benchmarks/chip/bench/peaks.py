"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

A device that is not in the table is an error, never a default: a
roofline share or an MFU against the wrong peak is a wrong number."""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peak:
    bf16_flops: float        # FLOP/s
    hbm_bytes_per_s: float   # B/s
    hbm_bytes: int           # device memory
    source: str


PEAKS = {
    "TPU v5 lite": Peak(bf16_flops=197e12, hbm_bytes_per_s=819e9,
                        hbm_bytes=16 * 2**30,
                        source='Google Cloud documentation, "TPU v5e"'),
}


def peak_for(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None
