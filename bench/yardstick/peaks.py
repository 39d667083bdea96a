"""Published peaks of one chip, by `jax.Device.device_kind` (copy of the
program's `repro.launch.roofline.PEAKS`).  A device that is not here
has no roofline: `peaks` raises rather than assume one."""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peaks:
    flops: float          # bf16 FLOP/s
    hbm_bw: float         # HBM bytes/s
    hbm_bytes: float      # HBM capacity


PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of
    # HBM at 819 GB/s
    "TPU v5 lite": Peaks(flops=197e12, hbm_bw=819e9, hbm_bytes=16e9),
}


def peaks(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
