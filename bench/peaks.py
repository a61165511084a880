"""Published peaks of one chip, keyed by JAX's `device_kind`.

Source: Google Cloud documentation, "TPU v5e" (per chip: 197 TFLOP/s
bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s).  A kind that is not here
is an error, never a default.
"""
from __future__ import annotations

from typing import NamedTuple


class Peaks(NamedTuple):
    flops: float      # bf16 FLOP/s; f32 matmuls at default precision run
                      # as bf16 passes on the MXU
    hbm_bw: float     # bytes/s
    hbm_bytes: float  # bytes of HBM


PEAKS = {"TPU v5 lite": Peaks(flops=197e12, hbm_bw=819e9, hbm_bytes=16e9)}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
