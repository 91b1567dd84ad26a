"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16, 393
TOP/s in int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip
interconnect. (The same numbers as the program's ``launch/roofline``
table, copied so the yardstick does not move with the program.) A kind that
is not listed is an error, never mapped onto another chip's peaks; there is
no CPU entry.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    kind: str
    peak_flops: float  # FLOP/s, bf16 matrix units
    hbm_bw: float  # bytes/s
    hbm_bytes: float


TPU_PEAKS = {
    "TPU v5 lite": Peaks("TPU v5 lite", 197e12, 819e9, 16e9),
}


def peaks_for(kind: str) -> Peaks:
    try:
        return TPU_PEAKS[kind]
    except KeyError:
        raise ValueError(f"no published peaks for device_kind {kind!r}; known: {sorted(TPU_PEAKS)}") from None


def least_time(flops: float, nbytes: float, peaks: Peaks):
    """(seconds, bound): the least time the chip could take for the work,
    and which of its two peaks sets it."""
    t_flops = flops / peaks.peak_flops
    t_bytes = nbytes / peaks.hbm_bw
    return (t_bytes, "bytes") if t_bytes >= t_flops else (t_flops, "flops")
