"""The chips a run is given: what JAX reports, and the peak memory."""
from __future__ import annotations


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def require_chips(n: int):
    """The first ``n`` TPU devices, or ``NoChip``."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX runs on {devices[0].platform!r}")
    if len(devices) < n:
        raise NoChip(f"the cell needs {n} chips, JAX sees {len(devices)}")
    return devices[:n]


def describe(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devices)}


def memory_peak(devices) -> int:
    """Peak bytes in use on the fullest chip (0 where not reported)."""
    peaks = [(dev.memory_stats() or {}).get("peak_bytes_in_use", 0) for dev in devices]
    return int(max(peaks))
