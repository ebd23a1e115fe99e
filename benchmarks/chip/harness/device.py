"""The chip a run measures, and the table of its published peaks.

A run refuses any platform but a TPU and any device that is not in the
table: a number read off the CPU is never reported under a device metric.
"""
from __future__ import annotations

import jax

# Published peaks per chip, keyed by JAX's ``device_kind``.
# TPU v5e: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16,
# 16 GB HBM at 819 GB/s).
PEAKS = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
}


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def peaks(kind: str) -> dict:
    """The published peaks of ``kind``; an unknown device is an error."""
    if kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[kind]


def require_chips(n: int) -> list:
    """The first ``n`` TPU devices; raises ``NoChip`` otherwise."""
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX found no devices: {e}") from e
    platform = devices[0].platform
    if platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found platform {platform!r}")
    if len(devices) < n:
        raise NoChip(f"the cell asks for {n} chips, JAX found "
                     f"{len(devices)}")
    peaks(devices[0].device_kind)
    return devices[:n]


def describe(devices, memory_peak_bytes: int) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices()),
            "memory_peak_bytes": int(memory_peak_bytes)}


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest of ``devices``."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak
