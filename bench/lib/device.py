"""The device a run measures: which chip it is, its published peaks, and
the refusal to measure anything else.

Peaks come from one table keyed by JAX's ``device_kind``; a kind that is
not in the table is an error, never a default.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float        # FLOP/s per chip, dense bf16 matmul
    hbm_bytes_per_s: float   # HBM bandwidth per chip
    source: str


# Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e):
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.
PEAKS: Dict[str, Peaks] = {
    "TPU v5 lite": Peaks(bf16_flops=197e12, hbm_bytes_per_s=819e9,
                         source='Google Cloud documentation, "TPU v5e"'),
}


class NoChip(Exception):
    """JAX found no accelerator of the kind a cell needs."""


def peaks_for(kind: str) -> Peaks:
    if kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[kind]


def require_chips(jax, chips: int) -> List:
    """The first ``chips`` TPU devices, or NoChip."""
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX found no backend: {e}") from None
    if not devs or devs[0].platform != "tpu":
        raise NoChip(f"JAX runs on {devs[0].platform if devs else 'nothing'}"
                     f", not on a TPU")
    if len(devs) < chips:
        raise NoChip(f"cell needs {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


def describe(devs) -> dict:
    d0 = devs[0]
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


def memory_peak_bytes(devs) -> int:
    """Peak bytes in use on the fullest chip, where the backend says."""
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def memory_limit_bytes(devs) -> int:
    """Bytes the backend lets a process use on the smallest chip."""
    return min(int((d.memory_stats() or {}).get("bytes_limit", 0))
               for d in devs)
