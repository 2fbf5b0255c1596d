"""The chip: which one JAX found, its published peaks, its memory peak.

The peak table is the benchmark's own, keyed by the ``device_kind`` JAX
reports.  A kind that is not in it is an error, never a default.
"""

from __future__ import annotations

import sys
from typing import Dict, List

# Published peaks of one chip.  Source: Google Cloud documentation,
# "TPU v5e" (cloud.google.com/tpu/docs/v5e): 197 TFLOP/s bf16, 394 TOP/s
# int8, 16 GB HBM2 at 819 GB/s, 1,600 Gbit/s inter-chip interconnect.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 394e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bytes_per_s": 200e9,
    },
}


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell needs."""


def peaks(kind: str) -> Dict[str, float]:
    if kind not in PEAKS:
        raise KeyError(f"no published peaks for device_kind {kind!r}; "
                       f"known kinds: {sorted(PEAKS)}")
    return PEAKS[kind]


def require_tpu(chips: int) -> List:
    """The first ``chips`` TPU devices; raises :class:`NoChip` when JAX
    sees no TPU or too few of them (never falls back to the CPU)."""
    import jax

    devices = jax.devices()
    d = devices[0]
    print(f"device: platform={d.platform} kind={d.device_kind} "
          f"count={len(devices)}", file=sys.stderr, flush=True)
    if d.platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {d.platform!r})")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees "
                     f"{len(devices)}")
    peaks(d.device_kind)
    return devices[:chips]


def describe(devices) -> Dict[str, object]:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def memory_peak_bytes(devices) -> int:
    """The peak bytes in use on the fullest chip (0 where the backend
    keeps no statistics, as the CPU does)."""
    best = 0
    for d in devices:
        stats = d.memory_stats() or {}
        best = max(best, int(stats.get("peak_bytes_in_use", 0)))
    return best
