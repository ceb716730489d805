"""The chip a run stands on: what JAX found, its published peaks, its memory.

A measurement path that finds no accelerator fails; it never falls back to
the CPU.  Peaks come from ``bench/peaks.json``, keyed by ``device_kind``; a
kind that is not in the table is an error, not a default.
"""
from __future__ import annotations

import json
import os
from typing import Any, Dict, List

from harness.spec import BENCH_DIR


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def peaks(device_kind: str) -> Dict[str, Any]:
    with open(os.path.join(BENCH_DIR, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"bench/peaks.json knows {sorted(table)}")
    return table[device_kind]


def chips(n: int) -> List[Any]:
    """The first ``n`` accelerator devices, or :class:`NoChip`."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX found {devices[0].platform} devices, not a TPU")
    if len(devices) < n:
        raise NoChip(f"the cell asks for {n} chips, JAX found {len(devices)}")
    return devices[:n]


def describe(devices) -> Dict[str, Any]:
    """``device`` of the result line: platform, kind, count, and the peak
    bytes in use on the fullest device."""
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": int(peak)}
