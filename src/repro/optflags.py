"""Beyond-paper optimization flags (EXPERIMENTS.md §Perf).

Read at trace time from ``REPRO_OPT`` (comma-separated), so the dry-run can
lower baseline and optimized variants of the same code path:

* ``chunked_attn``  — query-chunked attention (no (S,S) score tensor).
* ``ota_re``        — (retired; now always on) superpose only the REAL plane
                      of the OTA uplink (Θ = Re{y}/Σ|h|² never reads Im{y}).
                      ``core.transport.receive`` does this unconditionally —
                      it is bit-identical to Re{} of the full superposition —
                      so the flag remains only for dry-run CLI compat.
* ``chunked_scan``  — sequence-chunked gated linear recurrence (mirrors the
                      Pallas kernel's VMEM-carried structure in pure JAX).
* ``rs_grads``      — constrain per-worker grads to the parameter sharding
                      before sketching (reduce-scatter instead of all-reduce
                      in the sketched-mode worker loop).
"""
from __future__ import annotations

import os
from typing import Optional

#: default chunk sizes (tuned in §Perf iterations)
ATTN_CHUNK = int(os.environ.get("REPRO_ATTN_CHUNK", "512"))
SCAN_CHUNK = int(os.environ.get("REPRO_SCAN_CHUNK", "512"))


def enabled(name: str) -> bool:
    return name in os.environ.get("REPRO_OPT", "").split(",")


# ---------------------------------------------------------------------------
# OTA kernel tiling knobs — read at TRACE time (functions, not constants), so
# a CLI/config can set the env var after import and still take effect, and an
# autotune sweep (``transport.autotune_ota_round``) can report values that
# drop straight into a launch script.
# ---------------------------------------------------------------------------

def ota_block_rows() -> Optional[int]:
    """Row-block of the flat elementwise OTA kernels (modulate/demodulate/
    fading step): ``REPRO_OTA_BLOCK_ROWS`` rows × 1024 lanes per tile, or
    None when unset (the kernels then size the tile to their VMEM
    budget)."""
    env = os.environ.get("REPRO_OTA_BLOCK_ROWS")
    return int(env) if env else None


def ota_block_cols() -> Optional[int]:
    """Column-block of the worker-grid receive/round kernels
    (``kernels/ota_round.py``, ``ota_receive``): ``REPRO_OTA_BLOCK_COLS``
    lanes per grid step over the packed axis, or None when unset (the
    kernels then size the tile from W and their VMEM budget)."""
    env = os.environ.get("REPRO_OTA_BLOCK_COLS")
    return int(env) if env else None


def ota_worker_chunk() -> int:
    """Worker-chunk size of the streamed OTA round
    (``transport.ota_round_fused``): 0 (default) = monolithic one-shot over
    all W workers; C > 0 = lax.scan over ceil(W/C) cohorts so peak signal
    memory is O(C·D) instead of O(W·D)."""
    return int(os.environ.get("REPRO_OTA_WORKER_CHUNK", "0"))
