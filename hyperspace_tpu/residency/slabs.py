"""Build-side HBM slab accounting, shared with the residency budget.

The device-resident streaming build (docs/14-build-pipeline.md) pins
device memory OUTSIDE the residency caches: the double-buffered upload
slab pair and up to ``RUN_CHUNKS`` staged sorted chunks awaiting their
on-device run merge. Those bytes come out of the SAME physical HBM the
tier ladder budgets, so they must share the one budget instead of
silently oversubscribing it: a build that stages 3 GB of runs while the
caches believe they own the full 4 GB budget is exactly the blown
margin the ladder exists to prevent.

Discipline:

* a build RESERVES its worst-case slab footprint here before staging
  its first chunk (``try_reserve``) and releases it at finalize/abort —
  reservation is all-or-nothing, so a failed build can never leak a
  partial charge;
* reservations are capped at HALF the budget (``_BUILD_FRACTION``): the
  build may borrow headroom but never starve the serving caches — a
  build that needs more falls back to the per-chunk round-trip path
  (counted ``build.device.staging_declined.budget``), it does not queue;
* the caches see the borrowed bytes through ``held_bytes()``, which
  ``exec.hbm_cache._budget_bytes`` subtracts — their LRU eviction then
  makes room exactly as if a new table had been admitted.

This module deliberately holds NO jax arrays and NO references into the
build: it is pure byte bookkeeping, so the reservation lifetime is the
writer's explicit reserve/release calls and nothing can pin device
memory through it.
"""

from __future__ import annotations

import threading
from typing import Dict

from ..telemetry.metrics import metrics

# the build may reserve at most this fraction of the shared HBM budget;
# the rest always remains the serving caches' floor
_BUILD_FRACTION = 2  # denominator: budget // 2

_lock = threading.Lock()
_held: Dict[str, int] = {}


def _budget_total() -> int:
    from ..exec.bytecache import env_mb

    return env_mb("HYPERSPACE_TPU_HBM_BUDGET_MB", 4096)


def try_reserve(tag: str, nbytes: int) -> bool:
    """Reserve ``nbytes`` of build slab headroom under ``tag`` (one tag
    per writer; re-reserving a live tag replaces its charge). False =
    over the build's half-budget cap — the caller declines staging."""
    nbytes = max(0, int(nbytes))
    cap = _budget_total() // _BUILD_FRACTION
    with _lock:
        others = sum(v for k, v in _held.items() if k != tag)
        if others + nbytes > cap:
            metrics.incr("build.device.slab_reserve_refused")
            return False
        _held[tag] = nbytes
        total = others + nbytes
    metrics.gauge("build.device.slab_reserved_bytes", total)
    return True


def release(tag: str) -> None:
    """Drop ``tag``'s reservation. Idempotent — abort paths may race
    finalize teardown and both must be safe to call."""
    with _lock:
        _held.pop(tag, None)
        total = sum(_held.values())
    metrics.gauge("build.device.slab_reserved_bytes", total)


def held_bytes() -> int:
    """Bytes currently reserved by builds — what the residency caches
    subtract from their budget (exec.hbm_cache._budget_bytes)."""
    with _lock:
        return sum(_held.values())
