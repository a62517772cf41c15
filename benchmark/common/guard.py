"""The device-path guard: a window must move the device counters its
traffic (and configuration) name, and no counter that means a device
path fell back to the host or failed (``chip_smoke.FALLBACK_MARKERS``,
PR 21, copied)."""

from __future__ import annotations

FALLBACK_MARKERS = (
    "device_unreachable",
    "device_failed",
    "device_kernel_failed",
    "transfer_error",
    "populate_failed",
    "compile.lower_error",
    "dropped_on_device_loss",
    "serve.degraded",
)


def counters() -> dict:
    from hyperspace_tpu.telemetry.metrics import metrics

    return dict(metrics.snapshot()["counters"])


def moved(before: dict, after: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in after.items() if v != before.get(k, 0)}


def check(delta: dict, required_groups, forbidden_prefixes=()) -> tuple:
    """(fallbacks moved, required groups of which no counter moved)."""
    bad = {
        k: v for k, v in delta.items()
        if v and (any(m in k for m in FALLBACK_MARKERS) or k.startswith(tuple(forbidden_prefixes)))
    }
    missing = [g for g in required_groups if not any(delta.get(k, 0) > 0 for k in g)]
    return bad, missing
