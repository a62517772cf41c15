"""Backend compiles named by span.

One ``jax.monitoring`` duration listener, installed once per process at
the engine boundary (``ops.ensure_x64``). On every XLA backend compile it
counts ``compile.backend.compiles`` (surfaced by
``telemetry.metrics.compile_snapshot``) and adds the compile's seconds to
the ``xla_compile_s`` label of the span open on the compiling thread, so
a recompile inside a serving window names the stage that paid for it.
A persistent-cache hit is no backend compile and counts nothing.
"""

from __future__ import annotations

import threading

from .metrics import metrics
from .trace import active

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_installed = False
_lock = threading.Lock()


def _on_duration(event: str, duration: float, **_) -> None:
    if event != BACKEND_COMPILE_EVENT:
        return
    metrics.incr("compile.backend.compiles")
    s = active()
    if s is not None:
        s.labels["xla_compile_s"] = s.labels.get("xla_compile_s", 0.0) + duration


def install() -> None:
    """Register the listener (idempotent)."""
    global _installed
    if _installed:
        return
    with _lock:
        if _installed:
            return
        import jax.monitoring as mon

        mon.register_event_duration_secs_listener(_on_duration)
        _installed = True
