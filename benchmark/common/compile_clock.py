"""Backend compile seconds and persistent-cache traffic, from JAX's own
monitoring events (copied from ``chip_smoke.CompileClock``, PR 21, with
a compile count added)."""

from __future__ import annotations


class CompileClock:
    def __init__(self) -> None:
        import jax.monitoring as mon

        self.compiles = 0
        self.compile_s = 0.0
        self.retrieval_s = 0.0
        self.misses = 0
        self.hits = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += duration
        elif event == "/jax/compilation_cache/cache_retrieval_time_sec":
            self.retrieval_s += duration

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_misses":
            self.misses += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def reading(self) -> dict:
        return {
            "compiles": self.compiles,
            "compile_s": self.compile_s,
            "cache_hits": self.hits,
            "cache_misses": self.misses,
        }
