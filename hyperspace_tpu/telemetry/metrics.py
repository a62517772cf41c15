"""In-process metrics: phase timers and execution-path counters.

Parity: the reference delegates profiling to the Spark UI and exposes only
telemetry events (SURVEY.md §5.1; PlanAnalyzer.scala:233-271 counts physical
operators after the fact). The TPU build needs first-class observability of
*which engine executed* — Pallas kernel vs XLA vs numpy fallback — because
silent fallbacks hide performance bugs (round-1 verdict weak #3/#8).

Usage::

    from hyperspace_tpu.telemetry.metrics import metrics
    with metrics.timer("build.stream.chunk"):
        ...
    metrics.incr("join.path.pallas")

Counters and timers accumulate in a process-global registry; ``snapshot()``
returns a plain dict (surfaced by the benchmark harness and
explain(verbose)).

Concurrent serving adds a second axis: with many queries in flight the
global pool alone cannot say which query paid which cost. ``scoped()``
opens a contextvar-bound CHILD registry — every ``incr``/``record_time``
against the global registry also mirrors into the scope active on the
recording thread, so each query's execution gets its own attributable
snapshot while the global totals stay exactly as before. Scopes follow
``contextvars`` propagation: a thread (or context copy) that entered the
scope records into it; unrelated threads do not, so two concurrent
queries' scopes never bleed into each other. Scopes NEST: each recording
lands once in every enclosing scope (collect() opens its own scope, so a
caller wrapping collect() in another still sees the query's counters).
"""

from __future__ import annotations

import contextvars
import threading
import time
from contextlib import contextmanager
from typing import Dict, Optional, Sequence, Tuple

# the per-query child registry active on this thread/context (None = no
# scope; recording goes to the global registry only)
_SCOPE: "contextvars.ContextVar[Optional[MetricsRegistry]]" = (
    contextvars.ContextVar("hyperspace_tpu_metrics_scope", default=None)
)

# fixed histogram bucket ladders (telemetry/export.py renders them as
# Prometheus histograms). Seconds cover the serve tier's realistic range
# (sub-ms cache hits to multi-second SF100 scans); bytes cover link
# transfers (count-vector D2H to slab H2D). A name ending in ``_bytes``
# defaults to the byte ladder — one convention, no per-site buckets.
TIME_BUCKETS_S: Tuple[float, ...] = (
    0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
    0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0,
)
BYTE_BUCKETS: Tuple[float, ...] = (
    1024.0, 8192.0, 65536.0, 524288.0, 4194304.0,
    33554432.0, 268435456.0, 1073741824.0,
)


class _Histogram:
    """Fixed-bucket histogram cell: cumulative-style counts are derived
    at snapshot time; recording is one bisect + three adds."""

    __slots__ = ("bounds", "counts", "total", "count")

    def __init__(self, bounds: Tuple[float, ...]):
        self.bounds = bounds
        self.counts = [0] * (len(bounds) + 1)  # last = > max bound
        self.total = 0.0
        self.count = 0

    def observe(self, value: float) -> None:
        import bisect

        self.counts[bisect.bisect_left(self.bounds, value)] += 1
        self.total += value
        self.count += 1

    def snapshot(self) -> Dict[str, object]:
        return {
            "buckets": list(self.bounds),
            "counts": list(self.counts),
            "sum": round(self.total, 6),
            "count": self.count,
        }


def default_buckets(name: str) -> Tuple[float, ...]:
    return BYTE_BUCKETS if name.endswith("_bytes") else TIME_BUCKETS_S


class MetricsRegistry:
    """Thread-safe counters + cumulative timers + gauges + histograms.

    Metric TYPES (the export/snapshot contract, docs/18-observability.md):
    ``incr`` accumulates a counter; ``gauge`` SETS a level (PR-6
    semantics — repeated recordings report the level, not a sum) and the
    name is remembered in the ``gauges`` snapshot view so the exporter
    types it correctly; ``record_time``/``timer`` accumulate seconds with
    a call count; ``observe`` feeds a fixed-bucket histogram."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._counters: Dict[str, int] = {}
        self._timers: Dict[str, float] = {}
        self._timer_counts: Dict[str, int] = {}
        # gauge VALUES live in _counters (so counter() reads them and
        # every pre-histogram snapshot consumer keeps working); this set
        # records which names are levels — the type bit snapshot() and
        # the Prometheus exporter need
        self._gauge_names: set = set()
        self._hists: Dict[str, _Histogram] = {}
        # enclosing scope at scoped()-entry time; mirroring walks this
        # chain so a nested scope feeds every scope around it exactly once
        self._parent: Optional["MetricsRegistry"] = None

    def incr(self, name: str, by: int = 1) -> None:
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + by
        node = _SCOPE.get()
        while node is not None:
            if node is not self:
                with node._lock:
                    node._counters[name] = node._counters.get(name, 0) + by
            node = node._parent

    def gauge(self, name: str, value: int) -> None:
        """SET a counter to a level (worker counts, pool widths, queue
        depths): unlike incr, repeated recordings of the same
        configuration don't accumulate across builds in one process —
        the snapshot reports the level, not a running total. The name is
        recorded as a gauge so snapshot()["gauges"] and the Prometheus
        exporter type it as a level (never ``_total``)."""
        with self._lock:
            self._counters[name] = int(value)
            self._gauge_names.add(name)
        node = _SCOPE.get()
        while node is not None:
            if node is not self:
                with node._lock:
                    node._counters[name] = int(value)
                    node._gauge_names.add(name)
            node = node._parent

    def observe(
        self,
        name: str,
        value: float,
        buckets: Optional[Sequence[float]] = None,
    ) -> None:
        """Record ``value`` into the fixed-bucket histogram ``name``
        (latency seconds or transfer bytes — default_buckets picks the
        ladder from the name). Bounds are fixed at the FIRST recording;
        later ``buckets`` arguments are ignored so concurrent recorders
        can never disagree about the cell layout."""
        with self._lock:
            h = self._hists.get(name)
            if h is None:
                h = _Histogram(
                    tuple(buckets) if buckets else default_buckets(name)
                )
                self._hists[name] = h
            h.observe(float(value))
        node = _SCOPE.get()
        while node is not None:
            if node is not self:
                with node._lock:
                    nh = node._hists.get(name)
                    if nh is None:
                        nh = _Histogram(h.bounds)
                        node._hists[name] = nh
                    nh.observe(float(value))
            node = node._parent

    def record_time(self, name: str, seconds: float) -> None:
        with self._lock:
            self._timers[name] = self._timers.get(name, 0.0) + seconds
            self._timer_counts[name] = self._timer_counts.get(name, 0) + 1
        node = _SCOPE.get()
        while node is not None:
            if node is not self:
                with node._lock:
                    node._timers[name] = node._timers.get(name, 0.0) + seconds
                    node._timer_counts[name] = (
                        node._timer_counts.get(name, 0) + 1
                    )
            node = node._parent

    @contextmanager
    def timer(self, name: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.record_time(name, time.perf_counter() - t0)

    @contextmanager
    def scoped(self):
        """Bind a fresh child registry to the current context: everything
        recorded (through ANY registry) on this thread — and on contexts
        copied from it — until exit also lands in the child. Scopes nest
        via a parent chain: an inner scope's recordings land once in each
        enclosing scope too (never twice — the chain walk skips the
        registry doing the recording)."""
        child = MetricsRegistry()
        child._parent = _SCOPE.get()
        token = _SCOPE.set(child)
        try:
            yield child
        finally:
            _SCOPE.reset(token)

    def counter(self, name: str) -> int:
        with self._lock:
            return self._counters.get(name, 0)

    def time_of(self, name: str) -> float:
        with self._lock:
            return self._timers.get(name, 0.0)

    def snapshot(self) -> Dict[str, object]:
        with self._lock:
            return {
                "counters": dict(self._counters),
                "timers_s": {k: round(v, 6) for k, v in self._timers.items()},
                "timer_counts": dict(self._timer_counts),
                # TYPE view: gauge names -> current level (values also
                # stay in "counters" for the pre-histogram consumers);
                # the exporter reads this to emit TYPE gauge vs counter
                "gauges": {
                    k: self._counters[k]
                    for k in self._gauge_names
                    if k in self._counters
                },
                "histograms": {
                    k: h.snapshot() for k, h in self._hists.items()
                },
            }

    def reset(self) -> None:
        with self._lock:
            self._counters.clear()
            self._timers.clear()
            self._timer_counts.clear()
            self._gauge_names.clear()
            self._hists.clear()


metrics = MetricsRegistry()


def build_pipeline_snapshot(
    registry: Optional[MetricsRegistry] = None,
) -> Dict[str, object]:
    """Per-stage occupancy of the pipelined index build in one dict
    (docs/14-build-pipeline.md). ``*_busy_s`` sums WORKER time per stage, so
    with the pipeline on, busy sums legitimately exceed ``wall_s`` —
    that excess IS the overlap (serial mode: they add up to ≤ wall).
    ``*_occupancy`` divides by wall: the stage nearest its worker count
    is the bottleneck; a stage near zero has headroom (or did no work).
    """
    r = registry if registry is not None else metrics
    wall = r.time_of("build.stream.pipeline_wall")
    stages = {
        "ingest_decode": r.time_of("build.stream.ingest_decode"),
        "dispatch": r.time_of("build.stream.dispatch"),
        "spill_compute": r.time_of("build.stream.spill_compute"),
        "spill_write": r.time_of("build.stream.spill_write"),
    }
    out: Dict[str, object] = {"wall_s": round(wall, 4)}
    for name, busy in stages.items():
        out[f"{name}_busy_s"] = round(busy, 4)
        if wall > 0:
            out[f"{name}_occupancy"] = round(busy / wall, 3)
    out["ingest_wait_s"] = round(r.time_of("build.stream.ingest_wait"), 4)
    out["workers"] = {
        k.rsplit(".", 1)[-1]: r.counter(k)
        for k in (
            "build.stream.workers.ingest",
            "build.stream.workers.spill_compute",
            "build.stream.workers.spill_write",
        )
        if r.counter(k)
    }
    return out


def residency_snapshot(
    registry: Optional[MetricsRegistry] = None,
) -> Dict[str, object]:
    """The tier-ladder counter family in one dict — which tier served
    scans, what bit-packing bought (compressed vs raw bytes), and the
    streaming pipeline's window/prefetch behavior. Consumed by
    ``QueryServer.stats()["residency"]`` next to the per-cache table
    snapshots (docs/15-streaming-residency.md)."""
    r = registry if registry is not None else metrics
    raw = r.counter("residency.compressed.raw_bytes")
    packed = r.counter("residency.compressed.packed_bytes")
    out: Dict[str, object] = {
        "scans_resident": r.counter("scan.path.resident_device"),
        "scans_compressed": r.counter("scan.path.resident_compressed"),
        "scans_streaming": r.counter("scan.path.resident_streaming"),
        "compressed_tables_built": r.counter(
            "residency.tier.compressed_built"
        ),
        "streaming_tables_built": r.counter(
            "residency.tier.streaming_built"
        ),
        "compressed_raw_bytes": raw,
        "compressed_packed_bytes": packed,
        "stream_windows": r.counter("residency.stream.windows"),
        "stream_window_failures": r.counter(
            "residency.stream.window_failed"
        ),
        "stream_prefetch_hit": r.counter("residency.stream.prefetch_hit"),
        "stream_prefetch_stall": r.counter(
            "residency.stream.prefetch_stall"
        ),
        "stream_h2d_bytes": r.counter("residency.stream.h2d_bytes"),
    }
    if packed:
        out["effective_capacity_x"] = round(raw / packed, 2)
    return out


def compile_snapshot(
    registry: Optional[MetricsRegistry] = None,
) -> Dict[str, object]:
    """The whole-plan-compilation counter family in one dict — how often
    lowering ran vs the pipeline cache served (a repeated-structure burst
    keeps ``pipelines_lowered`` flat while ``cache_hits`` climbs), which
    kinds lowered, what the fused arms dispatched, and what degradation
    dropped. Consumed by ``QueryServer.stats()["compile"]``
    (docs/17-plan-compilation.md)."""
    r = registry if registry is not None else metrics
    out: Dict[str, object] = {
        "pipelines_lowered": r.counter("compile.lowered"),
        # XLA backend compiles (telemetry.compiles); the span that paid
        # for each carries it as its ``xla_compile_s`` label
        "backend_compiles": r.counter("compile.backend.compiles"),
        "lower_errors": r.counter("compile.lower_error"),
        "cache_hits": r.counter("compile.cache.hit"),
        "cache_misses": r.counter("compile.cache.miss"),
        "cache_evicted": r.counter("compile.cache.evicted"),
        "cache_invalidated": r.counter("compile.cache.invalidated"),
        "fused_dispatches": r.counter("compile.fused.dispatches"),
        "fused_queries": r.counter("compile.fused.queries"),
        "dropped_on_device_loss": r.counter(
            "compile.pipeline.dropped_on_device_loss"
        ),
        "result_hits": r.counter("compile.result_cache.hit"),
        "result_misses": r.counter("compile.result_cache.miss"),
        "result_admitted": r.counter("compile.result_cache.admitted"),
        "result_invalidated": r.counter("compile.result_cache.invalidated"),
        "warm_hints_offered": r.counter("compile.warm_hint.offered"),
        "warm_hints_adopted": r.counter("compile.warm_hint.adopted"),
        "warm_hints_declined": r.counter("compile.warm_hint.declined"),
    }
    runs = {
        kind: r.counter(f"compile.run.{kind}")
        for kind in ("scan", "agg_scan", "hybrid", "join_agg", "interpret")
    }
    out["runs"] = {k: v for k, v in runs.items() if v}
    return out


def result_cache_snapshot(
    registry: Optional[MetricsRegistry] = None,
) -> Dict[str, object]:
    """The result-cache counter families in one dict — per cache level
    (serve-side ``compile.result_cache.*``, fleet-side
    ``router.result_cache.*``): what telemetry-driven admission admitted
    or declined (cold structure vs byte economics), what GDSF/budget
    pressure evicted, hits/misses, and misses that were only stale by
    version token. Consumed by ``QueryServer.stats()["result_cache"]``
    (docs/17-plan-compilation.md)."""
    r = registry if registry is not None else metrics
    out: Dict[str, object] = {}
    for level, prefix in (
        ("serve", "compile.result_cache"),
        ("router", "router.result_cache"),
    ):
        out[level + "_counters"] = {
            "hits": r.counter(prefix + ".hit"),
            "misses": r.counter(prefix + ".miss"),
            "stale_misses": r.counter(prefix + ".stale_miss"),
            "admitted": r.counter(prefix + ".admitted"),
            "declined_cold": r.counter(prefix + ".declined_cold"),
            "declined_bytes": r.counter(prefix + ".declined_bytes"),
            "evicted": r.counter(prefix + ".evicted"),
            "invalidated": r.counter(prefix + ".invalidated"),
        }
    out["bypass_latched"] = r.counter("compile.result_cache.bypass_latched")
    return out


def serve_snapshot(registry: Optional[MetricsRegistry] = None) -> Dict[str, int]:
    """The serve-tier counter family in one dict — what admission let
    in, shed, or breaker-rejected, what the overload ladder disabled,
    and what the degradation paths absorbed (worker kills, host
    latches). Consumed by ``QueryServer.stats()["serve_counters"]``
    (docs/16-multitenant-serving.md)."""
    r = registry if registry is not None else metrics
    return {
        "submitted": r.counter("serve.submitted"),
        "completed": r.counter("serve.completed"),
        "shed": r.counter("serve.shed"),
        "shed_lowweight": r.counter("serve.shed.lowweight"),
        "cancelled": r.counter("serve.cancelled"),
        "deadline_missed": r.counter("serve.deadline_missed"),
        "plan_errors": r.counter("serve.plan_error"),
        "breaker_rejected": r.counter("serve.breaker.rejected"),
        "breaker_opened": r.counter("serve.breaker.opened"),
        "breaker_probes": r.counter("serve.breaker.probe"),
        "breaker_closed": r.counter("serve.breaker.closed"),
        "degraded_latches": r.counter("serve.degraded"),
        "workers_killed": r.counter("serve.worker_killed"),
        "client_retries": r.counter("serve.client.retry"),
        "client_retries_exhausted": r.counter("serve.client.exhausted"),
    }


def reliability_snapshot(registry: Optional[MetricsRegistry] = None) -> Dict[str, int]:
    """The crash-consistency counter family in one dict — what the
    reliability layer absorbed (storage retries), refused (fenced
    writers), and healed (auto-rollbacks, swept crash litter). Consumed
    by ``QueryServer.stats()["reliability"]`` and handy for dashboards;
    the same counters mirror into per-query ``scoped()`` children, so
    ``explain(verbose)`` shows a query's own share when its execution
    paid a retry (docs/12-reliability.md)."""
    r = registry if registry is not None else metrics
    return {
        "storage_retry_attempts": r.counter("storage.retry.attempts"),
        "storage_retry_exhausted": r.counter("storage.retry.exhausted"),
        "claim_self_wins": r.counter("storage.retry.claim_self_win"),
        "auto_rollbacks": r.counter("recovery.auto_rollback"),
        "recovery_sweeps": r.counter("recovery.sweep"),
        "orphan_tmp_swept": r.counter("recovery.orphan_tmp_swept"),
        "fenced_writers": r.counter("lease.fenced_writer_refused"),
        "lease_heartbeat_errors": r.counter("lease.heartbeat_error"),
        "doctor_issues_found": r.counter("doctor.issues_found"),
        "doctor_issues_repaired": r.counter("doctor.issues_repaired"),
    }
