"""CompiledPipeline: one lowered plan subtree, runnable against any
literal binding that shares its structural fingerprint.

A pipeline stores ROUTING, not values: which fused arm serves the
subtree and how to re-bind per-query operands (predicate literals,
projection order) from the concrete plan each run receives. The fused
arms reach the structure-keyed executables (literals as traced int32
operands), so every run of a pipeline — across a whole serving burst of
distinct keys — shares one compiled device program and ships home at
most ONE D2H transfer between plan arms (the count vector / finished
group table); the interpreter is the fallback leg for every per-query
eligibility miss, with results identical by the shared-procedure
argument (the fused arms and the interpreter call the same resolution
and host-leg code).

Device loss mid-fused-dispatch degrades exactly like the interpreter's
fused arms (the shared procedures drop the resident state and latch the
QUERY host-side), and additionally evicts THIS pipeline's cache entry —
not the whole cache — so the next structurally-equal query re-lowers
against post-loss residency instead of re-entering a dead routing
decision.
"""

from __future__ import annotations

import threading
from typing import Optional, Tuple

from ..storage.columnar import ColumnarBatch
from ..telemetry.metrics import metrics
from ..telemetry.trace import span

# the device-failure counters every fused arm's degradation path bumps
# (exec.scan / exec.executor / exec.join_residency): a run that moved any
# of them hit a dead device mid-dispatch. Read from the run's SCOPED
# child registry, never the global one — two concurrent queries' device
# failures must not cross-attribute (a global delta would evict a
# healthy pipeline because an unrelated table died on another worker)
_DEVICE_FAIL_COUNTERS = (
    "scan.resident.device_failed",
    "scan.resident_mesh.device_failed",
    "scan.resident_join.device_failed",
)


def _device_failures(registry) -> int:
    return sum(registry.counter(c) for c in _DEVICE_FAIL_COUNTERS)


class CompiledPipeline:
    """One lowered subtree. ``run(plan, executor)`` executes a concrete
    plan whose fingerprint equals this pipeline's."""

    def __init__(
        self,
        kind: str,
        fingerprint: Optional[tuple],
        tier: str,
        index_roots: Tuple[str, ...],
        boundary: tuple,
    ):
        self.kind = kind
        self.fingerprint = fingerprint
        self.tier = tier
        self.index_roots = index_roots
        self.boundary = boundary
        # short stable id of the structural fingerprint — the
        # "which executable" label every trace span and describe() carry
        # (the full tuple is unwieldy in a span tree)
        import hashlib

        self.fingerprint_id = (
            hashlib.blake2s(
                repr(fingerprint).encode("utf-8"), digest_size=4
            ).hexdigest()
            if fingerprint is not None
            else None
        )
        # set by PipelineCache when the pipeline is cached; forget-on-
        # device-loss needs them to evict exactly one entry
        self.cache = None
        self.cache_key = None
        # observability tallies, mutated by concurrent runs: guarded by
        # their own lock (a pipeline is shared across serve workers)
        self._stats_lock = threading.Lock()
        self.runs = 0
        self.fused_dispatches = 0

    # -- observability -------------------------------------------------------
    def describe(self) -> dict:
        return {
            "kind": self.kind,
            "tier": self.tier,
            "fingerprint": self.fingerprint_id,
            "boundary": list(self.boundary),
            "runs": self.runs,
            "fused_dispatches": self.fused_dispatches,
        }

    def matches_root(self, prefix: str) -> bool:
        return any(p.startswith(prefix) for p in self.index_roots)

    # -- execution -----------------------------------------------------------
    def run(self, plan, executor) -> ColumnarBatch:
        with self._stats_lock:
            self.runs += 1
        metrics.incr(f"compile.run.{self.kind}")
        # a scoped child registry attributes THIS run's recordings (the
        # fused arms record on this thread; the union host legs copy the
        # context into their pool threads) — global counter deltas would
        # cross-talk between concurrent queries
        with metrics.scoped() as run_metrics:
            try:
                # the trace's "which executable" span: kind + residency
                # tier at lowering + the structural fingerprint id
                with span(
                    "compile.pipeline_run",
                    kind=self.kind,
                    tier=self.tier,
                    fingerprint=self.fingerprint_id,
                ), metrics.timer("compile.pipeline_run"):
                    out = self._run_kind(plan, executor)
            finally:
                with self._stats_lock:
                    self.fused_dispatches += run_metrics.counter(
                        "compile.fused.dispatches"
                    )
                if _device_failures(run_metrics) > 0:
                    # the query already latched host-side through the
                    # shared degradation path; evict ONLY this pipeline
                    # so the next structurally-equal query re-lowers
                    # against post-loss residency (fault-injection-
                    # tested)
                    metrics.incr("compile.pipeline.dropped_on_device_loss")
                    if self.cache is not None:
                        self.cache.forget(self)
        return out

    def _run_kind(self, plan, executor) -> ColumnarBatch:
        from .lowering import classify_shape

        if self.kind == "interpret":
            return executor._exec(plan, predicate=None)
        shape = classify_shape(plan, executor.mesh)
        if shape.kind != self.kind:
            # fingerprint/classification drift (cannot happen for equal
            # fingerprints; guards a future structural-walk change):
            # interpret exactly
            metrics.incr("compile.shape_drift")
            return executor._exec(plan, predicate=None)
        if self.kind == "scan":
            out = self._run_scan(shape, executor)
            return _apply_projects(out, shape.projects)
        if self.kind == "agg_scan":
            out = self._run_agg_scan(shape, executor)
            return _apply_projects(out, shape.projects)
        if self.kind == "hybrid":
            out = self._run_hybrid(shape, executor)
            return _apply_projects(out, shape.projects)
        if self.kind == "join_agg":
            out = self._run_join_agg(shape, executor)
            return _apply_projects(out, shape.projects)
        if self.kind == "join_shuffle":
            out = self._run_join_shuffle(shape, executor)
            return _apply_projects(out, shape.projects)
        raise AssertionError(f"unknown pipeline kind {self.kind!r}")

    def _run_scan(self, shape, executor) -> ColumnarBatch:
        """The fused scan arm: exec.scan.index_scan with the structure-
        keyed counts dispatch — the ONE serving procedure (residency
        resolution, zone gate, host legs, empty-schema handling) the
        interpreter uses, so per-query eligibility misses degrade
        identically; only the executable keying differs (literals traced
        instead of baked in). Mesh sessions route the mesh arm."""
        from ..exec.scan import index_scan

        if executor.mesh is not None:
            return self._run_scan_mesh(shape, executor)
        scan = shape.scan
        entry = scan.entry
        return index_scan(
            entry.content.files(),
            list(scan.required_columns),
            shape.condition,
            device=executor.device,
            indexed_columns=entry.indexed_columns,
            dtypes=entry.schema,
            num_buckets=entry.num_buckets,
            structure_keyed=True,
        )

    def _run_scan_mesh(self, shape, executor) -> ColumnarBatch:
        """The fused MESH scan arm: when the shards are resident, the
        counts dispatch rides the structure-keyed shard_map batched
        entry (N=1, literals as traced operands — a fresh-literal burst
        shares one executable, the single-chip rule on the mesh); every
        miss — no table, narrow failure, device loss — falls to the
        interpreter's distributed scan, which owns population scheduling
        and the ship-per-query path."""
        from pathlib import Path

        from ..exec.mesh_cache import mesh_cache
        from ..exec.scan import _empty_result, prune_index_files

        scan = shape.scan
        entry = scan.entry
        predicate = shape.condition
        # resolve against the version's FULL file list (a table always
        # covers it, so pruning cannot change the hit outcome) and prune
        # only on a hit: the common miss then pays ONE registry probe —
        # which early-outs on an empty cache — before handing the query
        # to the interpreter's distributed scan, instead of re-running
        # file pruning the fallback repeats anyway
        all_files = entry.content.files()
        counts = None
        table = None
        files: list = []
        if all_files:
            table = mesh_cache.resident_for(
                all_files, sorted(predicate.columns()), executor.mesh
            )
        if table is not None:
            # the query's pruned subset restricts the host leg's reads
            files = prune_index_files(
                [Path(p) for p in all_files],
                predicate,
                entry.indexed_columns,
                entry.schema,
                entry.num_buckets,
            )
            try:
                # the mesh cache opens the scan.device_dispatch span
                m = mesh_cache.block_counts_batch(
                    table, [predicate], metric_ns="compile.fused"
                )
                counts = None if m is None else m[0]
            except Exception:  # noqa: BLE001 - device loss degrades
                mesh_cache.drop(table)
                metrics.incr("scan.resident_mesh.device_failed")
                counts = None
        if counts is None:
            return executor._exec_index_scan_distributed(scan, predicate)
        metrics.incr("scan.files_read", len(files))
        parts = mesh_cache.collect_parts(
            table, files, list(scan.required_columns), predicate, counts
        )
        if parts:
            return ColumnarBatch.concat(parts)
        # the ONE empty-result construction (exec.scan) — the host and
        # interpreter legs build theirs through the same helper
        return _empty_result(
            files, list(scan.required_columns), entry.schema
        )

    def _run_agg_scan(self, shape, executor) -> ColumnarBatch:
        """The agg_scan arm: DEVICE aggregation first — one executable
        fuses the predicate mask with dense-key segment reductions and
        ships the FINISHED group table home (exec.scan_agg; the PR-5
        resident_join_agg machinery generalized to single-table
        aggregates). Device-ineligible specs fall to the count-vector
        scan + host hash-aggregate tail, each decline counted under
        compile.agg.declined.<reason> — never a silent host tail."""
        from ..exec.aggregate import hash_aggregate

        fused = self._try_device_agg(shape, executor)
        if fused is not None:
            return fused
        if executor.mesh is not None:
            # the interpreter's whole Aggregate procedure: the mesh tail
            # keeps its two-phase distributed aggregate (per-device
            # partials, psum-style host merge) and its path counters —
            # a decline must not demote the mesh to gather-then-hash
            return executor._exec_aggregate(shape.agg, None)
        out = self._run_scan(shape, executor)
        out = _apply_projects(out, shape.inner_projects)
        return hash_aggregate(
            out, list(shape.agg.group_by), list(shape.agg.aggs)
        )

    def _try_device_agg(self, shape, executor) -> Optional[ColumnarBatch]:
        """The device-aggregation attempt, or None with its decline
        counted. Population: a no_table miss schedules the predicate AND
        group/agg columns, so the NEXT structurally-equal query
        aggregates on device. No selectivity zone gate applies — the
        device-agg host leg reads nothing, so a broad predicate has no
        host-read cost for the gate to protect (exec.scan_agg note)."""
        group_by = list(shape.agg.group_by)
        aggs = list(shape.agg.aggs)

        def decline(reason: str):
            metrics.incr(f"compile.agg.declined.{reason}")
            return None

        if not group_by:
            # the global-aggregate empty-input contract (one NULL-ish
            # row) belongs to the host tail
            return decline("shape")
        need = list(
            dict.fromkeys(group_by + [a.column for a in aggs if a.column])
        )
        # an inner projection that starves the aggregate must raise on
        # the host path, not silently aggregate on device
        for p in shape.inner_projects:
            if not set(need) <= set(p.columns):
                return decline("shape")
        entry = shape.scan.entry
        if any(c not in entry.schema for c in need):
            return decline("column")
        all_files = entry.content.files()
        if not all_files:
            return decline("no_table")
        pred_cols = sorted(shape.condition.columns())
        want_cols = sorted(set(pred_cols) | set(need))
        if executor.mesh is not None:
            from ..exec.mesh_cache import mesh_cache as cache

            table = cache.resident_for(all_files, want_cols, executor.mesh)
            fail_metric = "scan.resident_mesh.device_failed"
        else:
            from ..exec.hbm_cache import hbm_cache as cache

            table = cache.resident_for(all_files, want_cols)
            fail_metric = "scan.resident.device_failed"
        if table is None:
            if cache.auto_enabled():
                if executor.mesh is not None:
                    cache.note_touch(all_files, want_cols, executor.mesh)
                else:
                    cache.note_touch(all_files, want_cols)
            return decline("no_table")
        try:
            out, reason = cache.agg_scan(
                table, shape.condition, group_by, aggs
            )
        except Exception:  # noqa: BLE001 - device loss degrades
            # drop the table and latch THIS query host through the host
            # tail; the scoped failure counter also evicts this pipeline
            # (run()'s device-failure check)
            cache.drop(table)
            metrics.incr(fail_metric)
            return decline("device")
        if out is None:
            return decline(reason)
        metrics.incr("compile.fused.dispatches")
        metrics.incr("compile.agg.device")
        return out

    def _run_hybrid(self, shape, executor) -> ColumnarBatch:
        """The fused hybrid arm on the STRUCTURE-KEYED batched entry
        (structure_keyed=True routes hybrid_block_counts_batch N=1 with
        literals as traced operands, so a fresh-literal hybrid burst
        shares ONE executable — the same trick the scan arm rode since
        PR 10; the dispatch itself counts compile.fused.dispatches),
        falling to the concurrent per-side host union — the split entry
        points guarantee the fallback never re-runs the residency
        resolution (no double-counted declines)."""
        fused = executor._try_resident_hybrid(
            shape.union, shape.condition, structure_keyed=True
        )
        if fused is not None:
            return fused
        columns = (
            list(shape.projects[-1].columns) if shape.projects else None
        )
        return executor._exec_union_host(
            shape.union, shape.condition, columns
        )

    def _run_join_agg(self, shape, executor) -> ColumnarBatch:
        """The fused aggregate-join arm: the executor's Aggregate
        procedure (resident fused region dispatch first — single-chip
        AND mesh — then the host range-fusion, then gather+hash), as one
        lowered pipeline stage. Whether THIS run dispatched fused is
        read from a scoped child registry — a global counter diff would
        misattribute a concurrent query's dispatch (the same rule the
        device-failure check follows)."""
        with metrics.scoped() as jm:
            out = executor._exec_aggregate(shape.agg, None)
        if (
            jm.counter("scan.path.resident_join_agg")
            + jm.counter("scan.path.resident_join_agg_mesh")
            > 0
        ):
            metrics.incr("compile.fused.dispatches")
        return out


    def _run_join_shuffle(self, shape, executor) -> ColumnarBatch:
        """The shuffle-join arm: the executor's whole Join procedure
        (shuffle eligibility + planner + exchange, exact host join on
        every decline) as one lowered stage. Whether THIS run actually
        rode the ICI exchange is read from a scoped child registry —
        the _run_join_agg attribution rule."""
        with metrics.scoped() as jm:
            out = executor._exec_join(shape.join)
        if jm.counter("scan.path.resident_join_shuffle") > 0:
            metrics.incr("compile.fused.dispatches")
        return out


def _apply_projects(batch: ColumnarBatch, projects) -> ColumnarBatch:
    """Apply a collected Project stack innermost-first (``projects`` is
    outermost-first, the classify_shape order) — mirrors the
    interpreter's bottom-up select chain."""
    for p in reversed(projects):
        batch = batch.select(list(p.columns))
    return batch
