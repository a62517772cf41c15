"""Micro-batcher: coalesce compatible in-flight resident scans into ONE
device dispatch.

Every resident-scan query pays the same ~65 ms link round trip for its
count-vector D2H regardless of payload (exec/hbm_cache design note), so
N concurrent point lookups serialized through the single-query path cost
N round trips. Queries are COMPATIBLE when they hit the same resident
table (same index log version — the table key carries file identities)
with predicates that narrow to the same resident column set; a batch of
compatible queries stacks its predicates into one jitted mask+count
launch (``hbm_cache.block_counts_batch`` / the mesh twin) and ships home
one (N, n_blocks) count matrix — the inference-serving
continuous-batching shape applied to index scans. The host leg stays
per-query and exact: each query reads only ITS candidate blocks and
re-evaluates ITS predicate there, so batched results are bit-identical
to serial execution.

Classification happens against the OPTIMIZED plan (the server's plan
cache makes that cheap): the `[Project] → Filter → IndexScan` shape
qualifies, and so does the filter-shape HYBRID union
(`[Project] → Filter → Union(index side, appended side)`) when both the
base table and its appended delta are resident (exec.hbm_cache
DeltaRegion) — those coalesce like plain scans, with the stacked hybrid
dispatch covering base+delta+deletion-bitmask for the whole batch.
Joins, aggregates, mesh-session hybrids (served per-query by the
executor's own fused mesh path), resident-ineligible predicates and
queries the selectivity zone gate routes host all take the normal
executor path (a broad predicate batched onto the device would pay the
dispatch AND read nearly every block anyway).
"""

from __future__ import annotations

from dataclasses import dataclass
from pathlib import Path
from typing import List, Optional, Tuple

from ..plan.expr import Expr
from ..plan.ir import Aggregate, Filter, IndexScan, LogicalPlan, Project, Union
from ..storage.columnar import ColumnarBatch
from ..telemetry.metrics import metrics


@dataclass
class ResidentScanRequest:
    """One classified, batchable query: everything the batched executor
    needs, plus the compatibility key it coalesces under."""

    table: object  # ResidentTable | MeshResidentTable | JoinRegion
    entry: object  # IndexLogEntry (schema for empty results)
    files: List[Path]  # the QUERY's pruned file list (subset of table's)
    predicate: Expr
    output_columns: List[str]
    batch_key: Tuple
    mesh: object = None  # non-None routes the mesh cache protocol
    # prepare_resident_predicate result from classification — carried so
    # the dispatch leg doesn't rerun the narrow pipeline per query
    prepared: object = None
    # hybrid (delta-resident) requests only: the base table's delta
    # region, and the base host leg's exact predicate (user predicate
    # conjoined with the lineage NOT-IN when files were deleted)
    delta: object = None
    host_predicate: Optional[Expr] = None
    # resident JOIN requests only: "join_agg" coalesces aggregate-joins
    # under the join-extended key (region identity + aggregation spec) —
    # one fused dispatch serves the whole batch; ``table`` holds the
    # JoinRegion so the server's latch-drop path works unchanged
    kind: str = "scan"
    group_by: Tuple = ()
    aggs: Tuple = ()


def classify(session, plan: LogicalPlan) -> Optional[ResidentScanRequest]:
    """A ResidentScanRequest when ``plan`` can ride a batched resident
    dispatch, else None (the caller executes it normally). Never raises:
    any refusal is a routing decision, not an error."""
    from ..exec.hbm_cache import (
        _max_block_frac,
        hbm_cache,
        prepare_resident_predicate,
        zone_block_fraction,
    )
    from ..exec.scan import prune_index_files

    # every batch key folds the plan's COARSE pipeline fingerprint
    # (compile.fingerprint.batch_fingerprint): shape class + index-leaf
    # versions + predicate/projection column sets — the whole-plan twin
    # of the table-identity component, so two structurally-incompatible
    # pipelines can never share a stacked dispatch even if they resolve
    # to the same resident table. Full predicate structure stays
    # per-slot in the batched executable (mixed point/range bursts keep
    # coalescing). Computed only AFTER the structural early-returns —
    # the common non-batchable plan must not pay the fingerprint walk.
    from ..compile.fingerprint import batch_fingerprint

    output_columns = list(plan.output_columns())
    node = plan
    while isinstance(node, Project):
        node = node.child
    if isinstance(node, Aggregate):
        return _classify_join_aggregate(
            session, node, output_columns, batch_fingerprint(plan)
        )
    if not isinstance(node, Filter):
        return None
    if isinstance(node.child, Union):
        return _classify_hybrid(
            session,
            node.condition,
            node.child,
            output_columns,
            batch_fingerprint(plan),
        )
    if not isinstance(node.child, IndexScan):
        return None
    fp = batch_fingerprint(plan)
    predicate = node.condition
    scan = node.child
    entry = scan.entry
    # batched results come back as the scan's required columns projected
    # to the plan's output — a Project that REORDERS within required
    # columns is fine, anything else was already excluded by plan shape
    files = prune_index_files(
        [Path(p) for p in entry.content.files()],
        predicate,
        entry.indexed_columns,
        entry.schema,
        entry.num_buckets,
    )
    if not files:
        return None  # empty scans are cheap on the normal path
    pred_cols = sorted(predicate.columns())
    mesh = session.mesh if session.mesh is not None else None
    if mesh is not None and mesh.devices.size > 1:
        from ..exec.mesh_cache import mesh_cache

        table = mesh_cache.resident_for(files, pred_cols, mesh)
        if table is None:
            return None
        prepared = prepare_resident_predicate(table.columns, predicate)
        if prepared is None:
            return None
        # mesh streaming tables batch only within a WINDOW GENERATION —
        # the single-chip rule below, now that the mesh ladder accepts
        # the compressed-streaming rung
        gen = getattr(table, "window_gen", None)
        batch_key = (fp, id(table), frozenset(prepared[1])) + (
            (gen,) if gen is not None else ()
        )
        return ResidentScanRequest(
            table,
            entry,
            files,
            predicate,
            output_columns,
            batch_key,
            mesh,
            prepared,
        )
    table = hbm_cache.resident_for(files, pred_cols)
    if table is None:
        return None
    # same pre-dispatch selectivity gate as the single-query scan: a
    # predicate that cannot prune blocks reads nearly everything host-side
    # regardless, so batching its dispatch wins nothing
    frac = zone_block_fraction(table, predicate)
    if frac is not None and _max_block_frac() < 1.0 and frac >= _max_block_frac():
        return None
    prepared = prepare_resident_predicate(table.columns, predicate)
    if prepared is None:
        return None
    # streaming-tier tables batch only within a WINDOW GENERATION: the
    # generation bumps when a device failure tears the slab pair down
    # (residency.streaming), and a batch must never span that
    # discontinuity — half its queries would have classified against
    # state the other half's windows no longer reflect
    gen = getattr(table, "window_gen", None)
    batch_key = (fp, id(table), frozenset(prepared[1])) + (
        (gen,) if gen is not None else ()
    )
    return ResidentScanRequest(
        table,
        entry,
        files,
        predicate,
        output_columns,
        batch_key,
        None,
        prepared,
    )


def _classify_hybrid(
    session,
    predicate: Expr,
    union: LogicalPlan,
    output_columns: List[str],
    fp: Tuple,
) -> Optional[ResidentScanRequest]:
    """Classify a filter-shape hybrid union for the batched hybrid
    dispatch: base table AND delta region must be resident and the
    predicate must ride the shared encodings. Eligibility (residency,
    pruning, zone gate, host predicate) is exec.delta's
    resolve_hybrid_residency — the SAME procedure the executor's fused
    path runs, so a query never routes differently served vs collected.
    Mesh sessions decline — their hybrid queries are served per-query by
    the executor's fused mesh path (one shard_map dispatch each), which
    the normal path already provides."""
    from ..exec.delta import (
        prepare_hybrid_predicate,
        resolve_hybrid_residency,
    )
    from ..plan.rules.hybrid_scan import parse_hybrid_union

    if session.mesh is not None and session.mesh.devices.size > 1:
        return None
    info = parse_hybrid_union(union)
    if info is None:
        return None
    res = resolve_hybrid_residency(info, predicate)
    if res.status != "ok":
        return None
    prepared = prepare_hybrid_predicate(
        res.table.columns, res.delta.oov, predicate
    )
    if prepared is None:
        return None
    if any(
        n.split("\x00", 1)[0] not in res.delta.columns for n in prepared[1]
    ):
        return None
    return ResidentScanRequest(
        res.table,
        info.entry,
        res.files,
        predicate,
        output_columns,
        (fp, id(res.table), id(res.delta), frozenset(prepared[1])),
        None,
        prepared,
        res.delta,
        res.host_predicate,
    )


def _classify_join_aggregate(
    session, agg: Aggregate, output_columns: List[str], fp: Tuple
) -> Optional[ResidentScanRequest]:
    """Classify an Aggregate([Project](Join)) plan for the batched
    resident aggregate-join: both sides must resolve to pristine
    bucketed index scans with a registered join region covering the
    group/agg columns, and the spec must ride the device (the SAME
    resolve_join_residency + region_agg_plan pair the executor's fused
    arm runs — a query never routes differently served vs collected).
    Identical-spec queries coalesce under (region identity, spec): the
    whole batch is served from ONE fused dispatch. Mesh sessions
    decline — the executor's sharded fused arm serves them per-query."""
    from ..exec.join_residency import (
        orient_join_aggregate,
        region_agg_plan,
        resolve_join_residency,
    )

    if session.mesh is not None and session.mesh.devices.size > 1:
        return None
    if any(a.expr is not None for a in agg.aggs):
        return None  # the region's programs aggregate columns only
    oriented = orient_join_aggregate(agg)
    if oriented is None:
        return None
    left_plan, right_plan, lk, rk, group_by, aggs = oriented
    need = list(
        dict.fromkeys(group_by + [a.column for a in aggs if a.column])
    )
    res = resolve_join_residency(
        left_plan, right_plan, lk, rk, payload_columns=need
    )
    if res.status != "ok":
        return None
    if region_agg_plan(res.region, group_by, aggs) is None:
        return None
    spec = (tuple(group_by), tuple((a.fn, a.column, a.name) for a in aggs))
    return ResidentScanRequest(
        res.region,
        None,
        [],
        None,
        output_columns,
        (fp, id(res.region), "join_agg", spec),
        None,
        None,
        None,
        None,
        "join_agg",
        tuple(group_by),
        tuple(aggs),
    )


def execute_batch(
    requests: List[ResidentScanRequest],
) -> Optional[List[ColumnarBatch]]:
    """Results for a compatible batch — ONE device dispatch, then each
    query's exact host leg over its own candidate blocks. None when the
    stacked dispatch declines (caller falls back to per-query execution);
    device errors propagate so the server can latch degradation."""
    from ..exec.hbm_cache import hbm_cache
    from ..exec.scan import _resident_parts

    if requests[0].kind == "join_agg":
        # the whole batch shares one (region, spec) key, so ONE fused
        # aggregate-join dispatch serves every query in it
        group = hbm_cache.join_agg(
            requests[0].table,
            list(requests[0].group_by),
            list(requests[0].aggs),
        )
        if group is None:
            return None  # spec declined since classification: per-query
        results = [group.select(list(r.output_columns)) for r in requests]
        metrics.incr("serve.batch.coalesced", len(requests))
        metrics.incr("scan.path.resident_join_agg", len(requests))
        return results

    table = requests[0].table
    predicates = [r.predicate for r in requests]
    prepared = [r.prepared for r in requests]
    if requests[0].delta is not None:
        # hybrid batch: ONE stacked base+delta dispatch, then each
        # query's exact host legs (base blocks from mmap with the
        # lineage NOT-IN re-applied, delta blocks from the host-held
        # appended batch)
        delta = requests[0].delta
        pairs = hbm_cache.hybrid_block_counts_batch(
            table, delta, predicates, prepared
        )
        if pairs is None:
            return None
        results = []
        for r, (base_c, delta_c) in zip(requests, pairs):
            parts = _resident_parts(
                table,
                r.files,
                r.output_columns,
                r.host_predicate,
                base_c,
                path_metric=None,
            )
            parts += hbm_cache.delta_parts(
                delta, r.predicate, r.output_columns, delta_c
            )
            results.append(_concat_or_empty(parts, r))
        # counted once per batch: a counter's lock taken per rider, outside
        # the riders' leg spans, is contended by every serving thread
        metrics.incr("scan.path.resident_hybrid", len(requests))
        metrics.incr("serve.batch.coalesced", len(requests))
        return results
    if requests[0].mesh is not None:
        from ..exec.mesh_cache import mesh_cache

        counts = mesh_cache.block_counts_batch(table, predicates, prepared)
        if counts is None:
            return None
        results = []
        for r, c in zip(requests, counts):
            parts = mesh_cache.collect_parts(
                table, r.files, r.output_columns, r.predicate, c
            )
            results.append(_concat_or_empty(parts, r))
        metrics.incr("serve.batch.coalesced", len(requests))
        return results
    counts = hbm_cache.block_counts_batch(table, predicates, prepared)
    if counts is None:
        return None
    results = []
    for r, c in zip(requests, counts):
        parts = _resident_parts(
            table, r.files, r.output_columns, r.predicate, c
        )
        results.append(_concat_or_empty(parts, r))
    metrics.incr("serve.batch.coalesced", len(requests))
    return results


def _concat_or_empty(parts, r: ResidentScanRequest) -> ColumnarBatch:
    from ..exec.scan import empty_batch_for

    if parts:
        return ColumnarBatch.concat(parts)
    empty = empty_batch_for(r.output_columns, r.entry.schema)
    if empty is not None:
        return empty
    # no logged schema (cannot happen for covering indexes, which always
    # log one): fall back to a 0-row read of the first file
    import numpy as np

    from ..storage import layout

    eb = layout.read_batch(r.files[0], columns=r.output_columns)
    return eb.take(np.array([], dtype=np.int64))
