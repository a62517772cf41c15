"""Plan -> CompiledPipeline lowering: the residency-aware fusion pass.

The lowering walks an OPTIMIZED plan subtree and decides, per subtree,
which arm serves it fused — routing through the existing ONE-shared
eligibility procedures (exec.scan's resident branch, exec.delta's
resolve_hybrid_residency, exec.join_residency's resolve_join_residency)
rather than a parallel copy — and which falls to the exact host legs.
The interpreter (exec.executor._exec) is the fallback leg of every
pipeline: a shape the lowering doesn't recognize, a mesh arm it declines,
or a per-query eligibility miss all land there with identical results.

Shape classes (single-chip AND mesh unless noted):

* ``scan``       — ``[Project]* Filter IndexScan``: the filter-pushdown
  pipeline serves as ONE fused mask+count dispatch whose executable is
  keyed on predicate STRUCTURE with literals as traced operands
  (exec.scan.index_scan structure_keyed=True; mesh sessions ride the
  structure-keyed shard_map batched entry the same way), host legs
  exact.
* ``agg_scan``   — ``[Project]* Aggregate([Project]* Filter IndexScan)``:
  the group-by lowers ONTO THE DEVICE when the resident table covers
  the group/agg columns (exec.scan_agg — mask + dense-key segment
  sum/count/min/max in one executable, mesh partials psum-merged; ONE
  D2H ships the finished group table, no candidate blocks); device-
  ineligible specs route the count-vector scan + host hash-aggregate
  tail with a ``compile.agg.declined.<reason>`` counter.
* ``hybrid``     — ``[Project]* Filter Union(...)`` (single-chip): the
  delta-resident hybrid arm on the STRUCTURE-KEYED batched entry
  (fused base+delta dispatch, deletion bitmask on device, literals as
  traced operands — a fresh-literal hybrid burst shares one
  executable) with the concurrent per-side host union as fallback.
  Mesh hybrids stay with the interpreter's literal-keyed fused arm.
* ``join_agg``   — ``[Project]* Aggregate([Project](Join))``: the
  resident aggregate-join arm (single-chip AND mesh — the PR-5/8 fused
  kernels are the lowering targets), host range-fusion fallback.
* ``join_shuffle`` — ``[Project]* Join`` over bucketed sides with
  MISMATCHED bucket counts, mesh only: the ICI all-to-all shuffle
  repartitions the smaller side into the other's bucket space and the
  co-partitioned SMJ arms serve (distributed/shuffle.py); the planner
  and every exchange failure decline to the exact host join.
* ``interpret``  — everything else: the per-operator interpreter.

Lowering is cheap (a shape walk plus counter-free registry probes for
the advisory tier label) and NEVER raises — any internal error lowers to
``interpret``, counted under ``compile.lower_error``.
"""

from __future__ import annotations

from typing import List, Optional

from ..plan.ir import (
    Aggregate,
    Filter,
    IndexScan,
    Join,
    LogicalPlan,
    Project,
    Union,
)
from ..telemetry.metrics import metrics
from .pipeline import CompiledPipeline


class Shape:
    """The classified shape of a plan: which pipeline kind it lowers to
    plus the per-query operands re-bound at run time (projects stack,
    filter condition, leaf nodes). Literal-value-free by construction —
    run() re-extracts operands from the CONCRETE plan it is given."""

    __slots__ = (
        "kind",
        "projects",
        "condition",
        "scan",
        "union",
        "agg",
        "inner_projects",
        "join",
    )

    def __init__(
        self,
        kind: str,
        projects: Optional[List[Project]] = None,
        condition=None,
        scan: Optional[IndexScan] = None,
        union: Optional[Union] = None,
        agg: Optional[Aggregate] = None,
        inner_projects: Optional[List[Project]] = None,
        join: Optional[Join] = None,
    ):
        self.kind = kind
        self.projects = projects or []
        self.condition = condition
        self.scan = scan
        self.union = union
        self.agg = agg
        self.inner_projects = inner_projects or []
        self.join = join


def classify_shape(plan: LogicalPlan, mesh=None) -> Shape:
    """Structural classification — pure, no IO, no counters. Shared by
    lower() and CompiledPipeline.run()'s per-query operand re-binding
    (two plans with equal fingerprints classify identically, so the
    re-bind can never route a query differently than its pipeline)."""
    projects: List[Project] = []
    node = plan
    while isinstance(node, Project):
        projects.append(node)
        node = node.child
    if isinstance(node, Aggregate):
        if any(a.expr is not None for a in node.aggs):
            # an expression input is computed by the interpreter's
            # hash_aggregate; the fused pipelines read columns
            return Shape("interpret")
        inner = node.child
        inner_projects: List[Project] = []
        while isinstance(inner, Project):
            inner_projects.append(inner)
            inner = inner.child
        if isinstance(inner, Join):
            return Shape("join_agg", projects, agg=node)
        if isinstance(inner, Filter) and isinstance(inner.child, IndexScan):
            return Shape(
                "agg_scan",
                projects,
                inner.condition,
                inner.child,
                agg=node,
                inner_projects=inner_projects,
            )
        return Shape("interpret")
    if isinstance(node, Filter):
        child = node.child
        if isinstance(child, IndexScan):
            return Shape("scan", projects, node.condition, child)
        if isinstance(child, Union) and mesh is None:
            # mesh hybrids keep the interpreter's literal-keyed fused
            # arm — the structure-keyed hybrid batch entry is single-chip
            return Shape("hybrid", projects, node.condition, union=child)
    if isinstance(node, Join) and mesh is not None:
        # both sides bucketed but with MISMATCHED bucket counts: the
        # shuffle-repartition join (distributed/shuffle.py). Metadata
        # walk only — the executor's shuffle arm re-runs the full
        # eligibility (key sets, planner economics) per query and
        # declines to the exact host join identically.
        from ..exec.executor import bucketed_meta

        lm = bucketed_meta(node.left)
        rm = bucketed_meta(node.right)
        if (
            lm is not None
            and rm is not None
            and lm.entry.num_buckets != rm.entry.num_buckets
        ):
            return Shape("join_shuffle", projects, join=node)
    return Shape("interpret")


def _tier_label(shape: Shape, mesh=None) -> str:
    """Advisory residency label for the pipeline (explain/observability):
    which rung the fused arm WOULD serve on right now. Counter-free —
    registry probes only, never the counting eligibility procedures (a
    lowering must not skew per-query decline counters)."""
    try:
        if shape.kind in ("scan", "agg_scan") and shape.scan is not None:
            entry = shape.scan.entry
            pred_cols = sorted(shape.condition.columns())
            if shape.kind == "agg_scan" and shape.agg is not None:
                # the device-agg arm needs the GROUP/AGG columns resident
                # too — labeling from predicate coverage alone would
                # print a device tier above an "Aggregate ran: host hash"
                # line (explain contradiction)
                pred_cols = sorted(
                    set(pred_cols)
                    | set(shape.agg.group_by)
                    | {a.column for a in shape.agg.aggs if a.column}
                )
            if mesh is not None:
                from ..exec.mesh_cache import mesh_cache

                table = mesh_cache.resident_for(
                    entry.content.files(), pred_cols, mesh
                )
            else:
                from ..exec.hbm_cache import hbm_cache

                table = hbm_cache.resident_for(
                    entry.content.files(), pred_cols
                )
            return getattr(table, "tier", "resident") if table else "host"
        if shape.kind == "hybrid":
            from ..exec.hbm_cache import hbm_cache
            from ..plan.rules.hybrid_scan import parse_hybrid_union

            info = parse_hybrid_union(shape.union)
            if info is None:
                return "host"
            table = hbm_cache.resident_for(
                info.entry.content.files(),
                sorted(shape.condition.columns()),
            )
            return getattr(table, "tier", "resident") if table else "host"
        if shape.kind == "join_agg":
            from ..exec.hbm_cache import hbm_cache
            from ..exec.mesh_cache import mesh_cache

            return (
                "join_region"
                if (
                    hbm_cache.snapshot_joins()["regions"]
                    or mesh_cache.snapshot_joins()["regions"]
                )
                else "host"
            )
        if shape.kind == "join_shuffle":
            # mesh presence IS the classification gate; the per-query
            # economics (planner) may still decline to host
            return "mesh"
    except Exception:  # noqa: BLE001 - the label is advisory only
        metrics.incr("compile.tier_probe_error")
    return "host"


def lower(
    plan: LogicalPlan, conf, mesh=None, fingerprint: Optional[tuple] = None
) -> CompiledPipeline:
    """Lower ``plan`` to a CompiledPipeline. Never raises: an internal
    error lowers to the interpreter pipeline (counted)."""
    from .fingerprint import index_roots

    try:
        with metrics.timer("compile.lower"):
            shape = classify_shape(plan, mesh)
            pipeline = CompiledPipeline(
                kind=shape.kind,
                fingerprint=fingerprint,
                tier=_tier_label(shape, mesh),
                index_roots=index_roots(plan),
                boundary=_boundary(plan, shape),
            )
        metrics.incr("compile.lowered")
        metrics.incr(f"compile.lowered.{shape.kind}")
        return pipeline
    except Exception:  # noqa: BLE001 - lowering must never fail a query
        metrics.incr("compile.lower_error")
        return CompiledPipeline(
            kind="interpret",
            fingerprint=fingerprint,
            tier="host",
            index_roots=(),
            boundary=("interpret: lowering error",),
        )


def _boundary(plan: LogicalPlan, shape: Shape) -> tuple:
    """Human-readable fused-subtree boundary for explain(verbose): which
    operators ride the fused dispatch and where the host legs begin."""
    if shape.kind == "interpret":
        return ("interpret: " + plan.node_name + " (per-operator)",)
    lines = [f"fused[{shape.kind}]:"]
    fused_nodes = {
        "scan": "Filter→IndexScan (one mask+count dispatch)",
        "agg_scan": (
            "Aggregate→Filter→IndexScan (one dispatch: device "
            "segment-agg, host hash tail on decline)"
        ),
        "hybrid": "Filter→Union base+delta (one fused dispatch)",
        "join_agg": "Aggregate→Join (resident region dispatch)",
        "join_shuffle": (
            "Join (ICI all-to-all repartition → co-partitioned SMJ; "
            "planner may decline to host)"
        ),
    }
    lines.append("  device: " + fused_nodes[shape.kind])
    lines.append("  host legs: candidate-block reads + exact predicates")
    return tuple(lines)
