"""Structural pipeline fingerprints: the compiled-pipeline cache key.

A fingerprint captures everything that decides HOW a plan executes —
operator shapes, predicate STRUCTURE, projections, each index leaf's
logged version, each source leaf's file snapshot — while masking literal
VALUES out. Two queries that differ only in literals share a fingerprint
and therefore a CompiledPipeline; the fused dispatch they reach feeds
literals as traced int32 operands into the structure-keyed executables
(exec.hbm_cache's batched counts machinery), so a serving burst of fresh
keys reuses one compiled program instead of recompiling per literal.

Residency is deliberately NOT part of the structural walk for scan and
hybrid arms: the pipeline's fused legs resolve residency per dispatch
through the SAME shared eligibility procedures the interpreter uses
(resident_for / resolve_hybrid_residency), so a tier change — populate,
evict, device loss — degrades or upgrades the serving rung without
invalidating the program. The tier a pipeline last served on rides the
pipeline as observability (explain(verbose)), not as a key. Join shapes
are the exception: batch classification resolved a REGION generation, so
the fingerprint folds both caches' join_region_version — a region
register/evict re-lowers instead of serving a stale routing decision
(the same rule the serve plan cache's version token follows).
"""

from __future__ import annotations

from typing import Tuple

from ..plan.expr import And, Cmp, Col, Expr, In, Lit, Not, Or
from ..plan.ir import (
    Aggregate,
    BucketUnion,
    Filter,
    IndexScan,
    Join,
    Limit,
    LogicalPlan,
    Project,
    Repartition,
    Scan,
    Sort,
    Union,
)


def expr_structure(e: Expr) -> str:
    """Canonical structure string of a USER predicate with literal values
    masked — tolerant of every plan.expr node (the narrowed twin in
    exec.hbm_cache covers only post-narrowing shapes). IN keeps its value
    COUNT: narrowing expands IN into an OR chain per value, so two INs of
    different arity compile different executables."""
    if isinstance(e, (And, Or)):
        tag = "&" if isinstance(e, And) else "|"
        return f"({expr_structure(e.left)}{tag}{expr_structure(e.right)})"
    if isinstance(e, Not):
        return f"~({expr_structure(e.child)})"
    if isinstance(e, Cmp):
        return f"({expr_structure(e.left)} {e.op} {expr_structure(e.right)})"
    if isinstance(e, In):
        return f"in({expr_structure(e.child)},#{len(e.values)})"
    if isinstance(e, Col):
        return f"col({e.name})"
    if isinstance(e, Lit):
        return "?"
    # future expression nodes fingerprint by repr — conservative (repr
    # includes literals, so unknown shapes never falsely share)
    return repr(e)


def _node_sig(n: LogicalPlan) -> Tuple:
    if isinstance(n, Filter):
        return ("F", expr_structure(n.condition))
    if isinstance(n, Project):
        return ("P", tuple(n.columns))
    if isinstance(n, IndexScan):
        # (name, log id) IS the leaf's index-log version: a refresh or
        # optimize bumps the id, so pipelines never outlive the index
        # generation they were lowered against
        return (
            "I",
            n.entry.name,
            n.entry.id,
            tuple(n.required_columns),
            n.use_bucket_spec,
        )
    if isinstance(n, Scan):
        rel = n.relation
        return (
            "S",
            rel.file_format,
            tuple(rel.root_paths),
            tuple((f.name, f.size, f.modified_time) for f in rel.files),
        )
    if isinstance(n, Join):
        return ("J", expr_structure(n.condition), n.join_type)
    if isinstance(n, Aggregate):
        return (
            "A",
            tuple(n.group_by),
            # an expression's literals are part of the program, not
            # operands: its repr keeps them
            tuple((a.fn, a.column, a.name, repr(a.expr)) for a in n.aggs),
        )
    if isinstance(n, Sort):
        return ("O", tuple(n.keys), tuple(n.ascending))
    if isinstance(n, Limit):
        return ("L", n.n)
    if isinstance(n, BucketUnion):
        cols, nb = n.bucket_spec
        return ("BU", tuple(cols), nb)
    if isinstance(n, Repartition):
        return ("R", tuple(n.columns), n.num_buckets)
    if isinstance(n, Union):
        return ("U",)
    return (n.node_name,)


def _walk(n: LogicalPlan) -> Tuple:
    return (_node_sig(n), tuple(_walk(c) for c in n.children))


def plan_fingerprint(plan: LogicalPlan, mesh=None) -> Tuple:
    """The structural fingerprint of an optimized plan subtree. Folds the
    mesh topology (a mesh session lowers differently) and — for plans
    holding a Join — both residency caches' join-region generations
    (module note)."""
    parts: list = [_walk(plan)]
    parts.append(("mesh", int(mesh.devices.size) if mesh is not None else 0))
    if plan.collect(lambda n: isinstance(n, Join)):
        from ..exec.hbm_cache import hbm_cache
        from ..exec.mesh_cache import mesh_cache

        parts.append(
            (
                "join_regions",
                hbm_cache.join_region_version(),
                mesh_cache.join_region_version(),
            )
        )
    return tuple(parts)


def index_roots(plan: LogicalPlan) -> Tuple[str, ...]:
    """One sample data-file path per index leaf — the scoped-invalidation
    anchors of BOTH compile caches (collection_manager matches refresh/
    optimize/delete roots against these by prefix, the invalidate_joins
    rule). A join pipeline carries BOTH sides' leaves, so it drops on
    EITHER side's change. The ONE anchor convention — the pipeline cache
    and the result cache must never scope differently."""
    roots = []
    for n in plan.collect(lambda n: isinstance(n, IndexScan)):
        files = n.entry.content.files()
        if files:
            roots.append(str(files[0]))
    return tuple(roots)


def batch_fingerprint(plan: LogicalPlan) -> Tuple:
    """The COARSE fingerprint the serve micro-batcher folds into its
    batch keys: shape class + each index leaf's version + projection and
    predicate COLUMN SETS. Deliberately coarser than plan_fingerprint —
    the stacked batch executable is keyed per-slot on full predicate
    structure already (exec.hbm_cache._batched_counts_fn), so two
    structures over the same resident column set may still share a
    dispatch; folding full structure here would only shrink batches."""
    leaves = tuple(
        ("I", n.entry.name, n.entry.id)
        for n in plan.collect(lambda n: isinstance(n, IndexScan))
    )
    preds = tuple(
        frozenset(n.condition.columns())
        for n in plan.collect(lambda n: isinstance(n, Filter))
    )
    return (leaves, preds, frozenset(plan.output_columns()))
