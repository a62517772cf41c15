"""Device-resident join pipeline: regions, eligibility, fused dispatches.

The bench's weakest external speedups are exactly the join shapes
(BENCH_r05: 5.5-9.0x vs 88x for point filters) because only the *filter*
path is device-resident: the bucketed SMJ and the aggregate-over-join
fusion run on host numpy and re-touch per-bucket data every query. TQP
("Query Processing on Tensor Computation Runtimes") shows hash/merge
joins and grouped aggregation map cleanly onto tensor runtimes; Theseus
shows the win is dominated by *not moving the data*. This module carries
both conclusions into the residency design the scan path already proved:

* a **join region** keeps one (left index version, right index version,
  join keys) pair's *join codes* resident in HBM — the composite int64
  codes of `joins.join_codes` narrowed to the i32 transport, the right
  side globally pre-sorted at build (hash bucketing guarantees equal
  codes share a bucket, so one global sort replaces per-bucket merges)
  — plus the payload/group/agg columns an indexed aggregate-join needs,
  as raw-bit i32 planes (floats never cross the link as floats:
  ops.floatbits rationale);
* the fused dispatches then resolve a join ON device: one
  ``searchsorted`` pair over resident codes produces the match ranges
  (``scan.path.resident_join`` — only the (lo, counts) vectors come
  home, zero per-query H2D), and for Q17-shaped aggregate-joins the
  ranges feed segment-sum/count/min/max *in the same executable*
  (``scan.path.resident_join_agg``) so ONE D2H ships the finished group
  table;
* the **mesh variant** exploits the build's ``b % D`` placement: both
  sides' codes pack per owner device (equal keys share a bucket, so the
  sharded join is shuffle-free) and the aggregate runs two-phase —
  per-device partial group vectors, then ``psum``/``pmin``/``pmax``
  into one replicated group table.

Eligibility is ONE shared procedure (`resolve_join_residency`) used by
the executor's ``_exec_join`` / ``_try_join_aggregate`` arms and the
serve micro-batcher — mirroring ``exec.delta.resolve_hybrid_residency``
so a query never routes differently served vs collected. Hybrid
(bucket-union) and predicate-filtered join sides decline to host (their
row sets are not a pure function of the immutable index files), as do
dtype shapes the device cannot serve exactly; the host paths remain
exact fallbacks and parity is asserted by the tests and the bench gate.

Exactness contract: int aggregates are bit-exact (int64 segment sums and
prefix differences wrap exactly like the host's); float aggregates sum
in float64 on device, which is exact transport (bit planes) but
order-sensitive accumulation — parity there is asserted to float64
relative tolerance, the same gate the bench applies to host float
checksums. Float sums under duplicate right matches decline (the prefix
trick loses precision int64 never does — the host fusion's own rule).

Nothing here reads a device array back: uploads/fences live in the
builds below, dispatch readbacks live in the cache modules (the HS001
boundary, like exec.delta).
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, Optional, Tuple

import numpy as np

from ..exceptions import HyperspaceException
from ..plan.ir import (
    BucketUnion,
    Filter,
    IndexScan,
    Join,
    Project,
    Repartition,
    Union,
)
from ..storage.columnar import Column, ColumnarBatch, is_string, numpy_dtype
from ..telemetry.metrics import metrics
from ..telemetry.trace import add_bytes as _trace_bytes

# the device legs trace 64-bit lanes (f64 two-plane reprs, int64 sort
# keys); establish the x64 scope at import, before any jit body traces
from ..ops import ensure_x64

ensure_x64()

I32_MIN, I32_MAX = -(2**31), 2**31 - 1
# mesh shards pad both sides to a static per-device capacity; the pads
# must compare unequal to every real code AND to the other side's pads
# (a left pad searching the right side must land past every real code
# and every right pad), so two distinct top codes are reserved and the
# build refuses code domains that reach them
L_PAD = I32_MAX
R_PAD = I32_MAX - 1
_MAX_CODE = I32_MAX - 2

_AGG_FNS = ("count", "sum", "avg", "min", "max")


# ---------------------------------------------------------------------------
# region state
# ---------------------------------------------------------------------------


@dataclass
class JoinPayloadColumn:
    """One resident payload column of a join region. ``arrays`` are the
    device i32 planes (1 for int/f32bits, 2 for f64bits — raw IEEE bit
    planes, NOT the ordered encoding: the aggregate consumer needs
    VALUES, and host bitcast -> device bitcast round-trips exactly)."""

    arrays: tuple
    dtype_str: str
    enc: str  # 'int' | 'f32bits' | 'f64bits'
    nbytes: int
    # group-key service (dense-domain int columns only): device slot ids
    # slot = value - mn, plus the host-side (mn, span) that rebuilds key
    # values from kept slots — the same dense rule as aggregate._dense
    slots: Optional[object] = None
    mn: Optional[int] = None
    span: Optional[int] = None


@dataclass
class JoinRegion:
    """One (left index version, right index version, keys) pair's
    resident join state on the single-chip cache."""

    key: tuple  # (l_ident, r_ident, l_keys, r_keys)
    n_l: int
    n_r: int
    l_codes: object  # device i32 (n_l,)
    # globally sorted right codes. FoR-delta packed when the codec wins
    # (ops.bitpack.for_spec over the sorted stream — the PR-5 global
    # sort is exactly what makes per-block offsets small): ``r_codes``
    # then holds the packed WORDS, ``r_refs`` the per-block references,
    # and the dispatch executables fuse the decode ahead of their
    # searchsorted — budget accounting charges packed bytes.
    r_codes: object  # device i32: (n_r,) raw, or packed words
    r_order: np.ndarray  # host: sorted position -> original right row
    uniq_right: bool  # right codes unique (the FK->PK / Q17 shape)
    l_cols: Dict[str, JoinPayloadColumn]
    r_cols: Dict[str, JoinPayloadColumn]  # pre-permuted by r_order
    nbytes: int = 0
    last_used: float = field(default_factory=time.monotonic)
    r_pack: Optional[object] = None  # ops.bitpack.PackSpec (FoR) or None
    r_refs: Optional[object] = None  # device i32 (n_r // block,) refs


@dataclass
class MeshJoinRegion:
    """The mesh twin: both sides' codes packed per owner device under the
    build's ``b % D`` rule (equal keys share a bucket, so per-device
    merges see every possible match), right side sorted *within* each
    device, pads at the reserved top codes."""

    key: tuple
    mesh: object
    n_devices: int
    cap_l: int  # padded per-device left rows (pow2)
    cap_r: int
    dev_rows_l: list
    dev_rows_r: list
    l_codes: object  # device (D, cap_l) i32, NamedSharding
    r_codes: object  # device (D, cap_r) i32, sorted per device row
    uniq_right: bool
    l_cols: Dict[str, JoinPayloadColumn]
    r_cols: Dict[str, JoinPayloadColumn]
    n_l: int = 0
    n_r: int = 0
    nbytes: int = 0
    last_used: float = field(default_factory=time.monotonic)


def join_region_key(l_files, r_files, l_keys, r_keys) -> tuple:
    """Identity key of a join region: both sides' file identities (path +
    size + mtime — stale versions never match, hbm_cache's one rule) plus
    the oriented key columns. Raises OSError for vanished files (caller
    treats as no region)."""
    from .hbm_cache import _file_identity

    return (
        tuple(sorted(_file_identity(p) for p in l_files)),
        tuple(sorted(_file_identity(p) for p in r_files)),
        tuple(l_keys),
        tuple(r_keys),
    )


def region_roots(region) -> list:
    """The distinct parent-directory prefixes of a region's files — the
    scope invalidate_joins matches refresh/optimize roots against."""
    paths = [p for side in region.key[:2] for (p, _s, _m) in side]
    return paths


# ---------------------------------------------------------------------------
# eligibility — the ONE shared procedure (executor arms + serve batcher)
# ---------------------------------------------------------------------------


@dataclass
class JoinResidency:
    """Outcome of resolve_join_residency. ``declined`` statuses mirror
    the groups-cache opt-outs (``join.cache.optout.{hybrid,filtered}``):
    the same plan shapes that cannot carry a cross-query cache token
    cannot be served from a region built over pristine index files."""

    status: str  # "ok" | "no_region" | "declined" | "ineligible"
    reason: str = ""  # declined: "hybrid" | "filtered"
    region: object = None
    l_node: object = None  # the bucketed IndexScan each side resolves to
    r_node: object = None
    l_keys: tuple = ()  # keys reordered to the LEFT index's column order
    r_keys: tuple = ()


def orient_join_aggregate(agg):
    """(left_plan, right_plan, l_keys, r_keys, group_by, aggs) for an
    ``Aggregate([Project](Join))`` plan, oriented so the group keys live
    on the LEFT side (the inner join is symmetric) — the ONE orientation
    rule shared by the executor's fused/host aggregate-join arms and the
    serve batcher's classifier (a copy in each would drift and route the
    same query differently served vs collected). None when the shape or
    condition doesn't qualify."""
    from ..plan.rules.join_rule import (
        align_condition_sides,
        extract_equi_condition,
    )

    node = agg.child
    if isinstance(node, Project):
        node = node.child
    if not isinstance(node, Join):
        return None
    pairs = extract_equi_condition(node.condition)
    if pairs is None:
        return None
    oriented = align_condition_sides(
        pairs, node.left.output_columns(), node.right.output_columns()
    )
    if oriented is None:
        return None
    l_keys = [l for l, _ in oriented]
    r_keys = [r for _, r in oriented]
    group_by = list(agg.group_by)
    left_cols = {c.lower() for c in node.left.output_columns()}
    sides = (node.left, node.right, l_keys, r_keys)
    if not all(g.lower() in left_cols for g in group_by):
        right_cols = {c.lower() for c in node.right.output_columns()}
        if not all(g.lower() in right_cols for g in group_by):
            return None  # group keys span both sides: not fusable
        sides = (node.right, node.left, r_keys, l_keys)
    return (*sides, group_by, list(agg.aggs))


def _side_scan(plan):
    """The bucketed IndexScan a pristine join side resolves to, or
    (None, why). Filters and hybrid bucket-unions make the side's rows a
    per-query function of predicate/appended data — not servable from a
    region keyed only by file identities."""
    node = plan
    while isinstance(node, Project):
        node = node.child
    if isinstance(node, Filter):
        return None, "filtered"
    if isinstance(node, (BucketUnion, Union, Repartition)):
        return None, "hybrid"
    if isinstance(node, IndexScan) and node.use_bucket_spec:
        return node, ""
    return None, "shape"


def resolve_join_residency(
    left_plan, right_plan, l_keys, r_keys, mesh=None, payload_columns=()
) -> JoinResidency:
    """Resolve whether a bucketed equi-join can take the device-resident
    path on the cache ``mesh`` selects: residency mode, pristine-side
    shapes (hybrid/filtered decline — counted per cache prefix), bucket
    and key-vs-indexed-column compatibility, then the region lookup with
    payload-column coverage. Mirrors exec.delta.resolve_hybrid_residency:
    executor single-chip/mesh arms and the serve batcher all route
    through HERE, so a gate tweak cannot split their behavior."""
    from .hbm_cache import hbm_cache, residency_mode

    cache = hbm_cache
    if mesh is not None:
        from .mesh_cache import mesh_cache as cache  # noqa: F811

    if residency_mode() == "off":
        return JoinResidency("ineligible", "mode")
    l_node, l_why = _side_scan(left_plan)
    r_node, r_why = _side_scan(right_plan)
    if l_node is None or r_node is None:
        why = l_why or r_why
        if why in ("filtered", "hybrid"):
            metrics.incr(f"{cache._metric_prefix}.join.declined.{why}")
            return JoinResidency("declined", why)
        return JoinResidency("ineligible", why or "shape")
    if l_node.entry.num_buckets != r_node.entry.num_buckets:
        return JoinResidency("ineligible", "buckets")
    if {c.lower() for c in l_node.entry.indexed_columns} != {
        k.lower() for k in l_keys
    } or {c.lower() for c in r_node.entry.indexed_columns} != {
        k.lower() for k in r_keys
    }:
        return JoinResidency("ineligible", "keys")
    # merge order: both sides keyed in the LEFT index's column order (the
    # executor's own rule, so region codes match the host merge exactly)
    k2k = {a.lower(): b for a, b in zip(l_keys, r_keys)}
    lk = list(l_node.entry.indexed_columns)
    rk = [k2k[k.lower()] for k in lk]
    if mesh is None:
        region = cache.join_for(
            l_node.entry.content.files(),
            r_node.entry.content.files(),
            lk,
            rk,
            payload_columns,
        )
    else:
        region = cache.join_for(
            l_node.entry.content.files(),
            r_node.entry.content.files(),
            lk,
            rk,
            payload_columns,
            mesh,
        )
    status = "ok" if region is not None else "no_region"
    return JoinResidency(
        status, "", region, l_node, r_node, tuple(lk), tuple(rk)
    )


# ---------------------------------------------------------------------------
# host-side encode (build time)
# ---------------------------------------------------------------------------


def encode_join_payload(col: Column):
    """(host i32 plane tuple, enc) for a device join payload column, or
    None when the dtype cannot ride exactly: strings decline (an
    aggregate's group/value columns would pin unbounded vocab heaps),
    as does int64 beyond the i32 transport. Floats ride as raw IEEE bit
    planes — value-exact on the link, reassembled by bitcast on device."""
    if is_string(col.dtype_str):
        return None
    a = col.data
    if a.dtype == np.float64:
        bits = np.ascontiguousarray(a, dtype=np.float64).view(np.int64)
        hi = (bits >> np.int64(32)).astype(np.int32)
        lo = (bits & np.int64(0xFFFFFFFF)).astype(np.uint32).view(np.int32)
        return (hi, lo), "f64bits"
    if a.dtype == np.float32:
        return (np.ascontiguousarray(a).view(np.int32),), "f32bits"
    if a.dtype.kind in "iub":
        a64 = a.astype(np.int64)
        if len(a64) and (
            int(a64.min()) < I32_MIN or int(a64.max()) > I32_MAX
        ):
            return None
        return (a64.astype(np.int32),), "int"
    return None


def _encode_codes(l_codes: np.ndarray, r_codes: np.ndarray):
    """i32-narrowed join codes, or None when the composite code domain
    exceeds the transport (minus the reserved mesh pad codes). The
    narrowing is a plain cast — join codes are already exact int64 and
    both sides share one code space (joins.join_codes), so a shared
    range check keeps cross-side comparisons value-preserving."""
    lo_ = min(
        int(l_codes.min()) if len(l_codes) else 0,
        int(r_codes.min()) if len(r_codes) else 0,
    )
    hi_ = max(
        int(l_codes.max()) if len(l_codes) else 0,
        int(r_codes.max()) if len(r_codes) else 0,
    )
    if lo_ < I32_MIN or hi_ > _MAX_CODE:
        return None
    return l_codes.astype(np.int32), r_codes.astype(np.int32)


def _payload_specs(l_all, r_all, payload_columns, n_l):
    """Per-column host encode for the requested payload set: skips
    columns absent from both sides or unencodable (the caller's coverage
    check decides what that means). Returns (side, name, planes, enc,
    group_service) tuples; group service (slots, mn, span) attaches to
    dense-domain int LEFT columns only — the group-by side."""
    out = []
    for name in dict.fromkeys(payload_columns):
        for side, batch in (("l", l_all), ("r", r_all)):
            col = batch.columns.get(name)
            if col is None:
                continue
            e = encode_join_payload(col)
            if e is None:
                continue
            planes, enc = e
            service = None
            if side == "l" and enc == "int" and n_l:
                a64 = col.data.astype(np.int64)
                mn, mx = int(a64.min()), int(a64.max())
                span = mx - mn + 1
                # span must be O(n): the same dense-domain rule as
                # aggregate._dense / _join_ranges_native — the device
                # ships span-sized group vectors home
                if 0 < span <= max(4 * n_l, 1 << 16):
                    service = ((a64 - mn).astype(np.int32), mn, span)
            out.append((side, name, planes, enc, service))
            break
    return out


# ---------------------------------------------------------------------------
# region builds
# ---------------------------------------------------------------------------


def build_join_region(
    cache, l_by_bucket, r_by_bucket, l_keys, r_keys, key, payload_columns
):
    """(region, permanent_refusal) for the single-chip cache —
    hbm_cache._build semantics: permanent refusals are structural for
    this file-version pair (no common buckets, code domain beyond the
    transport); budget/IO/device refusals are transient."""
    from ..utils.deviceprobe import first_device_touch_ok
    from .hbm_cache import _budget_bytes, residency_mode
    from .joins import _bucketed_join_setup

    pfx = cache._metric_prefix
    if not first_device_touch_ok(required=residency_mode() == "force"):
        metrics.incr(f"{pfx}.device_unreachable")
        return None, False
    t0 = time.perf_counter()
    setup, _ck = _bucketed_join_setup(
        l_by_bucket, r_by_bucket, list(l_keys), list(r_keys)
    )
    if setup is None:
        return None, True  # no common buckets: nothing to serve
    l_all, r_all, l_codes, r_codes, _lb, _rb, _ps = setup
    enc = _encode_codes(l_codes, r_codes)
    if enc is None:
        return None, True
    l32, r32 = enc
    r_order = np.argsort(r_codes, kind="stable")
    r_sorted = r32[r_order]
    uniq_right = (
        bool((np.diff(r_sorted) > 0).all()) if len(r_sorted) > 1 else True
    )
    n_l, n_r = l_all.num_rows, r_all.num_rows
    # FoR-delta the sorted right codes when the codec wins: the global
    # sort bounds every in-block offset, so dense code domains (the
    # FK->PK shape) pack to a fraction of the raw plane and the budget
    # charge shrinks accordingly (hyperspace.residency.forDelta)
    r_pack = None
    r_pack_host = None
    from ..residency import for_delta_enabled

    if for_delta_enabled() and n_r:
        from ..ops import bitpack

        fspec = bitpack.for_spec(r_sorted, block=128)
        if fspec is not None and fspec.packed_nbytes < r_sorted.nbytes:
            r_pack = fspec
            r_pack_host = bitpack.pack_for(r_sorted, fspec)
            metrics.incr(f"{pfx}.join.for_delta_packed")
            metrics.incr(
                f"{pfx}.join.for_delta_saved_bytes",
                int(r_sorted.nbytes) - int(fspec.packed_nbytes),
            )
    specs = _payload_specs(l_all, r_all, payload_columns, n_l)
    dev_bytes = 4 * n_l + (
        r_pack.packed_nbytes if r_pack is not None else 4 * n_r
    )
    for _side, _name, planes, _e, service in specs:
        dev_bytes += sum(int(p.nbytes) for p in planes)
        if service is not None:
            dev_bytes += 4 * n_l
    host_bytes = int(r_order.nbytes)
    # headroom against the resident tables (the delta build's rule):
    # registration never evicts a TABLE for a join region, so a region
    # that only fits by exceeding the tables' remainder would be refused
    # there anyway, after paying the upload
    with cache._lock:
        headroom = _budget_bytes() - sum(t.nbytes for t in cache._tables)
    if dev_bytes + host_bytes > headroom:
        metrics.incr(f"{pfx}.join.over_budget_refused")
        return None, False

    import jax

    try:
        dev_l = jax.device_put(l32)
        dev_refs = None
        if r_pack is not None:
            words, refs = r_pack_host
            dev_r = jax.device_put(words)
            dev_refs = jax.device_put(refs)
        else:
            dev_r = jax.device_put(r_sorted)
        fences = [dev_l, dev_r] + ([dev_refs] if dev_refs is not None else [])
        l_cols: Dict[str, JoinPayloadColumn] = {}
        r_cols: Dict[str, JoinPayloadColumn] = {}
        for side, name, planes, enc_s, service in specs:
            if side == "r":
                planes = tuple(p[r_order] for p in planes)
            dev_planes = tuple(jax.device_put(p) for p in planes)
            fences.extend(dev_planes)
            nbytes_c = sum(int(p.nbytes) for p in planes)
            pc = JoinPayloadColumn(
                dev_planes,
                (l_all if side == "l" else r_all).columns[name].dtype_str,
                enc_s,
                nbytes_c,
            )
            if service is not None:
                slots, mn, span = service
                pc.slots = jax.device_put(slots)
                pc.mn, pc.span = mn, span
                pc.nbytes += int(slots.nbytes)
                fences.append(pc.slots)
            (l_cols if side == "l" else r_cols)[name] = pc
        from ..ops import fence_chain

        fence_chain(fences)
    except Exception:  # noqa: BLE001 - device loss: no residency
        metrics.incr(f"{pfx}.join.transfer_error")
        return None, False
    metrics.incr(f"{pfx}.join.h2d_bytes", dev_bytes)
    _trace_bytes("h2d_bytes", dev_bytes)
    metrics.record_time(f"{pfx}.join.prefetch", time.perf_counter() - t0)
    return (
        JoinRegion(
            key,
            n_l,
            n_r,
            dev_l,
            dev_r,
            r_order,
            uniq_right,
            l_cols,
            r_cols,
            dev_bytes + host_bytes,
            r_pack=r_pack,
            r_refs=dev_refs,
        ),
        False,
    )


def build_mesh_join_region(
    cache, l_by_bucket, r_by_bucket, l_keys, r_keys, key, payload_columns, mesh
):
    """(region, permanent_refusal) for the mesh cache: each device
    receives exactly its owned buckets' rows of BOTH sides (the build's
    ``b % D`` rule), so per-device merges are shuffle-free and complete.
    The right side sorts within each device; pads sit at the reserved top
    codes so they can never match."""
    from ..parallel.mesh import owner_of_bucket
    from ..utils.deviceprobe import first_device_touch_ok
    from ..utils.intmath import next_pow2
    from .hbm_cache import _budget_bytes, residency_mode
    from .joins import _bucketed_join_setup

    pfx = cache._metric_prefix
    if not first_device_touch_ok(required=residency_mode() == "force"):
        metrics.incr(f"{pfx}.device_unreachable")
        return None, False
    t0 = time.perf_counter()
    setup, _ck = _bucketed_join_setup(
        l_by_bucket, r_by_bucket, list(l_keys), list(r_keys)
    )
    if setup is None:
        return None, True
    l_all, r_all, l_codes, r_codes, l_bounds, r_bounds, _ps = setup
    enc = _encode_codes(l_codes, r_codes)
    if enc is None:
        return None, True
    l32, r32 = enc
    n_l, n_r = l_all.num_rows, r_all.num_rows
    # the SAME common-bucket derivation as _bucketed_join_setup, so
    # bounds index k maps to common[k]
    common = sorted(set(l_by_bucket) & set(r_by_bucket))
    D = int(mesh.devices.size)
    l_rows = [[] for _ in range(D)]
    r_rows = [[] for _ in range(D)]
    for k, b in enumerate(common):
        d = owner_of_bucket(int(b), D)
        # bounds are host segment offsets (np.cumsum over host batch row
        # counts, _bucketed_join_setup) — never device arrays
        l_rows[d].append(np.arange(int(l_bounds[k]), int(l_bounds[k + 1])))  # hslint: disable=HS001
        r_rows[d].append(np.arange(int(r_bounds[k]), int(r_bounds[k + 1])))  # hslint: disable=HS001
    l_idx = [
        np.concatenate(rs) if rs else np.empty(0, dtype=np.int64)
        for rs in l_rows
    ]
    r_idx = [
        np.concatenate(rs) if rs else np.empty(0, dtype=np.int64)
        for rs in r_rows
    ]
    dev_rows_l = [int(len(ix)) for ix in l_idx]
    dev_rows_r = [int(len(ix)) for ix in r_idx]
    cap_l = next_pow2(max(max(dev_rows_l), 1))
    cap_r = next_pow2(max(max(dev_rows_r), 1))
    # sort the right side within each device (global sortedness is
    # meaningless across shards); payload gathers ride the same order
    for d in range(D):
        if dev_rows_r[d]:
            order_d = np.argsort(r32[r_idx[d]], kind="stable")
            r_idx[d] = r_idx[d][order_d]
    r_sorted_global = np.sort(r32, kind="stable")
    uniq_right = (
        bool((np.diff(r_sorted_global) > 0).all())
        if len(r_sorted_global) > 1
        else True
    )
    specs = _payload_specs(l_all, r_all, payload_columns, n_l)
    dev_bytes = 4 * D * (cap_l + cap_r)
    for _side, _name, planes, _e, service in specs:
        per = cap_l if _side == "l" else cap_r
        dev_bytes += 4 * D * per * len(planes)
        if service is not None:
            dev_bytes += 4 * D * cap_l
    with cache._lock:
        headroom = _budget_bytes() - sum(t.nbytes for t in cache._tables)
    if dev_bytes > headroom:
        metrics.incr(f"{pfx}.join.over_budget_refused")
        return None, False

    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    sharding = NamedSharding(mesh, PartitionSpec(mesh.axis_names[0], None))

    def pack(flat: np.ndarray, idx_lists, cap: int, pad: int) -> np.ndarray:
        packed = np.full((D, cap), pad, dtype=np.int32)
        for d in range(D):
            if len(idx_lists[d]):
                packed[d, : len(idx_lists[d])] = flat[idx_lists[d]]
        return packed

    try:
        dev_l = jax.device_put(pack(l32, l_idx, cap_l, L_PAD), sharding)
        dev_r = jax.device_put(pack(r32, r_idx, cap_r, R_PAD), sharding)
        fences = [dev_l, dev_r]
        l_cols: Dict[str, JoinPayloadColumn] = {}
        r_cols: Dict[str, JoinPayloadColumn] = {}
        for side, name, planes, enc_s, service in specs:
            idx = l_idx if side == "l" else r_idx
            cap = cap_l if side == "l" else cap_r
            dev_planes = tuple(
                jax.device_put(pack(p, idx, cap, 0), sharding)
                for p in planes
            )
            fences.extend(dev_planes)
            pc = JoinPayloadColumn(
                dev_planes,
                (l_all if side == "l" else r_all).columns[name].dtype_str,
                enc_s,
                4 * D * cap * len(planes),
            )
            if service is not None:
                slots, mn, span = service
                pc.slots = jax.device_put(
                    pack(slots, l_idx, cap_l, 0), sharding
                )
                pc.mn, pc.span = mn, span
                pc.nbytes += 4 * D * cap_l
                fences.append(pc.slots)
            (l_cols if side == "l" else r_cols)[name] = pc
        from ..ops import fence_chain

        fence_chain(fences)
    except Exception:  # noqa: BLE001 - device loss: no residency
        metrics.incr(f"{pfx}.join.transfer_error")
        return None, False
    metrics.incr(f"{pfx}.join.h2d_bytes", dev_bytes)
    _trace_bytes("h2d_bytes", dev_bytes)
    metrics.record_time(f"{pfx}.join.prefetch", time.perf_counter() - t0)
    return (
        MeshJoinRegion(
            key,
            mesh,
            D,
            cap_l,
            cap_r,
            dev_rows_l,
            dev_rows_r,
            dev_l,
            dev_r,
            uniq_right,
            l_cols,
            r_cols,
            n_l,
            n_r,
            dev_bytes,
        ),
        False,
    )


# ---------------------------------------------------------------------------
# aggregate planning — which (group_by, aggs) shapes the device serves
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class _ColOps:
    name: str
    side: str  # 'l' | 'r'
    enc: str
    arity: int  # device planes consumed
    ops: tuple  # sorted subset of ('max', 'min', 'nn', 'sum')


@dataclass(frozen=True)
class AggPlan:
    group: str
    mn: int
    span: int
    uniq_right: bool
    cols: tuple  # _ColOps, deterministic order

    def signature(self) -> tuple:
        """The compile-cache key component: everything the traced fn's
        STRUCTURE depends on (names are positional at trace time)."""
        return (
            self.span,
            self.uniq_right,
            tuple((c.side, c.enc, c.arity, c.ops) for c in self.cols),
        )


def region_agg_plan(region, group_by, aggs) -> Optional[AggPlan]:
    """Device aggregation plan for (group_by, aggs) over ``region``, or
    None when the spec cannot ride the device exactly: multi-key
    grouping, non-dense/non-int group keys, unresident columns, float
    sums or any min/max under duplicate right matches (the prefix/range
    tricks are only exact where the host fusion's own rules say so).
    Declines route host — the exact fallback."""
    if len(group_by) != 1:
        return None
    g = group_by[0]
    gcol = region.l_cols.get(g)
    if gcol is None or gcol.slots is None:
        return None
    wants: Dict[Tuple[str, str], set] = {}
    for a in aggs:
        if a.fn not in _AGG_FNS:
            return None
        if a.column is None:
            continue
        if a.column in region.l_cols:
            side, pc = "l", region.l_cols[a.column]
        elif a.column in region.r_cols:
            side, pc = "r", region.r_cols[a.column]
        else:
            return None
        float_col = pc.enc != "int"
        if side == "r" and not region.uniq_right:
            if a.fn in ("min", "max"):
                return None
            if float_col and a.fn in ("sum", "avg"):
                return None
        need = wants.setdefault((side, a.column), set())
        if a.fn == "count":
            if float_col:
                need.add("nn")  # int count(col) == count(*): no NULLs
        elif a.fn == "sum":
            need.add("sum")
            if float_col:
                need.add("nn")  # SQL: all-NULL group sums to NULL
        elif a.fn == "avg":
            need.add("sum")
            if float_col:
                need.add("nn")
        else:
            need.add(a.fn)
            if float_col:
                need.add("nn")
    cols = tuple(
        _ColOps(
            name,
            side,
            (region.l_cols if side == "l" else region.r_cols)[name].enc,
            len((region.l_cols if side == "l" else region.r_cols)[name].arrays),
            tuple(sorted(ops)),
        )
        for (side, name), ops in sorted(wants.items())
    )
    return AggPlan(g, gcol.mn, gcol.span, region.uniq_right, cols)


def plan_device_arrays(region, plan: AggPlan) -> tuple:
    """The flat device plane tuple the jitted fn consumes, in plan.cols
    order (arity per column recorded in the plan)."""
    flat = []
    for c in plan.cols:
        pc = (region.l_cols if c.side == "l" else region.r_cols)[c.name]
        flat.extend(pc.arrays)
    return tuple(flat)


# ---------------------------------------------------------------------------
# device fns
# ---------------------------------------------------------------------------


def _core_agg(jnp, jax, specs, span, uniq_right, l_codes, r_codes, slots, flat):
    """The fused sorted-intersection + segment-aggregate body, shared by
    the single-chip jit and the mesh shard_fn (which adds collectives).
    Returns (outputs, kinds): kinds[i] in {'sum','min','max'} names the
    collective each partial needs under a mesh."""
    lo = jnp.searchsorted(r_codes, l_codes, side="left")
    hi = jnp.searchsorted(r_codes, l_codes, side="right")
    counts = (hi - lo).astype(jnp.int64)

    def seg_sum(x):
        return jax.ops.segment_sum(x, slots, num_segments=span)

    outs = [seg_sum(counts)]
    kinds = ["sum"]
    hit = counts > 0
    pos = jnp.where(hit, lo, 0)
    i = 0
    for side, enc, arity, ops in specs:
        if enc == "f64bits":
            word = (flat[i].astype(jnp.int64) << 32) | (
                flat[i + 1].astype(jnp.int64) & jnp.int64(0xFFFFFFFF)
            )
            v = jax.lax.bitcast_convert_type(word, jnp.float64)
            valid = ~jnp.isnan(v)
        elif enc == "f32bits":
            v = jax.lax.bitcast_convert_type(flat[i], jnp.float32).astype(
                jnp.float64
            )
            valid = ~jnp.isnan(v)
        else:
            v = flat[i].astype(jnp.int64)
            valid = None
        i += arity
        zero = jnp.zeros((), v.dtype)
        if side == "l":
            v0 = v if valid is None else jnp.where(valid, v, zero)
            per_sum = v0 * counts
            per_nn = (
                counts if valid is None else jnp.where(valid, counts, 0)
            )
            contrib = hit if valid is None else (hit & valid)
            vals = v
        elif uniq_right:
            vv = v[pos]
            ok = hit if valid is None else (hit & valid[pos])
            per_sum = jnp.where(ok, vv, zero)
            per_nn = ok.astype(jnp.int64)
            contrib = ok
            vals = vv
        else:
            # duplicate right matches: prefix differences over the code
            # runs. Value sums are int-only (the plan declines float
            # sum/avg/min/max here — the float prefix trick loses
            # precision int64 never does); int64 wraparound cancels
            # exactly like the host fusion's. count(float) DOES ride:
            # NaN (NULL) rows are excluded via an exact int64 prefix of
            # the validity mask, matching host NULL semantics.
            if "sum" in ops:
                cum = jnp.concatenate(
                    [jnp.zeros((1,), jnp.int64), jnp.cumsum(v)]
                )
                per_sum = cum[hi] - cum[lo]
            else:
                per_sum = None
            if valid is None:
                per_nn = counts
            else:
                ncum = jnp.concatenate(
                    [
                        jnp.zeros((1,), jnp.int64),
                        jnp.cumsum(valid.astype(jnp.int64)),
                    ]
                )
                per_nn = ncum[hi] - ncum[lo]
            contrib = None
            vals = None
        for op in ops:
            if op == "sum":
                outs.append(seg_sum(per_sum))
                kinds.append("sum")
            elif op == "nn":
                outs.append(seg_sum(per_nn))
                kinds.append("sum")
            elif op == "min":
                big = (
                    jnp.asarray(jnp.inf, vals.dtype)
                    if vals.dtype == jnp.float64
                    else jnp.asarray(jnp.iinfo(jnp.int64).max, vals.dtype)
                )
                outs.append(
                    jax.ops.segment_min(
                        jnp.where(contrib, vals, big),
                        slots,
                        num_segments=span,
                    )
                )
                kinds.append("min")
            else:  # max
                small = (
                    jnp.asarray(-jnp.inf, vals.dtype)
                    if vals.dtype == jnp.float64
                    else jnp.asarray(jnp.iinfo(jnp.int64).min, vals.dtype)
                )
                outs.append(
                    jax.ops.segment_max(
                        jnp.where(contrib, vals, small),
                        slots,
                        num_segments=span,
                    )
                )
                kinds.append("max")
    return outs, kinds


def _fn_cache():
    from .hbm_cache import BoundedFnCache

    global _FNS_MEMO
    if _FNS_MEMO is None:
        _FNS_MEMO = BoundedFnCache(64)
    return _FNS_MEMO


_FNS_MEMO = None
_RANGES_FN = None


def ranges_fn():
    """Jitted (l_codes, r_codes) -> (lo, counts) int32 — the match-range
    dispatch of the materializing resident join. Shape-polymorphic (jax
    retraces per region shape); literal-free."""
    global _RANGES_FN
    if _RANGES_FN is None:
        import jax
        import jax.numpy as jnp

        def resident_join_ranges(l_codes, r_codes):
            lo = jnp.searchsorted(r_codes, l_codes, side="left")
            hi = jnp.searchsorted(r_codes, l_codes, side="right")
            return lo.astype(jnp.int32), (hi - lo).astype(jnp.int32)

        _RANGES_FN = jax.jit(resident_join_ranges)
    return _RANGES_FN


def ranges_fn_packed(r_pack):
    """The FoR-delta twin of ranges_fn: (l_codes, r_words, r_refs) ->
    (lo, counts), the decode fused ahead of the searchsorted in the SAME
    executable — decompression never round-trips to host. Memoized per
    PackSpec (the decode structure) in the shared bounded cache."""
    key = ("jranges-for", r_pack)
    memo = _fn_cache()
    fn = memo.get(key)
    if fn is not None:
        return fn

    import jax
    import jax.numpy as jnp

    from ..ops.bitpack import unpack_for_jnp

    def resident_join_ranges(l_codes, r_words, r_refs):
        r_codes = unpack_for_jnp(r_words, r_refs, r_pack)
        lo = jnp.searchsorted(r_codes, l_codes, side="left")
        hi = jnp.searchsorted(r_codes, l_codes, side="right")
        return lo.astype(jnp.int32), (hi - lo).astype(jnp.int32)

    fn = jax.jit(resident_join_ranges)
    memo.put(key, fn)
    return fn


def join_agg_fn(plan: AggPlan, n_l: int, n_r: int, r_pack=None):
    """Jitted fused join-aggregate for the single-chip cache (profile
    name ``jit_resident_join_agg``), memoized on the plan STRUCTURE +
    shapes (hbm_cache compile-cache discipline).
    With ``r_pack`` set the signature grows a refs operand and the FoR
    decode fuses ahead of the sorted-intersection (ranges_fn_packed
    rationale)."""
    key = ("jagg1", plan.signature(), n_l, n_r, r_pack)
    memo = _fn_cache()
    fn = memo.get(key)
    if fn is not None:
        return fn

    import jax
    import jax.numpy as jnp

    specs = [(c.side, c.enc, c.arity, c.ops) for c in plan.cols]
    span, uniq = plan.span, plan.uniq_right

    if r_pack is not None:
        from ..ops.bitpack import unpack_for_jnp

        def resident_join_agg(l_codes, r_words, r_refs, slots, flat):
            r_codes = unpack_for_jnp(r_words, r_refs, r_pack)
            outs, _ = _core_agg(
                jnp, jax, specs, span, uniq, l_codes, r_codes, slots, flat
            )
            return tuple(outs)

    else:

        def resident_join_agg(l_codes, r_codes, slots, flat):
            outs, _ = _core_agg(
                jnp, jax, specs, span, uniq, l_codes, r_codes, slots, flat
            )
            return tuple(outs)

    fn = jax.jit(resident_join_agg)
    memo.put(key, fn)
    return fn


def mesh_join_agg_fn(mesh, plan: AggPlan, cap_l: int, cap_r: int):
    """Jitted shard_map fused join-aggregate (profile name
    ``jit_mesh_join_agg``): per-device partials over the full slot space, then psum/pmin/pmax into ONE replicated group
    table — the two-phase distributed aggregate with zero shuffles."""
    key = ("jaggM", mesh, plan.signature(), cap_l, cap_r)
    memo = _fn_cache()
    fn = memo.get(key)
    if fn is not None:
        return fn

    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec

    from jax import shard_map

    specs = [(c.side, c.enc, c.arity, c.ops) for c in plan.cols]
    span, uniq = plan.span, plan.uniq_right
    axis = mesh.axis_names[0]
    n_flat = sum(c.arity for c in plan.cols)

    def shard_fn(l_codes, r_codes, slots, flat):
        outs, kinds = _core_agg(
            jnp,
            jax,
            specs,
            span,
            uniq,
            l_codes.reshape(-1),
            r_codes.reshape(-1),
            slots.reshape(-1),
            tuple(a.reshape(-1) for a in flat),
        )
        merged = []
        for o, kind in zip(outs, kinds):
            if kind == "sum":
                merged.append(jax.lax.psum(o, axis))
            elif kind == "min":
                merged.append(jax.lax.pmin(o, axis))
            else:
                merged.append(jax.lax.pmax(o, axis))
        return tuple(merged)

    p_dev = PartitionSpec(axis, None)
    in_specs = (p_dev, p_dev, p_dev, tuple(p_dev for _ in range(n_flat)))
    n_out = 1 + sum(len(c.ops) for c in plan.cols)
    out_specs = tuple(PartitionSpec() for _ in range(n_out))
    sharded = shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        check_vma=False,
    )

    def mesh_join_agg(*args):
        return sharded(*args)

    fn = jax.jit(mesh_join_agg)
    memo.put(key, fn)
    return fn


# ---------------------------------------------------------------------------
# host finish — identical construction to aggregate._join_ranges_native
# ---------------------------------------------------------------------------


def finish_join_agg(region, plan: AggPlan, group_by, aggs, outs) -> ColumnarBatch:
    """Assemble the group table from the D2H'd span-sized vectors. Groups
    with zero joined rows do not appear (inner-join semantics); output
    order is ascending group key, the same as the host native fusion."""
    from ..plan.aggregates import output_dtype

    rows = outs[0]
    idx = 1
    per_col: Dict[str, tuple] = {}
    for c in plan.cols:
        got = {}
        for op in c.ops:
            got[op] = outs[idx]
            idx += 1
        per_col[c.name] = (c, got)
    keep = np.flatnonzero(rows > 0)
    rows_kept = rows[keep].astype(np.int64)
    g = group_by[0]
    gmeta = region.l_cols[g]
    out: Dict[str, Column] = {
        g: Column(
            gmeta.dtype_str,
            (keep + plan.mn).astype(numpy_dtype(gmeta.dtype_str)),
        )
    }
    for a in aggs:
        if a.column is None:
            out[a.name] = Column("int64", rows_kept)
            continue
        c, got = per_col[a.column]
        float_col = c.enc != "int"
        pc = (region.l_cols if c.side == "l" else region.r_cols)[c.name]
        dt = output_dtype(a, pc.dtype_str)
        nn = got.get("nn")
        nn_k = nn[keep].astype(np.int64) if nn is not None else rows_kept
        if a.fn == "count":
            out[a.name] = Column("int64", nn_k)
        elif a.fn == "avg":
            with np.errstate(invalid="ignore", divide="ignore"):
                out[a.name] = Column(
                    "float64", got["sum"][keep].astype(np.float64) / nn_k
                )
        elif a.fn == "sum":
            s = got["sum"][keep].astype(numpy_dtype(dt))
            if dt.startswith("float"):
                # SQL NULL: sum of an all-NULL group is NULL
                s = np.where(nn_k == 0, np.nan, s)
            out[a.name] = Column(dt, s)
        else:  # min / max
            vals = got[a.fn][keep]
            if float_col:
                vals = np.where(nn_k == 0, np.nan, vals)
            out[a.name] = Column(dt, vals.astype(numpy_dtype(dt)))
    return ColumnarBatch(out)


# ---------------------------------------------------------------------------
# the filtered join-aggregate top-k (the mesh arm of ORDER BY ... LIMIT)
# ---------------------------------------------------------------------------
# TPC-H Q3's shape: Limit(Sort(Aggregate(Join(Join(O, L), dim)))) over a
# bucketed pair of covering indexes (O's join key unique, L the many
# side), a dimension side joined to a foreign key of the pair, a filter on
# every side, sums over L per join key, ORDER BY the first sum and LIMIT
# n. Every group is one O row, which lives whole on the chip that owns its
# bucket (b % D), so each chip ranks its own groups and only its best
# candidates leave it. The join's structure is static between index
# versions: the region holds each O row's range of L rows, and a query
# computes only its masks, sums and top-k.

TOPK_SLACK = 8  # candidates a chip ships beyond the limit (ties on device)
_MAX_SCALE = 9  # decimal places a DECIMAL column may carry exactly
_SUM_BOUND = 1 << 62


@dataclass(frozen=True)
class TopkShape:
    """What a ``Limit(Sort(Aggregate(...)))`` plan asks of the arm."""

    l_node: object  # IndexScan of the many side
    l_pred: object  # Expr or None
    o_node: object  # IndexScan of the side whose join key is unique
    o_pred: object
    l_key: str
    o_key: str
    dim_scan: object  # Scan of the dimension side, or None
    dim_pred: object
    dim_key: Optional[str]
    fk: Optional[str]  # the pair's column the dimension key joins
    fk_side: str  # "l" | "o"
    group_by: tuple
    key_group: str  # the group column that is the join key
    aggs: tuple
    sort_keys: tuple
    ascending: tuple
    n: int

    def columns(self, side: str) -> tuple:
        """The columns the region holds for ``side`` ("l", "o", "d")."""
        if side == "d":
            need = [self.dim_key, *sorted(self.dim_pred.columns() if self.dim_pred else ())]
        elif side == "l":
            need = sorted(self.l_pred.columns()) if self.l_pred else []
            need += [c for a in self.aggs for c in a.input_columns()]
        else:
            need = sorted(self.o_pred.columns()) if self.o_pred else []
            need += [g for g in self.group_by if g != self.key_group]
        if self.fk is not None and side == self.fk_side:
            need.append(self.fk)
        return tuple(dict.fromkeys(need))

    def region_key(self) -> tuple:
        dim = ()
        if self.dim_scan is not None:
            dim = tuple((f.name, f.size, f.modified_time) for f in self.dim_scan.relation.files)
        # a join region's key (so refresh invalidation and the registry
        # read it alike) and what the arm holds besides
        return join_region_key(
            self.l_node.entry.content.files(), self.o_node.entry.content.files(),
            [self.l_key], [self.o_key],
        ) + (("topk", dim, self.dim_key, self.fk, self.fk_side,
              self.columns("l"), self.columns("o"), self.columns("d") if self.dim_scan else ()),)


def _filtered_side(plan):
    """(leaf, predicate) of ``[Project|Filter]* leaf``, the filters
    conjoined; the leaf an IndexScan or Scan."""
    from ..plan.expr import And

    pred, node = None, plan
    while isinstance(node, (Project, Filter)):
        if isinstance(node, Filter):
            pred = node.condition if pred is None else And(pred, node.condition)
        node = node.child
    return node, pred


def match_join_topk(limit):
    """(TopkShape, "") when ``limit`` is the arm's shape, else (None,
    why): why is "" for a plan of another shape, else the reason a
    Limit(Sort(Aggregate(Join))) plan cannot ride (counted by the
    caller)."""
    from ..plan.ir import Aggregate, IndexScan as _IS, Join as _J, Scan, Sort
    from ..plan.rules.join_rule import align_condition_sides, extract_equi_condition

    sort = limit.child
    if not isinstance(sort, Sort) or not isinstance(sort.child, Aggregate):
        return None, ""
    agg = sort.child
    outer = agg.child
    while isinstance(outer, Project):
        outer = outer.child
    if not isinstance(outer, _J):
        return None, ""
    if outer.join_type != "inner":
        return None, "shape"

    def strip(p):
        while isinstance(p, Project):
            p = p.child
        return p

    pair, dim, dim_pairs = outer, None, None
    if isinstance(strip(outer.left), _J) or isinstance(strip(outer.right), _J):
        left_pair = isinstance(strip(outer.left), _J)
        pair_side, dim = (outer.left, outer.right) if left_pair else (outer.right, outer.left)
        pair = strip(pair_side)
        if isinstance(strip(dim), _J) or pair.join_type != "inner":
            return None, "shape"
        dim_pairs = extract_equi_condition(outer.condition)
        dim_pairs = dim_pairs and align_condition_sides(
            dim_pairs, dim.output_columns(), pair_side.output_columns())
        if not dim_pairs or len(dim_pairs) != 1:
            return None, "condition"
    keys = extract_equi_condition(pair.condition)
    keys = keys and align_condition_sides(keys, pair.left.output_columns(), pair.right.output_columns())
    if not keys or len(keys) != 1:
        return None, "condition"
    (xs, xp), (ys, yp) = _filtered_side(pair.left), _filtered_side(pair.right)
    if not all(isinstance(s, _IS) and s.use_bucket_spec for s in (xs, ys)):
        return None, "shape"
    if xs.entry.num_buckets != ys.entry.num_buckets:
        return None, "buckets"
    xk, yk = keys[0]
    if [c.lower() for c in xs.entry.indexed_columns] != [xk.lower()] or [
        c.lower() for c in ys.entry.indexed_columns
    ] != [yk.lower()]:
        return None, "keys"
    d_scan, d_pred = (None, None) if dim is None else _filtered_side(dim)
    if dim is not None and not isinstance(d_scan, Scan):
        return None, "shape"
    # roles: the sums read L; the other group columns are O's
    group_by = tuple(agg.group_by)
    key_group = next((g for g in group_by if g.lower() in (xk.lower(), yk.lower())), None)
    if key_group is None:
        return None, "group"
    if any(a.fn not in ("sum", "count") or (a.fn == "count" and a.input_columns())
           for a in agg.aggs):
        return None, "agg"
    agg_cols = {c.lower() for a in agg.aggs for c in a.input_columns()}
    other = {g.lower() for g in group_by if g != key_group}
    def cols(scan):
        return {c.lower() for c in scan.required_columns}

    roles = None
    for (ls, lp, lk), (os_, op, ok) in (((xs, xp, xk), (ys, yp, yk)), ((ys, yp, yk), (xs, xp, xk))):
        if agg_cols <= cols(ls) and other <= cols(os_):
            roles = (ls, lp, lk, os_, op, ok)
            break
    if roles is None:
        return None, "group"
    ls, lp, lk, os_, op, ok = roles
    fk, fk_side, dim_key = None, "o", None
    if dim_pairs:
        dim_key, fk = dim_pairs[0]
        fk_side = "o" if fk.lower() in cols(os_) else "l"
    if sort.keys[0] not in [a.name for a in agg.aggs]:
        return None, "order"
    if limit.n < 1:
        return None, "limit"
    return TopkShape(
        ls, lp, os_, op, lk, ok, d_scan, d_pred, dim_key, fk, fk_side, group_by, key_group,
        tuple(agg.aggs), tuple(sort.keys), tuple(sort.ascending), int(limit.n),
    ), ""


@dataclass
class TopkColumn:
    """One resident column of a top-k region: ``array`` is int32 where
    the values fit, else int64; a DECIMAL (float64) column is its exact
    value times 10**scale as int64, verified when the region is built.
    ``bound`` is the largest magnitude of those integers."""

    array: object
    dtype_str: str
    scale: int
    bound: int


@dataclass
class MeshTopkRegion:
    """The top-k arm's resident state on the mesh (join-region registry:
    shared budget, eviction, invalidation). Per chip d, over the buckets
    it owns: L's columns in join-key order, each O row's range [lo, hi)
    of L rows, O's columns; the dimension side on every chip."""

    key: tuple
    mesh: object
    n_devices: int
    cap_l: int
    cap_o: int
    l_cols: Dict[str, TopkColumn]
    r_cols: Dict[str, TopkColumn]  # O's, the join key as well (a join region's right side)
    lo: object
    hi: object
    max_fanout: int  # most L rows one O row matches
    dim_mode: str  # "dense" | "sorted" | "none"
    dim_index: object  # dense: row of key - mn (-1 absent); sorted: the keys; none: 1 slot
    dim_cols: Dict[str, TopkColumn]
    dim_vocab: Dict[str, object]  # host vocab of each string dimension column
    dim_min: int
    dim_rows: int
    dim_cap: int  # the dimension columns' padded length
    n_l: int = 0
    n_r: int = 0
    nbytes: int = 0
    last_used: float = field(default_factory=time.monotonic)


def _decimal_scale(values: np.ndarray) -> Optional[int]:
    """The fewest decimal places that hold every float64 exactly: values
    equal to round(v * 10**s) / 10**s; None for NaN (NULL), infinities or
    more than _MAX_SCALE places."""
    if len(values) and not np.isfinite(values).all():
        return None
    for s in range(_MAX_SCALE + 1):
        scaled = np.round(values * 10.0**s)
        if np.abs(scaled).max(initial=0) >= 2**53:
            return None
        if np.array_equal(scaled / 10.0**s, values):
            return s
    return None


def _topk_encode(col: Column, decimal: bool):
    """(host int array, scale, bound) of one column, or None (why)."""
    a = col.data
    if is_string(col.dtype_str):
        return None
    if a.dtype.kind == "f":
        if not decimal:
            return None
        s = _decimal_scale(a.astype(np.float64))
        if s is None:
            return None
        ints = np.round(a.astype(np.float64) * 10.0**s).astype(np.int64)
    elif a.dtype.kind in "iub":
        s, ints = 0, a.astype(np.int64)
    else:
        return None
    bound = int(np.abs(ints).max(initial=0))
    if bound <= I32_MAX:
        ints = ints.astype(np.int32)
    return ints, s, bound


def build_topk_region(cache, shape: TopkShape, l_groups, o_groups, dim_batch, mesh):
    """(region, why) — why names a refusal: "not_unique" (O's join key or
    the dimension key repeats), "encoding" (a column cannot ride exactly),
    "budget", "device"; "" with a region."""
    from jax.sharding import NamedSharding, PartitionSpec

    import jax

    from ..parallel.mesh import owner_of_bucket
    from ..utils.deviceprobe import first_device_touch_ok
    from ..utils.intmath import next_pow2
    from .hbm_cache import _budget_bytes, residency_mode

    pfx = cache._metric_prefix
    if not first_device_touch_ok(required=residency_mode() == "force"):
        metrics.incr(f"{pfx}.device_unreachable")
        return None, "device"
    t0 = time.perf_counter()
    D = int(mesh.devices.size)

    def per_device(groups, names):
        out = []
        for d in range(D):
            parts = [groups[b].select(list(names)) for b in sorted(groups)
                     if owner_of_bucket(int(b), D) == d and groups[b].num_rows]
            out.append(ColumnarBatch.concat(parts) if parts else None)
        return out

    l_names = list(dict.fromkeys([shape.l_key, *shape.columns("l")]))
    o_names = list(dict.fromkeys([shape.o_key, *shape.columns("o")]))
    l_dev, o_dev = per_device(l_groups, l_names), per_device(o_groups, o_names)
    l_rows = [0 if b is None else b.num_rows for b in l_dev]
    o_rows = [0 if b is None else b.num_rows for b in o_dev]
    k = shape.n + TOPK_SLACK
    cap_l = next_pow2(max(max(l_rows), 1))
    cap_o = next_pow2(max(max(o_rows), k, 1))
    decimals = {c for a in shape.aggs for c in a.input_columns()}

    lo = np.zeros((D, cap_o), np.int32)
    hi = np.zeros((D, cap_o), np.int32)
    # per chip: each side's rows in join-key order, and each O row's
    # range of L rows
    l_sorted, o_sorted = [], []
    fanout = 0
    for d in range(D):
        lb, ob = l_dev[d], o_dev[d]
        lk = np.zeros(0, np.int64) if lb is None else lb.columns[shape.l_key].data.astype(np.int64)
        lorder = np.argsort(lk, kind="stable")
        l_sorted.append(None if lb is None else lb.take(lorder))
        if ob is None:
            o_sorted.append(None)
            continue
        ok = ob.columns[shape.o_key].data.astype(np.int64)
        oorder = np.argsort(ok, kind="stable")
        ok = ok[oorder]
        if len(ok) > 1 and not (np.diff(ok) > 0).all():
            return None, "not_unique"
        o_sorted.append(ob.take(oorder))
        lo[d, : len(ok)] = np.searchsorted(lk[lorder], ok, "left")
        hi[d, : len(ok)] = np.searchsorted(lk[lorder], ok, "right")
        fanout = max(fanout, int((hi[d] - lo[d]).max(initial=0)))

    def encode_side(batches, names, cap, decimal_cols):
        """{name: (D x cap host ints, dtype, scale, bound)} or None."""
        out = {}
        for name in names:
            per = []
            for b in batches:
                got = None if b is None else _topk_encode(b.columns[name], name in decimal_cols)
                if b is not None and got is None:
                    return None
                per.append(got)
            present = [g for g in per if g is not None]
            if not present:
                return None
            dtype_str = next(b for b in batches if b is not None).columns[name].dtype_str
            scale = max(g[1] for g in present)
            bound = max(g[2] * 10 ** (scale - g[1]) for g in present)
            packed = np.zeros((D, cap), np.int32 if bound <= I32_MAX else np.int64)
            for d, g in enumerate(per):
                if g is not None:
                    packed[d, : len(g[0])] = g[0].astype(np.int64) * 10 ** (scale - g[1])
            out[name] = (packed, dtype_str, scale, bound)
        return out

    l_enc = encode_side(l_sorted, shape.columns("l"), cap_l, decimals)
    o_enc = encode_side(o_sorted, o_names, cap_o, set())
    if l_enc is None or o_enc is None:
        return None, "encoding"

    # no dimension side: a one-slot placeholder operand
    dim_mode, dim_index, dim_min, dim_rows, d_enc, vocab = "none", np.zeros(1, np.int32), 0, 0, {}, {}
    dim_cap = 1
    if shape.dim_scan is not None:
        keys = dim_batch.columns[shape.dim_key]
        if is_string(keys.dtype_str) or keys.data.dtype.kind not in "iu":
            return None, "encoding"
        kv = keys.data.astype(np.int64)
        dim_rows = len(kv)
        if len(np.unique(kv)) != dim_rows:
            return None, "not_unique"
        order = np.argsort(kv, kind="stable")
        kv = kv[order]
        dim_cap = next_pow2(max(dim_rows, 1))
        # kv: the dimension's keys, read from its source files (host)
        dim_min = int(kv.min(initial=0)) if dim_rows else 0
        span = int(kv.max(initial=0)) - dim_min + 1 if dim_rows else 1
        if span <= 4 * dim_rows + 1024:
            dim_mode = "dense"
            dim_index = np.full(next_pow2(span), -1, np.int32)
            dim_index[kv - dim_min] = np.arange(dim_rows, dtype=np.int32)
        else:
            dim_mode = "sorted"
            dim_index = np.full(dim_cap, np.iinfo(np.int64).max, np.int64)
            dim_index[:dim_rows] = kv
        for name in shape.columns("d"):
            if name == shape.dim_key:
                continue
            col = dim_batch.columns[name]
            data = col.data[order]
            if is_string(col.dtype_str):
                vocab[name] = col.vocab
                ints, scale, bound = data.astype(np.int32), 0, int(np.abs(data).max(initial=0))
            else:
                got = _topk_encode(Column(col.dtype_str, data), False)
                if got is None:
                    return None, "encoding"
                ints, scale, bound = got
            packed = np.zeros(dim_cap, ints.dtype)
            packed[:dim_rows] = ints
            d_enc[name] = (packed, col.dtype_str, scale, bound)

    nbytes = int(lo.nbytes + hi.nbytes) + sum(
        int(v[0].nbytes) for enc in (l_enc, o_enc, d_enc) for v in enc.values()
    ) + int(dim_index.nbytes) * D
    nbytes += sum(int(v[0].nbytes) * (D - 1) for v in d_enc.values())
    with cache._lock:
        headroom = _budget_bytes() - sum(t.nbytes for t in cache._tables)
    if nbytes > headroom:
        metrics.incr(f"{pfx}.join.over_budget_refused")
        return None, "budget"
    sharded = NamedSharding(mesh, PartitionSpec(mesh.axis_names[0], None))
    replicated = NamedSharding(mesh, PartitionSpec())
    try:
        def put(enc, sharding):
            return {n: TopkColumn(jax.device_put(a, sharding), dt, s, b)
                    for n, (a, dt, s, b) in enc.items()}

        l_cols, o_cols, dim_cols = put(l_enc, sharded), put(o_enc, sharded), put(d_enc, replicated)
        dev_lo, dev_hi = jax.device_put(lo, sharded), jax.device_put(hi, sharded)
        dev_index = jax.device_put(dim_index, replicated)
        from ..ops import fence_chain

        fence_chain([c.array for side in (l_cols, o_cols, dim_cols) for c in side.values()]
                    + [dev_lo, dev_hi, dev_index])
    except Exception:  # noqa: BLE001 - device loss: no residency
        metrics.incr(f"{pfx}.join.transfer_error")
        return None, "device"
    metrics.incr(f"{pfx}.join.h2d_bytes", nbytes)
    _trace_bytes("h2d_bytes", nbytes)
    metrics.record_time(f"{pfx}.join_topk.prefetch", time.perf_counter() - t0)
    return MeshTopkRegion(
        shape.region_key(), mesh, D, cap_l, cap_o, l_cols, o_cols, dev_lo, dev_hi, fanout,
        dim_mode, dev_index, dim_cols, vocab, dim_min, dim_rows, dim_cap,
        n_l=sum(l_rows), n_r=sum(o_rows), nbytes=nbytes,
    ), ""


def _lit_scale(v) -> Optional[tuple]:
    """(integer, scale) of a numeric literal held exactly in decimal."""
    if isinstance(v, (bool, np.bool_)):
        return None
    if isinstance(v, (int, np.integer)):
        return int(v), 0
    if isinstance(v, (float, np.floating)):
        s = _decimal_scale(np.array([float(v)]))
        return None if s is None else (int(round(float(v) * 10**s)), s)
    return None


def fixed_point(expr, cols: Dict[str, TopkColumn]):
    """(structure, scale, bound) of a sum's input evaluated exactly in
    scaled int64 over the region's columns, or None: ``/``, a literal
    that is no exact decimal, or an intermediate that could pass 2**62.
    ``structure`` is a nested tuple the device body evaluates."""
    from ..plan.expr import Arith, Col, Lit

    if isinstance(expr, str):
        expr = Col(expr)
    if isinstance(expr, Col):
        c = cols.get(expr.name)
        return None if c is None else (("col", expr.name), c.scale, c.bound)
    if isinstance(expr, Lit):
        got = _lit_scale(expr.value)
        return None if got is None else (("lit", got[0]), got[1], abs(got[0]))
    if not isinstance(expr, Arith) or expr.op == "div":
        return None
    a, b = fixed_point(expr.left, cols), fixed_point(expr.right, cols)
    if a is None or b is None:
        return None
    if expr.op == "mul":
        out = (("mul", a[0], b[0]), a[1] + b[1], a[2] * b[2])
    else:
        s = max(a[1], b[1])
        fa, fb = 10 ** (s - a[1]), 10 ** (s - b[1])
        out = ((expr.op, ("mul", a[0], ("lit", fa)), ("mul", b[0], ("lit", fb))), s,
               a[2] * fa + b[2] * fb)
    return out if out[2] < _SUM_BOUND else None


def _eval_fixed(jnp, structure, arrays):
    op = structure[0]
    if op == "col":
        return arrays[structure[1]].astype(jnp.int64)
    if op == "lit":
        return jnp.int64(structure[1])
    a = _eval_fixed(jnp, structure[1], arrays)
    b = _eval_fixed(jnp, structure[2], arrays)
    return a * b if op == "mul" else (a + b if op == "add" else a - b)


@dataclass(frozen=True)
class TopkPlan:
    """The static half of one top-k dispatch: the compile-cache key."""

    caps: tuple  # (D, cap_l, cap_o, dim_mode, dim_cap)
    l_cols: tuple  # names of the L operands, in order
    o_cols: tuple
    d_cols: tuple
    l_pred: Optional[str]  # narrowed predicate structures
    o_pred: Optional[str]
    d_pred: Optional[str]
    sums: tuple  # per aggregate: fixed-point structure, or None for count(*)
    rank: int  # the aggregate the first sort key names
    descending: bool
    fk: Optional[str]
    fk_side: str
    dim_min: int
    out_cols: tuple  # O columns shipped with each candidate
    k: int
    n: int


def _bind_predicate(pred, cols: Dict[str, TopkColumn], vocab=None):
    """(narrowed expr, structure, literals) of a side's filter over the
    region's columns, literals integers, or None when it cannot ride:
    a float column, a literal that is no integer, or an unknown node."""
    from ..plan.expr import bind_string_literals
    from .hbm_cache import _expr_structure

    if pred is None:
        return None, None, []
    if any(c not in cols or cols[c].scale for c in pred.columns()):
        return None
    if vocab:
        shim = ColumnarBatch({n: Column("string", np.empty(0, np.int32), v) for n, v in vocab.items()})
        try:
            pred = bind_string_literals(pred, shim)
        except HyperspaceException:  # unbindable shape: host
            return None
    if any(cols[c].dtype_str in ("float32", "float64") for c in pred.columns()):
        return None
    lits: list = []
    _literals(pred, lits)
    if not all(isinstance(v, (int, np.integer)) and not isinstance(v, bool) for v in lits):
        return None
    try:
        structure = _expr_structure(pred)
    except TypeError:  # a node the traced evaluator does not take
        return None
    return pred, structure, [int(v) for v in lits]


def _literals(e, out: list) -> None:
    """A narrowed predicate's literal values, in the order
    hbm_cache._eval_with_literals consumes them."""
    from ..plan.expr import And, Cmp, Lit, Not, Or

    if isinstance(e, (And, Or)):
        _literals(e.left, out)
        _literals(e.right, out)
    elif isinstance(e, Not):
        _literals(e.child, out)
    elif isinstance(e, Cmp):
        out.extend(s.value for s in (e.left, e.right) if isinstance(s, Lit))


def plan_topk(region: MeshTopkRegion, shape: TopkShape):
    """(TopkPlan, narrowed predicates, literal vectors) or (None, why)."""
    preds, lits = [], []
    for side, pred, cols, vocab in (
        ("l", shape.l_pred, region.l_cols, None),
        ("o", shape.o_pred, region.r_cols, None),
        ("d", shape.dim_pred, region.dim_cols, region.dim_vocab),
    ):
        got = _bind_predicate(pred, cols, vocab)
        if got is None:
            return None, "predicate"
        preds.append(got)
        lits.append(np.array(got[2] or [0], dtype=np.int64))
    sums = []
    for a in shape.aggs:
        if a.fn == "count":
            sums.append(None)
            continue
        got = fixed_point(a.expr if a.expr is not None else a.column, region.l_cols)
        if got is None or got[2] * max(region.max_fanout, 1) >= _SUM_BOUND:
            return None, "expression"
        sums.append(got)
    rank = [a.name for a in shape.aggs].index(shape.sort_keys[0])
    out_cols = tuple([shape.o_key] + [g for g in shape.group_by if g != shape.key_group])
    if shape.n + TOPK_SLACK > region.cap_o:
        return None, "limit"
    plan = TopkPlan(
        (region.n_devices, region.cap_l, region.cap_o, region.dim_mode, region.dim_cap),
        tuple(sorted(shape.columns("l"))), tuple(sorted(region.r_cols)),
        tuple(sorted(region.dim_cols)),
        preds[0][1], preds[1][1], preds[2][1],
        tuple(None if s is None else (s[0], s[1]) for s in sums),
        rank, not shape.ascending[0], shape.fk, shape.fk_side, region.dim_min,
        out_cols, shape.n + TOPK_SLACK, shape.n,
    )
    return (plan, tuple(p[0] for p in preds), tuple(lits)), ""


def mesh_topk_fn(mesh, plan: TopkPlan, preds):
    """The jitted top-k program (profile name ``jit_mesh_join_topk``):
    per chip, the three masks, each sum per O row as an exact int64
    prefix difference over its L rows, and the chip's best ``k`` O rows
    by the ranked sum; returns (D, k) candidates and a (D,) flag that a
    chip's ties at its n-th value may not all be among them. Literals
    are traced operands, so one compile serves every draw."""
    key = ("jtopk", mesh, plan)
    memo = _fn_cache()
    fn = memo.get(key)
    if fn is not None:
        return fn

    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec

    from .hbm_cache import _eval_with_literals

    axis = mesh.axis_names[0]
    l_pred, o_pred, d_pred = preds
    D, cap_l, cap_o, dim_mode, dim_cap = plan.caps
    k, n = plan.k, plan.n

    def mask(pred, arrays, lits, size):
        if pred is None:
            return jnp.ones((size,), bool)
        return _eval_with_literals(pred, arrays, lits, [0])

    def shard_fn(l_arrays, o_arrays, lo, hi, d_index, d_arrays, l_lits, o_lits, d_lits):
        la = {c: a.reshape(-1) for c, a in zip(plan.l_cols, l_arrays)}
        oa = {c: a.reshape(-1) for c, a in zip(plan.o_cols, o_arrays)}
        da = dict(zip(plan.d_cols, d_arrays))
        lm = mask(l_pred, la, l_lits, cap_l)
        om = mask(o_pred, oa, o_lits, cap_o)
        if dim_mode != "none":
            fk = (la if plan.fk_side == "l" else oa)[plan.fk].astype(jnp.int64)
            dm = mask(d_pred, da, d_lits, dim_cap)
            if dim_mode == "dense":
                at = fk - plan.dim_min
                inside = (at >= 0) & (at < d_index.shape[0])
                row = d_index[jnp.clip(at, 0, d_index.shape[0] - 1)]
                member = inside & (row >= 0) & dm[jnp.clip(row, 0, dim_cap - 1)]
            else:
                pos = jnp.clip(jnp.searchsorted(d_index, fk), 0, dim_cap - 1)
                member = (d_index[pos] == fk) & dm[pos]
            if plan.fk_side == "l":
                lm = lm & member
            else:
                om = om & member
        lo_, hi_ = lo.reshape(-1), hi.reshape(-1)

        def range_sum(x):
            cum = jnp.concatenate([jnp.zeros((1,), x.dtype), jnp.cumsum(x)])
            return cum[hi_] - cum[lo_]

        count = range_sum(lm.astype(jnp.int32))
        sums = [count.astype(jnp.int64) if s is None else
                range_sum(jnp.where(lm, _eval_fixed(jnp, s[0], la), jnp.int64(0)))
                for s in plan.sums]
        valid = om & (count > 0)
        score = sums[plan.rank].astype(jnp.float32)
        score = jnp.where(valid, score if plan.descending else -score, -jnp.inf)
        best, at = jax.lax.top_k(score, k)
        tie = (best[n - 1] > -jnp.inf) & (best[k - 1] == best[n - 1]) if k < cap_o else jnp.bool_(False)
        outs = [valid[at]] + [s[at] for s in sums] + [oa[c][at] for c in plan.out_cols]
        return tuple(o.reshape(1, k) for o in outs) + (tie.reshape(1),)

    p_dev, rep = PartitionSpec(axis, None), PartitionSpec()
    n_out = 1 + len(plan.sums) + len(plan.out_cols)
    body = shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(
            tuple(p_dev for _ in plan.l_cols), tuple(p_dev for _ in plan.o_cols), p_dev, p_dev,
            rep, tuple(rep for _ in plan.d_cols), rep, rep, rep,
        ),
        out_specs=tuple(p_dev for _ in range(n_out)) + (PartitionSpec(axis),),
        check_vma=False,
    )

    def mesh_join_topk(*args):
        return body(*args)

    fn = jax.jit(mesh_join_topk)
    memo.put(key, fn)
    return fn


def finish_topk(region: MeshTopkRegion, shape: TopkShape, plan: TopkPlan, outs) -> Optional[ColumnarBatch]:
    """The answer from the chips' candidates: the valid ones as rows of
    the Aggregate's output, ordered by the Sort's keys on the host
    (exec.order), the first ``n``. None when a chip flagged ties past its
    candidates (the caller routes host)."""
    from ..plan.aggregates import input_dtype, output_dtype
    from .order import limit_batch, sort_batch

    # outs: host arrays, fetched once by the dispatch (mesh_cache.join_topk)
    if bool(outs[-1].any()):
        return None
    keep = np.flatnonzero(outs[0].reshape(-1))
    sums = [o.reshape(-1)[keep] for o in outs[1: 1 + len(plan.sums)]]
    ocols = {c: o.reshape(-1)[keep] for c, o in zip(plan.out_cols, outs[1 + len(plan.sums): -1])}
    schema = {**{c: region.r_cols[c].dtype_str for c in region.r_cols},
              **{c: region.l_cols[c].dtype_str for c in region.l_cols}}
    out: Dict[str, Column] = {}
    for g in shape.group_by:
        src = shape.o_key if g == shape.key_group else g
        dt = shape.o_node.entry.schema.get(g) or shape.l_node.entry.schema.get(g) or schema[src]
        out[g] = Column(dt, ocols[src].astype(numpy_dtype(dt)))
    for a, s, got in zip(shape.aggs, plan.sums, sums):
        dt = output_dtype(a, input_dtype(a, schema))
        if dt.startswith("float"):
            out[a.name] = Column(dt, got.astype(np.float64) / 10.0 ** s[1])
        else:
            out[a.name] = Column(dt, got.astype(np.int64))
    batch = ColumnarBatch(out)
    return limit_batch(sort_batch(batch, shape.sort_keys, shape.ascending), shape.n)
