"""Mesh-sharded HBM residency: the round-4 single-chip win carried to the
device mesh.

Round-4 verdict missing #1: the distributed query path re-shipped every
column host→device on every query (``exec/distributed.py`` ``device_put``
per call) — exactly the per-query-reshipping architecture the single-chip
resident cache (exec/hbm_cache.py) was built to kill. The reference gets
cross-query locality for free: Spark executors hold their partitions hot
in the OS page cache and ``BucketUnionExec.outputPartitioning`` preserves
placement across operators (BucketUnionExec.scala:104-121). Here the
equivalent is physical: index files are immutable, so an index version's
predicate columns upload ONCE into mesh-sharded HBM and every later
distributed query runs against the resident shards.

Layout: bucket b of the index lives on device ``owner_of_bucket(b, D) =
b % D`` — the SAME placement rule the sharded build writes with
(parallel.mesh), so residency preserves the build's partitioning and the
bucketed operators stay collective-free. Each device's shard is the
concatenation of its owned buckets' row segments (bucket-ascending, then
file-path order), padded to a static power-of-two capacity; columns ride
as int32 planes under the one narrowing contract (ops.kernels
narrow_arrays_to_i32 — int64 range-narrowed, float32 order-preserving,
strings as codes into one table-global sorted vocab that never uploads).

The resident query protocol is the single-chip one, vectorized over the
mesh: ONE shard_map call evaluates the predicate mask per device and
reduces it to per-block match counts; the only D2H is the (D, n_blocks)
int32 count matrix; the host then reads ONLY the matching blocks from
mmap, re-evaluates the predicate exactly there, and serves the output
columns locally — result bytes never cross the link, and repeat queries
pay ZERO per-query H2D (the ``dist.h2d_bytes`` counter that meters the
non-resident path stays flat).

Correctness does not rest on the device mask: the host re-evaluates every
candidate block exactly, and the narrowed encodings are order-preserving
(ops.kernels contracts), so device and host agree on which blocks can
contain matches. Pad rows (beyond a device's real rows) can only add
false-positive counts in tail blocks, which the host's segment mapping
clips away.

Env knobs are shared with the single-chip cache (HYPERSPACE_TPU_HBM,
.._BUDGET_MB, .._MIN_ROWS — hbm_cache module docstring): a session runs
either the single-device or the mesh engine, so the one budget bounds
whichever cache that session actually feeds.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..exceptions import HyperspaceException
from ..plan.expr import Expr, eval_mask
from ..storage import layout
from ..storage.columnar import Column, ColumnarBatch, is_string
from ..telemetry.metrics import metrics
from ..telemetry.trace import add_bytes as _trace_bytes
from ..telemetry.trace import span as _trace_span
from .hbm_cache import (
    BLOCK_ROWS,
    _MAX_FAILED_MEMO,
    _MAX_VOCAB,
    _auto_enabled,
    _budget_bytes,
    _encode_column,
    _file_identity,
    _hybrid_fns,
    _min_auto_rows,
    ResidentCacheBase,
    delta_snapshot_key,
)


@dataclass
class MeshResidentColumn:
    data: object  # jax.Array, (D, cap) int32, NamedSharding over the mesh
    dtype_str: str
    # 'int' | 'float32' (ordered-i32) | 'string' (global codes) |
    # 'f64' (two-plane ordered-i64: ``data`` = high plane, ``data2`` = low)
    enc: str
    nbytes: int
    vocab: Optional[np.ndarray] = None  # host-side global vocab (strings)
    data2: Optional[object] = None  # f64 low plane (ops.floatbits)
    # compressed tier (ops.bitpack.PackSpec over ONE device shard's cap
    # values — every shard shares the global frame, so one static spec
    # serves the whole mesh): ``data`` holds (D, cap // vpw) packed words
    pack: Optional[object] = None
    # int-encoded columns only: value-space bounds over the REAL rows
    # (mesh shards build no zone vectors, so the device scan-aggregate's
    # dense-key planner reads these — exec.scan_agg.column_value_bounds)
    vmin: Optional[int] = None
    vmax: Optional[int] = None


# one device's slice of one file: rows [file_lo, file_hi) of ``path`` live
# at device-local rows [dev_off, dev_off + (file_hi - file_lo))
Segment = Tuple[str, int, int, int]


@dataclass
class MeshDeltaRegion:
    """Appended-source residency for one mesh-sharded base table: the
    appended rows are hash-bucketized on the index's key columns and
    placed on their owner device (the build's ``b % D`` rule — the same
    placement a Repartition of the appended side would produce), so the
    fused base+delta dispatch stays collective-free. ``dev_idx`` maps
    each device-local row back to its row in the (host-held, decoded)
    appended batch for the exact host leg."""

    key: tuple  # appended snapshot ((name, size, mtime), ...) sorted
    base_key: tuple  # MeshResidentTable.key this delta extends
    deleted_ids: tuple
    mesh: object
    n_devices: int
    cap: int  # padded per-device delta rows (pow2)
    block: int  # delta count granularity (min(BLOCK_ROWS, cap))
    dev_rows: List[int]  # real delta rows per device
    dev_idx: List[np.ndarray]  # device-local row -> host_batch row
    columns: Dict[str, MeshResidentColumn]
    oov: Dict[str, np.ndarray]  # per string column: sorted OOV values
    host_batch: ColumnarBatch  # appended rows, user columns
    del_mask: Optional[object]  # (D, base cap) int32 device; 1 = deleted
    n_rows: int = 0
    nbytes: int = 0
    last_used: float = field(default_factory=time.monotonic)

    @property
    def n_blocks(self) -> int:
        return self.cap // self.block


@dataclass
class MeshResidentTable:
    key: tuple  # ((path, size, mtime_ns), ...) sorted by path
    mesh: object  # jax.sharding.Mesh the shards live on
    n_devices: int
    cap: int  # padded per-device rows (pow2, one static shape per table)
    block: int  # count granularity (min(BLOCK_ROWS, cap))
    dev_rows: List[int]  # real rows per device
    segments: List[List[Segment]]  # per device, dev_off-ascending
    columns: Dict[str, MeshResidentColumn]
    n_rows: int
    nbytes: int
    last_used: float = field(default_factory=time.monotonic)
    # tier ladder: "resident" or "compressed" — the streaming rung
    # registers its own table type (residency.streaming's mesh twin:
    # host-pinned shard matrices staged through a per-device slab pair);
    # hbm.mesh.residency.streaming_declined now counts only GENUINE
    # declines (the slab pair itself over budget)
    tier: str = "resident"
    raw_nbytes: int = 0

    @property
    def n_blocks(self) -> int:
        return self.cap // self.block


def _bucket_segments(paths: List[str]) -> Dict[int, List[Tuple[str, int, int]]]:
    """bucket -> [(path, file_row_lo, file_row_hi), ...] in path-sorted
    order, from per-bucket file names and run-file footers — the same
    bucket derivation the executor's group-by-bucket uses."""
    out: Dict[int, List[Tuple[str, int, int]]] = {}
    for p in paths:  # caller pre-sorts
        if layout.is_run_file(p):
            offs = layout.run_offsets_checked(p)
            for b in range(len(offs) - 1):
                s, e = int(offs[b]), int(offs[b + 1])
                if e > s:
                    out.setdefault(b, []).append((str(p), s, e))
        else:
            n = layout.cached_reader(p).num_rows
            if n:
                out.setdefault(layout.bucket_of_file(p), []).append(
                    (str(p), 0, n)
                )
    return out


# NOTE — no selectivity gate on the MESH resident path, deliberately.
# The single-chip gate (exec.scan) routes broad predicates to a host
# fallback that is genuinely cheaper there: an mmap scan with no device
# work at all. On a mesh session the fallback is the SHIP-per-query path
# (full column re-upload + the same dispatch + full-result compaction),
# which the resident path strictly dominates at every match density —
# the resident query's cost is one dispatch plus reads of matching
# blocks, a subset of the ship path's work. Zone vectors would gate
# nothing, so none are built.

_counts_fn_cache: dict = {}
_counts_fn_lock = threading.Lock()


def _mesh_counts_fn(mesh, bound_repr: str, bound: Expr, names: tuple,
                    cap: int, block: int, specs: Optional[tuple] = None):
    """Jitted shard_map: (dict of (D, cap) i32) -> (D, cap // block) i32
    per-block match counts, one device round trip for the whole mesh.
    ``specs`` (per-name PackSpec/None, hbm_cache._counts_fn contract)
    routes compressed shards through the fused in-shard decode."""
    if specs is None:
        specs = tuple(None for _ in names)
    key = (mesh, bound_repr, names, cap, block, specs)
    with _counts_fn_lock:
        fn = _counts_fn_cache.get(key)
        if fn is not None:
            return fn

    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec

    from jax import shard_map
    from .hbm_cache import _flatten_operands

    shim = ColumnarBatch(
        {name: Column("int32", np.empty(0, dtype=np.int32)) for name in names}
    )
    axis = mesh.axis_names[0]

    def shard_fn(arrays):
        flat = _flatten_operands(
            names, [arrays[n] for n in names], specs
        )
        m = eval_mask(bound, shim, flat)
        return jnp.sum(
            m.reshape(cap // block, block).astype(jnp.int32), axis=1
        )[None]

    spec = {name: PartitionSpec(axis, None) for name in names}
    fn = jax.jit(
        shard_map(
            shard_fn,
            mesh=mesh,
            in_specs=(spec,),
            out_specs=PartitionSpec(axis, None),
            check_vma=False,
        )
    )
    with _counts_fn_lock:
        if len(_counts_fn_cache) >= 128:
            _counts_fn_cache.pop(next(iter(_counts_fn_cache)))
        _counts_fn_cache[key] = fn
    return fn


def _mesh_batched_counts_fn(mesh, structures: tuple, slot_names: tuple,
                            exprs: list, cap: int, block: int,
                            spec_map: Optional[tuple] = None):
    """Jitted shard_map evaluating N predicate masks per device shard and
    reducing each to per-block counts: (cols dict, per-slot literal
    vectors) -> (D, N, cap // block) int32, one mesh round trip for the
    whole batch. Keyed on predicate STRUCTURE — literals are traced
    operands (hbm_cache._batched_counts_fn rationale); the memo is
    hbm_cache's shared BoundedFnCache (one compile-cache discipline for
    both entry points). ``spec_map`` decodes compressed shards in-shard
    (hbm_cache._batched_counts_fn contract)."""
    from .hbm_cache import _batch_fns

    key = (mesh, structures, slot_names, cap, block, spec_map)
    fn = _batch_fns.get(key)
    if fn is not None:
        return fn

    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec

    from jax import shard_map
    from .hbm_cache import _eval_with_literals, _flatten_operands

    exprs = list(exprs)
    names_per_slot = list(slot_names)
    axis = mesh.axis_names[0]
    union_names = tuple(
        dict.fromkeys(n for names in slot_names for n in names)
    )
    specs_by_name = dict(spec_map or ())

    def shard_fn(arrays, lit_vecs):
        flat = _flatten_operands(
            tuple(arrays),
            [arrays[n] for n in arrays],
            tuple(specs_by_name.get(n) for n in arrays),
        )
        outs = []
        for expr, names, lits in zip(exprs, names_per_slot, lit_vecs):
            mask = _eval_with_literals(expr, flat, lits, [0])
            outs.append(
                jnp.sum(
                    mask.reshape(cap // block, block).astype(jnp.int32),
                    axis=1,
                )
            )
        return jnp.stack(outs)[None]

    col_spec = {name: PartitionSpec(axis, None) for name in union_names}
    lit_spec = tuple(PartitionSpec() for _ in exprs)  # replicated literals
    fn = jax.jit(
        shard_map(
            shard_fn,
            mesh=mesh,
            in_specs=(col_spec, lit_spec),
            out_specs=PartitionSpec(axis, None, None),
            check_vma=False,
        )
    )
    _batch_fns.put(key, fn)
    return fn


def _mesh_hybrid_counts_fn(mesh, bound_repr: str, bound: Expr, names: tuple,
                           cap_b: int, block_b: int, cap_d: int,
                           block_d: int, has_mask: bool):
    """Jitted shard_map evaluating the predicate over base shards (AND NOT
    the deletion bitmask) and delta shards in ONE mesh round trip:
    (base dict, delta dict[, mask]) -> (D, base_blocks + delta_blocks)
    int32. Memoized in hbm_cache's shared hybrid compile cache."""
    key = ("hy1m", mesh, bound_repr, names, cap_b, block_b, cap_d, block_d,
           has_mask)
    fn = _hybrid_fns.get(key)
    if fn is not None:
        return fn

    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec

    from jax import shard_map

    shim = ColumnarBatch(
        {name: Column("int32", np.empty(0, dtype=np.int32)) for name in names}
    )
    axis = mesh.axis_names[0]

    def _counts(arrays, cap, block, live=None):
        flat = {n: a.reshape(-1) for n, a in arrays.items()}
        m = eval_mask(bound, shim, flat)
        if live is not None:
            m = m & live
        return jnp.sum(
            m.reshape(cap // block, block).astype(jnp.int32), axis=1
        )

    if has_mask:

        def shard_fn(base_arrays, delta_arrays, mask):
            cb = _counts(base_arrays, cap_b, block_b, mask.reshape(-1) == 0)
            cd = _counts(delta_arrays, cap_d, block_d)
            return jnp.concatenate([cb, cd])[None]

        in_specs = (
            {name: PartitionSpec(axis, None) for name in names},
            {name: PartitionSpec(axis, None) for name in names},
            PartitionSpec(axis, None),
        )
    else:

        def shard_fn(base_arrays, delta_arrays):
            cb = _counts(base_arrays, cap_b, block_b)
            cd = _counts(delta_arrays, cap_d, block_d)
            return jnp.concatenate([cb, cd])[None]

        in_specs = (
            {name: PartitionSpec(axis, None) for name in names},
            {name: PartitionSpec(axis, None) for name in names},
        )

    fn = jax.jit(
        shard_map(
            shard_fn,
            mesh=mesh,
            in_specs=in_specs,
            out_specs=PartitionSpec(axis, None),
            check_vma=False,
        )
    )
    _hybrid_fns.put(key, fn)
    return fn


class MeshHbmCache(ResidentCacheBase):
    """Mesh-sharded resident-table cache over immutable TCB index files,
    LRU-bounded by the shared HBM byte budget (registry/LRU/background-
    thread plumbing inherited from ResidentCacheBase)."""

    _metric_prefix = "hbm.mesh"

    def __init__(self) -> None:
        super().__init__()
        # top-k region key -> why it can never be built (note_touch_topk)
        self._topk_refused: dict = {}

    def reset(self) -> None:
        super().reset()
        with self._lock:
            self._topk_refused.clear()

    # -- population ----------------------------------------------------------
    def prefetch(
        self, files: List[str | Path], columns: List[str], mesh
    ) -> Optional[MeshResidentTable]:
        """Synchronously build and register a mesh-sharded resident table.
        Idempotent; returns None when nothing encodes or the table exceeds
        the budget (same refusal semantics as the single-chip cache)."""
        paths = sorted(str(p) for p in files)
        if not paths:
            return None
        try:
            key = tuple(_file_identity(p) for p in paths)
        except OSError:
            return None
        with self._lock:
            existing = self._covering_locked(
                {k[0]: k for k in key}, set(columns), mesh
            )
            if existing is not None:
                return existing
        table, _ = self._build(paths, key, columns, mesh)
        if table is None:
            return None
        self._register(table)
        return table

    def note_touch(
        self,
        files: List[str | Path],
        columns: List[str],
        mesh,
        n_rows_hint: Optional[int] = None,
    ) -> None:
        """First-touch population: background upload of this file set's
        predicate columns as mesh shards so REPEAT distributed queries go
        resident. Never blocks, never throws (hbm_cache.note_touch
        contract)."""
        if not _auto_enabled() or not files or not columns:
            return
        if n_rows_hint is not None and n_rows_hint < _min_auto_rows():
            return
        paths = sorted(str(p) for p in files)
        try:
            key = tuple(_file_identity(p) for p in paths)
        except OSError:
            return
        memo = (key, frozenset(columns))
        with self._lock:
            if key in self._pending or memo in self._failed:
                return
            if (
                self._covering_locked({k[0]: k for k in key}, set(columns), mesh)
                is not None
            ):
                return
            self._pending.add(key)
            epoch = self._epoch

        def bg():
            failed = False
            try:
                if n_rows_hint is None:
                    total = sum(
                        layout.cached_reader(p).num_rows for p in paths
                    )
                    if total < _min_auto_rows():
                        failed = True
                        return
                with self._lock:
                    prior = next(
                        (t for t in self._tables if t.key == key), None
                    )
                build_cols = list(
                    dict.fromkeys(
                        list(columns)
                        + (sorted(prior.columns) if prior else [])
                    )
                )
                table, permanent = self._build(paths, key, build_cols, mesh)
                if table is not None and set(columns) <= set(table.columns):
                    self._register(table, epoch=epoch)
                elif table is not None or permanent:
                    failed = True
            except Exception:  # noqa: BLE001 - population must never fail a scan
                metrics.incr("hbm.mesh.populate_failed")
            finally:
                with self._lock:
                    self._pending.discard(key)
                    if failed:
                        if len(self._failed) >= _MAX_FAILED_MEMO:
                            self._failed.clear()
                        self._failed.add(memo)

        t = threading.Thread(
            target=bg, daemon=True, name="hbm-mesh-populate"
        )
        self._track_for_exit(t)
        t.start()

    def _build(
        self, paths: List[str], key: tuple, columns: List[str], mesh
    ) -> Tuple[Optional[MeshResidentTable], bool]:
        """(table, permanent_refusal) — hbm_cache._build semantics, with
        the concat order replaced by the bucket-per-device packing."""
        from ..utils.deviceprobe import first_device_touch_ok
        from ..utils.intmath import next_pow2

        from .hbm_cache import residency_mode

        # hbm_cache._build has the same guard and rationale
        if not first_device_touch_ok(required=residency_mode() == "force"):
            metrics.incr("hbm.mesh.device_unreachable")
            return None, False

        t0 = time.perf_counter()
        try:
            by_bucket = _bucket_segments(paths)
        except HyperspaceException:
            return None, True
        except Exception:  # noqa: BLE001 - vanished file = no residency
            metrics.incr("hbm.mesh.prefetch_read_error")
            return None, False
        if not by_bucket:
            return None, True
        D = int(mesh.devices.size)
        from ..parallel.mesh import owner_of_bucket

        # device-local layouts: owned buckets ascending, segments in path
        # order inside each bucket
        dev_segs: List[List[Segment]] = [[] for _ in range(D)]
        dev_rows = [0] * D
        for b in sorted(by_bucket):
            d = owner_of_bucket(b, D)
            for path, lo, hi in by_bucket[b]:
                dev_segs[d].append((path, lo, hi, dev_rows[d]))
                dev_rows[d] += hi - lo
        n_rows = sum(dev_rows)
        if n_rows == 0:
            return None, True
        cap = next_pow2(max(dev_rows))

        # budget pre-check before any read or upload (hbm_cache rationale)
        from .bytecache import vocab_heap_bytes

        readers = {str(p): layout.cached_reader(p) for p in paths}
        first = readers[str(paths[0])]
        dtype_of = {m["name"]: m["dtype"] for m in first.footer["columns"]}
        encodable = [c for c in columns if c in dtype_of]
        if not encodable:
            return None, True
        vocab_est = 0
        for c in encodable:
            if is_string(dtype_of[c]):
                for r in readers.values():
                    m = next(
                        (x for x in r.footer["columns"] if x["name"] == c),
                        None,
                    )
                    if m is not None:
                        vocab_est += vocab_heap_bytes(m.get("vocab", ()))
        planes = sum(
            2 if dtype_of[c] == "float64" else 1 for c in encodable
        )
        from ..residency import knobs as _rknobs

        # the mesh ladder is resident -> compressed -> streaming -> host
        # (the full single-chip ladder since the mesh accepted the
        # compressed-streaming rung): the raw pre-check only refuses
        # outright when every lower rung is switched off
        ladder_open = (
            _rknobs.compression_mode() != "off"
            or _rknobs.streaming_enabled()
        )
        if planes * D * cap * 4 + vocab_est > _budget_bytes() and (
            not ladder_open
        ):
            metrics.incr("hbm.mesh.over_budget_refused")
            return None, False

        # shard packing reads every (file, bucket) segment of every run —
        # the third scattered-read site the segment planner coalesces:
        # ONE ordered sweep per run file (all encodable columns at once)
        # instead of a ranged read per (segment, column). Per-bucket
        # files read whole through the same map so read_seg below is a
        # dict probe either way.
        seg_by_range: Dict[Tuple[str, int, int], ColumnarBatch] = {}
        # THE footer-level per-column gate (one copy; the encode loop
        # below iterates exactly this list): packable columns are the
        # ones EVERY file carries, with every footer-decidable refusal
        # (mixed string dtypes, oversized unified vocab) applied BEFORE
        # the sweep so refused columns cost no IO. Data-dependent
        # refusals (NaN float64, mismatched int encodings) can only
        # surface after the read — those columns' sweep bytes are the
        # price of reading all packable columns in one pass per file.
        readable = []
        for c in encodable:
            metas = [
                next(
                    (m for m in r.footer["columns"] if m["name"] == c), None
                )
                for r in readers.values()
            ]
            if any(m is None for m in metas):
                continue
            if is_string(dtype_of[c]):
                if not all(is_string(m["dtype"]) for m in metas):
                    continue
                if sum(len(m.get("vocab", ())) for m in metas) > _MAX_VOCAB:
                    metrics.incr("hbm.mesh.vocab_too_large_refused")
                    continue
            readable.append(c)
        run_paths = [p for p in paths if layout.is_run_file(p)]
        if run_paths and readable:
            seg_map = layout.execute_segment_reads(
                layout.plan_segment_reads(run_paths), columns=readable
            )
        else:
            seg_map = {}
        if readable:
            for b, segs in by_bucket.items():
                for path, lo, hi in segs:
                    if layout.is_run_file(path):
                        seg_by_range[(path, lo, hi)] = seg_map[(path, b)]
                    else:
                        seg_by_range[(path, lo, hi)] = readers[path].read(
                            readable, row_range=(lo, hi)
                        )

        def read_seg(path: str, lo: int, hi: int, name: str) -> Column:
            return seg_by_range[(path, lo, hi)].columns[name]

        # --- encode phase: host (D, cap) matrices, no uploads yet -----------
        host_mats: Dict[str, tuple] = {}
        for name in readable:
            enc: Optional[str] = None
            vocab = None
            packed = np.zeros((D, cap), dtype=np.int32)
            if is_string(dtype_of[name]):
                from ..storage.columnar import unify_dictionaries

                flat_segs = [
                    (d, seg) for d in range(D) for seg in dev_segs[d]
                ]
                raw = [
                    read_seg(path, lo, hi, name)
                    for _, (path, lo, hi, _off) in flat_segs
                ]
                unified = unify_dictionaries(raw)
                vocab = next(
                    (u.vocab for u in unified if u.vocab is not None), None
                )
                if vocab is None:
                    continue
                for (d, (_p, lo, hi, off)), u in zip(flat_segs, unified):
                    packed[d, off : off + (hi - lo)] = u.data.astype(
                        np.int32, copy=False
                    )
                enc = "string"
            elif dtype_of[name] == "float64":
                from .hbm_cache import _encode_f64

                packed_lo = np.zeros((D, cap), dtype=np.int32)
                ok = True
                for d in range(D):
                    for path, lo, hi, off in dev_segs[d]:
                        e = _encode_f64(read_seg(path, lo, hi, name).data)
                        if e is None:
                            ok = False  # NaN data: refuse the column
                            break
                        packed[d, off : off + (hi - lo)] = e[0]
                        packed_lo[d, off : off + (hi - lo)] = e[1]
                    if not ok:
                        break
                if not ok:
                    continue
                host_mats[name] = (
                    "float64", "f64", None, {"hi": packed, "lo": packed_lo}
                )
                continue
            else:
                ok = True
                for d in range(D):
                    for path, lo, hi, off in dev_segs[d]:
                        e = _encode_column(read_seg(path, lo, hi, name))
                        if e is None:
                            ok = False
                            break
                        a, this_enc = e
                        if enc is None:
                            enc = this_enc
                        elif enc != this_enc:
                            ok = False
                            break
                        packed[d, off : off + (hi - lo)] = a
                    if not ok:
                        break
                if not ok or enc is None:
                    continue
            host_mats[name] = (dtype_of[name], enc, vocab, {"": packed})
        if not host_mats:
            return None, True

        # --- tier plan (shared ladder; streaming declines on a mesh) --------
        from ..ops import bitpack
        from ..residency import plan_tier

        pack_specs = {}
        raw_plane_bytes = 0
        unpacked_bytes = 0
        side_bytes = 0
        col_bounds: Dict[str, Tuple[int, int]] = {}
        for name, (_dts, enc, vocab, mats) in host_mats.items():
            if vocab is not None:
                side_bytes += vocab_heap_bytes(vocab)
            raw_plane_bytes += len(mats) * D * cap * 4
            spec = None
            if len(mats) == 1:
                mat = mats[""]
                # bounds from the REAL rows only: the matrix is
                # zero-padded past each shard's dev_rows, and a padded 0
                # would stretch the span of any offset-valued domain
                # (e.g. ids around 10^6) past the pack budget — the
                # single-chip path has the same rule via its unpadded
                # flats
                real = [mat[d, : dev_rows[d]] for d in range(D) if dev_rows[d]]
                if real:
                    vmin = min(int(r.min()) for r in real)
                    vmax = max(int(r.max()) for r in real)
                    if enc == "int":
                        # mesh shards carry no zone vectors; the device
                        # scan-aggregate's dense-key planner reads these
                        col_bounds[name] = (vmin, vmax)
                    spec = bitpack.pack_spec(vmin, vmax, cap)
                    if spec is not None and cap % spec.vpw != 0:
                        spec = None  # degenerate tiny shard: keep raw
            if spec is not None:
                pack_specs[name] = spec
            else:
                unpacked_bytes += len(mats) * D * cap * 4
        plan = plan_tier(
            raw_plane_bytes,
            _budget_bytes(),
            pack_specs,
            unpacked_bytes,
            side_bytes,
            streaming_ok=True,
            shard_count=D,  # per-shard specs upload D copies
        )
        if plan.tier == "host":
            # with streaming_ok=True the planner only lands here when
            # streaming is switched OFF — a knob refusal, not a decline
            metrics.incr("hbm.mesh.over_budget_refused")
            return None, False
        if plan.tier == "streaming":
            from ..residency.streaming import build_mesh_streaming_table

            table = build_mesh_streaming_table(
                key,
                mesh,
                dev_segs,
                dev_rows,
                n_rows,
                host_mats,
                plan.specs,
                _rknobs.streaming_window_rows(),
                col_bounds,
            )
            if table.nbytes > _budget_bytes():
                # even the per-device slab pair cannot fit: the ONE
                # genuine mesh streaming decline left
                metrics.incr("hbm.mesh.residency.streaming_declined")
                metrics.incr("hbm.mesh.over_budget_refused")
                return None, False
            metrics.incr("residency.tier.streaming_built")
            metrics.record_time(
                "hbm.mesh.prefetch", time.perf_counter() - t0
            )
            return table, False

        import jax
        from jax.sharding import NamedSharding, PartitionSpec

        sharding = NamedSharding(
            mesh, PartitionSpec(mesh.axis_names[0], None)
        )
        cols: Dict[str, MeshResidentColumn] = {}
        nbytes = 0
        for name, (dts, enc, vocab, mats) in host_mats.items():
            vocab_heap = vocab_heap_bytes(vocab)
            if enc == "f64":
                dev_hi = jax.device_put(mats["hi"], sharding)
                dev_lo = jax.device_put(mats["lo"], sharding)
                col_bytes = mats["hi"].nbytes + mats["lo"].nbytes
                cols[name] = MeshResidentColumn(
                    dev_hi, dts, "f64", col_bytes, None, dev_lo
                )
                nbytes += col_bytes
                continue
            spec = plan.specs.get(name)
            mat = mats[""]
            vmin, vmax = col_bounds.get(name, (None, None))
            if spec is not None:
                # pad rows re-encode at the frame reference (they were
                # zero-filled, which may sit OUTSIDE [ref0, ref0+2^bits)
                # for offset domains); ref0 pads are in-range garbage
                # the host leg clips, like every other tier's pads
                for d in range(D):
                    mat[d, dev_rows[d] :] = spec.ref0
                words = np.stack(
                    [bitpack.pack_plain(mat[d], spec) for d in range(D)]
                )
                dev = jax.device_put(words, sharding)
                col_bytes = words.nbytes + vocab_heap
                cols[name] = MeshResidentColumn(
                    dev, dts, enc, col_bytes, vocab, None, spec,
                    vmin, vmax,
                )
            else:
                dev = jax.device_put(mat, sharding)
                col_bytes = mat.nbytes + vocab_heap
                cols[name] = MeshResidentColumn(
                    dev, dts, enc, col_bytes, vocab, vmin=vmin, vmax=vmax
                )
            nbytes += col_bytes
        if not cols:
            return None, True
        _trace_bytes("h2d_bytes", nbytes)
        try:
            # materializing chain fence: closes the prefetch timer after
            # the uploads land and surfaces a dead device here, not at the
            # first query; one probe fences them all
            from ..ops import fence_chain

            fence_chain(
                [c.data for c in cols.values()]
                + [c.data2 for c in cols.values() if c.data2 is not None]
            )
        except Exception:  # noqa: BLE001 - device loss: no residency
            metrics.incr("hbm.mesh.device_transfer_error")
            return None, False
        if nbytes > _budget_bytes():
            metrics.incr("hbm.mesh.over_budget_refused")
            return None, False
        if plan.tier == "compressed":
            metrics.incr("residency.tier.compressed_built")
            metrics.incr("residency.compressed.packed_bytes", nbytes)
            metrics.incr(
                "residency.compressed.raw_bytes", raw_plane_bytes + side_bytes
            )
        metrics.record_time("hbm.mesh.prefetch", time.perf_counter() - t0)
        return (
            MeshResidentTable(
                key,
                mesh,
                D,
                cap,
                min(BLOCK_ROWS, cap),
                dev_rows,
                dev_segs,
                cols,
                n_rows,
                nbytes,
                tier=plan.tier,
                raw_nbytes=raw_plane_bytes + side_bytes,
            ),
            False,
        )

    # -- lookup --------------------------------------------------------------
    def _covering_locked(
        self, want_files: dict, want_cols: set, mesh
    ) -> Optional[MeshResidentTable]:
        for t in reversed(self._tables):
            if t.mesh is not mesh:
                continue
            have = {k[0]: k for k in t.key}
            if all(
                p in have and have[p] == ident
                for p, ident in want_files.items()
            ) and want_cols <= set(t.columns):
                return t
        return None

    def resident_for(
        self, files: List[str | Path], columns: List[str], mesh
    ) -> Optional[MeshResidentTable]:
        from .hbm_cache import residency_mode

        # mode "off" disables serving too (hbm_cache.resident_for rationale)
        if not files or residency_mode() == "off":
            return None
        with self._lock:
            if not self._tables:
                return None
        try:
            want = {str(p): _file_identity(p) for p in files}
        except OSError:
            return None
        with self._lock:
            t = self._covering_locked(want, set(columns), mesh)
            if t is not None:
                t.last_used = time.monotonic()
            return t

    # -- the resident query --------------------------------------------------
    def block_counts(
        self, table: MeshResidentTable, predicate: Expr
    ) -> Optional[np.ndarray]:
        """(D, n_blocks) per-block match counts in ONE mesh round trip.
        None when the predicate does not narrow to the resident encodings
        (caller routes the ship-per-query path). Tier-transparent like
        the single-chip twin: streaming tables run the per-shard
        double-buffered window loop (residency.streaming)."""
        from ..ops import kernels as K
        from .hbm_cache import (
            dispatch_span,
            prepare_resident_predicate,
            resident_arrays_for,
            resident_specs_for,
        )

        if getattr(table, "tier", "resident") == "streaming":
            from ..residency.streaming import mesh_stream_block_counts

            return mesh_stream_block_counts(table, predicate)
        # bind (string vocab) -> expand (f64 two-plane) -> narrow (i32):
        # the shared resident pipeline (hbm_cache)
        prepared = prepare_resident_predicate(table.columns, predicate)
        if prepared is None:
            return None
        with dispatch_span(table, 1, False, mesh=table.n_devices):
            narrowed, names = prepared
            fn = _mesh_counts_fn(
                table.mesh,
                repr(narrowed),
                narrowed,
                names,
                table.cap,
                table.block,
                resident_specs_for(table.columns, names),
            )
            cols = dict(
                zip(names, resident_arrays_for(table.columns, names))
            )
            with K._x32(), _trace_span("scan.device_launch"):
                pending = fn(cols)
            counts = np.asarray(pending)
            _trace_bytes("d2h_bytes", int(counts.nbytes))
            metrics.incr("scan.resident_mesh.d2h_bytes", int(counts.nbytes))
            return counts

    def block_counts_batch(
        self,
        table: MeshResidentTable,
        predicates: List[Expr],
        prepared: Optional[list] = None,
        metric_ns: str = "serve.batch",
    ) -> Optional[np.ndarray]:
        """(N, D, n_blocks) match counts for N predicates in ONE mesh
        round trip — the mesh leg of the serving micro-batcher
        (hbm_cache.block_counts_batch rationale: literal values ride as
        traced operands so serving bursts reuse the compiled executable;
        ``prepared`` optionally reuses the classifier's submit-time
        prepare_resident_predicate results), and (N=1, ``metric_ns``
        "compile.fused") the compiled mesh scan pipeline's structure-
        keyed single. None when any predicate fails to narrow (caller
        serves the batch per-query). Streaming tables window the whole
        batch through the per-shard slab pair."""
        from ..ops import kernels as K
        from .hbm_cache import (
            _expr_literals,
            _expr_structure,
            dispatch_span,
            prepare_resident_predicate,
            resident_arrays_for,
            resident_specs_for,
        )

        if getattr(table, "tier", "resident") == "streaming":
            from ..residency.streaming import mesh_stream_block_counts_batch

            return mesh_stream_block_counts_batch(
                table, predicates, prepared, metric_ns
            )
        if prepared is None:
            prepared = [
                prepare_resident_predicate(table.columns, p)
                for p in predicates
            ]
        if any(p is None for p in prepared):
            return None
        with dispatch_span(
            table, len(predicates), True, mesh=table.n_devices
        ):
            structures = tuple(_expr_structure(n) for n, _ in prepared)
            slot_names = tuple(names for _, names in prepared)
            union_names = tuple(
                dict.fromkeys(n for names in slot_names for n in names)
            )
            fn = _mesh_batched_counts_fn(
                table.mesh,
                structures,
                slot_names,
                [n for n, _ in prepared],
                table.cap,
                table.block,
                tuple(
                    zip(
                        union_names,
                        resident_specs_for(table.columns, union_names),
                    )
                ),
            )
            cols = dict(
                zip(
                    union_names,
                    resident_arrays_for(table.columns, union_names),
                )
            )
            lit_vecs = []
            for narrowed, _ in prepared:
                vals: list = []
                _expr_literals(narrowed, vals)
                lit_vecs.append(np.asarray(vals, dtype=np.int32))
            lit_vecs = tuple(lit_vecs)
            with K._x32(), _trace_span("scan.device_launch"):
                pending = fn(cols, lit_vecs)
            counts = np.asarray(pending)
            _trace_bytes("d2h_bytes", int(counts.nbytes))
            metrics.incr(f"{metric_ns}.dispatches")
            metrics.incr(f"{metric_ns}.queries", len(predicates))
            metrics.incr("scan.resident_mesh.d2h_bytes", int(counts.nbytes))
            # (D, N, n_blocks) -> per-predicate (D, n_blocks) slices, stacked
            # predicate-major so callers index counts[i] like block_counts()
            return np.swapaxes(counts, 0, 1)

    # -- host-side collection ------------------------------------------------
    def collect_parts(
        self,
        table: MeshResidentTable,
        files: List[str | Path],
        output_columns: List[str],
        predicate: Expr,
        counts: np.ndarray,
        path_metric: Optional[str] = "scan.path.resident_device_mesh",
    ) -> List[ColumnarBatch]:
        """Read ONLY the blocks the device counted matches in, re-evaluate
        the predicate exactly there, gather output columns from mmap —
        the single-chip _resident_parts protocol per device shard,
        restricted to the query's (pruned) ``files``. ``path_metric=None``
        suppresses the path counter (the hybrid fused path fires
        ``scan.path.resident_hybrid`` instead). Spanned as
        ``scan.host_leg``."""
        wanted = {str(Path(f)) for f in files}
        with _trace_span("scan.host_leg", files=len(wanted)) as leg:
            if path_metric is not None:
                metrics.incr(path_metric)
            touched = int(np.count_nonzero(counts))
            metrics.incr("scan.resident_mesh.blocks_touched", touched)
            metrics.incr("scan.resident_mesh.blocks_total", int(counts.size))
            if leg is not None:
                leg.labels["host_blocks"] = touched
            need = list(
                dict.fromkeys(
                    list(output_columns) + sorted(predicate.columns())
                )
            )
            keyed: List[Tuple[Tuple[str, int], ColumnarBatch]] = []
            for d in range(table.n_devices):
                cand = np.flatnonzero(counts[d])
                if cand.size == 0:
                    continue
                # merge adjacent candidate blocks into device-local row runs,
                # clipped to the device's real rows
                runs: List[List[int]] = []
                for blk in cand:
                    lo = int(blk) * table.block
                    hi = min((int(blk) + 1) * table.block, table.dev_rows[d])
                    if lo >= hi:
                        continue  # pad-only tail block
                    if runs and runs[-1][1] == lo:
                        runs[-1][1] = hi
                    else:
                        runs.append([lo, hi])
                for lo, hi in runs:
                    for path, flo, fhi, off in table.segments[d]:
                        seg_len = fhi - flo
                        a = max(lo, off)
                        b = min(hi, off + seg_len)
                        if a >= b or path not in wanted:
                            continue
                        r_lo = flo + (a - off)
                        r_hi = flo + (b - off)
                        batch = layout.cached_reader(path).read(
                            need, row_range=(r_lo, r_hi)
                        )
                        mask = np.asarray(eval_mask(predicate, batch))
                        idx = np.flatnonzero(mask)
                        if idx.size:
                            keyed.append(
                                (
                                    (path, r_lo),
                                    batch.take(idx).select(output_columns),
                                )
                            )
            keyed.sort(key=lambda kv: kv[0])
            return [b for _, b in keyed]

    # -- delta residency (hybrid scan's appended side) -----------------------
    def delta_for(
        self, table: MeshResidentTable, appended, columns, deleted_ids
    ) -> Optional[MeshDeltaRegion]:
        from .hbm_cache import residency_mode

        if residency_mode() == "off":
            return None
        dkey = delta_snapshot_key(appended)
        dels = tuple(sorted(int(i) for i in deleted_ids))
        with self._lock:
            for d in reversed(self._deltas):
                if (
                    d.base_key == table.key
                    and d.mesh is table.mesh
                    and d.key == dkey
                    and d.deleted_ids == dels
                    and set(columns) <= set(d.columns)
                ):
                    d.last_used = time.monotonic()
                    return d
        return None

    def prefetch_delta(
        self,
        table: MeshResidentTable,
        appended,
        relation,
        host_columns,
        deleted_ids,
        indexed_columns,
        num_buckets: int,
    ) -> Optional[MeshDeltaRegion]:
        """Synchronous mesh delta build + register (idempotent; a delta
        built against a narrower base is rebuilt — hbm_cache note)."""
        want = [c for c in host_columns if c in table.columns]
        existing = self.delta_for(table, appended, want, deleted_ids)
        if existing is not None:
            return existing
        delta, _ = self._build_delta(
            table, appended, relation, host_columns, deleted_ids,
            indexed_columns, num_buckets,
        )
        if delta is None:
            return None
        self._register_delta(delta)
        return delta

    def note_touch_delta(
        self,
        table: MeshResidentTable,
        appended,
        relation,
        host_columns,
        deleted_ids,
        indexed_columns,
        num_buckets: int,
    ) -> None:
        """Background mesh delta population (hbm_cache.note_touch_delta
        contract: never blocks, never throws, no row floor)."""
        if not _auto_enabled() or not appended:
            return
        dkey = delta_snapshot_key(appended)
        dels = tuple(sorted(int(i) for i in deleted_ids))
        want = {c for c in host_columns if c in table.columns}
        memo = ("delta", table.key, dkey, dels)
        with self._lock:
            if memo in self._pending or memo in self._failed:
                return
            # coverage, not mere existence (hbm_cache.note_touch_delta
            # rationale): a narrower delta must be rebuilt, not memoized
            if any(
                d.base_key == table.key
                and d.mesh is table.mesh
                and d.key == dkey
                and d.deleted_ids == dels
                and want <= set(d.columns)
                for d in self._deltas
            ):
                return
            self._pending.add(memo)
            epoch = self._epoch

        def bg():
            failed = False
            try:
                delta, permanent = self._build_delta(
                    table, appended, relation, host_columns, deleted_ids,
                    indexed_columns, num_buckets,
                )
                if delta is not None:
                    self._register_delta(delta, epoch=epoch)
                    if not want <= set(delta.columns):
                        # uncoverable want-set for this epoch: memoize or
                        # rebuild forever (hbm_cache.note_touch_delta)
                        failed = True
                elif permanent:
                    failed = True
            except Exception:  # noqa: BLE001 - population must never fail a scan
                metrics.incr("hbm.mesh.delta.populate_failed")
            finally:
                with self._lock:
                    self._pending.discard(memo)
                    if failed:
                        if len(self._failed) >= _MAX_FAILED_MEMO:
                            self._failed.clear()
                        self._failed.add(memo)

        t = threading.Thread(
            target=bg, daemon=True, name="hbm-mesh-delta-populate"
        )
        self._track_for_exit(t)
        t.start()

    def _build_delta(
        self,
        table: MeshResidentTable,
        appended,
        relation,
        host_columns,
        deleted_ids,
        indexed_columns,
        num_buckets: int,
    ) -> Tuple[Optional[MeshDeltaRegion], bool]:
        """(delta, permanent_refusal): decode the appended files once,
        hash-bucketize their rows to the build's ``b % D`` placement, and
        upload per-device delta shards + the base deletion bitmask."""
        from ..ops.hashing import bucket_ids_host, key_repr
        from ..parallel.mesh import owner_of_bucket
        from ..storage import parquet_io
        from ..utils.deviceprobe import first_device_touch_ok
        from ..utils.intmath import next_pow2
        from .bytecache import batch_nbytes, vocab_heap_bytes
        from .delta import encode_delta_columns

        if getattr(table, "tier", "resident") != "resident":
            # the fused hybrid dispatch reads raw base shards — a
            # compressed base cannot anchor a delta (hbm_cache rule)
            metrics.incr("hbm.mesh.delta.declined.tier")
            return None, True
        from .hbm_cache import residency_mode

        if not first_device_touch_ok(required=residency_mode() == "force"):
            metrics.incr("hbm.mesh.device_unreachable")
            return None, False

        t0 = time.perf_counter()
        dels = tuple(sorted(int(i) for i in deleted_ids))
        mesh = table.mesh
        D = table.n_devices
        # doomed-build pre-check before the decode (hbm_cache rationale)
        with self._lock:
            headroom0 = _budget_bytes() - sum(
                t.nbytes for t in self._tables
            )
        if sum(int(f.size) for f in appended) > headroom0:
            metrics.incr("hbm.mesh.delta.over_budget_refused")
            return None, False
        try:
            host_batch = parquet_io.read_relation(
                relation,
                paths=[f.name for f in appended],
                columns=list(host_columns),
            )
        except Exception:  # noqa: BLE001 - vanished file = no residency
            metrics.incr("hbm.mesh.delta.read_error")
            return None, False
        n_rows = host_batch.num_rows
        if n_rows == 0:
            return None, True
        if any(c not in host_batch.columns for c in indexed_columns):
            return None, True
        if dels:
            from .. import constants as C

            col_name = C.DATA_FILE_NAME_ID
            for segs in table.segments:
                for path, _lo, _hi, _off in segs:
                    footer_cols = {
                        m["name"]
                        for m in layout.cached_reader(path).footer["columns"]
                    }
                    if col_name not in footer_cols:
                        metrics.incr("hbm.mesh.delta.no_lineage_refused")
                        return None, True

        # the build's placement rule: bucket on the index's key columns,
        # owner = b % D; bucket-ascending order within each device
        buckets = bucket_ids_host(
            [key_repr(host_batch.columns[c]) for c in indexed_columns],
            num_buckets,
        )
        dev_idx: List[np.ndarray] = [
            np.empty(0, dtype=np.int64) for _ in range(D)
        ]
        per_dev: List[List[np.ndarray]] = [[] for _ in range(D)]
        for b in np.unique(buckets):
            d = owner_of_bucket(int(b), D)
            per_dev[d].append(np.flatnonzero(buckets == b))
        for d in range(D):
            if per_dev[d]:
                dev_idx[d] = np.concatenate(per_dev[d])
        dev_rows = [int(len(ix)) for ix in dev_idx]
        cap = next_pow2(max(max(dev_rows), 1))
        block = min(BLOCK_ROWS, cap)

        # shared per-column encode loop (exec.delta); the mesh resident
        # path is ungated, so zone vectors are skipped
        flats, encs, oov, planes, _zones = encode_delta_columns(
            host_batch, table.columns, with_zones=False
        )
        if not flats:
            return None, True
        host_bytes = batch_nbytes(host_batch)
        oov_bytes = sum(vocab_heap_bytes(side) for side in oov.values())
        mask_bytes = D * table.cap * 4 if dels else 0
        dev_bytes = planes * D * cap * 4 + mask_bytes
        # headroom against the resident tables, not the whole budget
        # (hbm_cache._build_delta rationale)
        with self._lock:
            headroom = _budget_bytes() - sum(
                t.nbytes for t in self._tables
            )
        if dev_bytes + host_bytes + oov_bytes > headroom:
            metrics.incr("hbm.mesh.delta.over_budget_refused")
            return None, False

        import jax
        from jax.sharding import NamedSharding, PartitionSpec

        sharding = NamedSharding(
            mesh, PartitionSpec(mesh.axis_names[0], None)
        )

        def pack(flat: np.ndarray) -> np.ndarray:
            packed = np.zeros((D, cap), dtype=np.int32)
            for d in range(D):
                if dev_rows[d]:
                    packed[d, : dev_rows[d]] = flat[dev_idx[d]]
            return packed

        try:
            cols: Dict[str, MeshResidentColumn] = {}
            for name, flat in flats.items():
                dtype_str, enc = encs[name]
                if enc == "f64":
                    hi, lo = flat
                    dev_hi = jax.device_put(pack(hi), sharding)
                    dev_lo = jax.device_put(pack(lo), sharding)
                    cols[name] = MeshResidentColumn(
                        dev_hi, dtype_str, "f64", 2 * D * cap * 4, None,
                        dev_lo,
                    )
                else:
                    dev = jax.device_put(pack(flat), sharding)
                    cols[name] = MeshResidentColumn(
                        dev,
                        dtype_str,
                        enc,
                        D * cap * 4,
                        table.columns[name].vocab if enc == "string" else None,
                    )
            del_mask = None
            if dels:
                del_mask = jax.device_put(
                    self._lineage_mask(table, dels), sharding
                )
            _trace_bytes(
                "h2d_bytes", sum(c.nbytes for c in cols.values())
            )
            from ..ops import fence_chain

            fence_chain(
                [c.data for c in cols.values()]
                + [c.data2 for c in cols.values() if c.data2 is not None]
                + ([del_mask] if del_mask is not None else [])
            )
        except Exception:  # noqa: BLE001 - device loss: no residency
            metrics.incr("hbm.mesh.delta.transfer_error")
            return None, False
        nbytes = dev_bytes + host_bytes + oov_bytes
        metrics.incr("hbm.mesh.delta.h2d_bytes", dev_bytes)
        metrics.record_time(
            "hbm.mesh.delta.prefetch", time.perf_counter() - t0
        )
        return (
            MeshDeltaRegion(
                delta_snapshot_key(appended),
                table.key,
                dels,
                mesh,
                D,
                cap,
                block,
                dev_rows,
                dev_idx,
                cols,
                oov,
                host_batch,
                del_mask,
                n_rows,
                nbytes,
            ),
            False,
        )

    @staticmethod
    def _lineage_mask(table: MeshResidentTable, dels: tuple) -> np.ndarray:
        """(D, cap) int32 deletion bitmask over the base shards, from the
        base files' lineage column read at the shard segments' row
        ranges (pad rows stay 0)."""
        from .. import constants as C

        mask = np.zeros((table.n_devices, table.cap), dtype=np.int32)
        dels_arr = np.asarray(dels, dtype=np.int64)
        for d in range(table.n_devices):
            for path, flo, fhi, off in table.segments[d]:
                vals = (
                    layout.cached_reader(path)
                    .read([C.DATA_FILE_NAME_ID], row_range=(flo, fhi))
                    .columns[C.DATA_FILE_NAME_ID]
                    .data
                )
                mask[d, off : off + (fhi - flo)] = np.isin(
                    np.asarray(vals, dtype=np.int64), dels_arr
                )
        return mask

    # -- the fused hybrid query ----------------------------------------------
    def hybrid_block_counts(
        self,
        table: MeshResidentTable,
        delta: MeshDeltaRegion,
        predicate: Expr,
    ) -> Optional[Tuple[np.ndarray, np.ndarray]]:
        """((D, base_blocks), (D, delta_blocks)) per-block match counts
        for base+delta in ONE mesh round trip, deletion bitmask applied
        on-device. None when the predicate cannot ride the shared
        encodings (caller routes the host union)."""
        from ..ops import kernels as K
        from .delta import prepare_hybrid_predicate
        from .hbm_cache import dispatch_span, resident_arrays_for

        prepared = prepare_hybrid_predicate(
            table.columns, delta.oov, predicate
        )
        if prepared is None:
            return None
        narrowed, names = prepared
        if any(n.split("\x00", 1)[0] not in delta.columns for n in names):
            return None
        with dispatch_span(
            table, 1, False, mesh=table.n_devices, hybrid=True
        ):
            fn = _mesh_hybrid_counts_fn(
                table.mesh,
                repr(narrowed),
                narrowed,
                names,
                table.cap,
                table.block,
                delta.cap,
                delta.block,
                delta.del_mask is not None,
            )
            bcols = dict(
                zip(names, resident_arrays_for(table.columns, names))
            )
            dcols = dict(
                zip(names, resident_arrays_for(delta.columns, names))
            )
            masks = (delta.del_mask,) if delta.del_mask is not None else ()
            with K._x32(), _trace_span("scan.device_launch"):
                pending = fn(bcols, dcols, *masks)
            counts = np.asarray(pending)
            _trace_bytes("d2h_bytes", int(counts.nbytes))
            metrics.incr("scan.resident_mesh.d2h_bytes", int(counts.nbytes))
            nb = table.n_blocks
            return counts[:, :nb], counts[:, nb:]

    def delta_parts(
        self,
        delta: MeshDeltaRegion,
        predicate: Expr,
        output_columns,
        counts: np.ndarray,
    ) -> List[ColumnarBatch]:
        """The mesh delta's host leg: per device, slice only the counted
        blocks' rows out of the host-held appended batch (via dev_idx),
        re-evaluate exactly, project. No parquet per query."""
        from .delta import blocks_to_runs

        parts: List[ColumnarBatch] = []
        touched = int(np.count_nonzero(counts))
        with _trace_span("scan.delta_leg", delta_blocks=touched):
            metrics.incr("scan.resident.delta_blocks_touched", touched)
            metrics.incr("scan.resident.delta_blocks_total", int(counts.size))
            for d in range(delta.n_devices):
                cand = np.flatnonzero(counts[d])
                if cand.size == 0:
                    continue
                for lo, hi in blocks_to_runs(
                    cand, delta.block, delta.dev_rows[d]
                ):
                    sub = delta.host_batch.take(delta.dev_idx[d][lo:hi])
                    mask = eval_mask(predicate, sub)
                    idx = np.flatnonzero(np.asarray(mask))
                    if idx.size:
                        parts.append(
                            sub.take(idx).select(list(output_columns))
                        )
        return parts

    # -- join regions (the shuffle-free sharded resident SMJ) ----------------
    def join_for(
        self, l_files, r_files, l_keys, r_keys, columns, mesh
    ) -> Optional[object]:
        """hbm_cache.join_for with the mesh identity check: a region's
        shards only serve the mesh they were placed on."""
        from .hbm_cache import residency_mode
        from .join_residency import join_region_key

        if residency_mode() == "off":
            return None
        with self._lock:
            if not self._joins:
                return None
        try:
            key = join_region_key(l_files, r_files, l_keys, r_keys)
        except OSError:
            return None
        with self._lock:
            for j in reversed(self._joins):
                if (
                    j.key == key
                    and j.mesh is mesh
                    and all(
                        c in j.l_cols or c in j.r_cols for c in columns
                    )
                ):
                    j.last_used = time.monotonic()
                    return j
        return None

    def note_touch_join(
        self, l_files, r_files, l_keys, r_keys, payload_columns, loader, mesh
    ) -> None:
        """Background mesh join-region population (hbm_cache
        note_touch_join contract: never blocks, never throws)."""
        from .hbm_cache import _auto_enabled as _auto
        from .join_residency import build_mesh_join_region, join_region_key

        if not _auto():
            return
        try:
            key = join_region_key(l_files, r_files, l_keys, r_keys)
        except OSError:
            return
        want = frozenset(payload_columns)
        memo = ("join", key, want)
        pending = ("join", key)
        with self._lock:
            if pending in self._pending or memo in self._failed:
                return
            if any(
                j.key == key
                and j.mesh is mesh
                and all(c in j.l_cols or c in j.r_cols for c in want)
                for j in self._joins
            ):
                return
            self._pending.add(pending)
            epoch = self._epoch

        def bg():
            failed = False
            try:
                groups = loader()
                if groups is None:
                    return
                with self._lock:
                    prior = next(
                        (j for j in self._joins if j.key == key), None
                    )
                cols = list(
                    dict.fromkeys(
                        list(payload_columns)
                        + (
                            sorted(set(prior.l_cols) | set(prior.r_cols))
                            if prior
                            else []
                        )
                    )
                )
                region, permanent = build_mesh_join_region(
                    self, groups[0], groups[1], key[2], key[3], key, cols,
                    mesh,
                )
                if region is not None:
                    self._register_join(region, epoch=epoch)
                    if not all(
                        c in region.l_cols or c in region.r_cols
                        for c in want
                    ):
                        failed = True  # uncoverable payload: memoize
                elif permanent:
                    failed = True
            except Exception:  # noqa: BLE001 - population must never fail a query
                metrics.incr("hbm.mesh.join.populate_failed")
            finally:
                with self._lock:
                    self._pending.discard(pending)
                    if failed:
                        if len(self._failed) >= _MAX_FAILED_MEMO:
                            self._failed.clear()
                        self._failed.add(memo)

        t = threading.Thread(
            target=bg, daemon=True, name="hbm-mesh-join-populate"
        )
        self._track_for_exit(t)
        t.start()

    def prefetch_join(
        self,
        l_by_bucket,
        r_by_bucket,
        l_files,
        r_files,
        l_keys,
        r_keys,
        payload_columns,
        mesh,
    ) -> Optional[object]:
        """Synchronous mesh join-region build + register (idempotent;
        a narrower region is rebuilt widened — hbm_cache note)."""
        from .join_residency import build_mesh_join_region, join_region_key

        try:
            key = join_region_key(l_files, r_files, l_keys, r_keys)
        except OSError:
            return None
        existing = self.join_for(
            l_files, r_files, l_keys, r_keys, payload_columns, mesh
        )
        if existing is not None:
            return existing
        region, _ = build_mesh_join_region(
            self,
            l_by_bucket,
            r_by_bucket,
            list(l_keys),
            list(r_keys),
            key,
            list(payload_columns),
            mesh,
        )
        if region is None:
            return None
        return region if self._register_join(region) else None

    def join_agg(self, region, group_by, aggs):
        """The two-phase mesh aggregate-join: per-device sorted
        intersection + partial segment aggregates over each device's
        owned buckets (the build's ``b % D`` placement makes the shard
        join complete without any shuffle), psum/pmin/pmax into ONE
        replicated group table, ONE D2H. None when the spec cannot ride
        (caller routes host); device errors propagate."""
        from jax import enable_x64
        from .join_residency import (
            finish_join_agg,
            mesh_join_agg_fn,
            plan_device_arrays,
            region_agg_plan,
        )

        plan = region_agg_plan(region, list(group_by), list(aggs))
        if plan is None:
            metrics.incr("hbm.mesh.join.declined.dtype")
            return None
        fn = mesh_join_agg_fn(region.mesh, plan, region.cap_l, region.cap_r)
        arrays = plan_device_arrays(region, plan)
        slots = region.l_cols[plan.group].slots
        t0 = time.perf_counter()
        with enable_x64(True):
            raw = fn(region.l_codes, region.r_codes, slots, arrays)
        outs = [np.asarray(o) for o in raw]
        metrics.record_time(
            "scan.resident_join_agg.mesh_device", time.perf_counter() - t0
        )
        metrics.incr(
            "scan.resident_join.d2h_bytes", sum(int(o.nbytes) for o in outs)
        )
        _trace_bytes("d2h_bytes", sum(int(o.nbytes) for o in outs))
        return finish_join_agg(region, plan, list(group_by), list(aggs), outs)

    # -- the filtered join-aggregate top-k (ORDER BY ... LIMIT) -------------
    def topk_for(self, shape, mesh):
        """The resident top-k region ``shape`` reads, or None."""
        from .hbm_cache import residency_mode

        if residency_mode() == "off":
            return None
        try:
            key = shape.region_key()
        except OSError:
            return None
        with self._lock:
            for j in reversed(self._joins):
                if j.key == key and j.mesh is mesh:
                    j.last_used = time.monotonic()
                    return j
        return None

    def note_touch_topk(self, shape, loader, mesh) -> None:
        """Schedule the region ``shape`` reads in the background (the
        note_touch contract: never blocks, never throws). A region that
        can never be built (a join key or dimension key that repeats, a
        column that cannot ride exactly) is remembered with its reason,
        and each later query counts ``hbm.mesh.join_topk.declined.<why>``
        as it goes to the host."""
        from .join_residency import build_topk_region

        try:
            key = shape.region_key() if _auto_enabled() else None
        except OSError:  # an index file went: a refresh is under way
            key = None
        if key is None:
            metrics.incr("hbm.mesh.join_topk.declined.residency")
            return
        pending = ("topk", key)
        with self._lock:
            refused = self._topk_refused.get(key)
            busy = pending in self._pending
            if refused is None and not busy:
                self._pending.add(pending)
                epoch = self._epoch
        if refused is not None:
            metrics.incr(f"hbm.mesh.join_topk.declined.{refused}")
            return
        if busy:  # the region is being built: this query goes host
            metrics.incr("hbm.mesh.join_topk.declined.pending")
            return

        def bg():
            why = ""
            try:
                groups = loader()
                region, why = build_topk_region(self, shape, *groups, mesh)
                if region is not None and not self._register_join(region, epoch=epoch):
                    why = "budget"
            except Exception:  # noqa: BLE001 - population must never fail a query
                metrics.incr("hbm.mesh.join.populate_failed")
            finally:
                with self._lock:
                    self._pending.discard(pending)
                    if why in ("not_unique", "encoding"):
                        if len(self._topk_refused) >= _MAX_FAILED_MEMO:
                            self._topk_refused.clear()
                        self._topk_refused[key] = why
            if why:
                metrics.incr(f"hbm.mesh.join_topk.declined.{why}")

        t = threading.Thread(target=bg, daemon=True, name="hbm-mesh-topk-populate")
        self._track_for_exit(t)
        t.start()

    def join_topk(self, region, shape):
        """One filtered join-aggregate top-k over ``region`` (module note
        of exec.join_residency): operands, launch, wait, the D2H of the
        chips' candidates and the host's final choice, all in the
        ``scan.join_topk.dispatch`` span. None when the query cannot ride
        (counted ``hbm.mesh.join_topk.declined.<why>``; the caller routes
        host); device errors propagate."""
        from jax import enable_x64

        from .join_residency import finish_topk, mesh_topk_fn, plan_topk

        with _trace_span("scan.join_topk.dispatch", mesh=region.n_devices, k=shape.n):
            got, why = plan_topk(region, shape)
            if got is None:
                metrics.incr(f"hbm.mesh.join_topk.declined.{why}")
                return None
            plan, preds, lits = got
            fn = mesh_topk_fn(region.mesh, plan, preds)
            with enable_x64(True):
                raw = fn(
                    tuple(region.l_cols[c].array for c in plan.l_cols),
                    tuple(region.r_cols[c].array for c in plan.o_cols),
                    region.lo, region.hi, region.dim_index,
                    tuple(region.dim_cols[c].array for c in plan.d_cols),
                    *lits,
                )
                outs = [np.asarray(o) for o in raw]
            nbytes = sum(int(o.nbytes) for o in outs)
            metrics.incr("scan.join_topk.d2h_bytes", nbytes)
            _trace_bytes("d2h_bytes", nbytes)
            out = finish_topk(region, shape, plan, outs)
            if out is None:
                metrics.incr("hbm.mesh.join_topk.declined.ties")
            return out

    # -- the fused scan-aggregate query --------------------------------------
    def agg_scan(self, table: MeshResidentTable, predicate: Expr, group_by, aggs):
        """The mesh device aggregation of an ``agg_scan`` pipeline:
        per-shard predicate mask + dense-key segment partials over the
        full slot space, psum/pmin/pmax into ONE replicated group table
        (exec.scan_agg's shard_map twin — the two-phase distributed
        aggregate with zero shuffles), ONE D2H. Same contract as the
        single-chip twin: ``(batch, "ok")`` or ``(None, reason)``;
        device errors propagate."""
        from jax import enable_x64
        from .hbm_cache import (
            _expr_literals,
            _expr_structure,
            prepare_resident_predicate,
            resident_arrays_for,
            resident_specs_for,
        )
        from .scan_agg import (
            finish_scan_agg,
            mesh_scan_agg_fn,
            plan_plane_names,
            scan_agg_plan,
        )

        plan, reason = scan_agg_plan(table, list(group_by), list(aggs))
        if plan is None:
            return None, reason
        prepared = prepare_resident_predicate(table.columns, predicate)
        if prepared is None:
            return None, "predicate"
        narrowed, names = prepared
        union_names = tuple(
            dict.fromkeys(tuple(names) + plan_plane_names(plan))
        )
        spec_map = tuple(
            zip(union_names, resident_specs_for(table.columns, union_names))
        )
        fn = mesh_scan_agg_fn(
            table.mesh,
            _expr_structure(narrowed),
            names,
            narrowed,
            union_names,
            spec_map,
            plan,
            table.cap,
        )
        cols = dict(
            zip(union_names, resident_arrays_for(table.columns, union_names))
        )
        vals: list = []
        _expr_literals(narrowed, vals)
        lits = np.asarray(vals, dtype=np.int32)
        t0 = time.perf_counter()
        with _trace_span(
            "scan.agg_dispatch",
            tier=getattr(table, "tier", "resident"),
            agg="segment_" + ",".join(sorted({a.fn for a in aggs})),
            span_slots=plan.span,
            mesh=table.n_devices,
        ):
            with enable_x64(True):
                raw = fn(
                    cols, lits, np.asarray(table.dev_rows, dtype=np.int32)
                )
            outs = [np.asarray(o) for o in raw]
        metrics.record_time(
            "scan.resident_agg.mesh_device", time.perf_counter() - t0
        )
        d2h = sum(int(o.nbytes) for o in outs)
        metrics.incr("scan.resident_mesh.d2h_bytes", d2h)
        _trace_bytes("d2h_bytes", d2h)
        batch = finish_scan_agg(table, plan, list(group_by), list(aggs), outs)
        metrics.incr("scan.path.resident_agg_mesh")
        return batch, "ok"

    # -- observability -------------------------------------------------------
    def snapshot(self) -> dict:
        with self._lock:
            return {
                "tables": len(self._tables),
                "deltas": len(self._deltas),
                "joins": len(self._joins),
                "resident_mb": round(
                    (
                        sum(t.nbytes for t in self._tables)
                        + sum(d.nbytes for d in self._deltas)
                        + sum(j.nbytes for j in self._joins)
                    )
                    / 1e6,
                    1,
                ),
                "budget_mb": _budget_bytes() >> 20,
                "per_table": [
                    {
                        "devices": t.n_devices,
                        "rows": t.n_rows,
                        "cap": t.cap,
                        "columns": sorted(t.columns),
                        "mb": round(t.nbytes / 1e6, 1),
                        "tier": getattr(t, "tier", "resident"),
                    }
                    for t in self._tables
                ],
                "per_delta": [
                    {
                        "devices": d.n_devices,
                        "rows": d.n_rows,
                        "cap": d.cap,
                        "columns": sorted(d.columns),
                        "deleted_ids": len(d.deleted_ids),
                        "mb": round(d.nbytes / 1e6, 1),
                    }
                    for d in self._deltas
                ],
            }

mesh_cache = MeshHbmCache()
