"""The streaming block-window tier: device-speed scans over tables whose
(even compressed) predicate planes exceed the HBM budget.

The table's planes live PINNED ON HOST — packed words where the codec
wins (ops.bitpack), raw int32 where it doesn't — pre-sliced into
fixed-size windows. A scan stages windows through a fixed PAIR of HBM
slab slots: while the mask+count executable runs over window k, window
k+1's bytes ride the link into the other slot, so the link and the
compute overlap instead of serializing (the double-buffered H2D ingest
of the PR-6 build pipeline, applied to the query path; Theseus's
storage->device pipeline is the design exemplar). Per-window the device
keeps only the (mask -> per-8192-row-block count) partials; the ONLY
D2H is the per-window count vector — finished results, never operands.

Window geometry: ``window_rows`` (hyperspace.residency.streaming.
windowRows) padded up to a multiple of BLOCK_ROWS, which is itself a
multiple of the mask tile and of every pack word width (vpw is a power
of two <= 32), so window slices land on word boundaries and block
boundaries simultaneously. Pad rows can only add false-positive counts
in tail blocks — the host leg re-evaluates candidate blocks exactly, the
same clipping contract as the resident tiers.

Batching: streaming scans coalesce in the serve micro-batcher like any
resident scan, but only within a WINDOW GENERATION — ``window_gen``
bumps when a device failure tears the slab pair down, so a batch never
spans the discontinuity (serve/batcher folds it into the batch key).

This module is deliberately OUTSIDE exec/ (the HS001 boundary): it is
the one place streaming readbacks and fences live, exactly like the
cache modules are for the resident tiers.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..exec.bytecache import vocab_heap_bytes
from ..ops.bitpack import PackSpec, pack_plain
from ..telemetry.metrics import metrics
from ..telemetry.trace import add_bytes as _trace_bytes
from ..telemetry.trace import span as _trace_span

# window padding grain: BLOCK_ROWS (8192) is a multiple of the mask tile
# (1024) and of every straddle-free word width, so one grain serves the
# count reduction, the tile and the packer simultaneously
_WINDOW_GRAIN = 8192

# an upload that completes under this is a prefetch HIT: the H2D landed
# while the previous window's kernel ran (the overlap working); above it
# the pipeline stalled on the link
_STALL_EPSILON_S = 0.002


@dataclass
class StreamPlane:
    """One host-pinned plane of a streaming column: packed words + spec,
    or a raw int32 flat (spec None). Length is padded to the table's
    window multiple so every window slice is full-size."""

    data: np.ndarray  # int32; words when spec is not None
    spec: Optional[PackSpec] = None


@dataclass
class StreamColumn:
    """Host-side column state; duck-typed against ResidentColumn for
    prepare_resident_predicate (enc / dtype_str / vocab)."""

    dtype_str: str
    enc: str  # 'int' | 'float32' | 'string' | 'f64'
    planes: Dict[str, StreamPlane]  # '' single-plane; 'hi'/'lo' for f64
    nbytes: int  # host bytes (pinned planes + vocab heap)
    vocab: Optional[np.ndarray] = None
    # int-encoded columns: value-space bounds over the real rows (the
    # scan-aggregate planner's input on tables without zone vectors;
    # streaming itself declines aggregation, so these are informational)
    vmin: Optional[int] = None
    vmax: Optional[int] = None


@dataclass
class StreamingResidentTable:
    """A resident-table stand-in at the streaming tier: same identity,
    coverage and zone surface as ResidentTable (the registry, lookup and
    selectivity-gate code paths serve it unchanged), but its planes are
    host-pinned and its budget charge is the SLAB PAIR, not the table."""

    tier = "streaming"

    key: tuple
    files: List[Tuple[str, int, int]]
    n_rows: int
    n_pad: int  # window-multiple padded rows
    window_rows: int
    n_windows: int
    columns: Dict[str, StreamColumn]
    nbytes: int  # budget-charged: 2 windows of operand bytes + vocab
    host_bytes: int  # pinned host planes (reported, not budget-charged)
    raw_nbytes: int  # what the planes would cost raw-resident (obsv.)
    zones: Dict[str, Tuple[str, np.ndarray, np.ndarray]] = field(
        default_factory=dict
    )
    window_gen: int = 0
    last_used: float = field(default_factory=time.monotonic)
    # serializes the window loop: the budget charges exactly ONE slab
    # pair per table, so concurrent scans must take turns — N parallel
    # loops would stage N pairs and blow the oversubscribed margin the
    # tier exists to respect (serve-side, compatible queries coalesce
    # into one loop anyway; only incompatible shapes ever queue here)
    _stream_lock: threading.Lock = field(
        default_factory=threading.Lock, repr=False
    )

    def file_span(self, path: str) -> Optional[Tuple[int, int]]:
        for p, start, n in self.files:
            if p == path:
                return start, start + n
        return None


def window_pad_rows(window_rows: int) -> int:
    return -(-max(int(window_rows), 1) // _WINDOW_GRAIN) * _WINDOW_GRAIN


def build_streaming_table(
    key: tuple,
    spans: List[Tuple[str, int, int]],
    n_rows: int,
    host_planes: dict,
    zones: dict,
    specs: Dict[str, PackSpec],
    window_rows: int,
) -> StreamingResidentTable:
    """Assemble the streaming table from the cache build's host flats.

    ``host_planes`` maps column name -> (dtype_str, enc, vocab, planes)
    where planes maps plane key ('' or 'hi'/'lo') to an int32 flat of
    n_rows values; ``specs`` carries the adopted PackSpec per single-
    plane column (from the tier planner). Packing and window padding
    happen here — the one place the host layout is defined."""
    W = window_pad_rows(window_rows)
    n_pad = -(-n_rows // W) * W
    n_windows = n_pad // W
    columns: Dict[str, StreamColumn] = {}
    host_bytes = 0
    raw_bytes = 0
    window_operand_bytes = 0
    for name, (dtype_str, enc, vocab, planes) in host_planes.items():
        sp: Dict[str, StreamPlane] = {}
        vocab_heap = vocab_heap_bytes(vocab)
        col_bytes = vocab_heap
        for pkey, flat in planes.items():
            raw_bytes += n_pad * 4
            spec = specs.get(name) if pkey == "" else None
            if spec is not None:
                # re-spec over the padded length; pad rows decode to
                # ref0 (in-range garbage the host leg clips)
                spec = dataclasses.replace(spec, n=n_pad)
                padded = np.full(n_pad, spec.ref0, dtype=np.int64)
                padded[:n_rows] = flat[:n_rows]
                words = pack_plain(padded, spec)
                sp[pkey] = StreamPlane(words, spec)
                col_bytes += words.nbytes
                window_operand_bytes += 4 * (W // spec.vpw)
            else:
                padded32 = np.zeros(n_pad, dtype=np.int32)
                padded32[:n_rows] = flat[:n_rows]
                sp[pkey] = StreamPlane(padded32, None)
                col_bytes += padded32.nbytes
                window_operand_bytes += 4 * W
        columns[name] = StreamColumn(dtype_str, enc, sp, col_bytes, vocab)
        host_bytes += col_bytes
    return StreamingResidentTable(
        key,
        spans,
        n_rows,
        n_pad,
        W,
        n_windows,
        columns,
        2 * window_operand_bytes
        + sum(vocab_heap_bytes(c.vocab) for c in columns.values()),
        host_bytes,
        raw_bytes,
        zones,
    )


def _resolve_plane(table: StreamingResidentTable, name: str) -> StreamPlane:
    if "\x00" in name:
        base, pkey = name.split("\x00", 1)
        return table.columns[base].planes[pkey]
    return table.columns[name].planes[""]


def _window_slice(
    plane: StreamPlane, w: int, W: int
) -> Tuple[np.ndarray, Optional[PackSpec]]:
    if plane.spec is None:
        return plane.data[w * W : (w + 1) * W], None
    wspec = dataclasses.replace(plane.spec, n=W)
    vpw = plane.spec.vpw
    return plane.data[w * W // vpw : (w + 1) * W // vpw], wspec


def _upload_window(table, names, w):
    """device_put one window's operand slices — the H2D leg the loop
    overlaps with the previous window's kernel. Returns (cols dict,
    specs tuple aligned with ``names``, bytes)."""
    import jax

    W = table.window_rows
    cols = {}
    specs = []
    nbytes = 0
    for n in names:
        sl, wspec = _window_slice(_resolve_plane(table, n), w, W)
        cols[n] = jax.device_put(sl)
        specs.append(wspec)
        nbytes += int(sl.nbytes)
    _trace_bytes("h2d_bytes", nbytes)  # label at the transfer site
    return cols, tuple(specs), nbytes


def _windowed_counts(table, dispatch, union_names):
    """The double-buffered window loop shared by the single and batched
    entry points. ``dispatch(cols, specs)`` enqueues the window's jitted
    mask+count and returns the un-fetched device result; this loop owns
    the overlap, the prefetch-hit/stall accounting and the generation
    bump on device failure. Returns the per-window numpy results in
    window order."""
    return _run_window_loop(
        table, lambda w: _upload_window(table, union_names, w), dispatch
    )


def _run_window_loop(table, upload, dispatch):
    """The tier's one pipeline loop, shared by the single-chip and mesh
    tables: ``upload(w)`` stages window ``w``'s operand slices into the
    free slab slot (single-chip: one HBM pair; mesh: one pair PER
    DEVICE, the upload device_put'ing (D, window) slices under the shard
    sharding) and returns (cols, specs, bytes)."""
    import jax

    out: list = []
    slots: list = [None, None]
    with table._stream_lock:
        return _windowed_counts_locked(
            table, upload, dispatch, jax, out, slots
        )


def _windowed_counts_locked(table, upload, dispatch, jax, out, slots):
    try:
        t0 = time.perf_counter()
        slots[0] = upload(0)
        metrics.record_time(
            "residency.stream.h2d", time.perf_counter() - t0
        )
        for w in range(table.n_windows):
            cols, specs, up_bytes = slots[w % 2]
            metrics.incr("residency.stream.h2d_bytes", up_bytes)
            # the slot's upload was dispatched while the PREVIOUS window
            # computed; if it is already on device this wait is ~zero
            # (prefetch hit), else the pipeline stalled on the link
            t0 = time.perf_counter()
            jax.block_until_ready(list(cols.values()))
            stall = time.perf_counter() - t0
            if w > 0:
                if stall < _STALL_EPSILON_S:
                    metrics.incr("residency.stream.prefetch_hit")
                else:
                    metrics.incr("residency.stream.prefetch_stall")
                    metrics.record_time("residency.stream.stall", stall)
            pending = dispatch(cols, specs)  # enqueue compute, no fetch
            if w + 1 < table.n_windows:
                t0 = time.perf_counter()
                slots[(w + 1) % 2] = upload(w + 1)
                metrics.record_time(
                    "residency.stream.h2d", time.perf_counter() - t0
                )
            out.append(np.asarray(pending))  # D2H: count partials only
            metrics.incr("residency.stream.windows")
    except Exception:
        # a dead device mid-window tears the slab pair down: bump the
        # generation so in-flight serve batches never span the
        # discontinuity, then let the caller drop the table and latch
        # the query host-side (the resident tiers' exact contract)
        table.window_gen += 1
        metrics.incr("residency.stream.window_failed")
        raise
    return out


def stream_block_counts(table: StreamingResidentTable, predicate):
    """Per-BLOCK_ROWS match counts over the whole streamed table — the
    streaming twin of HbmIndexCache.block_counts. None when the
    predicate cannot ride the resident encodings (caller routes host);
    device errors propagate (caller drops + degrades)."""
    from ..exec.hbm_cache import (
        BLOCK_ROWS,
        _LANES,
        _counts_fn,
        dispatch_span,
        prepare_resident_predicate,
    )
    from ..ops import kernels as K

    prepared = prepare_resident_predicate(table.columns, predicate)
    if prepared is None:
        return None
    narrowed, names = prepared

    def dispatch(cols, specs):
        fn = _counts_fn(
            narrowed, names, table.window_rows // _LANES, False, specs
        )
        with K._x32(), _trace_span("scan.device_launch"):
            return fn([cols[n] for n in names])

    with dispatch_span(table, 1, False):
        parts = _windowed_counts(table, dispatch, names)
        counts = np.concatenate(parts)
        _trace_bytes("d2h_bytes", int(counts.nbytes))
    metrics.incr("scan.resident.d2h_bytes", int(counts.nbytes))
    n_blocks = -(-table.n_rows // BLOCK_ROWS)
    return counts[:n_blocks]


def stream_block_counts_batch(
    table: StreamingResidentTable, predicates, prepared=None
):
    """(N, n_blocks) counts for N compatible predicates, every window
    dispatched ONCE for the whole batch — the streaming leg of the serve
    micro-batcher. None when any predicate fails to narrow."""
    from ..exec.hbm_cache import (
        BLOCK_ROWS,
        _LANES,
        _batched_counts_fn,
        _expr_literals,
        _expr_structure,
        dispatch_span,
        prepare_resident_predicate,
    )
    from ..ops import kernels as K

    if prepared is None:
        prepared = [
            prepare_resident_predicate(table.columns, p) for p in predicates
        ]
    if any(p is None for p in prepared):
        return None
    structures = tuple(_expr_structure(n) for n, _ in prepared)
    slot_names = tuple(names for _, names in prepared)
    exprs = [n for n, _ in prepared]
    union_names = tuple(
        dict.fromkeys(n for names in slot_names for n in names)
    )
    lit_vecs = []
    for narrowed, _ in prepared:
        vals: list = []
        _expr_literals(narrowed, vals)
        lit_vecs.append(np.asarray(vals, dtype=np.int32))
    lit_vecs = tuple(lit_vecs)

    def dispatch(cols, specs):
        spec_map = tuple(zip(union_names, specs))
        fn = _batched_counts_fn(
            structures,
            slot_names,
            exprs,
            table.window_rows // _LANES,
            spec_map,
        )
        with K._x32(), _trace_span("scan.device_launch"):
            return fn(cols, lit_vecs)

    with dispatch_span(table, len(predicates), True):
        parts = _windowed_counts(table, dispatch, union_names)
        counts = np.concatenate(parts, axis=1)
        _trace_bytes("d2h_bytes", int(counts.nbytes))
    metrics.incr("serve.batch.dispatches")
    metrics.incr("serve.batch.queries", len(predicates))
    metrics.incr("scan.resident.d2h_bytes", int(counts.nbytes))
    n_blocks = -(-table.n_rows // BLOCK_ROWS)
    return counts[:, :n_blocks]


# ---------------------------------------------------------------------------
# the MESH streaming rung: host-pinned shard matrices, a slab pair per
# device — the compressed-streaming tier the mesh ladder declined until
# now. Window w stages the (D, W) column slices under the mesh sharding
# (one device_put lands every shard's slab), the shard_map mask+count
# runs per device, and only (D, W // block) count partials come home.
# The budget charge is the PER-DEVICE slab pair times D — two windows of
# operand bytes across the mesh, regardless of table size.
# ---------------------------------------------------------------------------


@dataclass
class MeshStreamingResidentTable:
    """A MeshResidentTable stand-in at the streaming tier: same
    identity, coverage, segments and block geometry (collect_parts and
    the registry serve it unchanged), but its planes are host-pinned
    (D, padded-cap) matrices and the budget charge is the slab pair."""

    tier = "streaming"

    key: tuple
    mesh: object
    n_devices: int
    cap: int  # per-device rows padded to the window multiple
    block: int
    dev_rows: List[int]
    segments: List[List]  # per device, dev_off-ascending (mesh_cache)
    columns: Dict[str, StreamColumn]  # planes hold (D, ...) matrices
    n_rows: int
    n_pad: int  # == n_devices * cap (total padded rows)
    window_rows: int
    n_windows: int
    nbytes: int  # budget-charged: 2 windows of operand bytes (all shards)
    host_bytes: int
    raw_nbytes: int
    window_gen: int = 0
    last_used: float = field(default_factory=time.monotonic)
    _stream_lock: threading.Lock = field(
        default_factory=threading.Lock, repr=False
    )

    @property
    def n_blocks(self) -> int:
        return self.cap // self.block


def build_mesh_streaming_table(
    key: tuple,
    mesh,
    dev_segs,
    dev_rows,
    n_rows: int,
    host_mats: dict,
    specs: Dict[str, PackSpec],
    window_rows: int,
    col_bounds: Optional[dict] = None,
) -> MeshStreamingResidentTable:
    """Assemble the mesh streaming table from the mesh build's host
    (D, cap) matrices. ``host_mats`` maps column name -> (dtype_str,
    enc, vocab, {plane_key: (D, cap) int32 matrix}); ``specs`` carries
    the adopted PackSpec per packable column (global frame, one static
    spec serves every shard — the mesh compressed rule)."""
    D = int(mesh.devices.size)
    some = next(iter(host_mats.values()))
    cap_in = next(iter(some[3].values())).shape[1]
    W = window_pad_rows(window_rows)
    cap = -(-cap_in // W) * W
    n_windows = cap // W
    columns: Dict[str, StreamColumn] = {}
    host_bytes = 0
    raw_bytes = 0
    window_operand_bytes = 0
    for name, (dtype_str, enc, vocab, planes) in host_mats.items():
        sp: Dict[str, StreamPlane] = {}
        vocab_heap = vocab_heap_bytes(vocab)
        col_bytes = vocab_heap
        for pkey, mat in planes.items():
            raw_bytes += D * cap * 4
            spec = specs.get(name) if pkey == "" else None
            if spec is not None:
                # pad rows re-encode at the frame reference (zero pads
                # may sit outside the frame for offset domains — the
                # mesh compressed rule); ref0 pads are in-range garbage
                # the host leg clips
                wspec = dataclasses.replace(spec, n=cap)
                padded = np.full((D, cap), wspec.ref0, dtype=np.int64)
                for d in range(D):
                    padded[d, : dev_rows[d]] = mat[d, : dev_rows[d]]
                words = np.stack(
                    [pack_plain(padded[d], wspec) for d in range(D)]
                )
                sp[pkey] = StreamPlane(words, wspec)
                col_bytes += words.nbytes
                window_operand_bytes += 4 * D * (W // wspec.vpw)
            else:
                padded32 = np.zeros((D, cap), dtype=np.int32)
                padded32[:, :cap_in] = mat
                sp[pkey] = StreamPlane(padded32, None)
                col_bytes += padded32.nbytes
                window_operand_bytes += 4 * D * W
        bounds = (col_bounds or {}).get(name, (None, None))
        columns[name] = StreamColumn(
            dtype_str, enc, sp, col_bytes, vocab, bounds[0], bounds[1]
        )
        host_bytes += col_bytes
    return MeshStreamingResidentTable(
        key,
        mesh,
        D,
        cap,
        min(8192, cap),
        list(dev_rows),
        dev_segs,
        columns,
        n_rows,
        D * cap,
        W,
        n_windows,
        2 * window_operand_bytes
        + sum(vocab_heap_bytes(c.vocab) for c in columns.values()),
        host_bytes,
        raw_bytes,
    )


def _mesh_upload_window(table: MeshStreamingResidentTable, names, w: int):
    """device_put one window's (D, slice) operand matrices under the
    mesh sharding — ONE put per column lands every shard's slab slot."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    sharding = NamedSharding(
        table.mesh, PartitionSpec(table.mesh.axis_names[0], None)
    )
    W = table.window_rows
    cols = {}
    specs = []
    nbytes = 0
    for n in names:
        plane = _resolve_plane(table, n)
        if plane.spec is None:
            sl = plane.data[:, w * W : (w + 1) * W]
            wspec = None
        else:
            vpw = plane.spec.vpw
            sl = plane.data[:, w * W // vpw : (w + 1) * W // vpw]
            wspec = dataclasses.replace(plane.spec, n=W)
        cols[n] = jax.device_put(np.ascontiguousarray(sl), sharding)
        specs.append(wspec)
        nbytes += int(sl.nbytes)
    _trace_bytes("h2d_bytes", nbytes)  # label at the transfer site
    return cols, tuple(specs), nbytes


def mesh_stream_block_counts(table: MeshStreamingResidentTable, predicate):
    """(D, n_blocks) match counts over the streamed mesh shards — the
    streaming twin of MeshHbmCache.block_counts. None when the
    predicate cannot ride the resident encodings; device errors
    propagate (caller drops + degrades)."""
    from ..exec.hbm_cache import dispatch_span, prepare_resident_predicate
    from ..exec.mesh_cache import _mesh_counts_fn
    from ..ops import kernels as K

    prepared = prepare_resident_predicate(table.columns, predicate)
    if prepared is None:
        return None
    narrowed, names = prepared

    def dispatch(cols, specs):
        fn = _mesh_counts_fn(
            table.mesh,
            repr(narrowed),
            narrowed,
            names,
            table.window_rows,
            table.block,
            specs,
        )
        with K._x32(), _trace_span("scan.device_launch"):
            return fn(cols)

    with dispatch_span(table, 1, False, mesh=table.n_devices):
        parts = _run_window_loop(
            table, lambda w: _mesh_upload_window(table, names, w), dispatch
        )
        counts = np.concatenate(parts, axis=1)
        _trace_bytes("d2h_bytes", int(counts.nbytes))
    metrics.incr("scan.resident_mesh.d2h_bytes", int(counts.nbytes))
    return counts


def mesh_stream_block_counts_batch(
    table: MeshStreamingResidentTable,
    predicates,
    prepared=None,
    metric_ns: str = "serve.batch",
):
    """Per-predicate (D, n_blocks) counts for N compatible predicates,
    every window dispatched ONCE for the whole batch — the mesh
    streaming leg of the serve micro-batcher and (N=1) the compiled
    mesh scan pipeline. None when any predicate fails to narrow."""
    from ..exec.hbm_cache import (
        _expr_literals,
        _expr_structure,
        dispatch_span,
        prepare_resident_predicate,
    )
    from ..exec.mesh_cache import _mesh_batched_counts_fn
    from ..ops import kernels as K

    if prepared is None:
        prepared = [
            prepare_resident_predicate(table.columns, p) for p in predicates
        ]
    if any(p is None for p in prepared):
        return None
    structures = tuple(_expr_structure(n) for n, _ in prepared)
    slot_names = tuple(names for _, names in prepared)
    exprs = [n for n, _ in prepared]
    union_names = tuple(
        dict.fromkeys(n for names in slot_names for n in names)
    )
    lit_vecs = []
    for narrowed, _ in prepared:
        vals: list = []
        _expr_literals(narrowed, vals)
        lit_vecs.append(np.asarray(vals, dtype=np.int32))
    lit_vecs = tuple(lit_vecs)

    def dispatch(cols, specs):
        spec_map = tuple(zip(union_names, specs))
        fn = _mesh_batched_counts_fn(
            table.mesh,
            structures,
            slot_names,
            exprs,
            table.window_rows,
            table.block,
            spec_map,
        )
        with K._x32(), _trace_span("scan.device_launch"):
            return fn(cols, lit_vecs)

    with dispatch_span(table, len(predicates), True, mesh=table.n_devices):
        parts = _run_window_loop(
            table,
            lambda w: _mesh_upload_window(table, union_names, w),
            dispatch,
        )
        # per-window (D, N, W // block) -> (D, N, blocks) -> predicate-major
        counts = np.concatenate(parts, axis=2)
        _trace_bytes("d2h_bytes", int(counts.nbytes))
    metrics.incr(f"{metric_ns}.dispatches")
    metrics.incr(f"{metric_ns}.queries", len(predicates))
    metrics.incr("scan.resident_mesh.d2h_bytes", int(counts.nbytes))
    return np.swapaxes(counts, 0, 1)
