"""The index-build kernels: hash-bucketize + shuffle + per-bucket sort.

These are HOT LOOPS #1 and #2 of the reference's create path
(SURVEY.md §3.1): Spark's ``repartition(numBuckets, indexedCols)`` shuffle
(CreateActionBase.scala:129-130) and the per-bucket sort inside
``saveWithBuckets`` (DataFrameWriterExtensions.scala:49-72), re-expressed
as XLA programs:

* single-device: one fused ``lax.sort`` by (bucket, key...) — the bucket id
  is the leading sort key, so partitioning and per-bucket ordering happen
  in a single O(n log n) device sort;
* multi-device: ``shard_map`` over the bucket mesh axis — local bucketize,
  group by destination into fixed-capacity blocks, ``all_to_all`` over
  ICI (replacing Spark's netty shuffle service), then the same local
  (bucket, key...) sort. Bucket b lands on device ``b % n_devices``
  (parallel.mesh.owner_of_bucket). On one controller only the sort words
  and row ids travel, and the host takes the rows in the order that comes
  back.

Static shapes throughout: the exchange uses a host-computed per-(src,dst)
capacity so XLA sees fixed block sizes; validity is a boolean mask, and
invalid rows sort to the end via an out-of-range bucket key.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

from ..exceptions import HyperspaceException
from ..parallel.mesh import owner_of_bucket_array, owner_of_bucket_device
from ..storage.columnar import Column, ColumnarBatch, is_string
from ..telemetry.metrics import metrics
from . import ensure_x64
from .hashing import bucket_ids_host, fnv1a64, hash32_device, key_repr

ensure_x64()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax import lax  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec  # noqa: E402


# ---------------------------------------------------------------------------
# device-side key representation (twin of hashing.key_repr)
# ---------------------------------------------------------------------------
def vocab_hashes(col: Column) -> Optional[np.ndarray]:
    """Per-dictionary-entry FNV hashes for a string column (host, O(vocab));
    gathered on device through the codes."""
    if not is_string(col.dtype_str):
        return None
    return np.array([fnv1a64(v) for v in col.vocab], dtype=np.uint64).astype(np.int64)


def key_repr_device(arr, dtype_str: str, vhash=None):
    """int64 key representation on device (twin of hashing.key_repr).

    float64 columns arrive already encoded as ordered int64 (the device
    transport format, ops.floatbits) — their repr is the identity, matching
    the host key_repr which applies the same encoding."""
    if is_string(dtype_str):
        if vhash is None:
            raise HyperspaceException("String key column needs vocab hashes.")
        safe = jnp.clip(arr, 0, max(int(vhash.shape[0]) - 1, 0))
        gathered = vhash[safe] if int(vhash.shape[0]) else jnp.zeros_like(arr, jnp.int64)
        return jnp.where(arr >= 0, gathered, jnp.int64(-1))
    if dtype_str == "float64":
        if arr.dtype != jnp.int64:
            raise HyperspaceException(
                "float64 must be pre-encoded to ordered int64 before device "
                "transport (ops.floatbits)."
            )
        return arr
    if dtype_str == "float32":
        a = jnp.where(arr == 0.0, jnp.zeros_like(arr), arr)
        return lax.bitcast_convert_type(a, jnp.int32).astype(jnp.int64)
    return arr.astype(jnp.int64)


def encode_for_device(col: Column) -> np.ndarray:
    """Host buffer in device transport encoding (float64 → ordered int64;
    everything else raw). Same encoding ColumnarBatch.device_arrays applies."""
    if col.dtype_str == "float64":
        from .floatbits import f64_to_ordered_i64

        return f64_to_ordered_i64(col.data)
    return col.data


def decode_from_device(dtype_str: str, arr: np.ndarray) -> np.ndarray:
    from ..storage.columnar import decode_device_array

    return decode_device_array(dtype_str, arr)


def device_bucket_ids(
    arrays: Dict[str, "jax.Array"],
    dtypes: Dict[str, str],
    key_names: List[str],
    vhashes: Dict[str, "jax.Array"],
    num_buckets: int,
):
    reprs = [
        key_repr_device(arrays[k], dtypes[k], vhashes.get(k)) for k in key_names
    ]
    return (hash32_device(reprs) % jnp.uint32(num_buckets)).astype(jnp.int32)


# ---------------------------------------------------------------------------
# single-device build kernel
# ---------------------------------------------------------------------------
def _ordered_sort_operand(x):
    """Order-preserving integer view of a float sort operand, matching
    ops.floatbits' HOST encodings bit-for-bit (including the -0.0
    canonicalization): without it, lax.sort total-orders -0.0 strictly
    before +0.0 while the host twin treats them as equal ties kept in
    input order — the two engines would emit different row orders for
    float keys containing both zeros. Integers pass through."""
    if x.dtype == jnp.float32:
        x = jnp.where(x == jnp.float32(0.0), jnp.float32(0.0), x)
        bits = lax.bitcast_convert_type(x, jnp.int32)
        top = jnp.int32(-(2**31))
        return jnp.where(bits < 0, jnp.bitwise_xor(~bits, top), bits)
    if x.dtype == jnp.float64:
        x = jnp.where(x == jnp.float64(0.0), jnp.float64(0.0), x)
        bits = lax.bitcast_convert_type(x, jnp.int64)
        top = jnp.int64(-(2**63))
        return jnp.where(bits < 0, jnp.bitwise_xor(~bits, top), bits)
    return x


def _sort_by_bucket_and_keys(
    arrays: Dict[str, "jax.Array"],
    bucket,
    key_names: List[str],
    num_buckets: int,
):
    """Fused partition+sort: one lax.sort keyed on (bucket, keys..., iota).
    Returns (sorted arrays incl. bucket, per-bucket counts, permutation).
    Float key operands compare through their ordered-int encodings (see
    _ordered_sort_operand) so order and ties match the host twin."""
    n = bucket.shape[0]
    iota = lax.iota(jnp.int32, n)
    operands = (
        [bucket]
        + [_ordered_sort_operand(arrays[k]) for k in key_names]
        + [iota]
    )
    sorted_ops = lax.sort(operands, num_keys=1 + len(key_names))
    perm = sorted_ops[-1]
    out = {name: arr[perm] for name, arr in arrays.items()}
    counts = jnp.bincount(bucket, length=num_buckets)
    return out, sorted_ops[0], counts, perm


# The jitted programs carry stable names (jax names the module
# ``jit_<function name>``): ``build_bucket_sort``, ``_packed`` and
# ``_staged`` (bucketize + sort) and ``build_sort_merge_runs`` (the
# on-device run merge), so a device profile tells them apart.
#
# One jitted closure per (key schema, keys, num_buckets): jax.jit caches
# by function object, so a closure defined inside build_partition_single
# would RETRACE on every call — the persistent compile cache saves the
# XLA compile but the per-call retrace (~100ms+) was still charged to
# every streamed chunk. Array shapes
# vary freely under one cached closure (jit's own shape cache).
_single_kernel_cache: dict = {}


def _packed_minmax(arr: np.ndarray) -> Optional[Tuple[int, int]]:
    """(min, max) of a padded transport buffer as Python ints, or None
    for shapes the packed kernel declines: float32 travels raw (its
    device sort operand is a bit transform — bounding it on host would
    cost the very O(n) transform the pack exists to avoid) and uint64
    values beyond int64 (the int64 composite bias would wrap)."""
    if arr.dtype == np.float32 or arr.dtype == np.float64:
        return None
    if arr.size == 0:
        return None
    mn, mx = int(arr.min()), int(arr.max())
    if mx > (1 << 63) - 1 or mn < -(1 << 63):
        return None
    return mn, mx


def _pack_plan(
    bounds: List[Tuple[int, int]], bucket_bits: int
) -> Optional[List[Tuple[int, int]]]:
    """[(min, bits)] per key for the (bucket, keys...) radix pack, or
    None when ``bucket_bits`` plus the key widths don't fit 63 bits.
    THE one copy of the bit-budget rule — _pack_sort_keys (host) and
    build_partition_single (device) both size their composites here;
    they differ only in the bucket ceiling they pass (the device kernel
    must also fit the ``num_buckets`` invalid-row marker). Spans compute
    in Python ints — narrow-dtype-safe."""
    total_bits = bucket_bits
    plan: List[Tuple[int, int]] = []
    for mn, mx in bounds:
        kb = max(mx - mn, 1).bit_length()
        total_bits += kb
        if total_bits > 63:
            return None
        plan.append((mn, kb))
    return plan


def _single_perm_kernel(dtypes_key: tuple, key_names: tuple, num_buckets: int):
    """Permutation-returning sort kernel: uploads ONLY key columns and
    ships home a 4-byte-per-row permutation + bucket counts. The sorted
    VALUE columns never transit the link — the host applies one gather
    to data it already holds. Transfers drop from O(all columns × up +
    all columns × down) to O(keys up + 4B/row down): the device engine's
    floor on thin links is the transfer, not the sort."""
    cache_key = ("perm", dtypes_key, key_names, num_buckets)
    fn = _single_kernel_cache.get(cache_key)
    if fn is not None:
        return fn
    dtypes = dict(dtypes_key)
    keys = list(key_names)

    @jax.jit
    def build_bucket_sort(arrays, vh, n_valid):
        bucket = device_bucket_ids(arrays, dtypes, keys, vh, num_buckets)
        m = bucket.shape[0]
        bucket = jnp.where(
            lax.iota(jnp.int32, m) < n_valid, bucket, num_buckets
        )
        # XLA dead-code-eliminates the unused gathered outputs: only the
        # permutation and counts leave the device
        _out, _sb, counts, perm = _sort_by_bucket_and_keys(
            arrays, bucket, keys, num_buckets
        )
        return perm, counts

    if len(_single_kernel_cache) >= 64:
        _single_kernel_cache.pop(next(iter(_single_kernel_cache)))
    _single_kernel_cache[cache_key] = build_bucket_sort
    return build_bucket_sort


def _narrow_pack(plan: List[Tuple[int, int]], num_buckets: int) -> bool:
    """Whether a pack plan's (bucket, keys...) composite fits a
    non-negative int32. The TPU emulates int64 compares with 32-bit word
    pairs, so an int32 sort operand compiles about three times faster
    (v5e AOT at 64K rows: 15 s against 45 s) and sorts fewer words; the
    permutation is the same, since the pack is order-preserving either
    way."""
    bits = max(int(num_buckets), 1).bit_length() + sum(kb for _, kb in plan)
    return bits <= 31


def _pack_composite(arrays, keys, bucket, mins, shifts, narrow: bool):
    """The (bucket, key1-min1, ...) composite, int32 when ``narrow``."""
    ctype = jnp.int32 if narrow else jnp.int64
    packed = bucket.astype(ctype)
    for i, k in enumerate(keys):
        enc = _ordered_sort_operand(arrays[k]).astype(jnp.int64)
        packed = jnp.left_shift(packed, shifts[i].astype(ctype))
        packed = jnp.bitwise_or(packed, (enc - mins[i]).astype(ctype))
    return packed


def _single_perm_kernel_packed(
    dtypes_key: tuple, key_names: tuple, num_buckets: int, narrow: bool
):
    """Radix-partition twin of _single_perm_kernel: bit-packs
    (bucket, key1-min1, key2-min2, …) into ONE int64 sort operand —
    the device analog of build_partition_host's composite fast path —
    so lax.sort compares a single key instead of a 1+len(keys)-operand
    lexicographic comparator. Mins and shift widths enter as DEVICE
    OPERANDS (they vary chunk to chunk), so one compiled executable
    serves every chunk; whether the widths fit 63 bits is the host-side
    routing decision in build_partition_single. Order and stability are
    bit-identical to the unpacked kernel: the pack is order-preserving
    and iota remains the tie-break payload of a stable sort."""
    cache_key = ("perm-packed", dtypes_key, key_names, num_buckets, narrow)
    fn = _single_kernel_cache.get(cache_key)
    if fn is not None:
        return fn
    dtypes = dict(dtypes_key)
    keys = list(key_names)

    @jax.jit
    def build_bucket_sort_packed(arrays, vh, n_valid, mins, shifts):
        bucket = device_bucket_ids(arrays, dtypes, keys, vh, num_buckets)
        m = bucket.shape[0]
        iota = lax.iota(jnp.int32, m)
        bucket = jnp.where(iota < n_valid, bucket, num_buckets)
        packed = _pack_composite(arrays, keys, bucket, mins, shifts, narrow)
        _packed_sorted, perm = lax.sort([packed, iota], num_keys=1)
        counts = jnp.bincount(bucket, length=num_buckets)
        return perm, counts

    if len(_single_kernel_cache) >= 64:
        _single_kernel_cache.pop(next(iter(_single_kernel_cache)))
    _single_kernel_cache[cache_key] = build_bucket_sort_packed
    return build_bucket_sort_packed


def build_partition_single(
    batch: ColumnarBatch,
    key_names: List[str],
    num_buckets: int,
    pad_to: Optional[int] = None,
    defer: bool = False,
):
    """Single-device HOT LOOP: returns the batch reordered so rows are
    grouped by bucket (ascending) and sorted by the key columns within each
    bucket, plus per-bucket row counts.

    Rows are padded to the next power of two and the true row count enters
    the kernel as a *device scalar*, so one compiled executable (tens of
    seconds of TPU compile through the AOT helper) serves every dataset
    size in a 2x band — only (schema, keys, num_buckets, padded size)
    recompile. Pad rows get bucket id ``num_buckets`` and sort to the tail,
    where the host slice drops them.

    ``pad_to`` pins the padded size explicitly: the streaming build feeds
    fixed-capacity chunks so EVERY chunk (including the short tail) reuses
    one compiled executable — the steady-state throughput path.

    ``defer=True`` returns a zero-arg ``finish()`` callable instead of the
    result: the kernel is dispatched (async — JAX returns futures) and
    ``finish`` performs the blocking permutation fetch + the host gather.
    The streaming writer calls finish() on its spill thread so D2H
    overlaps the next chunk's H2D + compute. Only the KEY columns are
    uploaded and only the 4-byte-per-row sort permutation comes back —
    value columns never transit the link (4–6x less transfer than
    shipping sorted columns; on thin links the transfer IS the device
    engine's cost)."""
    dtypes = batch.schema()
    n = batch.num_rows
    from ..utils.intmath import next_pow2

    n_pad = pad_to if pad_to is not None else next_pow2(n)
    if n_pad < n:
        raise HyperspaceException(f"pad_to={n_pad} smaller than batch rows {n}.")
    # keys ONLY cross the link (see _single_perm_kernel)
    host_bufs = {
        k: np.pad(encode_for_device(batch.columns[k]), (0, n_pad - n))
        for k in key_names
    }
    arrays = {k: jnp.asarray(b) for k, b in host_bufs.items()}
    if defer:
        # streaming-writer dispatch: account the link both ways so the
        # staged path's R-fold D2H reduction is measurable
        metrics.incr(
            "build.stream.h2d_bytes",
            sum(int(b.nbytes) for b in host_bufs.values()),
        )
    vh = {
        k: jnp.asarray(vocab_hashes(batch.columns[k]))
        for k in key_names
        if is_string(dtypes[k])
    }
    n_dev = jnp.asarray(n, dtype=jnp.int32)
    key_dtypes = tuple(sorted((k, dtypes[k]) for k in key_names))
    # radix-pack routing: when every key's padded transport buffer bounds
    # to a 63-bit (bucket, keys…) composite, the single-operand packed
    # sort runs instead of the multi-operand comparator sort — same
    # permutation, fewer sort operands. The min/max host pass is one
    # bandwidth-bound sweep over buffers the pad already materialized.
    bounds = [_packed_minmax(host_bufs[k]) for k in key_names]
    plan = (
        _pack_plan(bounds, max(int(num_buckets), 1).bit_length())
        if all(b is not None for b in bounds)
        else None
    )
    if plan is not None:
        mins_dev = jnp.asarray(
            np.array([mn for mn, _ in plan], dtype=np.int64)
        )
        shifts_dev = jnp.asarray(
            np.array([kb for _, kb in plan], dtype=np.int32)
        )
        kernel = _single_perm_kernel_packed(
            key_dtypes,
            tuple(key_names),
            num_buckets,
            _narrow_pack(plan, num_buckets),
        )
        metrics.incr("build.engine.device_radix")
        perm_dev, counts_dev = kernel(arrays, vh, n_dev, mins_dev, shifts_dev)
    else:
        kernel = _single_perm_kernel(key_dtypes, tuple(key_names), num_buckets)
        metrics.incr("build.engine.device_sortfull")
        perm_dev, counts_dev = kernel(arrays, vh, n_dev)

    def finish() -> Tuple[ColumnarBatch, np.ndarray]:
        counts = np.asarray(counts_dev)[:num_buckets]
        perm = np.asarray(perm_dev)[:n].astype(np.int64, copy=False)
        if defer:
            # one blocking device round trip per chunk — the call count
            # the staged run merge divides by RUN_CHUNKS
            metrics.incr("build.stream.d2h_calls")
            metrics.incr(
                "build.stream.d2h_bytes", 4 * n_pad + 8 * num_buckets
            )
        out = batch.take(perm)
        for name, col in out.columns.items():
            if col.dtype_str == "float64":
                # match the host twin and the old transit-encoded path:
                # the f64 ordered-int64 encoding canonicalizes -0.0
                out.columns[name] = Column(
                    col.dtype_str,
                    np.where(col.data == 0.0, 0.0, col.data),
                    col.vocab,
                )
        return out, counts

    return finish if defer else finish()


# ---------------------------------------------------------------------------
# device-resident run staging (docs/14-build-pipeline.md, device build)
# ---------------------------------------------------------------------------
def _single_staged_kernel_packed(
    dtypes_key: tuple, key_names: tuple, num_buckets: int, narrow: bool
):
    """Run-staging twin of _single_perm_kernel_packed: same fused
    bucketize + radix pack + single-operand sort, but the sorted packed
    COMPOSITE stays on device alongside the permutation — the merge
    operand of the on-device run merge (_staged_merge_fn). Nothing is
    fetched here; the only D2H the staged path ever pays is the run's
    order, one call per run of up to ``RUN_CHUNKS`` chunks
    (index/stream_builder.py). The true row count enters as the device
    scalar ``n_valid``, so one compiled program serves full chunks and
    the partial tail: pad rows take bucket ``num_buckets`` (which the
    pack plan budgets for), sort last and are not counted."""
    cache_key = ("perm-packed-staged", dtypes_key, key_names, num_buckets, narrow)
    fn = _single_kernel_cache.get(cache_key)
    if fn is not None:
        return fn
    dtypes = dict(dtypes_key)
    keys = list(key_names)

    @jax.jit
    def build_bucket_sort_staged(arrays, vh, n_valid, mins, shifts):
        bucket = device_bucket_ids(arrays, dtypes, keys, vh, num_buckets)
        m = bucket.shape[0]
        iota = lax.iota(jnp.int32, m)
        bucket = jnp.where(iota < n_valid, bucket, num_buckets)
        packed = _pack_composite(arrays, keys, bucket, mins, shifts, narrow)
        packed_sorted, perm = lax.sort([packed, iota], num_keys=1)
        # the run merge unpacks int64 composites whatever the chunk's width
        packed_sorted = packed_sorted.astype(jnp.int64)
        counts = jnp.bincount(bucket, length=num_buckets)
        return packed_sorted, perm, counts

    if len(_single_kernel_cache) >= 64:
        _single_kernel_cache.pop(next(iter(_single_kernel_cache)))
    _single_kernel_cache[cache_key] = build_bucket_sort_staged
    return build_bucket_sort_staged


def _staged_merge_fn(nkeys: int, narrow: bool):
    """The on-device k-way run merge: takes R staged chunks' sorted
    composites (each packed with its own chunk plan), normalizes them
    onto ONE run-level plan — unpack with the chunk's mins/shifts,
    re-bias, re-pack with the run's — and merges them with ONE stable
    sort of the R runs flattened in chunk order, each row's original
    index riding as payload. Stability makes ties keep concatenation
    order: the left chunk wins and in-run order holds, exactly as the
    host merge_sorted_orders resolves them. The sort operand is int32
    when ``narrow`` (the run plan fits 31 bits, the _narrow_pack rule)
    and int64 otherwise. Chunk and run mins/shifts are DEVICE OPERANDS,
    so one compiled program serves every run of a given (chunk count,
    key count, width) shape. Returns (global row order into the R
    concatenated original chunks, summed per-bucket counts) — the run's
    ONLY D2H."""
    cache_key = ("staged-merge", nkeys, narrow)
    fn = _single_kernel_cache.get(cache_key)
    if fn is not None:
        return fn

    @jax.jit
    def build_sort_merge_runs(
        packed, perms, counts, cmins, cshifts, rmins, rshifts
    ):
        r, cap = packed.shape
        rem = packed
        fields: List = []
        for i in range(nkeys - 1, -1, -1):
            s = cshifts[:, i : i + 1].astype(jnp.int64)
            mask = jnp.left_shift(jnp.int64(1), s) - jnp.int64(1)
            fields.append(jnp.bitwise_and(rem, mask) + cmins[:, i : i + 1])
            rem = jnp.right_shift(rem, s)
        fields.reverse()
        comp = rem  # what remains above the key fields is the bucket id
        for i in range(nkeys):
            comp = jnp.bitwise_or(
                jnp.left_shift(comp, rshifts[i].astype(jnp.int64)),
                fields[i] - rmins[i],
            )
        base = jnp.arange(r, dtype=jnp.int64)[:, None] * jnp.int64(cap)
        orig = (base + perms.astype(jnp.int64)).astype(jnp.int32)
        if narrow:
            comp = comp.astype(jnp.int32)
        _keys, order = lax.sort(
            [comp.reshape(-1), orig.reshape(-1)], num_keys=1, is_stable=True
        )
        return order, counts.sum(axis=0)

    if len(_single_kernel_cache) >= 64:
        _single_kernel_cache.pop(next(iter(_single_kernel_cache)))
    _single_kernel_cache[cache_key] = build_sort_merge_runs
    return build_sort_merge_runs


class StagedChunk:
    """One device-resident sorted chunk awaiting its run merge: the
    packed composite and permutation stay in HBM; the host keeps only
    the pack plan (for the merge's unpack operands). The HBM footprint
    is charged up front by the writer's all-or-nothing slab reservation
    (_DeviceRunStager.ensure_reserved), not per chunk."""

    __slots__ = ("packed", "perm", "counts", "plan")

    def __init__(self, packed, perm, counts, plan):
        self.packed = packed
        self.perm = perm
        self.counts = counts
        self.plan = plan


def stage_encode(
    batch: ColumnarBatch, key_names: List[str]
) -> Tuple[Dict[str, np.ndarray], Optional[List[Tuple[int, int]]]]:
    """Host transport buffers + per-key (min, max) bounds of a full
    chunk — the staged path's routing input, computed BEFORE any upload
    so an ineligible chunk never touches the device. ``bounds`` is None
    when any key declines the 63-bit pack (float32 raw transport,
    uint64 beyond int64); the encoded buffers are still returned so the
    per-chunk fallback can reuse them if it wants."""
    encoded = {k: encode_for_device(batch.columns[k]) for k in key_names}
    bounds = []
    for k in key_names:
        b = _packed_minmax(encoded[k])
        if b is None:
            return encoded, None
        bounds.append(b)
    return encoded, bounds


def run_pack_plan(
    bounds: List[Tuple[int, int]], num_buckets: int
) -> Optional[List[Tuple[int, int]]]:
    """The RUN-level pack plan over accumulated per-chunk bound unions —
    the same _pack_plan budget rule (and the same bucket ceiling) the
    per-chunk kernels use, so chunk and run composites carry identical
    field layouts. None = the union span overflows 63 bits and the
    pending run must flush before this chunk starts a fresh one."""
    return _pack_plan(bounds, max(int(num_buckets), 1).bit_length())


def stage_chunk_packed(
    host_bufs: Dict[str, np.ndarray],
    dtypes: Dict[str, str],
    key_names: List[str],
    num_buckets: int,
    plan: List[Tuple[int, int]],
    n_valid: int,
) -> Tuple[StagedChunk, int]:
    """Dispatch one chunk through the staged kernel and leave its sorted
    composite + permutation resident on device. ``host_bufs`` are the
    chunk's capacity-sized transport buffers — a slot of the writer's
    slab pair (pre-staged, pinnable, reused every other chunk) — whose
    first ``n_valid`` rows are real; the rest hold an in-range key and
    sort last. Returns the staged handle and the H2D byte count. Caller
    guarantees: no string key columns, ``plan`` fits 63 bits."""
    h2d_bytes = 0
    arrays = {}
    for k in key_names:
        buf = host_bufs[k]
        arrays[k] = jax.device_put(buf)
        h2d_bytes += int(buf.nbytes)
    key_dtypes = tuple(sorted((k, dtypes[k]) for k in key_names))
    mins_dev = jnp.asarray(np.array([mn for mn, _ in plan], dtype=np.int64))
    shifts_dev = jnp.asarray(np.array([kb for _, kb in plan], dtype=np.int32))
    kernel = _single_staged_kernel_packed(
        key_dtypes, tuple(key_names), num_buckets, _narrow_pack(plan, num_buckets)
    )
    metrics.incr("build.engine.device_radix")
    n_dev = jnp.asarray(n_valid, dtype=jnp.int32)
    packed, perm, counts = kernel(arrays, {}, n_dev, mins_dev, shifts_dev)
    return StagedChunk(packed, perm, counts, plan), h2d_bytes


def merge_staged_chunks(
    staged: List[StagedChunk],
    run_plan: List[Tuple[int, int]],
    num_buckets: int,
):
    """Dispatch the on-device merge of R staged chunks into one sorted
    run and issue its D2H NON-BLOCKING (copy_to_host_async): the bytes
    ride the link while the next chunk's
    kernel runs, and the spill-compute worker's blocking fetch finds
    them already landing. Returns the un-fetched (order, counts) device
    arrays; order indexes the concatenation of the R original chunks.
    A run of one chunk is already sorted: its own permutation and counts
    ship, and no merge program compiles."""
    if len(staged) == 1:
        order_dev, counts_dev = staged[0].perm, staged[0].counts
        for arr in (order_dev, counts_dev):
            arr.copy_to_host_async()
        return order_dev, counts_dev
    nkeys = len(run_plan)
    packed = jnp.stack([s.packed for s in staged])
    perms = jnp.stack([s.perm for s in staged])
    counts = jnp.stack([s.counts for s in staged])
    cmins = jnp.asarray(
        np.array([[mn for mn, _ in s.plan] for s in staged], dtype=np.int64)
    )
    cshifts = jnp.asarray(
        np.array([[kb for _, kb in s.plan] for s in staged], dtype=np.int32)
    )
    rmins = jnp.asarray(np.array([mn for mn, _ in run_plan], dtype=np.int64))
    rshifts = jnp.asarray(np.array([kb for _, kb in run_plan], dtype=np.int32))
    narrow = _narrow_pack(run_plan, num_buckets)
    if narrow:
        metrics.incr("build.device.merge_narrow")
    fn = _staged_merge_fn(nkeys, narrow)
    order_dev, counts_dev = fn(
        packed, perms, counts, cmins, cshifts, rmins, rshifts
    )
    for arr in (order_dev, counts_dev):
        arr.copy_to_host_async()
    return order_dev, counts_dev


def _pack_sort_keys(
    encs: List[np.ndarray],
    bucket: Optional[np.ndarray],
    num_buckets: int,
) -> Optional[np.ndarray]:
    """Bit-pack (bucket?, enc1-min1, enc2-min2, …) into one int64 whose
    ascending order equals the lexicographic order of the inputs, or None
    when the widths don't fit 63 bits (caller falls back to lexsort).
    The budget rule lives in _pack_plan (shared with the device radix
    kernel); stability of the single argsort preserves tie order exactly
    like lexsort."""
    if not encs or not len(encs[0]):
        return None
    bounds = []
    i64_max, i64_min = (1 << 63) - 1, -(1 << 63)
    for e in encs:
        mn = int(e.min())
        mx = int(e.max())
        if mx > i64_max or mn < i64_min:
            return None  # uint64 beyond int64: the bias cast would raise
        bounds.append((mn, mx))
    bucket_bits = (
        max(int(num_buckets - 1), 1).bit_length() if bucket is not None else 0
    )
    plan = _pack_plan(bounds, bucket_bits)
    if plan is None:
        return None
    comp = (
        bucket.astype(np.int64)
        if bucket is not None
        else np.zeros(len(encs[0]), dtype=np.int64)
    )
    for e, (mn, kb) in zip(encs, plan):
        comp = (comp << np.int64(kb)) | (e.astype(np.int64) - np.int64(mn))
    return comp


def build_partition_host(
    batch: ColumnarBatch,
    key_names: List[str],
    num_buckets: int,
) -> Tuple[ColumnarBatch, np.ndarray]:
    """Host twin of build_partition_single: identical output (same hash,
    same (bucket, keys…) order, same stable tie-break) computed with one
    numpy lexsort — no device round trip.

    Exists for the streaming build's measured engine routing: on hosts
    whose device link is thin the D2H readback of
    every sorted chunk dominates the pipeline, and the honest answer — as
    with the join's path routing — is to measure both engines and take the
    faster, recording the choice in metrics."""
    from ..index.stream_builder import sort_encoding
    from .hashing import bucket_ids_host, key_repr

    bucket = bucket_ids_host(
        [key_repr(batch.columns[k]) for k in key_names], num_buckets
    )
    # lexsort: LAST key is primary → (keyN … key1, bucket); stable, so ties
    # keep original order exactly like the device kernel's iota tie-break.
    # Fast path: pack (bucket, key1-min1, key2-min2, …) into ONE int64 and
    # run one stable argsort — numpy's stable int sort is radix, and one
    # composite pass measures ~2x faster than the multi-key lexsort (the
    # spill pipeline's hottest host work at scale). Only when the packed
    # width fits 63 bits; ties and order are bit-identical to lexsort.
    encs = [sort_encoding(batch.columns[k]) for k in key_names]
    order = None
    comp = _pack_sort_keys(encs, bucket, num_buckets)
    if comp is not None:
        order = np.argsort(comp, kind="stable")
    if order is None:
        order = np.lexsort(tuple(reversed(encs)) + (bucket,))
    counts = np.bincount(bucket, minlength=num_buckets).astype(np.int64)
    out = batch.take(order)
    _canonicalize_f64(out)
    return out, counts


def _canonicalize_f64(out: ColumnarBatch) -> None:
    """-0.0 → +0.0 on float64 columns, matching the device transport
    encoding (ops.floatbits): every engine must produce identical bytes."""
    for name, col in out.columns.items():
        if col.dtype_str == "float64":
            out.columns[name] = Column(
                col.dtype_str, np.where(col.data == 0.0, 0.0, col.data)
            )


def merge_sorted_orders(
    runs: List[Tuple[np.ndarray, np.ndarray]],
) -> np.ndarray:
    """Merge per-run (sorted_keys, row_indices) pairs into one global
    row-index order, STABLY: ties keep run order (run i's rows before
    run j's for i < j), exactly like a stable argsort over the
    concatenation. Pairwise searchsorted tournament — every pass is a
    handful of vectorized O(m log m) binary-search merges instead of the
    full O(n log n) re-sort the old concat+lexsort paid; this is the
    shared engine of merge_sorted_runs (finalize) and the multi-core
    host partition."""
    runs = [r for r in runs if len(r[1])]
    if not runs:
        return np.empty(0, dtype=np.int64)
    while len(runs) > 1:
        nxt: List[Tuple[np.ndarray, np.ndarray]] = []
        # adjacent pairs only: merging (0,1),(2,3)… preserves the global
        # run order that makes the merge stable
        for i in range(0, len(runs) - 1, 2):
            (ak, ai), (bk, bi) = runs[i], runs[i + 1]
            la, lb = len(ak), len(bk)
            # merged position of a[x] = x + |b strictly before a[x]|;
            # of b[y] = y + |a at-or-before b[y]| (ties: a first)
            pos_a = np.arange(la, dtype=np.int64) + np.searchsorted(
                bk, ak, side="left"
            )
            pos_b = np.arange(lb, dtype=np.int64) + np.searchsorted(
                ak, bk, side="right"
            )
            mk = np.empty(la + lb, dtype=ak.dtype)
            mi = np.empty(la + lb, dtype=np.int64)
            mk[pos_a] = ak
            mk[pos_b] = bk
            mi[pos_a] = ai
            mi[pos_b] = bi
            nxt.append((mk, mi))
        if len(runs) % 2:
            nxt.append(runs[-1])
        runs = nxt
    return np.asarray(runs[0][1], dtype=np.int64)


# Below this many rows the slice/merge machinery costs more than the one
# stable argsort it replaces; the serial twin handles small chunks.
HOST_PARALLEL_MIN_ROWS = 1 << 16


def build_partition_host_parallel(
    batch: ColumnarBatch,
    key_names: List[str],
    num_buckets: int,
    workers: int,
) -> Tuple[ColumnarBatch, np.ndarray]:
    """Multi-core twin of build_partition_host: identical output, the
    O(n log n) stable sort split across ``workers`` host threads.

    Rows split into contiguous slices; each worker stable-argsorts its
    slice of the packed (bucket, keys…) composite (numpy's sort releases
    the GIL, so threads scale on real cores); slices then merge via the
    stable searchsorted tournament. Contiguous slices + left-run-wins
    ties reproduce the serial stable argsort bit-for-bit. Shapes the
    composite cannot pack (63-bit overflow, float32 keys' raw transport)
    fall back to the serial twin — parity over parallelism."""
    n = batch.num_rows
    if workers <= 1 or n < HOST_PARALLEL_MIN_ROWS:
        return build_partition_host(batch, key_names, num_buckets)
    from ..index.stream_builder import sort_encoding
    from ..parallel.pool import run_parallel

    bucket = bucket_ids_host(
        [key_repr(batch.columns[k]) for k in key_names], num_buckets
    )
    encs = [sort_encoding(batch.columns[k]) for k in key_names]
    comp = _pack_sort_keys(encs, bucket, num_buckets)
    if comp is None:
        return build_partition_host(batch, key_names, num_buckets)
    workers = min(int(workers), max(n // HOST_PARALLEL_MIN_ROWS, 1))
    step = -(-n // workers)
    spans = [(s, min(s + step, n)) for s in range(0, n, step)]

    def slice_sort(span: Tuple[int, int]):
        s, e = span
        order = np.argsort(comp[s:e], kind="stable").astype(np.int64) + s
        return comp[order], order

    sorted_slices = run_parallel(
        [lambda sp=sp: slice_sort(sp) for sp in spans],
        workers,
        name="host-partition",
    )
    order = merge_sorted_orders(sorted_slices)
    counts = np.bincount(bucket, minlength=num_buckets).astype(np.int64)
    out = batch.take(order)
    _canonicalize_f64(out)
    metrics.incr("build.engine.host_parallel")
    return out, counts


# ---------------------------------------------------------------------------
# multi-device build kernel (shard_map + all_to_all over ICI)
# ---------------------------------------------------------------------------
_sharded_build_cache: dict = {}


# replicated operands of the packed sharded sort (never column names:
# they cannot collide with the vocab-hash entries keyed by column)
_PACK_MINS = "\x00pack_mins"
_PACK_SHIFTS = "\x00pack_shifts"
# the plane of row ids the program makes and returns (never a column name
# either)
_ROW_ID = "\x00row_id"


def _sharded_build_fn(
    mesh: Mesh,
    axis: str,
    dtypes_sig: tuple,
    key_names: tuple,
    vh_names: tuple,
    num_buckets: int,
    cap: int,
    narrow: Optional[bool],
    carry: tuple,
):
    """Build (and cache) the jitted shard_map program for one
    (mesh, schema, keys, num_buckets, capacity, carried columns)
    signature. The streaming build calls this per chunk; without the
    cache every chunk would re-trace and re-compile, forfeiting the
    fixed-executable steady state the chunked design exists for. ``cap``
    and the shard row count are quantized to powers of two by the caller
    so per-chunk skew variation doesn't mint new executables.

    Each row crosses the all_to_all as its sort words, its row id (its
    index in the padded global array, made from the shard's index) and
    the columns named in ``carry``; each device returns its rows' ids and
    carried columns in (bucket, keys..., row id) order, with its
    per-bucket counts and its valid row count. The row id as the last
    sort key keeps ties in source-row order, as the one-device build
    does. With a pack plan the sort word is the packed (bucket, keys...)
    composite, made on the source shard, int32 when ``narrow`` and int64
    otherwise (``_pack_composite``, as the single-chip kernel does),
    whose mins and shifts arrive as the replicated ``vh`` entries
    _PACK_MINS/_PACK_SHIFTS; ``narrow=None`` (keys that cannot be packed)
    sends the bucket and the keys' sort operands. A process that holds
    every row takes them itself by row id (``carry=()``); one that cannot
    reach other processes' rows carries every column.

    No plane is scattered: the shard sorts by destination, and each
    (D, cap) block is a slice at the destination's start whose slots past
    its count take a fill that sorts after every row. Both sorts are
    unstable (the row id makes the receiver's order total): a stable sort
    doubles the TPU compile."""
    key = (mesh, axis, dtypes_sig, key_names, vh_names, num_buckets, cap, narrow, carry)
    fn = _sharded_build_cache.get(key)
    if fn is not None:
        return fn
    dtypes = dict(dtypes_sig)
    keys = list(key_names)
    D = mesh.devices.size

    def shard_fn(arrays, valid, vh):
        # local shapes: (shard_rows,)
        bucket = device_bucket_ids(arrays, dtypes, keys, vh, num_buckets)
        m = bucket.shape[0]
        row_id = lax.axis_index(axis).astype(jnp.int32) * m + lax.iota(jnp.int32, m)
        # (plane, fill) pairs: the sort words, the row id, the carried columns
        if narrow is not None:
            packed = _pack_composite(
                arrays, keys, bucket, vh[_PACK_MINS], vh[_PACK_SHIFTS], narrow
            )
            words = [(packed, jnp.iinfo(packed.dtype).max)]
        else:
            words = [(bucket, num_buckets)] + [
                (_ordered_sort_operand(arrays[k]), 0) for k in keys
            ]
        travel = words + [(row_id, 0)] + [(arrays[name], 0) for name in carry]

        # invalid (padding) rows -> out of range, so they never leave the
        # shard; placement via the ONE shared rule
        dest = jnp.where(valid, owner_of_bucket_device(bucket, D), D)
        grouped = lax.sort(
            [dest] + [x for x, _ in travel], num_keys=1, is_stable=False
        )
        starts = jnp.searchsorted(grouped[0], jnp.arange(D + 1, dtype=jnp.int32))
        in_block = lax.iota(jnp.int32, cap)[None, :] < (starts[1:] - starts[:-1])[:, None]

        def exchange(x, fill):
            padded = jnp.concatenate([x, jnp.full((cap,), fill, x.dtype)])
            blocks = jnp.stack(
                [lax.dynamic_slice_in_dim(padded, starts[d], cap) for d in range(D)]
            )
            blocks = jnp.where(in_block, blocks, jnp.asarray(fill, x.dtype))
            recv = lax.all_to_all(blocks, axis, split_axis=0, concat_axis=0, tiled=False)
            return recv.reshape(D * cap)

        recv = [exchange(x, fill) for x, (_, fill) in zip(grouped[1:], travel)]
        nw = len(words)
        out = lax.sort(recv, num_keys=nw + 1, is_stable=False)
        # rows come grouped by bucket: bucket b starts at the first sort
        # word at or above b's lowest
        lows = jnp.arange(num_buckets + 1, dtype=out[0].dtype)
        if narrow is not None:
            lows = jnp.left_shift(lows, vh[_PACK_SHIFTS].sum().astype(lows.dtype))
        bounds = jnp.searchsorted(out[0], lows)
        local_counts = bounds[1:] - bounds[:-1]
        n_valid = bounds[num_buckets:]  # rank-1 for out_specs
        carried = dict(zip((_ROW_ID,) + carry, out[nw:]))
        return carried, local_counts, n_valid

    from jax import shard_map

    in_specs = (
        {name: PartitionSpec(axis) for name, _ in dtypes_sig},
        PartitionSpec(axis),
        {k: PartitionSpec() for k in vh_names},
    )
    out_specs = (
        {name: PartitionSpec(axis) for name in (_ROW_ID,) + carry},
        PartitionSpec(axis),
        PartitionSpec(axis),
    )
    sharded = shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        check_vma=False,
    )

    # named like the single-chip programs: the profile shows
    # ``jit_build_sharded_exchange``
    @jax.jit
    def build_sharded_exchange(arrays, valid, vh):
        return sharded(arrays, valid, vh)

    if len(_sharded_build_cache) >= 64:
        _sharded_build_cache.pop(next(iter(_sharded_build_cache)))
    _sharded_build_cache[key] = build_sharded_exchange
    return build_sharded_exchange


def _exchange_cap(
    host_dest: np.ndarray, shard_rows: int, n: int, n_shards: int, D: int
) -> int:
    """Max rows any one source shard sends to any one destination device —
    the static all_to_all block capacity, shared by the single-controller
    and multihost packers (they must agree or executables stop caching)."""
    cap = 1
    for s in range(n_shards):
        seg = host_dest[s * shard_rows : min((s + 1) * shard_rows, n)]
        if seg.size:
            cap = max(cap, int(np.bincount(seg, minlength=D).max()))
    return cap


# jitted consensus/reduction programs per (mesh, D, num_buckets) — fresh
# jit(lambda) objects would re-trace on every build (jit caches on the
# function object), so they are built once and reused
_mh_reduce_cache: Dict[tuple, dict] = {}


def _mh_reducers(mesh: Mesh, axis: str, D: int, num_buckets: int) -> dict:
    key = (mesh, axis, D, num_buckets)
    out = _mh_reduce_cache.get(key)
    if out is not None:
        return out
    replicated = NamedSharding(mesh, PartitionSpec())
    out = {
        "max": jax.jit(jnp.max, out_shardings=replicated),
        "sum_counts": jax.jit(
            lambda c: c.reshape(D, num_buckets).sum(axis=0),
            out_shardings=replicated,
        ),
        "sum_valid": jax.jit(lambda v: v.sum(), out_shardings=replicated),
    }
    if len(_mh_reduce_cache) >= 32:
        _mh_reduce_cache.pop(next(iter(_mh_reduce_cache)))
    _mh_reduce_cache[key] = out
    return out


def unify_vocabs_shared_storage(
    local_batch: ColumnarBatch,
    scratch_dir,
    barrier,
    process_index: int,
    process_count: int,
    timeout_s: float = 30.0,
) -> ColumnarBatch:
    """Cross-process dictionary union over shared storage: every process
    writes its string columns' vocabs, a collective barrier orders the
    writes before any read, and each process re-encodes onto the union —
    after this, codes are globally comparable and string columns transit
    the exchange like any numeric column. (Vocabs ride shared storage
    rather than a collective because they are ragged bytes; index data
    already lives on shared storage, so this adds no new requirement.)

    ``barrier`` is any zero-arg callable that returns only after every
    process has entered it (a replicated-output collective works)."""
    import pickle
    from pathlib import Path

    names = [
        n for n, c in local_batch.columns.items() if c.vocab is not None
    ]
    if not names:
        return local_batch
    scratch = Path(scratch_dir)
    scratch.mkdir(parents=True, exist_ok=True)
    payload = {n: local_batch.columns[n].vocab for n in names}
    import os as _os
    import time as _time

    tmp = scratch / f".vocab-{process_index:05d}.tmp"
    tmp.write_bytes(pickle.dumps(payload))
    # durable on REAL shared storage: fsync the file and its directory
    # before the barrier, or a peer's post-barrier read can miss the
    # rename under NFS-style caching
    fd = _os.open(tmp, _os.O_RDONLY)
    try:
        _os.fsync(fd)
    finally:
        _os.close(fd)
    tmp.replace(scratch / f"vocab-{process_index:05d}.pkl")
    dfd = _os.open(scratch, _os.O_RDONLY)
    try:
        _os.fsync(dfd)
    finally:
        _os.close(dfd)
    barrier()  # all vocab files durable before anyone reads
    merged: Dict[str, np.ndarray] = {}
    for p in range(process_count):
        path = scratch / f"vocab-{p:05d}.pkl"
        deadline = _time.monotonic() + timeout_s
        while True:  # belt to the fsync braces: retry stale-cache misses
            try:
                data = pickle.loads(path.read_bytes())
                metrics.incr("build.multihost.vocab_read")
                break
            except FileNotFoundError:
                if _time.monotonic() >= deadline:
                    raise
                metrics.incr("build.multihost.vocab_stale_retry")
                _time.sleep(0.05)
        for n, v in data.items():
            merged.setdefault(n, []).append(v)
    # second barrier: nobody may overwrite these files (a later build
    # reusing the scratch dir) until EVERY process has finished reading —
    # without it, successive builds race and unions silently diverge
    barrier()
    out = dict(local_batch.columns)
    for n in names:
        union = np.unique(np.concatenate(merged[n]))
        out[n] = local_batch.columns[n].reencode(union)
    return ColumnarBatch(out)


def build_partition_sharded_multihost(
    local_batch: ColumnarBatch,
    key_names: List[str],
    num_buckets: int,
    mesh: Mesh,
    scratch_dir=None,
) -> Tuple[List[Tuple[ColumnarBatch, np.ndarray]], np.ndarray]:
    """Multi-CONTROLLER twin of build_partition_sharded: every process
    calls this SPMD-style with its OWN local rows (e.g. its share of the
    source files), and ingest never funnels through one host's NIC —
    each process feeds its local devices via
    ``jax.make_array_from_process_local_data`` and the hash repartition
    rides the same all_to_all program (ICI within a slice, DCN across
    hosts; parallel.mesh.initialize_multihost is the control-plane seam; docs/05 the story).

    Returns ``(per_local_device, global_counts)``: this process's devices'
    (batch, bucket_ids) pairs — grouped by bucket, key-sorted — plus the
    replicated global per-bucket counts. Shape consensus (max shard rows,
    exchange capacity) runs as two tiny device collectives so every
    process compiles the identical program.

    String columns require ``scratch_dir`` (a shared-storage directory):
    per-process dictionaries union there (unify_vocabs_shared_storage) so
    codes become globally comparable before the exchange."""
    import jax as _jax

    axis = mesh.axis_names[0]
    D = mesh.devices.size
    local_devs = [d for d in mesh.devices.flat if d.process_index == _jax.process_index()]
    L = len(local_devs)
    if L == 0:
        raise HyperspaceException("This process owns no devices of the mesh.")
    reducers = _mh_reducers(mesh, axis, D, num_buckets)

    def consensus_max(value: int) -> int:
        """Max of a per-process value, agreed via one replicated-output
        collective (every process must end up with identical statics).
        consensus_max(0) doubles as the collective barrier."""
        sharding = NamedSharding(mesh, PartitionSpec(axis))
        arr = _jax.make_array_from_process_local_data(
            sharding, np.full(L, value, dtype=np.int64), (D,)
        )
        return int(reducers["max"](arr))

    if any(c.vocab is not None for c in local_batch.columns.values()):
        if scratch_dir is None:
            raise HyperspaceException(
                "multihost build with string columns needs scratch_dir on "
                "shared storage for the cross-process vocab union."
            )
        local_batch = unify_vocabs_shared_storage(
            local_batch,
            scratch_dir,
            lambda: consensus_max(0),
            _jax.process_index(),
            _jax.process_count(),
        )
    dtypes = local_batch.schema()
    n_local = local_batch.num_rows

    from ..utils.intmath import next_pow2

    shard_rows = next_pow2(consensus_max(max(-(-n_local // L), 1)))
    pad_local = shard_rows * L

    host_dest = owner_of_bucket_array(
        bucket_ids_host(
            [key_repr(local_batch.columns[k]) for k in key_names], num_buckets
        ),
        D,
    )
    cap = next_pow2(
        consensus_max(_exchange_cap(host_dest, shard_rows, n_local, L, D))
    )

    def pad(a: np.ndarray) -> np.ndarray:
        return np.pad(a, (0, pad_local - n_local))

    sharding = NamedSharding(mesh, PartitionSpec(axis))
    dev_arrays = {
        name: _jax.make_array_from_process_local_data(
            sharding, pad(encode_for_device(local_batch.columns[name])),
            (shard_rows * D,),
        )
        for name in local_batch.column_names
    }
    valid = _jax.make_array_from_process_local_data(
        sharding, pad(np.ones(n_local, dtype=bool)), (shard_rows * D,)
    )
    # string KEY columns hash through replicated per-vocab-entry hashes;
    # the vocab union made every process's vocab (hence these arrays)
    # identical, so each process supplies the full replicated value
    replicated = NamedSharding(mesh, PartitionSpec())
    vh_np = {
        k: vocab_hashes(local_batch.columns[k])
        for k in key_names
        if is_string(dtypes[k])
    }
    vh_dev = {
        k: _jax.make_array_from_process_local_data(replicated, v, v.shape)
        for k, v in vh_np.items()
    }

    # a process cannot take rows another process holds: every column
    # travels, and comes back in sorted order
    fn = _sharded_build_fn(
        mesh,
        axis,
        tuple(dtypes.items()),
        tuple(key_names),
        tuple(sorted(vh_np)),
        num_buckets,
        cap,
        None,
        tuple(local_batch.column_names),
    )
    out_arrays, counts_all, n_valid_all = fn(dev_arrays, valid, vh_dev)

    # replicate the global bucket counts (the per-device counts array is
    # distributed; only a replicated reduction is host-readable everywhere)
    global_counts = np.asarray(reducers["sum_counts"](counts_all))
    n_global = int(np.asarray(reducers["sum_valid"](n_valid_all)))
    if int(global_counts.sum()) != n_global:
        raise HyperspaceException(
            f"Multihost shuffle lost rows: {int(global_counts.sum())} != {n_global}."
        )

    # this process's output shards only (device d holds D*cap rows)
    shard_of = {s.device: s for s in out_arrays[local_batch.column_names[0]].addressable_shards}
    per_local: List[Tuple[ColumnarBatch, np.ndarray]] = []
    nv_shards = {s.device: s for s in n_valid_all.addressable_shards}
    count_shards = {s.device: s for s in counts_all.addressable_shards}
    col_shards = {
        name: {s.device: s for s in out_arrays[name].addressable_shards}
        for name in local_batch.column_names
    }
    vocabs = {name: local_batch.columns[name].vocab for name in local_batch.column_names}
    buckets = np.arange(num_buckets, dtype=np.int32)
    for dev in shard_of:
        nv = int(np.asarray(nv_shards[dev].data)[0])
        cols = {
            name: Column(
                dtypes[name],
                decode_from_device(
                    dtypes[name], np.asarray(col_shards[name][dev].data)[:nv]
                ),
                vocabs[name],
            )
            for name in local_batch.column_names
        }
        # rows come grouped by bucket, so the counts give each row's bucket
        per_local.append(
            (ColumnarBatch(cols), np.repeat(buckets, np.asarray(count_shards[dev].data)))
        )
    return per_local, global_counts


def build_partition_sharded(
    batch: ColumnarBatch,
    key_names: List[str],
    num_buckets: int,
    mesh: Mesh,
) -> Tuple[List[Tuple[ColumnarBatch, np.ndarray]], np.ndarray]:
    """Multi-device HOT LOOP.

    Returns ``(per_device, global_counts)`` where ``per_device[d]`` is the
    (batch, bucket_ids) of valid rows that landed on device d — grouped by
    bucket and key-sorted — and ``global_counts[b]`` is the global row
    count of bucket b. Device d owns buckets ``{b : b % D == d}``.

    Only the key columns go up; the all_to_all carries each row's sort
    words and its row id in the chunk, and each device sends back its
    rows' order. The host, which holds every row, takes them in that
    order (the multihost twin cannot, and moves every column).

    Two spans split the call: ``build.sharded_plan`` (host bucket hash,
    exchange capacity, pack plan, the H2D puts) and
    ``build.sharded_exchange`` (the program, the D2H of the row ids and
    counts, and the host take of each device's rows). ``build.sharded.rows``
    and ``build.sharded.slots`` count the rows and the ``D·D·cap`` slots
    the all_to_all carries, ``build.sharded.row_id_chunks`` the calls.
    """
    from ..telemetry.trace import add_bytes, span
    from ..utils.intmath import next_pow2

    axis = mesh.axis_names[0]
    D = mesh.devices.size
    n = batch.num_rows
    dtypes = batch.schema()

    with span("build.sharded_plan", rows=n) as plan_span:
        # Host-side twin hash for capacity planning (static shapes for XLA).
        host_bucket = bucket_ids_host(
            [key_repr(batch.columns[k]) for k in key_names], num_buckets
        )
        host_dest = owner_of_bucket_array(host_bucket, D)

        # shard rows quantized to a power of two so repeated chunked calls
        # of similar sizes share one executable
        shard_rows = next_pow2(max(-(-n // D), 1))
        n_pad = shard_rows * D
        # max rows any one src shard sends to any one dst device, power-of-
        # two quantized for the same reason (skew varies chunk to chunk)
        cap = next_pow2(_exchange_cap(host_dest, shard_rows, n, D, D))
        if plan_span is not None:
            plan_span.labels.update(cap=cap, shard_rows=shard_rows)

        def pad(a: np.ndarray) -> np.ndarray:
            return np.pad(a, (0, n_pad - n))

        def put(a: np.ndarray, sharding):
            add_bytes("h2d_bytes", a.nbytes)
            return jax.device_put(a, sharding)

        sharding = NamedSharding(mesh, PartitionSpec(axis))
        enc = {k: encode_for_device(batch.columns[k]) for k in key_names}
        dev_keys = {k: put(pad(enc[k]), sharding) for k in key_names}
        valid = put(pad(np.ones(n, dtype=bool)), sharding)
        replicated = NamedSharding(mesh, PartitionSpec())
        vh = {
            k: put(vocab_hashes(batch.columns[k]), replicated)
            for k in key_names
            if is_string(dtypes[k])
        }
        # the padding rows never leave their shard: the pack's bounds
        # need cover only the chunk's rows
        bounds = [_packed_minmax(enc[k]) for k in key_names]
        plan = None
        if all(b is not None for b in bounds):
            plan = _pack_plan(bounds, max(int(num_buckets), 1).bit_length())
        narrow = None if plan is None else _narrow_pack(plan, num_buckets)
        if plan is not None:
            vh[_PACK_MINS] = put(
                np.array([mn for mn, _ in plan], dtype=np.int64), replicated
            )
            vh[_PACK_SHIFTS] = put(
                np.array([kb for _, kb in plan], dtype=np.int32), replicated
            )

    # bytes a slot carries: the sort words (the composite, or the bucket
    # and each key's sort operand) and the int32 row id
    if plan is not None:
        word_bytes = 4 if narrow else 8
    else:
        word_bytes = 4 + sum(enc[k].dtype.itemsize for k in key_names)
    payload_bytes = D * D * cap * (word_bytes + 4)
    with span(
        "build.sharded_exchange", cap=cap, narrow=narrow, payload_bytes=payload_bytes
    ):
        fn = _sharded_build_fn(
            mesh,
            axis,
            tuple((k, dtypes[k]) for k in key_names),
            tuple(key_names),
            tuple(sorted(vh)),
            num_buckets,
            cap,
            narrow,
            (),
        )
        carried, counts_all, n_valid_all = fn(dev_keys, valid, vh)

        row_ids = np.asarray(carried[_ROW_ID])
        counts_all = np.asarray(counts_all).reshape(D, num_buckets)
        n_valid_all = np.asarray(n_valid_all).reshape(D)
        add_bytes(
            "d2h_bytes", row_ids.nbytes + counts_all.nbytes + n_valid_all.nbytes
        )
        per_device: List[Tuple[ColumnarBatch, np.ndarray]] = []
        rows_per_dev = D * cap
        buckets = np.arange(num_buckets, dtype=np.int32)
        for d in range(D):
            nv = int(n_valid_all[d])
            rows = batch.take(row_ids[d * rows_per_dev : d * rows_per_dev + nv])
            # the bytes the device round trip of the columns gave
            _canonicalize_f64(rows)
            # rows come grouped by bucket, so the counts give each row's bucket
            per_device.append((rows, np.repeat(buckets, counts_all[d])))
    metrics.incr("build.sharded.rows", n)
    metrics.incr("build.sharded.slots", D * D * cap)
    metrics.incr("build.sharded.row_id_chunks")
    global_counts = counts_all.sum(axis=0)
    # Sanity: every input row landed exactly once.
    if int(global_counts.sum()) != n:
        raise HyperspaceException(
            f"Shuffle lost rows: {int(global_counts.sum())} != {n}."
        )
    return per_device, global_counts
