"""Bucketed ICI all-to-all shuffle: repartitioning a join side on-mesh.

Until this subsystem, the only all-to-all program in the tree was the
build kernel (ops/build.py) — every query path was deliberately
shuffle-free because build-time ``b % D`` placement makes co-partitioned
joins exchange-free. That leaves one hole: two indexes bucketed with
DIFFERENT ``num_buckets`` share no bucket space, so their join fell all
the way back to the host. This module closes the hole with the same
machinery the build already proved out:

* the moved side's columns transit in the device transport encoding
  (ops.build.encode_for_device: float64 → ordered int64, strings as
  dictionary codes with the unified vocab reattached host-side);
* rows pack into fixed-capacity (D, cap) blocks — capacity from the same
  ``_exchange_cap`` + ``next_pow2`` discipline as the build, so skewed
  batches don't mint new executables;
* destination devices come from the ONE shared placement rule
  (parallel.mesh.owner_of_bucket_device) applied to the row's bucket in
  the TARGET side's bucket space — the hash is value-stable
  (ops.hashing.key_repr), so equal join keys land in equal buckets no
  matter which index they came from;
* exactly ONE ``lax.all_to_all`` round moves everything: every payload
  plane, the target bucket ids, and the validity mask ride the same
  round-counted exchange.

After the exchange both sides are co-partitioned in the target bucket
space and the join rides the EXISTING fused arms
(exec.distributed.distributed_bucketed_join on-mesh, or the host
``bucketed_join_pairs``) unchanged. Any device failure mid-exchange
latches to the exact host join and freezes a flight-recorder snapshot —
the standard degradation ladder (docs/16).
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional

import numpy as np

from ..exceptions import HyperspaceException
from ..ops import ensure_x64
from ..ops.build import _exchange_cap, encode_for_device
from ..ops.hashing import bucket_ids_host, key_repr
from ..parallel.mesh import owner_of_bucket_array, owner_of_bucket_device
from ..storage.columnar import Column, ColumnarBatch, decode_device_array
from ..telemetry.metrics import metrics
from ..telemetry.recorder import flight_recorder
from ..telemetry.trace import add_bytes as _trace_bytes
from ..telemetry.trace import span
from ..utils.intmath import next_pow2

ensure_x64()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax import lax  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec  # noqa: E402

from jax import shard_map  # noqa: E402

__all__ = ["repartition_by_bucket", "try_shuffle_join"]


# jitted exchange programs per (mesh, plane dtypes, cap) — same bounded
# executable cache the build and mesh-join kernels keep
_shuffle_cache: dict = {}


def _shuffle_fn(mesh: Mesh, dtypes_sig: tuple, cap: int):
    """The one-round repartition program: scatter rows into (D, cap)
    blocks by destination device, all_to_all every plane + the target
    bucket ids + the validity mask. No sort-by-key epilogue — the join
    arms downstream do their own sorting. (The build kernel's exchange,
    ops/build.py _sharded_build_fn, slices a destination-sorted shard
    instead of scattering, and carries row ids rather than columns.)"""
    axis = mesh.axis_names[0]
    key = (mesh, dtypes_sig, cap)
    fn = _shuffle_cache.get(key)
    if fn is not None:
        return fn
    D = mesh.devices.size

    def shard_fn(planes, dest, bucket, valid):
        m = dest.shape[0]
        iota = lax.iota(jnp.int32, m)
        sorted_dest, perm = lax.sort([dest, iota], num_keys=1)
        counts = jnp.bincount(dest, length=D)
        starts = jnp.concatenate(
            [jnp.zeros(1, counts.dtype), jnp.cumsum(counts)]
        )[: D + 1]
        pos = iota - starts[jnp.clip(sorted_dest, 0, D)].astype(jnp.int32)

        def exchange(x):
            buf = jnp.zeros((D, cap), x.dtype)
            buf = buf.at[sorted_dest, pos].set(x[perm], mode="drop")
            out = lax.all_to_all(
                buf, axis, split_axis=0, concat_axis=0, tiled=False
            )
            return out.reshape(D * cap)

        vmask = jnp.zeros((D, cap), jnp.bool_)
        vmask = vmask.at[sorted_dest, pos].set(valid[perm], mode="drop")
        vmask = lax.all_to_all(
            vmask, axis, split_axis=0, concat_axis=0, tiled=False
        ).reshape(D * cap)

        recv = [exchange(x) for x in planes]
        recv_bucket = exchange(bucket)
        return recv, recv_bucket, vmask

    in_specs = (
        [PartitionSpec(axis)] * len(dtypes_sig),
        PartitionSpec(axis),
        PartitionSpec(axis),
        PartitionSpec(axis),
    )
    out_specs = (
        [PartitionSpec(axis)] * len(dtypes_sig),
        PartitionSpec(axis),
        PartitionSpec(axis),
    )
    fn = jax.jit(
        shard_map(
            shard_fn,
            mesh=mesh,
            in_specs=in_specs,
            out_specs=out_specs,
            check_vma=False,
        )
    )
    if len(_shuffle_cache) >= 64:
        _shuffle_cache.pop(next(iter(_shuffle_cache)))
    _shuffle_cache[key] = fn
    return fn


def repartition_by_bucket(
    by_bucket: Dict[int, ColumnarBatch],
    key_cols: List[str],
    target_num_buckets: int,
    mesh: Mesh,
) -> Optional[Dict[int, ColumnarBatch]]:
    """Move one join side into ``target_num_buckets`` bucket space over a
    single ICI all-to-all round; rows land on their new bucket's owner
    device (the shared ``b % D`` rule) and come back host-side grouped by
    new bucket id. Returns None on a device failure mid-exchange (the
    caller latches to the host join); raises only on row loss, which
    would mean the exchange itself is wrong."""
    if not by_bucket:
        return {}
    whole = ColumnarBatch.concat([by_bucket[b] for b in sorted(by_bucket)])
    n = whole.num_rows
    D = mesh.devices.size
    if n == 0:
        return {}

    # target-space bucket of every row, via the value-stable host hash —
    # equal join keys on the unmoved side got equal bucket ids at build
    # time from this same (key_repr, bucket_ids_host) pair
    target_bucket = bucket_ids_host(
        [key_repr(whole.columns[k]) for k in key_cols], target_num_buckets
    )
    dest_unpadded = owner_of_bucket_array(target_bucket, D).astype(np.int32)

    shard_rows = next_pow2(max(math.ceil(n / D), 1))
    total = shard_rows * D
    cap = next_pow2(_exchange_cap(dest_unpadded, shard_rows, n, D, D))

    pad = total - n
    dest = np.concatenate([dest_unpadded, np.full(pad, D, np.int32)])
    bucket = np.concatenate(
        [target_bucket.astype(np.int32), np.full(pad, target_num_buckets, np.int32)]
    )
    valid = np.concatenate([np.ones(n, bool), np.zeros(pad, bool)])

    names = list(whole.columns)
    planes = []
    dtypes_sig = []
    for name in names:
        data = encode_for_device(whole.columns[name])
        planes.append(np.concatenate([data, np.zeros(pad, data.dtype)]))
        dtypes_sig.append((name, str(data.dtype)))
    dtypes_sig = tuple(dtypes_sig)

    rows_sh = NamedSharding(mesh, PartitionSpec(mesh.axis_names[0]))
    h2d = sum(p.nbytes for p in planes) + dest.nbytes + bucket.nbytes + valid.nbytes
    ici = (sum(p.itemsize for p in planes) + bucket.itemsize + 1) * D * D * cap
    fn = _shuffle_fn(mesh, dtypes_sig, cap)

    with span(
        "shuffle.all_to_all",
        devices=D,
        rows=n,
        capacity=cap,
        planes=len(planes),
        target_buckets=target_num_buckets,
    ):
        try:
            dev_planes = [jax.device_put(p, rows_sh) for p in planes]
            dev_dest = jax.device_put(dest, rows_sh)
            dev_bucket = jax.device_put(bucket, rows_sh)
            dev_valid = jax.device_put(valid, rows_sh)
            metrics.incr("shuffle.rounds")
            recv, recv_bucket, vmask = fn(
                dev_planes, dev_dest, dev_bucket, dev_valid
            )
            recv = [np.asarray(x) for x in recv]
            recv_bucket = np.asarray(recv_bucket)
            vmask = np.asarray(vmask)
        except HyperspaceException:
            raise
        except Exception as e:  # device loss / fenced chip mid-exchange
            metrics.incr("shuffle.device_failed")
            flight_recorder.snapshot(f"shuffle_device_loss: {type(e).__name__}")
            return None
        metrics.incr("shuffle.h2d_bytes", h2d)
        metrics.incr("shuffle.ici_bytes", ici)
        d2h = sum(x.nbytes for x in recv) + recv_bucket.nbytes + vmask.nbytes
        metrics.incr("shuffle.d2h_bytes", d2h)
        metrics.incr("shuffle.rows_moved", n)
        _trace_bytes("h2d_bytes", h2d)
        _trace_bytes("ici_bytes", ici)
        _trace_bytes("d2h_bytes", d2h)

    got = int(vmask.sum())
    if got != n:
        raise HyperspaceException(
            f"Shuffle lost rows: sent {n}, received {got}."
        )

    keep = np.flatnonzero(vmask)
    kept_bucket = recv_bucket[keep]
    # received rows are already grouped by owner device; a stable sort on
    # bucket id within the kept rows yields contiguous per-bucket runs
    order = np.argsort(kept_bucket, kind="stable")
    kept_bucket = kept_bucket[order]
    uniq, starts = np.unique(kept_bucket, return_index=True)
    bounds = list(starts) + [kept_bucket.size]

    cols_decoded: Dict[str, np.ndarray] = {}
    for (name, _), plane in zip(dtypes_sig, recv):
        cols_decoded[name] = plane[keep][order]

    out: Dict[int, ColumnarBatch] = {}
    for i, b in enumerate(uniq):
        lo, hi = bounds[i], bounds[i + 1]
        cols: Dict[str, Column] = {}
        for name in names:
            src = whole.columns[name]
            seg = cols_decoded[name][lo:hi]
            if src.vocab is not None:
                cols[name] = Column(
                    src.dtype_str, seg.astype(np.int32), vocab=src.vocab
                )
            else:
                cols[name] = Column(
                    src.dtype_str, decode_device_array(src.dtype_str, seg)
                )
        out[int(b)] = ColumnarBatch(cols)
    return out


def try_shuffle_join(
    l_by_bucket: Dict[int, ColumnarBatch],
    r_by_bucket: Dict[int, ColumnarBatch],
    l_keys: List[str],
    r_keys: List[str],
    moved_side: str,
    target_num_buckets: int,
    mesh: Mesh,
    dist_min_rows: int,
) -> Optional[List[ColumnarBatch]]:
    """Repartition ``moved_side`` into the other side's bucket space, then
    ride the existing co-partitioned join arms. ``l_keys``/``r_keys`` must
    already be in the UNMOVED side's index order (the caller reorders —
    same discipline as the co-partitioned SMJ). Returns the join parts, or
    None when the exchange declined (device failure) so the caller falls
    back to the exact host join."""
    if moved_side == "right":
        moved = repartition_by_bucket(
            r_by_bucket, r_keys, target_num_buckets, mesh
        )
        if moved is None:
            return None
        r_by_bucket = moved
    else:
        moved = repartition_by_bucket(
            l_by_bucket, l_keys, target_num_buckets, mesh
        )
        if moved is None:
            return None
        l_by_bucket = moved

    total_rows = sum(b.num_rows for b in l_by_bucket.values()) + sum(
        b.num_rows for b in r_by_bucket.values()
    )
    if total_rows >= dist_min_rows:
        from ..exec.distributed import distributed_bucketed_join

        parts = distributed_bucketed_join(
            l_by_bucket, r_by_bucket, l_keys, r_keys, mesh
        )
    else:
        from ..exec.joins import bucketed_join_pairs

        parts = bucketed_join_pairs(l_by_bucket, r_by_bucket, l_keys, r_keys)
    metrics.incr("scan.path.resident_join_shuffle")
    return parts
