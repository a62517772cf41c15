"""The index-build engine: orchestrates the device kernels and writes the
bucketed, sorted TCB layout.

Parity: this is the TPU replacement for CreateActionBase.write
(CreateActionBase.scala:122-140) — project columns, hash-repartition into
``num_buckets``, per-bucket sort on the indexed columns, write one file per
bucket into a version directory. Execution is ops.build (XLA); storage is
storage.layout (TCB).
"""

from __future__ import annotations

from pathlib import Path
from typing import List, Optional, Tuple

import numpy as np

from ..exceptions import HyperspaceException
from ..storage import layout
from ..storage.columnar import ColumnarBatch
from ..telemetry.metrics import metrics
from ..utils import resolver


def resolve_index_columns(
    schema_cols: List[str], indexed: List[str], included: List[str]
) -> Tuple[List[str], List[str]]:
    """Case-insensitive resolution of user columns against the source schema
    (CreateActionBase.resolveConfig, CreateActionBase.scala:142-162)."""
    r_indexed = resolver.resolve_all(indexed, schema_cols)
    r_included = resolver.resolve_all(included, schema_cols)
    if r_indexed is None or r_included is None:
        missing = [
            c
            for c in list(indexed) + list(included)
            if resolver.resolve(c, schema_cols) is None
        ]
        raise HyperspaceException(
            f"Columns {missing} could not be resolved against source schema "
            f"{schema_cols}."
        )
    return r_indexed, r_included


@metrics.timer("build.total")
def write_index_data(
    batch: ColumnarBatch,
    indexed_cols: List[str],
    num_buckets: int,
    out_dir: str | Path,
    mesh=None,
    extra_meta: Optional[dict] = None,
    engine: str = "auto",
    host_workers: int = 1,
) -> List[Path]:
    """Partition+sort ``batch`` and write one TCB file per non-empty bucket
    into ``out_dir``. Returns written paths. ``mesh`` selects the sharded
    (ICI all_to_all) path; None routes between the single-device kernel
    and its host twin (``engine``: device | host | auto — see
    resolve_build_engine). ``host_workers`` > 1 runs the host twin's
    one big stable sort across that many threads
    (ops.build.build_partition_host_parallel — identical output): the
    in-memory build has a single sort, so intra-sort parallelism is the
    only way the worker pool can help it."""
    out_dir = Path(out_dir)
    out_dir.mkdir(parents=True, exist_ok=True)
    written: List[Path] = []

    def write_bucket(b: int, bucket_batch: ColumnarBatch) -> None:
        if bucket_batch.num_rows == 0:
            return  # empty buckets have no file, as with Spark's bucketed write
        p = out_dir / layout.bucket_file_name(b)
        layout.write_batch(
            p, bucket_batch, sorted_by=list(indexed_cols), bucket=b, extra=extra_meta
        )
        written.append(p)

    if mesh is not None and mesh.devices.size > 1:
        from ..ops.build import build_partition_sharded

        per_device, _global_counts = build_partition_sharded(
            batch, indexed_cols, num_buckets, mesh
        )
        for _d, (dev_batch, bucket_ids) in enumerate(per_device):
            if dev_batch.num_rows == 0:
                continue
            # rows are grouped by bucket ascending
            bounds = np.flatnonzero(np.diff(bucket_ids)) + 1
            starts = np.concatenate([[0], bounds])
            ends = np.concatenate([bounds, [len(bucket_ids)]])
            for s, e in zip(starts, ends):
                write_bucket(int(bucket_ids[s]), dev_batch.take(np.arange(s, e)))
    else:
        engine = resolve_build_engine(engine, batch.num_rows, streaming=False)
        if engine == "host":
            from ..ops.build import build_partition_host_parallel

            metrics.incr("build.engine.host")
            sorted_batch, counts = build_partition_host_parallel(
                batch, indexed_cols, num_buckets, host_workers
            )
        else:
            from ..ops.build import build_partition_single

            metrics.incr("build.engine.device")
            sorted_batch, counts = build_partition_single(
                batch, indexed_cols, num_buckets
            )
        offsets = np.concatenate([[0], np.cumsum(counts)])
        for b in range(num_buckets):
            s, e = int(offsets[b]), int(offsets[b + 1])
            if e > s:
                write_bucket(b, sorted_batch.take(np.arange(s, e)))
    return sorted(written)


# In-memory builds run ONE kernel launch, so a fresh XLA compile (tens of
# seconds on TPU) cannot amortize the way the streaming build's per-chunk
# executable does. (build_partition_single's jitted closure is cached per
# (schema, keys, buckets), so repeats DO reuse the executable — but a
# one-shot build's FIRST launch still bears the compile.) Below this many
# rows the host twin is therefore the sure win.
INMEMORY_HOST_MAX_ROWS = 1 << 22


def resolve_build_engine(engine: str, n_rows: int, streaming: bool) -> str:
    """The engine of one build (``hyperspace.index.build.engine``),
    resolved once per build by both writers. ``device`` and ``host`` pin.
    ``auto`` is host on a CPU platform, host for an in-memory build below
    INMEMORY_HOST_MAX_ROWS rows, host when the process's first device
    touch failed (counted ``build.engine.device_unreachable``), and device
    otherwise: every chunk of a streaming build, its tail included. A
    failed first touch raises DeviceFault where the device is required —
    ``device`` pinned, or a TPU platform (utils/deviceprobe.py)."""
    from .. import ops
    from ..utils.deviceprobe import first_device_touch_ok

    if engine == "host":
        return "host"
    if engine == "device":
        first_device_touch_ok(required=True)
        return "device"
    if engine != "auto":
        raise HyperspaceException(
            f"Unknown build engine {engine!r}; expected device, host, or auto."
        )
    if ops.configured_platform() == "cpu":
        return "host"
    if not streaming and n_rows < INMEMORY_HOST_MAX_ROWS:
        return "host"
    if not first_device_touch_ok():
        metrics.incr("build.engine.device_unreachable")
        return "host"
    return "device"
