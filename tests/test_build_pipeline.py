"""Pipelined-build tests: the parallel.pool worker layer, multi-stage
spill overlap, parallel ingest, k-way merges, the multi-core host
partition, the packed radix device kernel, and fault injection that kills
each stage mid-build.

The invariant every parity test here enforces: pipelining must never
change ONE BYTE of the built index — chunk order is preserved through
ordered ingest, runs carry sequence numbers, and every merge is stable by
run order, so serial and pipelined builds are interchangeable.
"""

import threading
import time

import numpy as np
import pytest

from hyperspace_tpu import constants as C
from hyperspace_tpu.config import HyperspaceConf
from hyperspace_tpu.exceptions import HyperspaceException
from hyperspace_tpu.index.stream_builder import (
    BuildPipelineConfig,
    StreamingIndexWriter,
    merge_sorted_runs,
    sort_encoding,
    write_index_data_streaming,
)
from hyperspace_tpu.parallel.pool import (
    FirstError,
    WorkerPool,
    ordered_map,
    run_parallel,
)
from hyperspace_tpu.storage import layout, parquet_io
from hyperspace_tpu.storage.columnar import ColumnarBatch

POOL_PREFIXES = ("spill-compute", "spill-write", "ingest", "bucket-merge")


def _no_pool_threads(deadline_s: float = 5.0) -> bool:
    deadline = time.time() + deadline_s
    while time.time() < deadline:
        if not any(
            t.name.startswith(POOL_PREFIXES) and t.is_alive()
            for t in threading.enumerate()
        ):
            return True
        time.sleep(0.05)
    return False


def sample(n=5000, seed=0):
    rng = np.random.default_rng(seed)
    return ColumnarBatch.from_pydict(
        {
            "orderkey": rng.integers(0, 10**6, n).astype(np.int64),
            "qty": rng.integers(0, 50, n).astype(np.int32),
            "price": (rng.random(n) * 1e4).astype(np.float64),
            "flag": rng.choice([b"A", b"N", b"R", b"F"], n).astype(object),
        },
        schema={
            "orderkey": "int64",
            "qty": "int32",
            "price": "float64",
            "flag": "string",
        },
    )


def chunks_of(batch, size):
    for s in range(0, batch.num_rows, size):
        yield batch.take(np.arange(s, min(s + size, batch.num_rows)))


def pipelined(**over) -> BuildPipelineConfig:
    base = dict(
        enabled=True,
        ingest_workers=2,
        spill_compute_workers=2,
        spill_write_workers=2,
        merge_workers=2,
        queue_depth=2,
    )
    base.update(over)
    return BuildPipelineConfig(**base)


def file_bytes(paths):
    """bucket -> full decoded content of every column, for byte-level
    parity across build configurations."""
    out = {}
    for f in sorted(paths):
        fb = layout.read_batch(f)
        key = layout.bucket_of_file(f)
        out[key] = {
            name: col.to_values().tolist() for name, col in fb.columns.items()
        }
    return out


# ---------------------------------------------------------------------------
# parallel.pool primitives
# ---------------------------------------------------------------------------
def test_ordered_map_preserves_order_and_parallelizes():
    running = []
    peak = []
    lock = threading.Lock()

    def work(i):
        with lock:
            running.append(i)
            peak.append(len(running))
        time.sleep(0.01)
        with lock:
            running.remove(i)
        return i * i

    got = list(ordered_map(work, range(40), workers=4, window=8))
    assert got == [i * i for i in range(40)]
    assert max(peak) > 1  # genuinely concurrent


def test_ordered_map_propagates_failure_and_joins():
    def work(i):
        if i == 7:
            raise ValueError("boom at 7")
        return i

    with pytest.raises(ValueError, match="boom at 7"):
        list(ordered_map(work, range(100), workers=3, window=4))
    assert _no_pool_threads()


def test_ordered_map_iterator_error_and_early_close():
    def items():
        yield 1
        yield 2
        raise OSError("source died")

    with pytest.raises(OSError, match="source died"):
        list(ordered_map(lambda x: x, items(), workers=2, window=4))

    # consumer abandons: workers must join without draining everything
    seen = []

    def slow(i):
        seen.append(i)
        time.sleep(0.01)
        return i

    g = ordered_map(slow, range(1000), workers=2, window=4, name="early")
    assert next(g) == 0
    g.close()
    assert len(seen) < 1000


def test_worker_pool_failure_drains_and_submit_reports():
    pool = WorkerPool(2, "unit-pool", queue_depth=1)

    def boom():
        raise RuntimeError("task failed")

    assert pool.submit(boom)
    deadline = time.time() + 5
    while not pool.failure.failed.is_set() and time.time() < deadline:
        time.sleep(0.01)
    assert pool.failure.failed.is_set()
    # post-failure submits refuse (drain mode) instead of queuing forever
    assert pool.submit(lambda: None) is False
    pool.close()
    with pytest.raises(RuntimeError, match="task failed"):
        pool.failure.check()


def test_run_parallel_results_in_order():
    assert run_parallel([lambda i=i: i * 2 for i in range(20)], 4) == [
        i * 2 for i in range(20)
    ]
    with pytest.raises(KeyError):
        run_parallel([lambda: {}["missing"]] * 3, 2)


def test_first_error_keeps_first():
    fe = FirstError()
    fe.fail(ValueError("first"))
    fe.fail(RuntimeError("second"))
    with pytest.raises(ValueError, match="first"):
        fe.check()


# ---------------------------------------------------------------------------
# serial/pipelined parity
# ---------------------------------------------------------------------------
def test_pipeline_on_off_identical_bytes(tmp_path):
    b = sample(6000, seed=3)
    nb = 8
    serial = write_index_data_streaming(
        chunks_of(b, 700),
        ["orderkey", "flag"],
        nb,
        tmp_path / "serial",
        chunk_capacity=700,
        pipeline=BuildPipelineConfig.serial(),
    )
    piped = write_index_data_streaming(
        chunks_of(b, 700),
        ["orderkey", "flag"],
        nb,
        tmp_path / "piped",
        chunk_capacity=700,
        pipeline=pipelined(),
    )
    assert file_bytes(serial) == file_bytes(piped)
    # ties: duplicate keys keep ingest order under both modes
    dup = ColumnarBatch.from_pydict(
        {
            "k": np.array([5] * 64, dtype=np.int64),
            "tag": np.arange(64, dtype=np.int64),
        }
    )
    s2 = write_index_data_streaming(
        chunks_of(dup, 8), ["k"], 2, tmp_path / "s2", chunk_capacity=8,
        pipeline=BuildPipelineConfig.serial(),
    )
    p2 = write_index_data_streaming(
        chunks_of(dup, 8), ["k"], 2, tmp_path / "p2", chunk_capacity=8,
        pipeline=pipelined(),
    )
    assert file_bytes(s2) == file_bytes(p2)


def test_pipeline_runs_mode_sequenced_runs(tmp_path):
    """Runs-mode finalize promotes spill runs; with concurrent write
    workers the run ORDER (file sequence) must still follow chunk order."""
    b = sample(4000, seed=11)
    files = write_index_data_streaming(
        chunks_of(b, 512),
        ["orderkey"],
        4,
        tmp_path / "runs",
        chunk_capacity=512,
        finalize_mode="runs",
        pipeline=pipelined(),
    )
    assert all(layout.is_run_file(f) for f in files)
    # rows across runs in file order == ingest order chunked at capacity
    got = np.concatenate(
        [layout.read_batch(f).columns["qty"].data for f in sorted(files)]
    )
    assert got.shape[0] == 4000


def test_serial_mode_uses_no_threads(tmp_path):
    b = sample(2000, seed=5)
    before = {t.name for t in threading.enumerate()}
    write_index_data_streaming(
        chunks_of(b, 512),
        ["orderkey"],
        4,
        tmp_path / "o",
        chunk_capacity=512,
        pipeline=BuildPipelineConfig.serial(),
    )
    after = {t.name for t in threading.enumerate()}
    new = {
        n for n in after - before if n.startswith(POOL_PREFIXES + ("chunk-prefetch",))
    }
    assert new == set()


# ---------------------------------------------------------------------------
# parallel ingest (chunk tasks)
# ---------------------------------------------------------------------------
def test_file_chunk_tasks_match_serial_iterator(tmp_path):
    import pyarrow.parquet as pq

    b = sample(5000, seed=21)
    p = tmp_path / "d.parquet"
    import pyarrow as pa

    arrays = {n: pa.array(c.to_values()) for n, c in b.columns.items()}
    pq.write_table(pa.table(arrays), str(p), row_group_size=600)

    serial = list(parquet_io.iter_file_batches("parquet", p, chunk_rows=700))
    tasks = parquet_io.file_chunk_tasks("parquet", p, chunk_rows=700)
    assert len(tasks) > 1  # row groups actually split
    parallel = [c for t in tasks for c in t()]
    s_all = ColumnarBatch.concat(serial)
    p_all = ColumnarBatch.concat(parallel)
    np.testing.assert_array_equal(
        s_all.columns["orderkey"].data, p_all.columns["orderkey"].data
    )
    np.testing.assert_array_equal(
        s_all.columns["price"].data, p_all.columns["price"].data
    )


def test_chunk_tasks_ingest_parity(tmp_path):
    import pyarrow as pa
    import pyarrow.parquet as pq

    b = sample(6000, seed=8)
    p = tmp_path / "src.parquet"
    arrays = {n: pa.array(c.to_values()) for n, c in b.columns.items()}
    pq.write_table(pa.table(arrays), str(p), row_group_size=500)
    tasks = parquet_io.file_chunk_tasks("parquet", p, chunk_rows=600)
    via_tasks = write_index_data_streaming(
        None,
        ["orderkey"],
        8,
        tmp_path / "tasks",
        chunk_capacity=600,
        chunk_tasks=tasks,
        pipeline=pipelined(ingest_workers=3),
    )
    via_iter = write_index_data_streaming(
        parquet_io.iter_file_batches("parquet", p, chunk_rows=600),
        ["orderkey"],
        8,
        tmp_path / "iter",
        chunk_capacity=600,
        pipeline=BuildPipelineConfig.serial(),
    )
    assert file_bytes(via_tasks) == file_bytes(via_iter)


# ---------------------------------------------------------------------------
# fault injection: kill each stage mid-build
# ---------------------------------------------------------------------------
def test_kill_ingest_worker_mid_build(tmp_path):
    b = sample(4000, seed=13)
    pieces = list(chunks_of(b, 512))

    def make_task(i, chunk):
        def task():
            if i == 4:
                raise ValueError("ingest worker died")
            return [chunk]

        return task

    tasks = [make_task(i, c) for i, c in enumerate(pieces)]
    with pytest.raises(ValueError, match="ingest worker died"):
        write_index_data_streaming(
            None,
            ["orderkey"],
            4,
            tmp_path / "o",
            chunk_capacity=512,
            chunk_tasks=tasks,
            pipeline=pipelined(),
        )
    assert _no_pool_threads()
    assert not (tmp_path / "o" / ".spill").exists()


def test_kill_spill_compute_worker_mid_build(tmp_path, monkeypatch):
    from hyperspace_tpu.ops import build as ops_build

    b = sample(4000, seed=17)
    real = ops_build.build_partition_host
    calls = []

    def dying(*a, **k):
        calls.append(1)
        if len(calls) >= 3:
            raise RuntimeError("spill-compute worker died")
        return real(*a, **k)

    monkeypatch.setattr(ops_build, "build_partition_host", dying)
    with pytest.raises(RuntimeError, match="spill-compute worker died"):
        write_index_data_streaming(
            chunks_of(b, 512),
            ["orderkey"],
            4,
            tmp_path / "o",
            chunk_capacity=512,
            engine="host",
            pipeline=pipelined(),
        )
    assert _no_pool_threads()
    assert not (tmp_path / "o" / ".spill").exists()


def test_kill_write_worker_mid_build(tmp_path, monkeypatch):
    from hyperspace_tpu.index import stream_builder as sb

    b = sample(4000, seed=19)
    real = sb.layout.write_batch
    calls = []

    def dying(*a, **k):
        calls.append(1)
        if len(calls) >= 2:
            raise OSError("write worker died")
        return real(*a, **k)

    monkeypatch.setattr(sb.layout, "write_batch", dying)
    with pytest.raises(OSError, match="write worker died"):
        write_index_data_streaming(
            chunks_of(b, 512),
            ["orderkey"],
            4,
            tmp_path / "o",
            chunk_capacity=512,
            engine="host",
            pipeline=pipelined(),
        )
    assert _no_pool_threads()
    assert not (tmp_path / "o" / ".spill").exists()


def test_abort_idempotent_and_reusable_writer(tmp_path):
    w = StreamingIndexWriter(
        ["orderkey"], 4, tmp_path / "o", chunk_capacity=512,
        pipeline=pipelined(),
    )
    w.add_chunk(sample(1000, seed=1))
    w.abort()
    w.abort()  # safe to repeat
    assert _no_pool_threads()
    with pytest.raises(HyperspaceException):
        w.add_chunk(sample(10, seed=2))  # finalized by abort


# ---------------------------------------------------------------------------
# k-way merge: parity + asymptotics (no full re-sort on sorted runs)
# ---------------------------------------------------------------------------
def _sorted_runs(rng, n_runs, rows, key_low, key_high):
    runs = []
    for _ in range(n_runs):
        k = np.sort(rng.integers(key_low, key_high, rows)).astype(np.int64)
        v = rng.integers(0, 10**6, rows).astype(np.int64)
        runs.append(
            ColumnarBatch.from_pydict(
                {"k": k, "v": v}, {"k": "int64", "v": "int64"}
            )
        )
    return runs


def test_merge_sorted_runs_parity_with_lexsort_oracle():
    rng = np.random.default_rng(29)
    runs = _sorted_runs(rng, 5, 400, 0, 50)  # heavy duplicates: tie stress
    got = merge_sorted_runs(runs, ["k"])
    merged = ColumnarBatch.concat(runs)
    order = np.lexsort((sort_encoding(merged.columns["k"]),))
    exp = merged.take(np.argsort(sort_encoding(merged.columns["k"]), kind="stable"))
    assert got.columns["k"].data.tolist() == exp.columns["k"].data.tolist()
    assert got.columns["v"].data.tolist() == exp.columns["v"].data.tolist()
    assert order is not None  # oracle actually computed


def test_merge_sorted_runs_multikey_and_string_parity():
    rng = np.random.default_rng(31)
    runs = []
    for _ in range(4):
        n = 300
        k1 = np.sort(rng.integers(0, 40, n)).astype(np.int64)
        k2 = rng.integers(0, 10, n).astype(np.int32)
        s = rng.choice([b"aa", b"bb", b"cc", b"zz"], n).astype(object)
        b = ColumnarBatch.from_pydict(
            {"k1": k1, "k2": k2, "s": s},
            {"k1": "int64", "k2": "int32", "s": "string"},
        )
        # sort each run by (k1, k2) to make it a genuine sorted run
        order = np.lexsort((k2, k1))
        runs.append(b.take(order))
    got = merge_sorted_runs(runs, ["k1", "k2"])
    merged = ColumnarBatch.concat(runs)
    encs = [sort_encoding(merged.columns[c]) for c in ("k1", "k2")]
    exp = merged.take(np.lexsort(list(reversed(encs))))
    assert got.columns["k1"].data.tolist() == exp.columns["k1"].data.tolist()
    assert got.columns["k2"].data.tolist() == exp.columns["k2"].data.tolist()
    assert got.columns["s"].to_values().tolist() == (
        exp.columns["s"].to_values().tolist()
    )


def test_merge_sorted_runs_never_full_sorts_packable_keys(monkeypatch):
    """Asymptotics guard: for packable keys the merge must run on
    searchsorted alone — a full argsort/lexsort over the concatenation
    (the old O(n log n) behavior) trips the patched sorts."""
    rng = np.random.default_rng(37)
    runs = _sorted_runs(rng, 6, 500, 0, 1000)

    def trap(*a, **k):
        raise AssertionError("full sort called on already-sorted runs")

    monkeypatch.setattr(np, "argsort", trap)
    monkeypatch.setattr(np, "lexsort", trap)
    got = merge_sorted_runs(runs, ["k"])
    ks = got.columns["k"].data
    assert (ks[1:] >= ks[:-1]).all()
    assert got.num_rows == 3000


def test_merge_sorted_runs_unpackable_falls_back_to_lexsort(monkeypatch):
    """Two full-range int64 keys cannot pack into 63 bits — the merge
    falls back to the stable lexsort (correctness over asymptotics)."""
    rng = np.random.default_rng(41)
    runs = []
    for _ in range(2):
        n = 100
        k1 = np.sort(rng.integers(-(2**62), 2**62, n)).astype(np.int64)
        k2 = rng.integers(-(2**62), 2**62, n).astype(np.int64)
        runs.append(
            ColumnarBatch.from_pydict(
                {"k1": k1, "k2": k2}, {"k1": "int64", "k2": "int64"}
            )
        )
    called = []
    real = np.lexsort

    def spy(*a, **k):
        called.append(1)
        return real(*a, **k)

    monkeypatch.setattr(np, "lexsort", spy)
    got = merge_sorted_runs(runs, ["k1", "k2"])
    assert called  # fallback actually taken
    k1 = got.columns["k1"].data
    assert (k1[1:] >= k1[:-1]).all()


# ---------------------------------------------------------------------------
# multi-core host partition + packed radix device kernel
# ---------------------------------------------------------------------------
def test_host_parallel_partition_identical_to_serial(monkeypatch):
    from hyperspace_tpu.ops import build as ops_build

    monkeypatch.setattr(ops_build, "HOST_PARALLEL_MIN_ROWS", 256)
    b = sample(5000, seed=43)
    for keys in (["orderkey"], ["orderkey", "flag"]):
        serial_b, serial_c = ops_build.build_partition_host(b, keys, 8)
        par_b, par_c = ops_build.build_partition_host_parallel(b, keys, 8, 4)
        np.testing.assert_array_equal(serial_c, par_c)
        for name in b.column_names:
            np.testing.assert_array_equal(
                serial_b.columns[name].data, par_b.columns[name].data
            )
    # duplicates: stability must match the serial stable sort exactly
    dup = ColumnarBatch.from_pydict(
        {
            "k": np.array([3] * 2000, dtype=np.int64),
            "tag": np.arange(2000, dtype=np.int64),
        }
    )
    s_b, _ = ops_build.build_partition_host(dup, ["k"], 4)
    p_b, _ = ops_build.build_partition_host_parallel(dup, ["k"], 4, 3)
    np.testing.assert_array_equal(s_b.columns["tag"].data, p_b.columns["tag"].data)


def test_host_parallel_unpackable_falls_back():
    from hyperspace_tpu.ops import build as ops_build

    rng = np.random.default_rng(47)
    b = ColumnarBatch.from_pydict(
        {
            "k1": rng.integers(-(2**62), 2**62, 70000).astype(np.int64),
            "k2": rng.integers(-(2**62), 2**62, 70000).astype(np.int64),
        }
    )
    s_b, s_c = ops_build.build_partition_host(b, ["k1", "k2"], 8)
    p_b, p_c = ops_build.build_partition_host_parallel(b, ["k1", "k2"], 8, 4)
    np.testing.assert_array_equal(s_c, p_c)
    np.testing.assert_array_equal(s_b.columns["k1"].data, p_b.columns["k1"].data)


def test_packed_device_kernel_parity_and_routing():
    from hyperspace_tpu.ops import build as ops_build
    from hyperspace_tpu.telemetry.metrics import metrics

    b = sample(3000, seed=53)
    metrics.reset()
    host_b, host_c = ops_build.build_partition_host(b, ["orderkey", "flag"], 8)
    dev_b, dev_c = ops_build.build_partition_single(b, ["orderkey", "flag"], 8)
    assert metrics.counter("build.engine.device_radix") == 1
    np.testing.assert_array_equal(host_c, dev_c)
    for name in b.column_names:
        np.testing.assert_array_equal(
            host_b.columns[name].data, dev_b.columns[name].data
        )
    # full-range keys overflow the 63-bit composite: fallback kernel, same
    # bytes
    rng = np.random.default_rng(59)
    wide = ColumnarBatch.from_pydict(
        {
            "k1": rng.integers(-(2**62), 2**62, 2000).astype(np.int64),
            "k2": rng.integers(-(2**62), 2**62, 2000).astype(np.int64),
        }
    )
    metrics.reset()
    h_b, h_c = ops_build.build_partition_host(wide, ["k1", "k2"], 4)
    d_b, d_c = ops_build.build_partition_single(wide, ["k1", "k2"], 4)
    assert metrics.counter("build.engine.device_sortfull") == 1
    np.testing.assert_array_equal(h_c, d_c)
    np.testing.assert_array_equal(
        h_b.columns["k1"].data, d_b.columns["k1"].data
    )
    # uint64 beyond int64: the composite bias would wrap — must decline
    # the pack (fallback kernel) and still match the host twin
    big = ColumnarBatch.from_pydict(
        {"k": np.arange(500, dtype=np.uint64) + np.uint64(1 << 63)},
        {"k": "uint64"},
    )
    metrics.reset()
    hb_b, hb_c = ops_build.build_partition_host(big, ["k"], 4)
    db_b, db_c = ops_build.build_partition_single(big, ["k"], 4)
    assert metrics.counter("build.engine.device_radix") == 0
    np.testing.assert_array_equal(hb_c, db_c)
    np.testing.assert_array_equal(hb_b.columns["k"].data, db_b.columns["k"].data)


# ---------------------------------------------------------------------------
# conf plumbing + occupancy snapshot
# ---------------------------------------------------------------------------
def test_conf_build_pipeline_parsing():
    on = HyperspaceConf({}).build_pipeline()
    assert on.enabled and on.spill_compute_workers >= 1
    off = HyperspaceConf({C.BUILD_PIPELINE: "off"}).build_pipeline()
    assert not off.enabled and off.host_width() == 1
    custom = HyperspaceConf(
        {
            C.BUILD_INGEST_WORKERS: 3,
            C.BUILD_SPILL_COMPUTE_WORKERS: "5",
            C.BUILD_SPILL_WRITE_WORKERS: 2,
            C.BUILD_MERGE_WORKERS: 7,
            C.BUILD_QUEUE_DEPTH: 4,
        }
    ).build_pipeline()
    assert (
        custom.ingest_workers,
        custom.spill_compute_workers,
        custom.spill_write_workers,
        custom.merge_workers,
        custom.queue_depth,
    ) == (3, 5, 2, 7, 4)
    with pytest.raises(HyperspaceException):
        HyperspaceConf({C.BUILD_PIPELINE: "sideways"}).build_pipeline()


def test_pipeline_occupancy_snapshot(tmp_path):
    from hyperspace_tpu.telemetry.metrics import (
        build_pipeline_snapshot,
        metrics,
    )

    b = sample(4000, seed=61)
    metrics.reset()
    write_index_data_streaming(
        chunks_of(b, 512),
        ["orderkey"],
        4,
        tmp_path / "o",
        chunk_capacity=512,
        engine="host",
        pipeline=pipelined(),
    )
    snap = build_pipeline_snapshot()
    assert snap["wall_s"] > 0
    assert snap["spill_compute_busy_s"] > 0
    assert snap["spill_write_busy_s"] > 0
    assert "spill_compute_occupancy" in snap
    assert snap["workers"]["spill_compute"] == 2


def test_device_inflight_chunks_bounded(tmp_path, monkeypatch):
    """The device engine's dispatched-but-unfetched chunks (the HBM
    high-water) stay at DEVICE_INFLIGHT_CHUNKS no matter how wide the
    spill-compute pool is — extra workers help the host engine only."""
    from hyperspace_tpu.index import stream_builder as sb
    from hyperspace_tpu.ops import build as ops_build

    inflight = {"cur": 0, "peak": 0}
    lock = threading.Lock()
    real = ops_build.build_partition_single

    def wrapped(batch, keys, nb, pad_to=None, defer=False):
        with lock:
            inflight["cur"] += 1
            inflight["peak"] = max(inflight["peak"], inflight["cur"])
        inner = real(batch, keys, nb, pad_to=pad_to, defer=defer)

        def finish():
            time.sleep(0.02)  # slow D2H: lets dispatch run ahead
            out = inner()
            with lock:
                inflight["cur"] -= 1
            return out

        return finish if defer else finish()

    monkeypatch.setattr(ops_build, "build_partition_single", wrapped)
    b = sample(8192, seed=71)
    write_index_data_streaming(
        chunks_of(b, 512),
        # a string key declines run staging, so every chunk takes THIS
        # per-chunk dispatch path, the one the bound protects (the staged
        # path holds slots per run merge, tested separately)
        ["flag", "orderkey"],
        4,
        tmp_path / "o",
        chunk_capacity=512,
        engine="device",
        pipeline=pipelined(spill_compute_workers=8, spill_write_workers=2),
    )
    assert inflight["peak"] <= sb.DEVICE_INFLIGHT_CHUNKS
    assert inflight["peak"] >= 2  # the pipeline did run ahead of the fetch


def test_worker_gauges_do_not_accumulate_across_builds(tmp_path):
    from hyperspace_tpu.telemetry.metrics import build_pipeline_snapshot, metrics

    metrics.reset()
    for sub in ("a", "b"):
        write_index_data_streaming(
            chunks_of(sample(1500, seed=73), 512),
            ["orderkey"],
            4,
            tmp_path / sub,
            chunk_capacity=512,
            engine="host",
            pipeline=pipelined(),
        )
    snap = build_pipeline_snapshot()
    # two builds, one process, no reset: still the configured LEVEL
    assert snap["workers"]["spill_compute"] == 2
    assert snap["workers"]["spill_write"] == 2


def test_create_action_pipeline_off_matches_on(tmp_path):
    """End-to-end through the session/create path: pipeline=off and the
    default pipelined build produce identical index bytes and identical
    query results."""
    from hyperspace_tpu.hyperspace import Hyperspace
    from hyperspace_tpu.index.index_config import IndexConfig
    from hyperspace_tpu.plan.expr import col
    from hyperspace_tpu.session import HyperspaceSession

    rng = np.random.default_rng(67)
    n = 5000
    batch = ColumnarBatch.from_pydict(
        {
            "k": rng.integers(0, 400, n).astype(np.int64),
            "v": rng.integers(0, 10**6, n).astype(np.int64),
            "s": rng.choice([b"aa", b"bb", b"cc"], n).astype(object),
        },
        {"k": "int64", "v": "int64", "s": "string"},
    )
    src = tmp_path / "data"
    src.mkdir()
    parquet_io.write_parquet(src / "part-0.parquet", batch)
    parquet_io.write_parquet(src / "part-1.parquet", batch.take(np.arange(100)))

    results = {}
    for mode in ("off", "on"):
        conf = HyperspaceConf(
            {
                C.INDEX_SYSTEM_PATH: str(tmp_path / f"idx_{mode}"),
                C.INDEX_NUM_BUCKETS: 8,
                C.BUILD_MODE: C.BUILD_MODE_STREAMING,
                C.BUILD_CHUNK_ROWS: 512,
                C.BUILD_PIPELINE: mode,
            }
        )
        session = HyperspaceSession(conf)
        hs = Hyperspace(session)
        hs.create_index(
            session.read.parquet(str(src)), IndexConfig("pi", ["k"], ["v", "s"])
        )
        vdir = tmp_path / f"idx_{mode}" / "pi" / "v__=0"
        results[mode] = file_bytes(sorted(vdir.glob("*.tcb")))
        session.enable_hyperspace()
        key = int(batch.columns["k"].data[7])
        got = (
            session.read.parquet(str(src))
            .filter(col("k") == key)
            .select("k", "v")
            .collect()
        )
        results[f"q_{mode}"] = sorted(got.columns["v"].data.tolist())
    assert results["off"] == results["on"]
    assert results["q_off"] == results["q_on"]


# ---------------------------------------------------------------------------
# device-resident run staging (docs/14-build-pipeline.md, device build):
# double-buffered H2D slab pair + on-device k-way run merge of RUN_CHUNKS
# chunks. The parity invariant: the staged path must not change ONE BYTE
# of the built index vs the host engine, because runs reserve their first
# chunk's sequence slot and the device merge is stable by chunk order
# exactly like the host merge is by run order.
# ---------------------------------------------------------------------------
from hyperspace_tpu.index.stream_builder import RUN_CHUNKS  # noqa: E402
from hyperspace_tpu.residency import slabs as slab_budget  # noqa: E402


def _int_sample(n=2048, seed=0):
    rng = np.random.default_rng(seed)
    return ColumnarBatch.from_pydict(
        {
            "orderkey": rng.integers(0, 10**6, n).astype(np.int64),
            "qty": rng.integers(0, 50, n).astype(np.int32),
            "price": (rng.random(n) * 1e4).astype(np.float64),
        },
        schema={"orderkey": "int64", "qty": "int32", "price": "float64"},
    )


def _bucket_bytes(out_dir):
    return {
        p.name.split("-")[0]: p.read_bytes()
        for p in sorted(out_dir.glob("*.tcb"))
    }


def _staged_build(tmp_path, tag, keys=("orderkey", "qty"), batch=None,
                  pipeline=None, chunk=512, engine="device"):
    out = tmp_path / tag
    write_index_data_streaming(
        chunks_of(batch if batch is not None else _int_sample(2048 + 100),
                  chunk),
        list(keys),
        8,
        out,
        chunk_capacity=chunk,
        engine=engine,
        pipeline=pipeline or BuildPipelineConfig.serial(),
    )
    return _bucket_bytes(out)


@pytest.mark.parametrize("width", ["narrow", "wide"])
@pytest.mark.parametrize("r", [2, 3, 4])
def test_device_run_merge_matches_host_merge(r, width):
    """The on-device run merge against the host tournament over the same
    staged runs, with heavy key ties (fewer distinct keys than a chunk
    holds): same order (left chunk wins ties, in-run order kept) and the
    same counts. A narrow run plan (<= 31 bits) sorts as int32 and counts
    ``build.device.merge_narrow``; a key spanning about 40 bits sorts as
    int64 and does not."""
    from hyperspace_tpu.ops import build as ops_build
    from hyperspace_tpu.ops.hashing import bucket_ids_host, key_repr
    from hyperspace_tpu.telemetry.metrics import metrics

    cap, nb = 512, 8
    rng = np.random.default_rng(61 + r)
    distinct = (
        np.arange(200, dtype=np.int64)
        if width == "narrow"
        else rng.integers(0, 1 << 40, 64).astype(np.int64)
    )
    chunks = [
        ColumnarBatch.from_pydict(
            {"k": rng.choice(distinct, cap)}, schema={"k": "int64"}
        )
        for _ in range(r)
    ]
    metrics.reset()
    staged, union = [], None
    for b in chunks:
        encoded, bounds = ops_build.stage_encode(b, ["k"])
        plan = ops_build.run_pack_plan(bounds, nb)
        union = bounds if union is None else [
            (min(a, mn), max(c, mx))
            for (a, c), (mn, mx) in zip(union, bounds)
        ]
        s, _ = ops_build.stage_chunk_packed(
            encoded, b.schema(), ["k"], nb, plan, cap
        )
        staged.append(s)
    run_plan = ops_build.run_pack_plan(union, nb)
    order_dev, counts_dev = ops_build.merge_staged_chunks(staged, run_plan, nb)
    assert metrics.counter("build.device.merge_narrow") == (
        1 if width == "narrow" else 0
    )

    merged = ColumnarBatch.concat(chunks)
    bucket = bucket_ids_host([key_repr(merged.columns["k"])], nb)
    comp = ops_build._pack_sort_keys(
        [sort_encoding(merged.columns["k"])], bucket, nb
    )
    runs = []
    for c, s in enumerate(staged):
        rows = c * cap + np.asarray(s.perm).astype(np.int64)
        runs.append((comp[rows], rows))
    np.testing.assert_array_equal(
        np.asarray(order_dev), ops_build.merge_sorted_orders(runs)
    )
    np.testing.assert_array_equal(
        np.asarray(counts_dev)[:nb], np.bincount(bucket, minlength=nb)
    )


def _tied_sample(n, seed):
    """Few distinct orderkeys, each on many rows: the ties of lineitem's
    l_orderkey, many times over."""
    b = _int_sample(n, seed)
    rng = np.random.default_rng(seed)
    b.columns["orderkey"].data[:] = rng.integers(0, 300, n)
    return b


@pytest.mark.parametrize(
    "full_chunks, keys, make, runs",
    [
        # four full chunks fill a run; the tail stages as a run of its own
        (4, ("orderkey", "qty"), _int_sample, 2),
        # the sf1-build shape in miniature: two staged chunks and the
        # partial tail make one 3-chunk run, ties spread over all three
        (2, ("orderkey",), _tied_sample, 1),
    ],
    ids=["distinct", "tied"],
)
def test_staged_device_build_matches_per_chunk_bytes(
    tmp_path, full_chunks, keys, make, runs
):
    """Byte parity host engine vs staged device build (serial AND
    pipelined), with a partial tail chunk in the stream that stages
    through the kernel's n_valid operand; the staged side pays one
    blocking D2H call per run, not one per chunk."""
    from hyperspace_tpu.telemetry.metrics import metrics

    b = make(full_chunks * 512 + 100, seed=29)
    h_bytes = _staged_build(
        tmp_path, "host", keys=keys, batch=b, engine="host"
    )
    metrics.reset()
    s_bytes = _staged_build(tmp_path, "staged", keys=keys, batch=b)
    s_calls = metrics.counter("build.stream.d2h_calls")
    assert metrics.counter("build.device.staged_chunks") == full_chunks + 1
    assert metrics.counter("build.device.staging_declined.tail") == 0
    assert metrics.counter("build.device.staged_runs") == runs
    assert metrics.counter("build.stream.direct_finalize") == (runs == 1)
    assert h_bytes == s_bytes
    # each staged run, the tail's included, pays ONE blocking fetch
    assert s_calls == runs
    p_bytes = _staged_build(
        tmp_path, "staged_pipe", keys=keys, batch=b, pipeline=pipelined(),
    )
    assert p_bytes == h_bytes
    assert slab_budget.held_bytes() == 0


def test_string_key_declines_staging_with_parity(tmp_path):
    """Per-chunk vocab codes are not comparable across chunks, so a
    string KEY routes every chunk per-chunk (counted decline) — and the
    result is still byte-identical to the host engine's."""
    from hyperspace_tpu.telemetry.metrics import metrics

    b = sample(2048, seed=31)  # has the "flag" string column
    metrics.reset()
    s_bytes = _staged_build(
        tmp_path, "str_staged", keys=("orderkey", "flag"), batch=b,
    )
    assert metrics.counter("build.device.staging_declined.string_key") > 0
    assert metrics.counter("build.device.staged_chunks") == 0
    h_bytes = _staged_build(
        tmp_path, "str_host", keys=("orderkey", "flag"), batch=b,
        engine="host",
    )
    assert s_bytes == h_bytes


def test_staged_runs_group_run_chunks_then_tail(tmp_path):
    """Nine staged full chunks and a partial tail spill as runs of 4, 4
    and 2 chunks: the tail stages and joins the last run."""
    from hyperspace_tpu.telemetry.metrics import metrics

    assert RUN_CHUNKS == 4
    cap = 512
    b = _int_sample(9 * cap + 100, seed=53)
    metrics.reset()
    files = write_index_data_streaming(
        chunks_of(b, cap), ["orderkey"], 8, tmp_path / "o",
        chunk_capacity=cap, engine="device",
        pipeline=BuildPipelineConfig.serial(), finalize_mode="runs",
    )
    assert [layout.read_batch(p).num_rows for p in files] == [
        4 * cap, 4 * cap, cap + 100,
    ]
    assert metrics.counter("build.device.staged_chunks") == 10
    assert metrics.counter("build.device.staged_runs") == 3
    assert metrics.counter("build.device.staging_declined.tail") == 0
    assert metrics.counter("build.stream.d2h_calls") == 3
    assert slab_budget.held_bytes() == 0


def _vocab_chunks(full_chunks, cap, seed):
    """Chunks that each carry their own string vocab (one value only a
    single chunk holds) and float64 zeros of both signs: the shapes the
    direct bucket write must gather exactly as a spilled run reads."""
    rng = np.random.default_rng(seed)
    out = []
    for c in range(full_chunks + 1):
        n = cap if c < full_chunks else 100
        price = rng.choice([0.0, -0.0, 1.5, 2.25], n)
        out.append(ColumnarBatch.from_pydict(
            {
                "orderkey": rng.integers(0, 10**6, n).astype(np.int64),
                "price": price.astype(np.float64),
                "flag": rng.choice(
                    [b"A", b"N", b"R", b"C%d" % c], n
                ).astype(object),
            },
            schema={"orderkey": "int64", "price": "float64", "flag": "string"},
        ))
    return out


@pytest.mark.parametrize(
    "full_chunks, direct",
    [(2, 1), (9, 0)],
    ids=["one-run", "nine-plus-tail"],
)
@pytest.mark.parametrize("pipe", ["serial", "pipelined"])
def test_direct_finalize_matches_merge_bytes(
    tmp_path, monkeypatch, full_chunks, direct, pipe
):
    """A build that is one staged run writes its buckets straight from
    the run's order: every bucket file is byte-identical to the spill,
    re-read and merge of the same chunks, and no spill directory is
    made. A nine-chunk-plus-tail build is three runs and still merges."""
    from hyperspace_tpu.telemetry.metrics import metrics

    cap = 512
    config = BuildPipelineConfig.serial() if pipe == "serial" else pipelined()

    def build(tag):
        out = tmp_path / tag
        write_index_data_streaming(
            iter(_vocab_chunks(full_chunks, cap, seed=71)), ["orderkey"], 8,
            out, chunk_capacity=cap, engine="device", pipeline=config,
        )
        return _bucket_bytes(out)

    metrics.reset()
    d_bytes = build("direct")
    assert metrics.counter("build.stream.direct_finalize") == direct
    assert not (tmp_path / "direct" / ".spill").exists()
    monkeypatch.setattr(
        StreamingIndexWriter, "_one_staged_run", lambda self: False
    )
    metrics.reset()
    m_bytes = build("merged")
    assert metrics.counter("build.stream.direct_finalize") == 0
    assert len(d_bytes) == 8
    assert d_bytes == m_bytes
    assert slab_budget.held_bytes() == 0


@pytest.mark.parametrize("pipe", ["serial", "pipelined"])
def test_one_chunk_build_compiles_no_merge(tmp_path, monkeypatch, pipe):
    """A source below one chunk is one staged run of the tail alone: the
    staged kernel's own permutation ships, no merge program is built,
    and the bytes equal the host engine's."""
    from hyperspace_tpu.ops import build as ops_build
    from hyperspace_tpu.telemetry.metrics import metrics

    def no_merge(*a, **k):
        raise AssertionError("a one-chunk run built a merge program")

    b = _int_sample(300, seed=73)
    h_bytes = _staged_build(tmp_path, "host", batch=b, engine="host")
    monkeypatch.setattr(ops_build, "_staged_merge_fn", no_merge)
    metrics.reset()
    config = BuildPipelineConfig.serial() if pipe == "serial" else pipelined()
    s_bytes = _staged_build(tmp_path, "one", batch=b, pipeline=config)
    assert metrics.counter("build.device.staged_chunks") == 1
    assert metrics.counter("build.device.staged_runs") == 1
    assert metrics.counter("build.stream.d2h_calls") == 1
    assert metrics.counter("build.stream.direct_finalize") == 1
    assert s_bytes == h_bytes
    assert slab_budget.held_bytes() == 0


def test_budget_decline_routes_per_chunk(tmp_path, monkeypatch):
    """No slab-budget headroom: the build quietly runs the per-chunk
    path (counted), never fails, and leaks no reservation."""
    from hyperspace_tpu.telemetry.metrics import metrics

    monkeypatch.setenv("HYPERSPACE_TPU_HBM_BUDGET_MB", "0")
    b = _int_sample(4 * 512, seed=37)
    metrics.reset()
    out = _staged_build(tmp_path, "nobudget", batch=b)
    assert metrics.counter("build.device.staging_declined.budget") > 0
    assert metrics.counter("build.device.staged_runs") == 0
    assert metrics.counter("build.stream.d2h_calls") == 4
    assert len(out) > 0
    assert slab_budget.held_bytes() == 0


def test_slab_budget_accounting_and_cache_subtraction(monkeypatch):
    """residency.slabs: all-or-nothing reservation, half-budget cap,
    idempotent release, and the serving caches see held bytes through
    exec.hbm_cache._budget_bytes."""
    from hyperspace_tpu.exec import hbm_cache

    monkeypatch.setenv("HYPERSPACE_TPU_HBM_BUDGET_MB", "64")
    base = hbm_cache._budget_bytes()
    assert base == 64 << 20
    assert slab_budget.try_reserve("t-a", 10 << 20)
    assert hbm_cache._budget_bytes() == base - (10 << 20)
    # over the half-budget cap (32 MB): refused, prior charge intact
    assert not slab_budget.try_reserve("t-b", 30 << 20)
    assert slab_budget.held_bytes() == 10 << 20
    # re-reserving a live tag REPLACES its charge
    assert slab_budget.try_reserve("t-a", 4 << 20)
    assert slab_budget.held_bytes() == 4 << 20
    slab_budget.release("t-a")
    slab_budget.release("t-a")  # idempotent
    assert slab_budget.held_bytes() == 0
    assert hbm_cache._budget_bytes() == base


# -- fault injection: device loss at each staged-path phase -----------------
def test_device_loss_mid_slab_upload_clean_teardown(tmp_path, monkeypatch):
    from hyperspace_tpu.ops import build as ops_build

    real = ops_build.stage_chunk_packed
    calls = []

    def dying(*a, **k):
        calls.append(1)
        if len(calls) >= 2:
            raise RuntimeError("device lost mid slab upload")
        return real(*a, **k)

    monkeypatch.setattr(ops_build, "stage_chunk_packed", dying)
    with pytest.raises(RuntimeError, match="mid slab upload"):
        _staged_build(
            tmp_path, "loss_upload", batch=_int_sample(4 * 512, seed=41)
        )
    assert _no_pool_threads()
    assert not (tmp_path / "loss_upload" / ".spill").exists()
    assert slab_budget.held_bytes() == 0


def test_device_loss_mid_device_merge_clean_teardown_and_host_parity(
    tmp_path, monkeypatch
):
    from hyperspace_tpu.ops import build as ops_build

    b = _int_sample(4 * 512, seed=43)

    def dying(*a, **k):
        raise RuntimeError("device lost mid run merge")

    monkeypatch.setattr(ops_build, "merge_staged_chunks", dying)
    with pytest.raises(RuntimeError, match="mid run merge"):
        _staged_build(tmp_path, "loss_merge", batch=b)
    assert _no_pool_threads()
    assert not (tmp_path / "loss_merge" / ".spill").exists()
    assert slab_budget.held_bytes() == 0
    monkeypatch.undo()
    # host-engine fallback parity: the same source through the host
    # engine produces the same index bytes the device path would have
    host_out = tmp_path / "host_fb"
    write_index_data_streaming(
        chunks_of(b, 512), ["orderkey", "qty"], 8, host_out,
        chunk_capacity=512, engine="host",
        pipeline=BuildPipelineConfig.serial(),
    )
    dev_bytes = _staged_build(tmp_path, "dev_ok", batch=b)
    assert _bucket_bytes(host_out) == dev_bytes


def test_failure_with_async_d2h_in_flight_clean_teardown(
    tmp_path, monkeypatch
):
    """A spill-write failure while a staged run's non-blocking D2H is
    still in flight: the FIRST error re-raises on the main thread, the
    stager's device references and budget charge are dropped, no pool
    thread parks on the device slot."""
    from hyperspace_tpu.index import stream_builder as sb

    def dying(*a, **k):
        raise OSError("spill write died under in-flight D2H")

    monkeypatch.setattr(sb.layout, "write_batch", dying)
    with pytest.raises(OSError, match="in-flight D2H"):
        _staged_build(
            tmp_path, "loss_d2h", batch=_int_sample(6 * 512, seed=47),
            pipeline=pipelined(),
        )
    assert _no_pool_threads()
    assert not (tmp_path / "loss_d2h" / ".spill").exists()
    assert slab_budget.held_bytes() == 0
