"""Out-of-core streaming build tests: spill/merge parity with the in-memory
kernel, chunked ingest, lineage preservation, row-range reads, and the
end-to-end create path in streaming mode.

Parity model: the reference streams splits through executors
(CreateActionBase.scala:122-140) so an index build is memory-bounded by
partition size, not dataset size. These tests assert the explicit TPU
pipeline (chunk -> device bucketize+sort -> spill run -> per-bucket merge)
yields byte-identical bucket contents to the one-shot kernel.
"""

import numpy as np
import pytest

from hyperspace_tpu import constants as C
from hyperspace_tpu.exceptions import HyperspaceException
from hyperspace_tpu.index.builder import write_index_data
from hyperspace_tpu.index.stream_builder import (
    StreamingIndexWriter,
    merge_sorted_runs,
    write_index_data_streaming,
)
from hyperspace_tpu.storage import layout, parquet_io
from hyperspace_tpu.storage.columnar import ColumnarBatch


def sample(n=5000, seed=0):
    rng = np.random.default_rng(seed)
    return ColumnarBatch.from_pydict(
        {
            "orderkey": rng.integers(0, 10**9, n).astype(np.int64),
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


def bucket_contents(files, col="orderkey"):
    out = {}
    for f in files:
        fb = layout.read_batch(f)
        out.setdefault(layout.bucket_of_file(f), []).append(fb.columns[col].data)
    return {k: np.concatenate(v).tolist() for k, v in out.items()}


def test_row_range_read(tmp_path):
    b = sample(1000)
    p = tmp_path / "x.tcb"
    layout.write_batch(p, b)
    sl = layout.read_batch(p, row_range=(100, 250))
    assert sl.num_rows == 150
    np.testing.assert_array_equal(
        sl.columns["orderkey"].data, b.columns["orderkey"].data[100:250]
    )
    np.testing.assert_array_equal(
        sl.columns["price"].data, b.columns["price"].data[100:250]
    )
    # string codes share the file vocab, so decoded values match
    assert sl.columns["flag"].to_values().tolist() == (
        b.columns["flag"].to_values()[100:250].tolist()
    )
    with pytest.raises(HyperspaceException):
        layout.read_batch(p, row_range=(900, 1100))


def test_streaming_matches_inmemory(tmp_path):
    b = sample(6000, seed=1)
    nb = 16
    single = write_index_data(b, ["orderkey"], nb, tmp_path / "single")
    streamed = write_index_data_streaming(
        chunks_of(b, 700), ["orderkey"], nb, tmp_path / "stream", chunk_capacity=700
    )
    # same buckets, same sorted per-bucket key sequences (both paths write
    # rows key-sorted within each bucket)
    assert bucket_contents(streamed) == bucket_contents(single)
    # spill dir cleaned up
    assert not (tmp_path / "stream" / ".spill").exists()
    # footers carry sort/bucket metadata
    for f in streamed:
        footer = layout.read_footer(f)
        assert footer["sortedBy"] == ["orderkey"]
        assert footer["bucket"] == layout.bucket_of_file(f)


def test_host_engine_identical_to_device(tmp_path):
    """build_partition_host is an exact twin of the device kernel: same
    hash → same buckets, same (bucket, keys…) order, same stable ties —
    streamed outputs are byte-identical for every engine choice."""
    b = sample(4000, seed=5)
    nb = 8
    outs = {}
    for engine in ("device", "host", "auto"):
        outs[engine] = write_index_data_streaming(
            chunks_of(b, 600),
            ["orderkey", "flag"],
            nb,
            tmp_path / engine,
            chunk_capacity=600,
            engine=engine,
        )
    dev = bucket_contents(outs["device"])
    assert bucket_contents(outs["host"]) == dev
    assert bucket_contents(outs["auto"]) == dev
    # ties: duplicate keys keep ingest order under both engines
    dup = ColumnarBatch.from_pydict(
        {
            "orderkey": np.array([7, 7, 7, 7, 7, 7], dtype=np.int64),
            "qty": np.arange(6, dtype=np.int32),
        },
        schema={"orderkey": "int64", "qty": "int32"},
    )
    d1 = write_index_data_streaming(
        chunks_of(dup, 3), ["orderkey"], 2, tmp_path / "d1",
        chunk_capacity=8, engine="device",
    )
    d2 = write_index_data_streaming(
        chunks_of(dup, 3), ["orderkey"], 2, tmp_path / "d2",
        chunk_capacity=8, engine="host",
    )
    assert bucket_contents(d1, "qty") == bucket_contents(d2, "qty")


def test_inmemory_engine_routing_and_parity(tmp_path, monkeypatch):
    """The in-memory (single-launch) build routes small batches to the
    host twin by default — one kernel launch cannot amortize a fresh XLA
    compile — and both engines write byte-identical buckets."""
    from hyperspace_tpu import ops
    from hyperspace_tpu.index import builder
    from hyperspace_tpu.telemetry.metrics import metrics

    b = sample(3000, seed=21)
    # auto below the threshold → host
    metrics.reset()
    auto = write_index_data(b, ["orderkey"], 8, tmp_path / "auto")
    assert metrics.snapshot()["counters"].get("build.engine.host") == 1
    metrics.reset()
    forced = write_index_data(
        b, ["orderkey"], 8, tmp_path / "dev", engine="device"
    )
    assert metrics.snapshot()["counters"].get("build.engine.device") == 1
    assert bucket_contents(auto) == bucket_contents(forced)
    # above the threshold on an accelerator, one launch can amortize the
    # compile → device
    monkeypatch.setattr(ops, "configured_platform", lambda: "tpu")
    assert builder.resolve_build_engine("auto", 1 << 23, False) == "device"
    assert builder.resolve_build_engine("host", 1 << 23, False) == "host"


@pytest.mark.parametrize(
    "platform, engine, n_rows, streaming, touch_ok, expected",
    [
        pytest.param("cpu", "auto", 512, True, True, "host",
                     id="cpu-streaming"),
        pytest.param("cpu", "auto", 1 << 23, False, True, "host",
                     id="cpu-inmemory-large"),
        pytest.param("tpu", "auto", 512, True, True, "device",
                     id="accel-streaming-full-chunk"),
        pytest.param("tpu", "auto", 100, True, True, "device",
                     id="accel-streaming-tail"),
        pytest.param("tpu", "auto", (1 << 22) - 1, False, True, "host",
                     id="accel-inmemory-below-floor"),
        pytest.param("tpu", "auto", 1 << 22, False, True, "device",
                     id="accel-inmemory-at-floor"),
        pytest.param("gpu", "auto", 512, True, False, "host",
                     id="auto-device-unreachable"),
        pytest.param("cpu", "device", 100, False, True, "device",
                     id="pinned-device"),
        pytest.param("tpu", "host", 1 << 23, True, True, "host",
                     id="pinned-host"),
    ],
)
def test_build_engine_rule(
    monkeypatch, platform, engine, n_rows, streaming, touch_ok, expected
):
    """The one static rule both writers resolve their engine by: host on
    a CPU platform, for an in-memory build below the floor, or when the
    first device touch failed (counted); device otherwise, for every
    chunk of a streaming build, the tail included."""
    from hyperspace_tpu import ops
    from hyperspace_tpu.index import builder
    from hyperspace_tpu.telemetry.metrics import metrics
    from hyperspace_tpu.utils import deviceprobe

    assert builder.INMEMORY_HOST_MAX_ROWS == 1 << 22
    monkeypatch.setattr(ops, "configured_platform", lambda: platform)
    monkeypatch.setattr(
        deviceprobe, "_FIRST_TOUCH",
        {"ok": touch_ok, "error": None if touch_ok else "RuntimeError()"},
    )
    metrics.reset()
    assert builder.resolve_build_engine(engine, n_rows, streaming) == expected
    assert metrics.counter("build.engine.device_unreachable") == (
        0 if touch_ok else 1
    )


@pytest.mark.parametrize(
    "platform, engine",
    [("tpu", "auto"), ("gpu", "device")],
    ids=["tpu-auto", "pinned-device"],
)
def test_build_engine_rule_raises_where_device_required(
    monkeypatch, platform, engine
):
    """A failed first touch raises DeviceFault on a TPU platform or with
    the device pinned — it never routes such a build host-side."""
    from hyperspace_tpu import ops
    from hyperspace_tpu.index import builder
    from hyperspace_tpu.utils import deviceprobe

    monkeypatch.setattr(ops, "configured_platform", lambda: platform)
    monkeypatch.setattr(
        deviceprobe, "_FIRST_TOUCH", {"ok": False, "error": "RuntimeError()"}
    )
    with pytest.raises(deviceprobe.DeviceFault):
        builder.resolve_build_engine(engine, 512, True)


@pytest.mark.parametrize("mode", ["streaming", "inmemory"])
def test_build_leaves_no_state_in_home(tmp_path, monkeypatch, mode):
    """An auto build that resolves to the device writes nothing under
    $HOME: the engine follows the platform, so no verdict is kept for a
    later process."""
    from hyperspace_tpu import ops
    from hyperspace_tpu.index import builder
    from hyperspace_tpu.telemetry.metrics import metrics

    home = tmp_path / "home"
    home.mkdir()
    monkeypatch.setenv("HOME", str(home))
    # an accelerator platform whose Pallas kernels stay off (only "tpu"
    # turns them on); the CPU backend does the device engine's work
    monkeypatch.setattr(ops, "configured_platform", lambda: "gpu")
    monkeypatch.setattr(builder, "INMEMORY_HOST_MAX_ROWS", 1024)
    b = sample(3000, seed=41)
    metrics.reset()
    if mode == "streaming":
        write_index_data_streaming(
            chunks_of(b, 512), ["orderkey"], 4, tmp_path / "o",
            chunk_capacity=512, engine="auto",
        )
    else:
        write_index_data(b, ["orderkey"], 4, tmp_path / "o", engine="auto")
    assert metrics.counter("build.engine.device") >= 1
    assert metrics.counter("build.engine.host") == 0
    assert sorted(home.rglob("*")) == []


def test_streaming_string_key_cross_chunk_vocabs(tmp_path):
    # chunks see disjoint vocabularies; merge must re-encode onto a shared
    # vocab and keep runs sorted
    b1 = ColumnarBatch.from_pydict(
        {"s": np.array(["d", "a", "c", "b"] * 50, dtype=object),
         "v": np.arange(200, dtype=np.int64)},
        {"s": "string", "v": "int64"},
    )
    b2 = ColumnarBatch.from_pydict(
        {"s": np.array(["z", "aa", "m", "c"] * 50, dtype=object),
         "v": np.arange(200, 400, dtype=np.int64)},
        {"s": "string", "v": "int64"},
    )
    nb = 4
    w = StreamingIndexWriter(["s"], nb, tmp_path / "out", chunk_capacity=256)
    w.add_chunk(b1)
    w.add_chunk(b2)
    files = w.finalize()
    whole = ColumnarBatch.concat([b1, b2])
    single = write_index_data(whole, ["s"], nb, tmp_path / "single")
    got = {
        k: sorted(v) for k, v in bucket_contents(files, "v").items()
    }
    exp = {
        k: sorted(v) for k, v in bucket_contents(single, "v").items()
    }
    assert got == exp
    # within each streamed bucket file, strings are sorted ascending
    for f in files:
        vals = layout.read_batch(f).columns["s"].to_values()
        assert list(vals) == sorted(vals)


def test_merge_sorted_runs_is_sorted_and_stable():
    r1 = ColumnarBatch.from_pydict(
        {"k": np.array([1, 3, 5, 7], dtype=np.int64),
         "tag": np.array([10, 30, 50, 70], dtype=np.int64)}
    )
    r2 = ColumnarBatch.from_pydict(
        {"k": np.array([2, 3, 6], dtype=np.int64),
         "tag": np.array([20, 31, 60], dtype=np.int64)}
    )
    m = merge_sorted_runs([r1, r2], ["k"])
    assert m.columns["k"].data.tolist() == [1, 2, 3, 3, 5, 6, 7]
    # stable: equal keys keep run order (r1's 30 before r2's 31)
    assert m.columns["tag"].data.tolist() == [10, 20, 30, 31, 50, 60, 70]


def test_streaming_sharded_mesh(tmp_path):
    from hyperspace_tpu.parallel.mesh import make_mesh

    b = sample(3000, seed=7)
    nb = 8
    mesh = make_mesh(8)
    streamed = write_index_data_streaming(
        chunks_of(b, 640), ["orderkey"], nb, tmp_path / "stream",
        chunk_capacity=640, mesh=mesh,
    )
    single = write_index_data(b, ["orderkey"], nb, tmp_path / "single")
    got = {k: sorted(v) for k, v in bucket_contents(streamed).items()}
    exp = {k: sorted(v) for k, v in bucket_contents(single).items()}
    assert got == exp


def test_create_action_streaming_mode(tmp_path):
    # end-to-end: create in forced streaming mode with tiny chunks; query
    # results must match the unrewritten plan (off/on row parity oracle)
    from hyperspace_tpu.config import HyperspaceConf
    from hyperspace_tpu.hyperspace import Hyperspace
    from hyperspace_tpu.index.index_config import IndexConfig
    from hyperspace_tpu.plan.expr import col
    from hyperspace_tpu.plan.ir import IndexScan
    from hyperspace_tpu.session import HyperspaceSession
    from tests.e2e_utils import assert_row_parity

    rng = np.random.default_rng(3)
    n = 4000
    batch = ColumnarBatch.from_pydict(
        {
            "k": rng.integers(0, 500, n).astype(np.int64),
            "v": rng.integers(0, 10**6, n).astype(np.int64),
            "s": rng.choice([b"aa", b"bb", b"cc"], n).astype(object),
        },
        {"k": "int64", "v": "int64", "s": "string"},
    )
    src = tmp_path / "data"
    src.mkdir()
    parquet_io.write_parquet(src / "part-0.parquet", batch)

    conf = HyperspaceConf(
        {
            C.INDEX_SYSTEM_PATH: str(tmp_path / "indexes"),
            C.INDEX_NUM_BUCKETS: 8,
            C.BUILD_MODE: C.BUILD_MODE_STREAMING,
            C.BUILD_CHUNK_ROWS: 512,
        }
    )
    session = HyperspaceSession(conf)
    hs = Hyperspace(session)
    df = session.read.parquet(str(src))
    hs.create_index(df, IndexConfig("si", ["k"], ["v", "s"]))

    key = int(batch.columns["k"].data[17])
    q = session.read.parquet(str(src)).filter(col("k") == key).select("k", "v")
    off = q.collect()
    session.enable_hyperspace()
    on = q.collect()
    assert_row_parity(off, on)
    assert q.optimized_plan().collect(lambda nd: isinstance(nd, IndexScan))


def test_iter_file_batches_parquet(tmp_path):
    b = sample(2500, seed=11)
    p = tmp_path / "d.parquet"
    parquet_io.write_parquet(p, b)
    chunks = list(parquet_io.iter_file_batches("parquet", p, chunk_rows=1000))
    assert [c.num_rows for c in chunks] == [1000, 1000, 500]
    re = ColumnarBatch.concat(chunks)
    np.testing.assert_array_equal(
        re.columns["orderkey"].data, b.columns["orderkey"].data
    )
    # projection pushdown
    chunks = list(
        parquet_io.iter_file_batches("parquet", p, columns=["qty"], chunk_rows=1000)
    )
    assert all(c.column_names == ["qty"] for c in chunks)


def test_writer_stats_and_guards(tmp_path):
    b = sample(1200, seed=13)
    w = StreamingIndexWriter(["orderkey"], 4, tmp_path / "o", chunk_capacity=512)
    for c in chunks_of(b, 512):
        w.add_chunk(c)
    files = w.finalize()
    st = w.stats
    assert st["rows"] == 1200
    assert st["chunks"] == 3  # 512, 512, tail 176
    assert "first_chunk_s" in st and "steady_chunk_s_avg" in st
    assert sum(layout.read_footer(f)["numRows"] for f in files) == 1200
    with pytest.raises(HyperspaceException):
        w.finalize()
    with pytest.raises(HyperspaceException):
        w.add_chunk(b)  # finalized


def test_writer_coalesces_small_chunks(tmp_path):
    # many tiny add_chunk calls (small-file sources) must coalesce into
    # capacity-sized device runs, not one padded run per file
    b = sample(2000, seed=17)
    w = StreamingIndexWriter(["orderkey"], 4, tmp_path / "o", chunk_capacity=1024)
    for c in chunks_of(b, 50):  # 40 tiny files
        w.add_chunk(c)
    files = w.finalize()
    st = w.stats
    assert st["chunks"] == 2  # 1024 + 976, not 40
    assert sum(layout.read_footer(f)["numRows"] for f in files) == 2000
    single = write_index_data(b, ["orderkey"], 4, tmp_path / "single")
    assert bucket_contents(files) == bucket_contents(single)


def test_prefetch_chunks_completion_and_abort():
    import threading
    import time

    from hyperspace_tpu.index.stream_builder import prefetch_chunks

    # normal completion: all items arrive, sentinel delivered, thread gone
    assert list(prefetch_chunks(iter(range(50)))) == list(range(50))

    # producer exception re-raises at the consumer
    def boom():
        yield 1
        raise ValueError("producer died")

    with pytest.raises(ValueError):
        list(prefetch_chunks(boom()))

    # consumer abort: the producer thread must exit instead of blocking
    # forever on the full queue with a chunk pinned
    produced = []

    def chunks():
        for i in range(100):
            produced.append(i)
            yield i

    g = prefetch_chunks(chunks())
    next(g)
    next(g)
    g.close()
    deadline = time.time() + 5
    while time.time() < deadline and any(
        t.name == "chunk-prefetch" and t.is_alive() for t in threading.enumerate()
    ):
        time.sleep(0.05)
    assert not any(
        t.name == "chunk-prefetch" and t.is_alive() for t in threading.enumerate()
    )
    assert len(produced) < 100  # stopped early, not fully drained


def test_writer_splits_oversized_batch(tmp_path):
    b = sample(3000, seed=19)
    w = StreamingIndexWriter(["orderkey"], 4, tmp_path / "o", chunk_capacity=1024)
    w.add_chunk(b)  # 3x capacity in one call
    files = w.finalize()
    assert w.stats["chunks"] == 3
    assert sum(layout.read_footer(f)["numRows"] for f in files) == 3000
    single = write_index_data(b, ["orderkey"], 4, tmp_path / "single")
    assert bucket_contents(files) == bucket_contents(single)


def test_streaming_failure_tears_down_pipeline(tmp_path, monkeypatch):
    """A spill failure mid-build must stop every pool worker (no parked
    daemons) and clean the spill dir, then re-raise."""
    import threading
    import time

    from hyperspace_tpu.index import stream_builder as sb

    b = sample(3000, seed=23)

    def failing_write(*a, **k):
        raise OSError("disk full")

    monkeypatch.setattr(sb.layout, "write_batch", failing_write)
    with pytest.raises(OSError):
        sb.write_index_data_streaming(
            chunks_of(b, 512), ["orderkey"], 4, tmp_path / "o", chunk_capacity=512
        )
    pool_prefixes = ("spill-compute", "spill-write", "ingest", "bucket-merge")
    deadline = time.time() + 5
    while time.time() < deadline and any(
        t.name.startswith(pool_prefixes) and t.is_alive()
        for t in threading.enumerate()
    ):
        time.sleep(0.05)
    assert not any(
        t.name.startswith(pool_prefixes) and t.is_alive()
        for t in threading.enumerate()
    )
    assert not (tmp_path / "o" / ".spill").exists()


def test_sum_of_all_null_group_is_null():
    """SQL NULL semantics: sum over a group whose float values are all NULL
    is NULL (NaN), matching avg/min/max of the same group — on both the
    host hash_aggregate and the distributed merge path (ADVICE r2)."""
    import numpy as np

    from hyperspace_tpu.exec.aggregate import hash_aggregate
    from hyperspace_tpu.plan.aggregates import AggSpec
    from hyperspace_tpu.storage.columnar import Column, ColumnarBatch

    batch = ColumnarBatch(
        {
            "g": Column("int64", np.array([0, 0, 1, 1])),
            "v": Column("float64", np.array([1.0, 2.0, np.nan, np.nan])),
        }
    )
    out = hash_aggregate(
        batch, ["g"], [AggSpec("sum", "v", "s"), AggSpec("avg", "v", "a")]
    )
    rows = {int(g): (s, a) for g, s, a in zip(
        out.columns["g"].data, out.columns["s"].data, out.columns["a"].data
    )}
    assert rows[0][0] == 3.0
    assert np.isnan(rows[1][0]) and np.isnan(rows[1][1])


def test_exact_int_sum_guard_handles_int64_min():
    """np.abs(int64 min) wraps negative; the exactness bound must be
    computed in Python ints so a column containing -2^63 routes through
    the exact int64 accumulator (ADVICE r2)."""
    import numpy as np

    from hyperspace_tpu.exec.aggregate import hash_aggregate
    from hyperspace_tpu.plan.aggregates import AggSpec
    from hyperspace_tpu.storage.columnar import Column, ColumnarBatch

    lo = np.int64(np.iinfo(np.int64).min)
    batch = ColumnarBatch(
        {
            "g": Column("int64", np.array([0, 0])),
            "v": Column("int64", np.array([lo, 3], dtype=np.int64)),
        }
    )
    out = hash_aggregate(batch, ["g"], [AggSpec("sum", "v", "s")])
    # exact int64 wrap-around semantics, not a float64 rounding
    assert out.columns["s"].data[0] == np.int64(lo + 3)
