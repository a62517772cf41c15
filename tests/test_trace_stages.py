"""Stage spans on the serve batch, the single path and the build
finalize; named device programs; spans in a ``jax.profiler`` session;
backend compiles named by span (docs/18-observability.md)."""

import itertools
from pathlib import Path

import numpy as np
import pytest

from hyperspace_tpu import constants as C
from hyperspace_tpu.config import HyperspaceConf
from hyperspace_tpu.exec import hbm_cache as hbm_mod
from hyperspace_tpu.exec.hbm_cache import hbm_cache
from hyperspace_tpu.hyperspace import Hyperspace
from hyperspace_tpu.index.index_config import IndexConfig
from hyperspace_tpu.plan.expr import col, lit
from hyperspace_tpu.serve import QueryServer, ServeConfig
from hyperspace_tpu.session import HyperspaceSession
from hyperspace_tpu.storage import parquet_io
from hyperspace_tpu.storage.columnar import ColumnarBatch
from hyperspace_tpu.telemetry.recorder import flight_recorder
from hyperspace_tpu.telemetry.trace import span, start_trace


@pytest.fixture(autouse=True)
def _isolated(monkeypatch):
    monkeypatch.setenv("HYPERSPACE_TPU_HBM", "force")
    monkeypatch.setenv("HYPERSPACE_TPU_HBM_MIN_ROWS", "1")
    hbm_cache.reset()
    flight_recorder.reset()
    yield
    hbm_cache.reset()
    flight_recorder.reset()


def _source(n=60_000, seed=0):
    rng = np.random.default_rng(seed)
    return ColumnarBatch.from_pydict(
        {
            "k": rng.integers(0, 20_000, n).astype(np.int64),
            "v": rng.integers(0, 1000, n).astype(np.int64),
        }
    )


@pytest.fixture
def env(tmp_path):
    batch = _source()
    src = tmp_path / "data"
    src.mkdir()
    parquet_io.write_parquet(src / "part-0.parquet", batch)
    conf = HyperspaceConf(
        {C.INDEX_SYSTEM_PATH: str(tmp_path / "indexes"), C.INDEX_NUM_BUCKETS: 4}
    )
    session = HyperspaceSession(conf)
    hs = Hyperspace(session)
    hs.create_index(session.read.parquet(str(src)), IndexConfig("tidx", ["k"], ["v"]))
    session.enable_hyperspace()
    assert hs.prefetch_index("tidx")
    return session, hs, src, batch


def _lookup(session, src, key):
    return session.read.parquet(str(src)).filter(col("k") == lit(int(key))).select("k", "v")


def _children(s, name):
    return [c for c in s.children if c.name == name]


def _served_burst(session, queries):
    server = QueryServer(session, ServeConfig(max_workers=1, batch_max=8, autostart=False))
    tickets = [server.submit(q) for q in queries]
    server.start()
    for t in tickets:
        t.result(timeout=120)
    server.close()
    return tickets


# --- the serve batch and the single path ------------------------------------


def test_coalesced_batch_spans_dispatch_launch_and_host_legs(env):
    session, hs, src, batch = env
    keys = [int(batch.columns["k"].data[i]) for i in range(0, 80, 20)]
    tickets = _served_burst(session, [_lookup(session, src, k) for k in keys])
    assert all(t.batch_size == len(keys) for t in tickets)
    bd = tickets[0].trace.find("serve.batch_dispatch")
    assert bd is not None
    (ds,) = _children(bd, "scan.device_dispatch")
    assert ds.labels["n"] == len(keys)
    assert ds.labels["tier"] == "resident" and ds.labels["structure_keyed"] is True
    assert ds.labels["d2h_bytes"] > 0
    (launch,) = _children(ds, "scan.device_launch")
    assert ds.t0 <= launch.t0 <= launch.t1 <= ds.t1
    legs = _children(bd, "scan.host_leg")
    assert len(legs) == len(keys)
    assert all(leg.labels["files"] >= 1 and "host_blocks" in leg.labels for leg in legs)
    # riders adopt the one subtree: the dispatch is spanned once
    assert all(t.trace.find("scan.device_dispatch") is ds for t in tickets)


def test_served_submission_spans_classify(env):
    session, hs, src, batch = env
    (t,) = _served_burst(session, [_lookup(session, src, batch.columns["k"].data[3])])
    cl = t.trace.find("serve.classify")
    assert cl is not None and cl.t1 is not None
    assert cl.labels["batchable"] is True
    assert cl in t.trace.root.children  # at submit, under serve.query


def test_single_path_spans_the_dispatch_once(env):
    session, hs, src, batch = env
    _lookup(session, src, batch.columns["k"].data[5]).collect()
    t = session.last_trace
    assert t.spans().count("scan.device_dispatch") == 1
    ds = t.find("scan.device_dispatch")
    assert ds.labels["n"] == 1
    assert len(_children(ds, "scan.device_launch")) == 1
    assert t.spans().count("scan.host_leg") == 1


def test_hybrid_batch_spans_the_delta_leg(env):
    from hyperspace_tpu.plan.ir import Union
    from hyperspace_tpu.plan.rules.hybrid_scan import parse_hybrid_union

    session, hs, src, batch = env
    session.conf.set(C.INDEX_HYBRID_SCAN_ENABLED, True)
    parquet_io.write_parquet(src / "part-append.parquet", _source(2000, seed=5))
    keys = [int(batch.columns["k"].data[i]) for i in range(0, 60, 20)]
    queries = [_lookup(session, src, k) for k in keys]
    info = parse_hybrid_union(queries[0].optimized_plan().collect(lambda n: isinstance(n, Union))[0])
    table = hbm_cache.prefetch(info.entry.content.files(), ["k"])
    assert hbm_cache.prefetch_delta(
        table, info.appended, info.relation, list(info.user_cols), info.deleted_ids
    ) is not None
    tickets = _served_burst(session, queries)
    assert all(t.batch_size == len(keys) for t in tickets)
    bd = tickets[0].trace.find("serve.batch_dispatch")
    (ds,) = _children(bd, "scan.device_dispatch")
    assert ds.labels["hybrid"] is True and ds.labels["n"] == len(keys)
    assert len(_children(bd, "scan.delta_leg")) == len(keys)
    assert len(_children(bd, "scan.host_leg")) == len(keys)


# --- the build finalize -----------------------------------------------------


def test_streamed_build_finalize_spans_drain_and_bucket_merge(tmp_path):
    src = tmp_path / "data"
    src.mkdir()
    parquet_io.write_parquet(src / "part-0.parquet", _source(20_000))
    conf = HyperspaceConf(
        {
            C.INDEX_SYSTEM_PATH: str(tmp_path / "indexes"),
            C.INDEX_NUM_BUCKETS: 4,
            C.BUILD_MODE: "streaming",
            C.BUILD_ENGINE: "device",
            C.BUILD_CHUNK_ROWS: 4096,
        }
    )
    session = HyperspaceSession(conf)
    Hyperspace(session).create_index(session.read.parquet(str(src)), IndexConfig("bidx", ["k"], ["v"]))
    (t,) = [t for t in flight_recorder.last() if t.root.labels.get("index") == "bidx"]
    fin = t.find("build.finalize")
    assert fin is not None
    (drain,) = _children(fin, "build.drain")
    (merge,) = _children(fin, "build.bucket_merge")
    assert drain.labels["chunks"] >= 2 and merge.labels["runs"] >= 2
    assert fin.t0 <= drain.t0 <= drain.t1 <= merge.t0 <= merge.t1 <= fin.t1


# --- named device programs --------------------------------------------------


def _spy(monkeypatch, module, factory):
    """Record (jitted fn, args) of every program ``factory`` hands out."""
    seen = []
    real = getattr(module, factory)

    def wrapped(*a, **k):
        fn = real(*a, **k)

        def call(*args):
            seen.append((fn, args))
            return fn(*args)

        return call

    monkeypatch.setattr(module, factory, wrapped)
    return seen


def _module_name(seen, x32=True):
    """The lowered module's name; the resident programs run in 32-bit
    mode, the build programs in 64-bit."""
    import contextlib

    from hyperspace_tpu.ops import kernels as K

    fn, args = seen[-1]
    with K._x32() if x32 else contextlib.nullcontext():
        text = fn.lower(*args).as_text()
    return text.split("module @", 1)[1].split(" ", 1)[0]


def test_resident_programs_carry_their_names(env, monkeypatch):
    session, hs, src, batch = env
    monkeypatch.setenv("HYPERSPACE_TPU_KERNELS", "off")  # the XLA arm
    files = sorted(Path(hs.index("tidx").index_location).glob("v__=*/*.tcb"))
    table = hbm_cache.resident_for(files, ["k"])
    pred = col("k") == lit(int(batch.columns["k"].data[0]))
    single = _spy(monkeypatch, hbm_mod, "_counts_fn")
    batched = _spy(monkeypatch, hbm_mod, "_batched_counts_fn")
    hbm_cache.block_counts(table, pred)
    hbm_cache.block_counts_batch(table, [pred, pred])
    assert _module_name(single) == "jit_resident_counts"
    assert _module_name(batched) == "jit_resident_counts_batched"


def test_hybrid_programs_carry_their_names(env, monkeypatch):
    from hyperspace_tpu.plan.ir import Union
    from hyperspace_tpu.plan.rules.hybrid_scan import parse_hybrid_union

    session, hs, src, batch = env
    session.conf.set(C.INDEX_HYBRID_SCAN_ENABLED, True)
    parquet_io.write_parquet(src / "part-append.parquet", _source(2000, seed=5))
    q = _lookup(session, src, batch.columns["k"].data[0])
    info = parse_hybrid_union(q.optimized_plan().collect(lambda n: isinstance(n, Union))[0])
    table = hbm_cache.prefetch(info.entry.content.files(), ["k"])
    delta = hbm_cache.prefetch_delta(table, info.appended, info.relation, list(info.user_cols), info.deleted_ids)
    pred = col("k") == lit(int(batch.columns["k"].data[0]))
    single = _spy(monkeypatch, hbm_mod, "_hybrid_counts_fn")
    batched = _spy(monkeypatch, hbm_mod, "_hybrid_batched_counts_fn")
    hbm_cache.hybrid_block_counts(table, delta, pred)
    hbm_cache.hybrid_block_counts_batch(table, delta, [pred])
    assert _module_name(single) == "jit_resident_hybrid_counts"
    assert _module_name(batched) == "jit_resident_hybrid_counts_batched"


def test_build_programs_carry_their_names():
    import jax
    import jax.numpy as jnp

    from hyperspace_tpu.ops import build as B

    S = jax.ShapeDtypeStruct
    arrays = {"k": S((1024,), jnp.int64)}
    n = S((), jnp.int32)
    mins, shifts = S((1,), jnp.int64), S((1,), jnp.int32)
    dtypes = (("k", "int64"),)
    cases = [
        (B._single_perm_kernel(dtypes, ("k",), 8), (arrays, {}, n)),
        (B._single_perm_kernel_packed(dtypes, ("k",), 8, True), (arrays, {}, n, mins, shifts)),
        (B._single_staged_kernel_packed(dtypes, ("k",), 8, True), (arrays, {}, n, mins, shifts)),
        (
            B._staged_merge_fn(1, True),
            (S((2, 1024), jnp.int64), S((2, 1024), jnp.int32), S((2, 8), jnp.int32),
             S((2, 1), jnp.int64), S((2, 1), jnp.int32), mins, shifts),
        ),
    ]
    names = [_module_name([c], x32=False) for c in cases]
    assert names == ["jit_build_bucket_sort", "jit_build_bucket_sort_packed",
                     "jit_build_bucket_sort_staged", "jit_build_sort_merge_runs"]


# --- spans in a profiler session, compiles named by span --------------------


def test_spans_enter_an_active_profiler_session(tmp_path):
    import jax

    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        with start_trace("serve.query"):
            with span("serve.classify"):
                with span("scan.host_leg"):
                    pass
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    names = {
        ev.name
        for plane in ProfileData.from_file(str(path)).planes
        for line in plane.lines
        for ev in line.events
    }
    assert {"serve.query", "serve.classify", "scan.host_leg"} <= names


_fresh = itertools.count()


def test_backend_compile_is_named_by_the_open_span():
    import jax
    import jax.numpy as jnp

    from hyperspace_tpu.telemetry.metrics import compile_snapshot

    before = compile_snapshot()["backend_compiles"]
    c = float(np.random.default_rng().random()) + next(_fresh)  # a program no cache holds
    with start_trace("query.collect") as t:
        with span("compile.lower") as s:
            jax.jit(lambda x: x * c + 1.0)(jnp.ones(7)).block_until_ready()
    assert compile_snapshot()["backend_compiles"] > before
    assert s.labels["xla_compile_s"] > 0
    assert "xla_compile_s" not in t.root.labels
