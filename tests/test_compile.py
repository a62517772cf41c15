"""Whole-plan compilation (hyperspace_tpu/compile): lowering, the
compiled-pipeline cache, fused-arm parity, scoped invalidation, device-
loss degradation, the serve-tier integration, and the RESULT cache stub.

Parity discipline: every compiled execution is compared against the
SAME query with ``hyperspace.compile.mode=off`` (the per-operator
interpreter) — the pipeline must be invisible in results, visible only
in counters and reuse.
"""

import numpy as np
import pytest

from hyperspace_tpu import constants as C
from hyperspace_tpu.compile.cache import pipeline_cache
from hyperspace_tpu.compile.fingerprint import (
    batch_fingerprint,
    expr_structure,
    plan_fingerprint,
)
from hyperspace_tpu.compile.result_cache import result_cache
from hyperspace_tpu.config import HyperspaceConf
from hyperspace_tpu.exec import executor as EX
from hyperspace_tpu.exec import joins as J
from hyperspace_tpu.exec.executor import Executor
from hyperspace_tpu.exec.hbm_cache import HbmIndexCache, hbm_cache
from hyperspace_tpu.exec.mesh_cache import mesh_cache
from hyperspace_tpu.hyperspace import Hyperspace
from hyperspace_tpu.index.index_config import IndexConfig
from hyperspace_tpu.plan.aggregates import agg_count, agg_sum
from hyperspace_tpu.plan.expr import col, lit
from hyperspace_tpu.serve import QueryServer, ServeConfig
from hyperspace_tpu.session import HyperspaceSession
from hyperspace_tpu.storage import parquet_io
from hyperspace_tpu.storage.columnar import ColumnarBatch
from hyperspace_tpu.telemetry.metrics import metrics
from tests.e2e_utils import assert_row_parity


@pytest.fixture(autouse=True)
def _force_residency(monkeypatch):
    monkeypatch.setenv("HYPERSPACE_TPU_HBM", "force")
    monkeypatch.setenv("HYPERSPACE_TPU_HBM_MIN_ROWS", "1")
    monkeypatch.setenv("HYPERSPACE_TPU_HBM_MAX_BLOCK_FRAC", "1.0")
    hbm_cache.reset()
    mesh_cache.reset()
    pipeline_cache.reset()
    result_cache.reset()
    EX.reset_groups_cache()
    J.reset_setup_cache()
    yield
    hbm_cache.reset()
    mesh_cache.reset()
    pipeline_cache.reset()
    result_cache.reset()
    EX.reset_groups_cache()
    J.reset_setup_cache()


N_ROWS = 40_000


def _source(n=N_ROWS, seed=0):
    rng = np.random.default_rng(seed)
    return ColumnarBatch.from_pydict(
        {
            "k": rng.integers(0, 10_000, n).astype(np.int64),
            "v": rng.integers(0, 1000, n).astype(np.int64),
            "g": rng.integers(0, 40, n).astype(np.int64),
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
    hs.create_index(
        session.read.parquet(str(src)), IndexConfig("cidx", ["k"], ["v", "g"])
    )
    session.enable_hyperspace()
    assert hs.prefetch_index("cidx")
    return session, hs, src, batch


def _lookup(session, src, key):
    return (
        session.read.parquet(str(src))
        .filter(col("k") == lit(int(key)))
        .select("k", "v")
    )


def _with_compile_off(session, fn):
    session.conf.set(C.COMPILE_MODE, C.COMPILE_MODE_OFF)
    try:
        return fn()
    finally:
        session.conf.unset(C.COMPILE_MODE)


# ---------------------------------------------------------------------------
# fused scan pipelines: parity + one lowering per structure
# ---------------------------------------------------------------------------
def test_scan_burst_shares_one_pipeline_with_parity(env):
    session, hs, src, batch = env
    keys = [int(batch.columns["k"].data[i * 997]) for i in range(12)]
    expected = _with_compile_off(
        session, lambda: [_lookup(session, src, k).collect() for k in keys]
    )
    pipeline_cache.reset()
    metrics.reset()
    got = [_lookup(session, src, k).collect() for k in keys]
    for e, g in zip(expected, got):
        assert_row_parity(e, g)
    snap = metrics.snapshot()["counters"]
    # one STRUCTURE -> one lowering; every later literal is a cache hit
    assert snap.get("compile.lowered") == 1
    assert snap.get("compile.cache.hit") == len(keys) - 1
    assert snap.get("compile.run.scan") == len(keys)
    # the fused arm served every query through the structure-keyed
    # executable: one dispatch (== one D2H) per query, resident path
    assert snap.get("compile.fused.dispatches") == len(keys)
    assert snap.get("scan.path.resident_device") == len(keys)
    assert pipeline_cache.snapshot()["entries"] == 1


def test_distinct_structures_lower_separately(env):
    session, hs, src, batch = env
    k = int(batch.columns["k"].data[0])
    metrics.reset()
    _lookup(session, src, k).collect()
    q_range = (
        session.read.parquet(str(src))
        .filter((col("k") >= lit(k)) & (col("k") <= lit(k + 50)))
        .select("k", "v")
    )
    off = _with_compile_off(session, q_range.collect)
    on = q_range.collect()
    assert_row_parity(off, on)
    assert metrics.counter("compile.lowered") == 2
    assert pipeline_cache.snapshot()["kinds"].get("scan") == 2


def test_agg_over_scan_pipeline_parity_and_single_dispatch(env):
    session, hs, src, batch = env
    k = int(batch.columns["k"].data[7])

    def q():
        return (
            session.read.parquet(str(src))
            .filter(col("k") >= lit(k))
            .group_by("g")
            .agg(agg_sum("v", "sv"), agg_count())
        )

    off = _with_compile_off(session, lambda: q().collect())
    metrics.reset()
    with metrics.scoped() as qm:
        on = q().collect()
    assert_row_parity(off, on)
    assert metrics.counter("compile.run.agg_scan") == 1
    # the WHOLE pipeline (filter scan + aggregate) shipped at most one
    # D2H between arms — the acceptance bound
    assert qm.snapshot()["counters"].get("compile.fused.dispatches", 0) <= 1


def test_compile_off_interprets_without_pipeline(env):
    session, hs, src, batch = env
    k = int(batch.columns["k"].data[3])
    metrics.reset()
    session.conf.set(C.COMPILE_MODE, C.COMPILE_MODE_OFF)
    try:
        executor = Executor(session.conf)
        plan = _lookup(session, src, k).optimized_plan()
        executor.execute(plan)
        assert executor.last_pipeline is None
    finally:
        session.conf.unset(C.COMPILE_MODE)
    assert metrics.counter("compile.lowered") == 0


# ---------------------------------------------------------------------------
# hybrid pipelines
# ---------------------------------------------------------------------------
def test_hybrid_pipeline_parity_after_append(tmp_path):
    conf = HyperspaceConf(
        {
            C.INDEX_SYSTEM_PATH: str(tmp_path / "indexes"),
            C.INDEX_NUM_BUCKETS: 4,
            C.INDEX_HYBRID_SCAN_ENABLED: True,
        }
    )
    session = HyperspaceSession(conf)
    hs = Hyperspace(session)
    src = tmp_path / "data"
    src.mkdir()
    batch = _source(8000)
    parquet_io.write_parquet(src / "part-0.parquet", batch)
    hs.create_index(
        session.read.parquet(str(src)), IndexConfig("hidx", ["k"], ["v"])
    )
    parquet_io.write_parquet(src / "part-1.parquet", _source(500, seed=5))
    session.enable_hyperspace()

    key = int(batch.columns["k"].data[11])
    q = (
        session.read.parquet(str(src))
        .filter(col("k") == lit(key))
        .select("k", "v")
    )
    off = _with_compile_off(session, q.collect)
    metrics.reset()
    on = q.collect()
    assert_row_parity(off, on)
    assert metrics.counter("compile.run.hybrid") == 1
    assert pipeline_cache.snapshot()["kinds"].get("hybrid") == 1
    # residency population for base+delta is backgrounded by the run;
    # once it lands, the SAME pipeline serves the fused arm
    hbm_cache.wait_background(timeout_s=30.0)
    before = metrics.counter("scan.path.resident_hybrid")
    on2 = q.collect()
    assert_row_parity(off, on2)
    if metrics.counter("scan.path.resident_hybrid") == before + 1:
        assert metrics.counter("compile.fused.dispatches") >= 1


def test_hybrid_burst_shares_one_executable_compile_flat(tmp_path):
    """Tentpole acceptance: a fresh-literal hybrid serving burst shares
    ONE compiled executable (the structure-keyed batched entry, N=1) —
    one lowering, at most one new hybrid fn, every dispatch fused."""
    from hyperspace_tpu.exec.hbm_cache import _hybrid_fns

    conf = HyperspaceConf(
        {
            C.INDEX_SYSTEM_PATH: str(tmp_path / "indexes"),
            C.INDEX_NUM_BUCKETS: 4,
            C.INDEX_HYBRID_SCAN_ENABLED: True,
        }
    )
    session = HyperspaceSession(conf)
    hs = Hyperspace(session)
    src = tmp_path / "data"
    src.mkdir()
    batch = _source(20_000, seed=9)
    parquet_io.write_parquet(src / "p0.parquet", batch)
    hs.create_index(
        session.read.parquet(str(src)), IndexConfig("hb", ["k"], ["v"])
    )
    parquet_io.write_parquet(src / "p1-append.parquet", _source(900, seed=10))
    session.enable_hyperspace()

    keys = [int(batch.columns["k"].data[i * 731]) for i in range(10)]

    def q(k):
        return (
            session.read.parquet(str(src))
            .filter(col("k") == lit(int(k)))
            .select("k", "v")
        )

    q(keys[0]).collect()  # schedules base+delta population
    hbm_cache.wait_background(timeout_s=30.0)
    expected = _with_compile_off(
        session, lambda: [q(k).collect() for k in keys]
    )
    pipeline_cache.reset()
    metrics.reset()
    fns_before = len(_hybrid_fns._fns)
    got = [q(k).collect() for k in keys]
    for e, g in zip(expected, got):
        assert_row_parity(e, g)
    snap = metrics.snapshot()["counters"]
    # one STRUCTURE -> one lowering; the whole distinct-literal burst
    # rides ONE structure-keyed executable (vs one per literal before)
    assert snap.get("compile.lowered") == 1
    assert snap.get("scan.path.resident_hybrid") == len(keys)
    assert snap.get("compile.fused.dispatches") == len(keys)
    assert len(_hybrid_fns._fns) - fns_before <= 1


# ---------------------------------------------------------------------------
# join-aggregate pipelines + either-side invalidation
# ---------------------------------------------------------------------------
def _join_env(tmp_path):
    rng = np.random.default_rng(11)
    n, n_r = 12_000, 3_000
    left = ColumnarBatch.from_pydict(
        {
            "lk": rng.integers(0, n_r, n).astype(np.int64),
            "lg": rng.integers(0, 30, n).astype(np.int64),
            "lv": rng.integers(0, 100, n).astype(np.int64),
        }
    )
    right = ColumnarBatch.from_pydict(
        {
            "rk": np.arange(n_r, dtype=np.int64),
            "rv": rng.integers(0, 100, n_r).astype(np.int64),
        }
    )
    for name, b in (("l", left), ("r", right)):
        (tmp_path / name).mkdir()
        parquet_io.write_parquet(tmp_path / name / "p.parquet", b)
    session = HyperspaceSession(
        HyperspaceConf(
            {C.INDEX_SYSTEM_PATH: str(tmp_path / "idx"), C.INDEX_NUM_BUCKETS: 8}
        )
    )
    hs = Hyperspace(session)
    hs.create_index(
        session.read.parquet(str(tmp_path / "l")),
        IndexConfig("jl", ["lk"], ["lg", "lv"]),
    )
    hs.create_index(
        session.read.parquet(str(tmp_path / "r")),
        IndexConfig("jr", ["rk"], ["rv"]),
    )
    session.enable_hyperspace()
    return session, hs


def _agg_q(session, tmp_path):
    return (
        session.read.parquet(str(tmp_path / "l"))
        .join(
            session.read.parquet(str(tmp_path / "r")),
            col("lk") == col("rk"),
        )
        .group_by("lg")
        .agg(agg_sum("rv", "srv"), agg_count())
    )


def test_join_agg_pipeline_parity(tmp_path):
    session, hs = _join_env(tmp_path)
    q = _agg_q(session, tmp_path)
    off = _with_compile_off(session, q.collect)
    metrics.reset()
    on = q.collect()
    assert_row_parity(off, on)
    assert metrics.counter("compile.run.join_agg") == 1
    assert pipeline_cache.snapshot()["kinds"].get("join_agg") == 1


def test_join_pipeline_drops_on_either_sides_index_change(tmp_path):
    session, hs = _join_env(tmp_path)
    _agg_q(session, tmp_path).collect()
    assert pipeline_cache.snapshot()["kinds"].get("join_agg") == 1
    before = metrics.counter("compile.cache.invalidated")
    hs.refresh_index("jr")  # RIGHT side: the pipeline must drop
    assert metrics.counter("compile.cache.invalidated") == before + 1
    assert pipeline_cache.snapshot()["entries"] == 0

    _agg_q(session, tmp_path).collect()
    assert pipeline_cache.snapshot()["kinds"].get("join_agg") == 1
    hs.refresh_index("jl")  # LEFT side: must drop too
    assert pipeline_cache.snapshot()["entries"] == 0


# ---------------------------------------------------------------------------
# scoped cache invalidation across refresh/optimize/delete
# ---------------------------------------------------------------------------
def _two_index_env(tmp_path):
    conf = HyperspaceConf(
        {C.INDEX_SYSTEM_PATH: str(tmp_path / "indexes"), C.INDEX_NUM_BUCKETS: 4}
    )
    session = HyperspaceSession(conf)
    hs = Hyperspace(session)
    srcs = []
    for i in range(2):
        src = tmp_path / f"data{i}"
        src.mkdir()
        parquet_io.write_parquet(src / "part-0.parquet", _source(6000, seed=i))
        srcs.append(src)
    hs.create_index(
        session.read.parquet(str(srcs[0])), IndexConfig("ia", ["k"], ["v"])
    )
    hs.create_index(
        session.read.parquet(str(srcs[1])), IndexConfig("ib", ["k"], ["v"])
    )
    session.enable_hyperspace()
    return session, hs, srcs


def test_invalidation_scoped_to_refreshed_index(tmp_path):
    session, hs, srcs = _two_index_env(tmp_path)
    _lookup(session, srcs[0], 5).collect()
    _lookup(session, srcs[1], 5).collect()
    assert pipeline_cache.snapshot()["entries"] == 2

    hs.refresh_index("ia")
    # only ia's pipeline drops; ib's survives the unrelated refresh
    assert pipeline_cache.snapshot()["entries"] == 1
    out = _lookup(session, srcs[0], 5).collect()  # re-lowers cleanly
    assert sorted(out.column_names) == ["k", "v"]
    assert pipeline_cache.snapshot()["entries"] == 2

    hs.optimize_index("ib")
    assert pipeline_cache.snapshot()["entries"] == 1

    _lookup(session, srcs[1], 5).collect()
    hs.delete_index("ib")
    assert pipeline_cache.snapshot()["entries"] == 1  # ia's only


# ---------------------------------------------------------------------------
# device loss mid-fused-dispatch
# ---------------------------------------------------------------------------
def test_device_loss_drops_only_that_pipeline_and_serves_host(env, monkeypatch):
    session, hs, src, batch = env
    k = int(batch.columns["k"].data[21])
    expected = _with_compile_off(
        session, lambda: _lookup(session, src, k).collect()
    )
    # two cached pipelines: the point structure and a range structure
    _lookup(session, src, k).collect()
    (
        session.read.parquet(str(src))
        .filter(col("k") >= lit(k))
        .select("k", "v")
    ).collect()
    assert pipeline_cache.snapshot()["entries"] == 2

    real = HbmIndexCache.block_counts_batch
    boom = {"armed": True}

    def dying(self, table, predicates, prepared=None, metric_ns="serve.batch"):
        if boom["armed"]:
            boom["armed"] = False
            raise RuntimeError("device lost mid-dispatch")
        return real(self, table, predicates, prepared, metric_ns)

    monkeypatch.setattr(HbmIndexCache, "block_counts_batch", dying)
    before_drop = metrics.counter("compile.pipeline.dropped_on_device_loss")
    out = _lookup(session, src, k).collect()  # latches host, stays exact
    assert_row_parity(expected, out)
    assert metrics.counter("scan.resident.device_failed") >= 1
    assert (
        metrics.counter("compile.pipeline.dropped_on_device_loss")
        == before_drop + 1
    )
    # ONLY the dispatching pipeline's entry dropped — the range
    # structure's pipeline still serves from cache
    assert pipeline_cache.snapshot()["entries"] == 1


# ---------------------------------------------------------------------------
# serve integration: burst reuse + snapshot-pinned wholesale reads
# ---------------------------------------------------------------------------
def test_serve_burst_hits_compiled_pipeline_cache(env):
    session, hs, src, batch = env
    keys = [int(batch.columns["k"].data[i * 499]) for i in range(10)]
    queries = [_lookup(session, src, k) for k in keys]
    serial = _with_compile_off(
        session, lambda: [q.collect() for q in queries]
    )
    pipeline_cache.reset()
    metrics.reset()
    # batch_max=1 disables widening: every query executes singly through
    # the compiled pipeline — the compile-count must stay FLAT across
    # the repeated-structure burst while the cache serves it. ONE worker
    # makes the count exact: two workers racing the first miss may both
    # lower before either registers (benign — last write wins)
    server = QueryServer(
        session, ServeConfig(max_workers=1, batch_max=1, autostart=False)
    )
    tickets = [server.submit(q) for q in queries]
    server.start()
    results = [t.result(timeout=120) for t in tickets]
    for s, r in zip(serial, results):
        assert_row_parity(s, r)
    assert metrics.counter("compile.lowered") == 1
    assert metrics.counter("compile.cache.hit") >= len(keys) - 1
    assert server.stats()["compile"]["pipelines"]["entries"] == 1
    server.close()


def test_serve_pinned_snapshot_serves_wholesale_across_refresh(env, tmp_path):
    session, hs, src, batch = env
    key = int(batch.columns["k"].data[5])
    q = _lookup(session, src, key)
    pre = q.collect()
    server = QueryServer(
        session, ServeConfig(max_workers=2, batch_max=1, autostart=False)
    )
    tickets = [server.submit(_lookup(session, src, key)) for _ in range(6)]
    # a refresh commits while the burst sits queued: the tickets pinned
    # the pre-refresh token at admission, so they serve that snapshot
    # WHOLESALE — the compiled-pipeline key folds the pinned token
    hs.refresh_index("cidx")
    server.start()
    for t in tickets:
        assert_row_parity(pre, t.result(timeout=120))
    server.close()


# ---------------------------------------------------------------------------
# RESULT cache: telemetry-driven admission
# ---------------------------------------------------------------------------
def test_result_cache_admission_then_hit_and_invalidate_on_refresh(env):
    session, hs, src, batch = env
    key = int(batch.columns["k"].data[9])
    session.conf.set(C.COMPILE_RESULT_CACHE, C.COMPILE_RESULT_CACHE_ON)
    try:
        server = QueryServer(session, ServeConfig(max_workers=2, batch_max=1))
        # telemetry-driven admission: the COLD first sighting of this
        # structural fingerprint declines (a cache can't help a shape
        # that never repeats); the second sighting admits; the third
        # query serves from the memo
        first = server.submit(_lookup(session, src, key)).result(timeout=120)
        assert metrics.counter("compile.result_cache.declined_cold") >= 1
        assert result_cache.snapshot()["entries"] == 0
        second = server.submit(_lookup(session, src, key)).result(timeout=120)
        assert metrics.counter("compile.result_cache.admitted") >= 1
        assert server.stats()["compile"]["results"]["entries"] == 1
        hits_before = metrics.counter("compile.result_cache.hit")
        third = server.submit(_lookup(session, src, key)).result(timeout=120)
        assert metrics.counter("compile.result_cache.hit") == hits_before + 1
        assert_row_parity(first, second)
        assert_row_parity(first, third)
        assert server.stats()["result_cache"]["serve"]["entries"] == 1

        hs.refresh_index("cidx")
        assert result_cache.snapshot()["entries"] == 0  # scoped drop
        fourth = server.submit(_lookup(session, src, key)).result(timeout=120)
        assert_row_parity(first, fourth)
        server.close()
    finally:
        session.conf.unset(C.COMPILE_RESULT_CACHE)


def test_result_cache_respects_byte_ceiling(env):
    session, hs, src, batch = env
    key = int(batch.columns["k"].data[9])
    session.conf.set(C.COMPILE_RESULT_CACHE, C.COMPILE_RESULT_CACHE_ON)
    session.conf.set(C.COMPILE_RESULT_CACHE_MAX_BYTES, 1)
    try:
        server = QueryServer(session, ServeConfig(max_workers=2, batch_max=1))
        # over the per-entry byte ceiling: declines on BYTES even on the
        # first (cold) sighting — the ceiling outranks the repeat rule
        server.submit(_lookup(session, src, key)).result(timeout=120)
        assert metrics.counter("compile.result_cache.declined_bytes") >= 1
        assert result_cache.snapshot()["entries"] == 0
        server.close()
    finally:
        session.conf.unset(C.COMPILE_RESULT_CACHE)
        session.conf.unset(C.COMPILE_RESULT_CACHE_MAX_BYTES)


# ---------------------------------------------------------------------------
# fingerprints + explain
# ---------------------------------------------------------------------------
def test_fingerprint_masks_literals_but_not_structure(env):
    session, hs, src, batch = env
    p1 = _lookup(session, src, 5).optimized_plan()
    p2 = _lookup(session, src, 99).optimized_plan()
    assert plan_fingerprint(p1) == plan_fingerprint(p2)
    p3 = (
        session.read.parquet(str(src))
        .filter(col("k") >= lit(5))
        .select("k", "v")
    ).optimized_plan()
    assert plan_fingerprint(p1) != plan_fingerprint(p3)
    # the coarse batch fingerprint folds projection + leaf versions but
    # keeps point/range compatible (they share the stacked executable)
    assert batch_fingerprint(p1) == batch_fingerprint(p3)
    p4 = (
        session.read.parquet(str(src))
        .filter(col("k") == lit(5))
        .select("k", "v", "g")
    ).optimized_plan()
    assert batch_fingerprint(p1) != batch_fingerprint(p4)


def test_expr_structure_masks_in_values_by_arity():
    from hyperspace_tpu.plan.expr import is_in

    a = expr_structure(is_in(col("k"), [1, 2, 3]))
    b = expr_structure(is_in(col("k"), [7, 8, 9]))
    c = expr_structure(is_in(col("k"), [1, 2]))
    assert a == b
    assert a != c
    assert "?" not in a or "1" not in a  # no literal values leak


def test_explain_verbose_prints_fused_boundary(env):
    session, hs, src, batch = env
    k = int(batch.columns["k"].data[2])
    q = _lookup(session, src, k)
    q.collect()
    text = hs.explain(q, verbose=True)
    assert "Whole-plan compilation (last query):" in text
    assert "fused[scan]" in text
    assert "Residency tier at lowering:" in text
