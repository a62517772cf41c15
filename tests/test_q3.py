"""TPC-H Q3 through the normal path (CPU, small scale): the arithmetic
nodes, expression aggregates, ORDER BY/LIMIT, the join reorder rule,
and the mesh's filtered join-aggregate top-k arm, each against the
plain numpy reference of the benchmark (benchmark/configs/
tpch_q3_reference.py) on seeded TPC-H tables, on a 4-device CPU mesh
and on a one-device session."""

import importlib.util
from pathlib import Path

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from benchmark.common import tpch
from hyperspace_tpu import constants as C
from hyperspace_tpu.config import HyperspaceConf
from hyperspace_tpu.dataframe import desc
from hyperspace_tpu.exec import executor as EX
from hyperspace_tpu.exec import joins as J
from hyperspace_tpu.exec.aggregate import hash_aggregate
from hyperspace_tpu.exec.hbm_cache import hbm_cache
from hyperspace_tpu.exec.mesh_cache import mesh_cache
from hyperspace_tpu.exec.order import sort_batch
from hyperspace_tpu.hyperspace import Hyperspace
from hyperspace_tpu.index.index_config import IndexConfig
from hyperspace_tpu.plan.aggregates import agg_avg, agg_count, agg_sum
from hyperspace_tpu.plan.expr import col, eval_mask, eval_value, lit, value_dtype
from hyperspace_tpu.plan.ir import IndexScan, Join, Limit, Sort
from hyperspace_tpu.session import HyperspaceSession
from hyperspace_tpu.storage.columnar import Column, ColumnarBatch
from hyperspace_tpu.telemetry.metrics import metrics

REPO = Path(__file__).resolve().parents[1]
SCALE = 0.002
SERVED = "scan.path.resident_join_topk_mesh"


def _load(rel):
    spec = importlib.util.spec_from_file_location(rel.replace("/", "_").replace(".", "_"), REPO / rel)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load("benchmark/configs/tpch_q3_reference.py")
SHAPE = _load("benchmark/shapes/q3.py")


def day(iso):
    return int(np.datetime64(iso, "D").astype(np.int64))


@pytest.fixture(autouse=True)
def _force_residency(monkeypatch):
    monkeypatch.setenv("HYPERSPACE_TPU_HBM", "force")
    monkeypatch.setenv("HYPERSPACE_TPU_HBM_MIN_ROWS", "1")
    for reset in (hbm_cache.reset, mesh_cache.reset, EX.reset_groups_cache, J.reset_setup_cache):
        reset()
    yield
    for reset in (hbm_cache.reset, mesh_cache.reset, EX.reset_groups_cache, J.reset_setup_cache):
        reset()


def _deploy(root, devices, mutate=None, seed=11):
    """The three TPC-H tables from ``seed`` (``mutate`` may edit them),
    written as parquet, a session on ``devices`` chips with the
    orderkey covering indexes built, and the reference over the same
    tables."""
    made = tpch.tables(["lineitem", "orders", "customer"], SCALE, seed)
    if mutate is not None:
        made = mutate(made)
    paths = {}
    for name, files in (("lineitem", 4), ("orders", 2), ("customer", 1)):
        tpch.write_files(made[name], root / name, files)
        paths[name] = root / name
    conf = {"hyperspace.index.numBuckets": "8", "hyperspace.compile.resultCache": "off",
            C.INDEX_SYSTEM_PATH: str(root / "indexes")}
    if devices > 1:
        conf["hyperspace.tpu.mesh.devices"] = str(devices)
    session = HyperspaceSession(HyperspaceConf(conf))
    session.enable_hyperspace()
    hs = Hyperspace(session)
    hs.create_index(session.read.parquet(str(paths["lineitem"])), IndexConfig(
        "li_orderkey", ["l_orderkey"], ["l_partkey", "l_extendedprice", "l_discount", "l_shipdate"]))
    hs.create_index(session.read.parquet(str(paths["orders"])), IndexConfig(
        "or_orderkey", ["o_orderkey"], ["o_custkey", "o_orderdate", "o_shippriority"]))
    ref = REF.Reference({t: REF.concat([made[t]]) for t in made})
    return session, paths, ref


def _answer(session, paths, params):
    batch = SHAPE.dataframe(session, paths, params).collect()
    return {c: np.asarray(batch.columns[c].data) for c in SHAPE.COLUMNS}


def _served():
    return metrics.snapshot()["counters"].get(SERVED, 0)


def _warm(session, paths, params):
    """The first touch schedules the region; wait for it."""
    _answer(session, paths, params)
    hbm_cache.wait_background(120)


# ---------------------------------------------------------------------------
# arithmetic
# ---------------------------------------------------------------------------
def _batch():
    return ColumnarBatch({
        "p": Column("float64", np.array([10.0, 20.5, np.nan, 4.0])),
        "d": Column("float64", np.array([0.1, 0.0, 0.05, 0.5])),
        "q": Column("int64", np.array([1, 2, 3, 0])),
        "s": Column.from_values(np.array(["a", "b", "c", "a"])),
    })


def test_arithmetic_on_host_propagates_nulls():
    b = _batch()
    rev = eval_value(col("p") * (lit(1) - col("d")), b)
    np.testing.assert_array_equal(rev[[0, 1, 3]], [10.0 * 0.9, 20.5, 2.0])
    assert np.isnan(rev[2])  # a NULL price is a NULL revenue
    ints = eval_value(col("q") * 3 + 1, b)
    assert ints.dtype == np.int64 and ints.tolist() == [4, 7, 10, 1]
    ratio = eval_value(col("q") / col("q"), b)
    assert ratio.dtype == np.float64 and np.isnan(ratio[3]) and ratio[:3].tolist() == [1.0] * 3
    assert value_dtype(col("q") - 1, b.schema()) == "int64"
    assert value_dtype(col("q") * 0.5, b.schema()) == "float64"
    with pytest.raises(Exception):
        value_dtype(col("s") + 1, b.schema())
    # a computed side compares: NULL satisfies nothing
    assert eval_mask(col("p") * 2 > 20.0, b).tolist() == [False, True, False, False]


def test_arithmetic_on_device_matches_host():
    import jax.numpy as jnp

    b = _batch()
    arrays = {n: jnp.asarray(c.data) for n, c in b.columns.items()}
    for e in (col("p") * (lit(1) - col("d")), col("q") * 3 - col("q"), col("p") / col("q"), 2.5 + col("q")):
        host, dev = eval_value(e, b), np.asarray(eval_value(e, b, arrays))
        assert dev.dtype == host.dtype
        np.testing.assert_array_equal(dev, host)


def test_expression_aggregate_on_host_skips_nulls():
    b = _batch()
    out = hash_aggregate(b, ["s"], [agg_sum(col("p") * (lit(1) - col("d")), "rev"),
                                    agg_count(col("p") * 2, "n"), agg_avg(col("q") + 1, "m"),
                                    agg_sum(col("q") * 2, "qq")])
    got = {s: i for i, s in enumerate(out.columns["s"].to_values())}
    rev, n = out.columns["rev"].data, out.columns["n"].data
    assert rev[got["a"]] == 9.0 + 2.0 and rev[got["b"]] == 20.5 and np.isnan(rev[got["c"]])
    assert n[got["c"]] == 0 and n[got["a"]] == 2
    assert out.columns["qq"].dtype_str == "int64" and out.columns["m"].dtype_str == "float64"
    with pytest.raises(Exception):
        agg_sum(col("p") * 2)  # an expression needs an output name


# ---------------------------------------------------------------------------
# ORDER BY / LIMIT
# ---------------------------------------------------------------------------
def test_sort_orders_nulls_like_spark_and_is_stable():
    b = ColumnarBatch({
        "x": Column("float64", np.array([2.0, np.nan, 1.0, 2.0, 3.0])),
        "s": Column.from_optional_values(["b", "a", None, "a", "c"]),
        "i": Column("int64", np.arange(5)),
    })
    asc = sort_batch(b, ["x"], [True]).columns["i"].data.tolist()
    assert asc == [1, 2, 0, 3, 4]  # NULL first, ties in input order
    dsc = sort_batch(b, ["x"], [False]).columns["i"].data.tolist()
    assert dsc == [4, 0, 3, 2, 1]  # NULL last
    two = sort_batch(b, ["x", "s"], [False, True]).columns["i"].data.tolist()
    assert two == [4, 3, 0, 2, 1]
    assert sort_batch(b, ["s"], [False]).columns["i"].data.tolist() == [4, 0, 1, 3, 2]


def test_order_by_and_limit_through_the_dataframe(tmp_path):
    session, paths, _ = _deploy(tmp_path, 1)
    od = session.read.parquet(str(paths["orders"]))
    top = od.order_by(desc("o_totalprice"), "o_orderkey").limit(5).collect()
    want = REF.concat([pq.read_table(f) for f in sorted(paths["orders"].glob("*.parquet"))])
    order = np.lexsort((want["o_orderkey"], -want["o_totalprice"]))[:5]
    assert top.columns["o_orderkey"].data.tolist() == want["o_orderkey"][order].tolist()
    assert od.limit(0).collect().num_rows == 0
    assert od.limit(10**9).collect().num_rows == len(want["o_orderkey"])
    with pytest.raises(Exception):
        od.order_by("no_such_column")


# ---------------------------------------------------------------------------
# the join reorder rule
# ---------------------------------------------------------------------------
def _q3_df(session, paths, segment="BUILDING", date=day("1995-03-15")):
    return SHAPE.dataframe(session, paths, {"segment": segment, "date": date})


def test_reorder_joins_the_indexed_pair_first(tmp_path):
    session, paths, _ = _deploy(tmp_path, 1)
    plan = _q3_df(session, paths).optimized_plan()
    scans = plan.collect(lambda n: isinstance(n, IndexScan))
    assert sorted(s.entry.name for s in scans) == ["li_orderkey", "or_orderkey"]
    assert all(s.use_bucket_spec for s in scans)
    inner = [j for j in plan.collect(lambda n: isinstance(n, Join))
             if all(not isinstance(c, Join) for c in j.children)
             and all(x.collect(lambda n: isinstance(n, IndexScan)) for x in j.children)]
    assert len(inner) == 1  # orders ⋈ lineitem, over the two index scans
    text = _q3_df(session, paths).explain()
    assert "Name: or_orderkey" in text and "Name: li_orderkey" in text
    assert text.index("Join [(col(c_custkey)") < text.index("Join [(col(l_orderkey)")


def test_reorder_leaves_alone_what_it_cannot_improve(tmp_path):
    session, paths, _ = _deploy(tmp_path, 1)
    cu = session.read.parquet(str(paths["customer"]))
    od = session.read.parquet(str(paths["orders"]))
    li = session.read.parquet(str(paths["lineitem"]))
    # already joined first as written: the rule changes nothing above it
    written = od.join(li, col("o_orderkey") == col("l_orderkey")).join(cu, col("o_custkey") == col("c_custkey"))
    plan = written.optimized_plan()
    top = plan
    while not isinstance(top, Join):
        top = top.children[0]
    assert top.right.collect(lambda n: hasattr(n, "relation")) and not top.right.collect(
        lambda n: isinstance(n, IndexScan))
    # no pair has compatible indexes: nothing reorders, nothing rewrites
    no_pair = cu.join(od, col("c_custkey") == col("o_custkey")).join(
        li, col("o_custkey") == col("l_partkey"))
    assert not no_pair.optimized_plan().collect(lambda n: isinstance(n, IndexScan))
    # a non-equi condition is left alone
    theta = cu.join(od, col("c_custkey") == col("o_custkey")).join(
        li, (col("l_orderkey") == col("o_orderkey")) & (col("l_partkey") > col("o_custkey")))
    assert not theta.optimized_plan().collect(lambda n: isinstance(n, IndexScan))


# ---------------------------------------------------------------------------
# Q3 against the reference, on both arms
# ---------------------------------------------------------------------------
DATES = ["1995-03-01", "1995-03-15", "1995-03-31"]


@pytest.mark.parametrize("devices", [4, 1], ids=["mesh4-device-arm", "one-device-host-arm"])
def test_q3_matches_the_reference(tmp_path, devices):
    session, paths, ref = _deploy(tmp_path, devices)
    _warm(session, paths, {"segment": "BUILDING", "date": day("1995-03-15")})
    before = _served()
    n = 0
    for segment in tpch.SEGMENTS:
        for d in DATES:
            params = {"segment": segment, "date": day(d)}
            got = _answer(session, paths, params)
            wrong, gap = SHAPE.compare(got, ref.q3(**params))
            assert not wrong and gap <= 1e-12, (segment, d, gap)
            n += 1
    assert _served() - before == (n if devices > 1 else 0)


def test_q3_empty_answer_on_both_arms(tmp_path):
    session, paths, ref = _deploy(tmp_path, 4)
    params = {"segment": "BUILDING", "date": day("1990-01-01")}  # no order before it
    _warm(session, paths, params)
    before = _served()
    got = _answer(session, paths, params)
    assert len(got["l_orderkey"]) == 0 and len(ref.q3(**params)["l_orderkey"]) == 0
    assert _served() == before + 1
    one, one_paths, _ = _deploy(tmp_path / "one", 1)
    assert len(_answer(one, one_paths, params)["l_orderkey"]) == 0


def _tie_at_the_cut(copies):
    """Give ``copies`` orders of one BUILDING customer the same date and
    one identical line worth more than any other order: they tie for
    the first places, and with more than 10 of them, at the 10th."""
    def mutate(made):
        li, od, cu = (made[t].to_pydict() for t in ("lineitem", "orders", "customer"))
        cust = next(k for k, s in zip(cu["c_custkey"], cu["c_mktsegment"]) if s == "BUILDING")
        date = np.datetime64("1995-02-01").astype(object)
        chosen = set(od["o_orderkey"][:copies])
        for i, k in enumerate(od["o_orderkey"]):
            if k in chosen:
                od["o_custkey"][i], od["o_orderdate"][i] = cust, date
        first = set()
        for i, k in enumerate(li["l_orderkey"]):
            if k in chosen:
                keep = k not in first
                first.add(k)
                li["l_extendedprice"][i] = 999_999.99 if keep else 0.0
                li["l_discount"][i] = 0.05
                li["l_shipdate"][i] = np.datetime64("1995-04-01").astype(object)
        return {"lineitem": pa.table(li, schema=made["lineitem"].schema),
                "orders": pa.table(od, schema=made["orders"].schema), "customer": made["customer"]}
    return mutate


@pytest.mark.parametrize("copies", [12, 100], ids=["ties-within-the-candidates", "ties-past-the-candidates"])
def test_q3_exact_tie_at_the_tenth_rank(tmp_path, copies):
    session, paths, ref = _deploy(tmp_path, 4, _tie_at_the_cut(copies))
    params = {"segment": "BUILDING", "date": day("1995-03-15")}
    _warm(session, paths, params)
    before = _served()
    declined = metrics.snapshot()["counters"].get("hbm.mesh.join_topk.declined.ties", 0)
    want = ref.q3(**params)
    assert len(want["l_orderkey"]) == copies  # the reference lists every tie
    got = _answer(session, paths, params)
    wrong, gap = SHAPE.compare(got, want)
    assert not wrong and gap <= 1e-12
    assert len(set(got["l_orderkey"].tolist())) == 10
    moved = metrics.snapshot()["counters"].get("hbm.mesh.join_topk.declined.ties", 0) - declined
    if copies > 4 * (10 + 8):
        # some chip holds more ties than the candidates it ships: the
        # query goes to the host, which lists them all
        assert _served() == before and moved == 1
    else:
        assert _served() == before + 1 and moved == 0


def test_duplicate_customer_key_declines_to_the_exact_host_path(tmp_path):
    def dup(made):
        cu = made["customer"]
        return dict(made, customer=pa.concat_tables([cu, cu.slice(0, 40)]))

    session, paths, ref = _deploy(tmp_path, 4, dup)
    params = {"segment": "BUILDING", "date": day("1995-03-15")}
    _warm(session, paths, params)
    before = metrics.snapshot()["counters"].get("hbm.mesh.join_topk.declined.not_unique", 0)
    served = _served()
    for d in DATES:
        p = {"segment": "BUILDING", "date": day(d)}
        wrong, gap = SHAPE.compare(_answer(session, paths, p), ref.q3(**p))
        assert not wrong and gap <= 1e-12
    after = metrics.snapshot()["counters"].get("hbm.mesh.join_topk.declined.not_unique", 0)
    assert after - before == len(DATES) and _served() == served


def test_sparse_customer_keys_ride_the_sorted_dimension_index(tmp_path):
    """Customer keys spread far wider than their count: the region holds
    the dimension's sorted keys and searches them, in place of a dense
    key-to-row table."""
    def spread(made):
        cu, od = made["customer"].to_pydict(), made["orders"].to_pydict()
        cu["c_custkey"] = [k * 1000 for k in cu["c_custkey"]]
        od["o_custkey"] = [k * 1000 for k in od["o_custkey"]]
        return dict(made, customer=pa.table(cu, schema=made["customer"].schema),
                    orders=pa.table(od, schema=made["orders"].schema))

    session, paths, ref = _deploy(tmp_path, 4, spread)
    _warm(session, paths, {"segment": "BUILDING", "date": day("1995-03-15")})
    snap = mesh_cache.snapshot_joins()
    assert snap["regions"] == 1
    with mesh_cache._lock:
        assert [j.dim_mode for j in mesh_cache._joins] == ["sorted"]
    before = _served()
    for segment in ("AUTOMOBILE", "HOUSEHOLD"):
        for d in DATES:
            params = {"segment": segment, "date": day(d)}
            wrong, gap = SHAPE.compare(_answer(session, paths, params), ref.q3(**params))
            assert not wrong and gap <= 1e-12, (segment, d, gap)
    assert _served() - before == 2 * len(DATES)


def test_q3_plan_shape_routes_to_the_arm(tmp_path):
    session, paths, _ = _deploy(tmp_path, 4)
    plan = _q3_df(session, paths).optimized_plan()
    assert isinstance(plan, Limit) and isinstance(plan.child, Sort)
    from hyperspace_tpu.exec.join_residency import match_join_topk

    shape, why = match_join_topk(plan)
    assert why == "" and shape is not None
    assert shape.o_node.entry.name == "or_orderkey" and shape.l_node.entry.name == "li_orderkey"
    assert shape.fk == "o_custkey" and shape.fk_side == "o" and shape.n == 10


def test_topk_region_lives_in_the_join_registry(tmp_path):
    session, paths, ref = _deploy(tmp_path, 4)
    params = {"segment": "MACHINERY", "date": day("1995-03-20")}
    _warm(session, paths, params)
    snap = mesh_cache.snapshot_joins()
    assert snap["regions"] == 1 and snap["per_region"][0]["keys"] == ["l_orderkey"]
    # a refresh of either index drops the region: the next query goes
    # host, exact, and schedules a new one
    mesh_cache.invalidate_joins(str(tmp_path / "indexes" / "or_orderkey"))
    assert mesh_cache.snapshot_joins()["regions"] == 0
    before = _served()
    wrong, gap = SHAPE.compare(_answer(session, paths, params), ref.q3(**params))
    assert not wrong and gap <= 1e-12 and _served() == before
    hbm_cache.wait_background(120)
    _answer(session, paths, params)
    assert _served() == before + 1
