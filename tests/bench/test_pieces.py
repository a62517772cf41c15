"""The benchmark's pieces on known inputs: roofline bytes, the Zipf and
RF1 generators, the device-path guard, and a configuration, a traffic
mix and a metric added as new files only."""

import json

import numpy as np
import pytest

from benchtools import REPO, run


def test_roofline_bytes_on_known_shapes():
    from benchmark.common import roofline

    # the int64 key, read once and written once per row
    assert roofline.build_sort_bytes(6_001_215) == 2 * 8 * 6_001_215
    assert roofline.build_sort_bytes(1_500_000, "o_orderkey") == 2 * 8 * 1_500_000


# (table, columns of the spec with the type the generator writes them in)
SPEC_COLUMNS = {
    "lineitem": {
        "l_orderkey": "int64", "l_partkey": "int64", "l_suppkey": "int64", "l_linenumber": "int32",
        "l_quantity": "double", "l_extendedprice": "double", "l_discount": "double", "l_tax": "double",
        "l_returnflag": "string", "l_linestatus": "string", "l_shipdate": "date32[day]",
        "l_commitdate": "date32[day]", "l_receiptdate": "date32[day]", "l_shipinstruct": "string",
        "l_shipmode": "string", "l_comment": "string",
    },
    "orders": {
        "o_orderkey": "int64", "o_custkey": "int64", "o_orderstatus": "string", "o_totalprice": "double",
        "o_orderdate": "date32[day]", "o_orderpriority": "string", "o_clerk": "string",
        "o_shippriority": "int32", "o_comment": "string",
    },
}


@pytest.mark.parametrize("table", ["lineitem", "orders"])
def test_tables_have_every_column_of_the_spec(table):
    """All of TPC-H's columns in the spec's order, as the configuration
    lists them, at dbgen's value rules."""
    import pyarrow.compute as pc

    from benchmark.common import tpch

    li, od = tpch.tpch_tables(0.01, 2**31 + 3)
    t = {"lineitem": li, "orders": od}[table]
    assert {n: str(ty) for n, ty in zip(t.schema.names, t.schema.types)} == SPEC_COLUMNS[table]
    cfg = json.loads((REPO / "benchmark/configs/tpch-sf1.json").read_text())
    assert cfg["columns"][table] == list(SPEC_COLUMNS[table])
    lo, hi = {"lineitem": (10, 43), "orders": (19, 78)}[table]
    n = pc.utf8_length(t.column(f"{table[0]}_comment"))
    assert pc.min(n).as_py() >= lo and pc.max(n).as_py() <= hi
    if table == "lineitem":
        ship, receipt = (t.column(c).cast("int32").to_numpy() for c in ("l_shipdate", "l_receiptdate"))
        flag = np.array(t.column("l_returnflag").to_pylist())
        status = np.array(t.column("l_linestatus").to_pylist())
        assert ((flag == "N") == (receipt > tpch.CURRENT_DAY)).all()
        assert ((status == "O") == (ship > tpch.CURRENT_DAY)).all()
        assert (receipt > ship).all()
    else:
        assert set(t.column("o_orderstatus").to_pylist()) == {"O", "F", "P"}
        assert set(t.column("o_orderpriority").to_pylist()) == set(tpch.PRIORITIES)


def test_zipf_ranks_follow_the_law():
    from benchmark.common.keys import Zipf, stream

    z = Zipf(1000, 0.99)
    r = z.ranks(stream(1, 0), 200_000)
    assert r.min() >= 0 and r.max() < 1000
    freq = np.bincount(r, minlength=1000) / len(r)
    # P(rank 0) / P(rank 9) = 10 ** 0.99
    assert freq[0] / freq[9] == pytest.approx(10 ** 0.99, rel=0.1)


def test_streams_repeat_from_the_seed():
    from benchmark.common.traffic import Keyspace, Stream

    mix = json.loads((REPO / "benchmark/traffic/point-zipf-closed32.json").read_text())
    ks = {"orders.o_orderkey": Keyspace(np.arange(1, 5000) * 4, 2**33 + 1)}
    a = [next(Stream(mix, ks, 2**33 + 1, 3, 0)) for _ in range(5)]
    b = [next(Stream(mix, ks, 2**33 + 1, 3, 0)) for _ in range(5)]
    assert a == b
    two = {"requests": [{"shape": "a"}, {"shape": "b"}]}
    shapes = [next(Stream(two, {}, 5, c, 0))[0] for c in range(4)]
    assert shapes == ["a", "b", "a", "b"]  # clients start on different shapes


def test_rf1_keys_are_new_and_sparse():
    from benchmark.common import tpch

    li, od = tpch.tpch_tables(0.01, 3)
    sets = tpch.rf1_sets(0.01, 3, 10)
    new = np.concatenate([o.column("o_orderkey").to_numpy() for _, o in sets])
    assert len(new) == 10 * 15 and len(np.unique(new)) == len(new)
    assert not np.isin(new, od.column("o_orderkey").to_numpy()).any()
    assert ((new - 1) % 32 >= 8).all() and ((new - 1) % 32 < 16).all()
    for li_s, od_s in sets:
        assert np.isin(li_s.column("l_orderkey").to_numpy(), od_s.column("o_orderkey").to_numpy()).all()


def test_guard_counts_fallbacks_and_missing_paths():
    from benchmark.common import guard

    bad, missing = guard.check(
        {"scan.path.resident_device": 3, "hbm.populate_failed": 1, "build.engine.host": 2},
        [["scan.path.resident_device"], ["join.path.device_kernel", "scan.path.resident_join_agg"]],
        ["build.engine.host"],
    )
    assert set(bad) == {"hbm.populate_failed", "build.engine.host"}
    assert missing == [["join.path.device_kernel", "scan.path.resident_join_agg"]]


def test_new_config_traffic_and_metric_found_by_name(tiny_root):
    """A configuration, a traffic mix and a metric placed as new files,
    with their BENCHMARK.json entries, run with no code edited."""
    cfg = json.loads((tiny_root / "benchmark/configs/tpch-sf1.json").read_text())
    cfg["name"] = "tpch-new"
    cfg["tables"]["lineitem"]["files"] = 3
    (tiny_root / "benchmark/configs/tpch-new.json").write_text(json.dumps(cfg))
    mix = json.loads((tiny_root / "benchmark/traffic/point-zipf-closed32.json").read_text())
    mix["clients"] = 2
    mix["requests"][0]["params"]["key"]["zipf"] = 0.5
    (tiny_root / "benchmark/traffic/point-new.json").write_text(json.dumps(mix))
    (tiny_root / "benchmark/metrics/answers_read.py").write_text(
        "def read(run):\n    return float(sum(r.ok for r in run.records))\n"
    )
    spec = json.loads((tiny_root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tpch-new", "source": "test", "file": "benchmark/configs/tpch-new.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "new-cell", "config": "tpch-new", "traffic": "point-new",
                              "chips": 1, "why": "test"})
    spec["end_to_end"].append({"name": "answers_read", "unit": "queries", "better": "higher",
                               "bound": 0.1, "source": "host_clock", "workloads": ["new-cell"]})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(spec))
    out = run(tiny_root, "new-cell")
    assert out["correct"] is True, out["compared"]
    assert out["metrics"]["answers_read"]["value"] == out["attempted"]
    assert "setup_s" in out["metrics"]
