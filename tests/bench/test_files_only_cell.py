"""A query cell added as new files only (CPU, tiny scale): a shape that
joins ``customer`` with ``orders`` and aggregates, its own reference
module, a traffic mix drawing from a list and from a date range, a
configuration listing ``customer``, and the cell's entries appended to
BENCHMARK.json. It runs correct with no existing file edited, and a
reference off by one row makes it incorrect."""

import hashlib
import json

import pytest

from benchtools import REPO, SCALE, run

SHAPE = '''"""``segment_orders``: the orders before a day of the customers of one
market segment, counted and their prices summed per nation."""

COLUMNS = ["c_nationkey", "orders", "total"]
KEYS = ["c_nationkey"]


def dataframe(session, paths, params):
    from hyperspace_tpu.plan.aggregates import agg_count, agg_sum
    from hyperspace_tpu.plan.expr import col, lit

    cu = session.read.parquet(str(paths["customer"])).filter(col("c_mktsegment") == lit(params["segment"]))
    od = session.read.parquet(str(paths["orders"])).filter(col("o_orderdate") < lit(int(params["date"])))
    return (od.join(cu, col("o_custkey") == col("c_custkey"))
            .group_by("c_nationkey")
            .agg(agg_count(None, "orders"), agg_sum("o_totalprice", "total")))
'''

REFERENCE = '''"""The plain reference of ``segment_orders``, in numpy over every table
the configuration lists, strings kept."""

import numpy as np

DROP_ONE_ROW = {drop}


def columns_of(table):
    import pyarrow as pa

    out = {{}}
    for name in table.column_names:
        arr = table.column(name)
        if pa.types.is_date32(arr.type):
            arr = arr.cast(pa.int32())
        out[name] = arr.to_numpy() if not pa.types.is_string(arr.type) else np.array(arr.to_pylist(), object)
    return out


def concat(tables):
    cols = [columns_of(t) for t in tables]
    return {{c: np.concatenate([d[c] for d in cols]) for c in cols[0]}}


class Reference:
    def __init__(self, tables, dtype=np.float64):
        self.t = {{name: {{c: v.astype(dtype) if v.dtype.kind == "f" else v for c, v in cols.items()}}
                  for name, cols in tables.items()}}

    def answer(self, shape, params):
        assert shape == "segment_orders", shape
        cu, od = self.t["customer"], self.t["orders"]
        keys = cu["c_custkey"][cu["c_mktsegment"] == params["segment"]]
        rows = np.flatnonzero((od["o_orderdate"] < params["date"]) & np.isin(od["o_custkey"], keys))
        if DROP_ONE_ROW:
            rows = rows[:-1]
        nation = cu["c_nationkey"][np.searchsorted(cu["c_custkey"], od["o_custkey"][rows])]
        groups, inv = np.unique(nation, return_inverse=True)
        return {{"c_nationkey": groups, "orders": np.bincount(inv, minlength=len(groups)),
                "total": np.bincount(inv, weights=od["o_totalprice"][rows], minlength=len(groups))}}
'''

SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD"]


def _digests(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in root.rglob("*") if p.is_file() and "__pycache__" not in p.parts}


def _add_cell(root, drop_one_row: bool):
    """The cell's files, all new, and its BENCHMARK.json entries."""
    b = root / "benchmark"
    (b / "shapes" / "segment_orders.py").write_text(SHAPE)
    (b / "configs" / "segment_reference.py").write_text(REFERENCE.format(drop=drop_one_row))
    (b / "traffic" / "segment-closed2.json").write_text(json.dumps({
        "kind": "queries", "clients": 2,
        "requests": [{"shape": "segment_orders", "params": {
            "segment": {"choice": SEGMENTS}, "date": {"uniform": ["1995-03-01", "1995-03-31"]}}}],
        "warmup": {"seconds": 0.5},
        "limits": {"answers_failed": 0, "answers_wrong": 0, "max_rel_gap": 1e-9,
                   "fallback_counters": 0, "device_paths_missing": 0},
    }))
    (b / "configs" / "tpch-segments.json").write_text(json.dumps({
        "name": "tpch-segments", "reference": "benchmark/configs/segment_reference.py",
        "scale_factor": SCALE,
        "tables": {"orders": {"files": 2}, "customer": {"files": 1}},
        "indexes": [], "num_buckets": 8, "conf": {"hyperspace.index.numBuckets": 8},
    }))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tpch-segments", "source": "test",
                            "file": "benchmark/configs/tpch-segments.json", "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "segment-cell", "config": "tpch-segments",
                              "traffic": "segment-closed2", "chips": 1, "why": "test"})
    next(m for m in spec["end_to_end"] if m["name"] == "queries_per_s")["workloads"].append("segment-cell")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))


@pytest.mark.parametrize("drop_one_row", [False, True], ids=["reference", "reference-off-by-one-row"])
def test_query_cell_from_new_files_only(tiny_root, drop_one_row):
    before = _digests(tiny_root)
    _add_cell(tiny_root, drop_one_row)
    out = run(tiny_root, "segment-cell")

    assert out["attempted"] > 0 and out["failed"] == 0
    assert {"queries_per_s", "setup_s"} <= set(out["metrics"])
    if drop_one_row:
        assert out["correct"] is False
        assert out["compared"]["answers_wrong"]["value"] > 0
    else:
        assert out["correct"] is True, out["compared"]

    # no file that was there before the cell's was edited, but for the
    # entries appended to BENCHMARK.json; the harness's code is the repo's
    after = _digests(tiny_root)
    changed = {p for p, d in before.items() if after.get(p) != d}
    assert changed == {tiny_root.joinpath("BENCHMARK.json").relative_to(tiny_root)}
    for p in before:
        if p.suffix == ".py":
            assert (tiny_root / p).read_bytes() == (REPO / p).read_bytes(), p
    old, new = (json.loads(t) for t in ((REPO / "BENCHMARK.json").read_text(),
                                        (tiny_root / "BENCHMARK.json").read_text()))
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        assert len(new[group]) >= len(old[group])
        for a, b in zip(old[group], new[group]):
            assert {k: v for k, v in b.items() if k != "workloads"} == {k: v for k, v in a.items() if k != "workloads"}
            assert b.get("workloads", [])[:len(a.get("workloads", []))] == a.get("workloads", [])
