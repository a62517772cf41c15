"""The four-chip Q3 cell, ``mesh4-join-agg``, from the committed files
alone (CPU, tiny scale, four of the virtual devices): it runs correct
and traced; its shape's ordered comparison finds a reference that drops
a row or moves a revenue by 1e-9 of itself; and the float32 control is
not correct under the cell's limits."""

import json

import numpy as np

from benchtools import REPO, run

SPEC = json.loads((REPO / "BENCHMARK.json").read_text())
NEW = {"topk_device_ms.join", "topk_roofline.join", "topk_dispatch_ms.join", "d2h_bytes.join"}


def test_mesh4_join_agg_is_declared():
    (cell,) = [w for w in SPEC["workloads"] if w["name"] == "mesh4-join-agg"]
    assert cell["chips"] == 4 and cell["config"] == "tpch-sf1-q3-mesh4" and cell["traffic"] == "q3-closed2"
    (config,) = [c for c in SPEC["configs"] if c["name"] == "tpch-sf1-q3-mesh4"]
    assert config["reduced"] == ["scale_factor"]
    conf = json.loads((REPO / config["file"]).read_text())
    assert int(conf["conf"]["hyperspace.tpu.mesh.devices"]) == 4
    assert conf["device_counters"]["queries"] == [["scan.path.resident_join_topk_mesh"]]
    declared = {m["name"] for m in SPEC["per_layer"] if "mesh4-join-agg" in m.get("workloads", ())}
    assert declared == NEW
    qps = next(m for m in SPEC["end_to_end"] if m["name"] == "queries_per_s")
    assert "mesh4-join-agg" in qps["workloads"]


def test_traced_mesh4_join_agg_runs_correct_and_reads_its_spans(tiny_root):
    out = run(tiny_root, "mesh4-join-agg", trace=True)
    assert out["correct"] is True, out["compared"]
    assert out["device"]["count"] == 4 and out["attempted"] > 0
    m = out["metrics"]
    assert m["topk_dispatch_ms.join"]["value"] > 0
    assert m["d2h_bytes.join"]["value"] > 0
    # no TPU plane on the CPU: the device readings find nothing to read
    assert not {"topk_device_ms.join", "topk_roofline.join"} & set(m)


def _answers(tiny_root):
    """The reference's answers to a few Q3 requests over the cell's data,
    and the cell's shape."""
    from benchmark.common.harness import Bench
    from benchmark.common.system import Deployment

    bench = Bench(tiny_root)
    cell = bench.cell("mesh4-join-agg")
    config = bench.config(cell["config"])
    shape = bench.shapes(bench.traffic(cell["traffic"]))["q3"]
    ref_mod = bench.reference(config)
    dep = Deployment(config, 2**31 + 7, tiny_root / "work")
    dep.generate()
    tables = {t: ref_mod.concat(dep.tables[t]) for t in config["tables"]}
    day = int(np.datetime64("1995-03-15", "D").astype(np.int64))
    params = [{"segment": s, "date": day} for s in ("BUILDING", "MACHINERY", "HOUSEHOLD")]
    return shape, ref_mod, tables, params


def test_q3_compare_finds_a_dropped_row_and_a_moved_revenue(tiny_root):
    shape, ref_mod, tables, params = _answers(tiny_root)
    ref = ref_mod.Reference(tables)
    for p in params:
        want = ref.answer("q3", p)
        assert len(want["l_orderkey"]) >= 10
        top = {c: v[:10] for c, v in want.items()}
        assert shape.compare(top, want) == (False, 0.0)
        dropped = {c: np.delete(v, 3) for c, v in top.items()}
        assert shape.compare(dropped, want)[0]
        moved = dict(top, revenue=top["revenue"] * np.where(np.arange(10) == 5, 1 + 1e-9, 1.0))
        wrong, gap = shape.compare(moved, want)
        assert wrong or gap > 1e-12
        swapped = {c: v[[1, 0, *range(2, 10)]] for c, v in top.items()}
        assert shape.compare(swapped, want)[0]  # no exact tie: a swap is wrong


def test_float32_control_is_not_correct(tiny_root):
    from benchmark.common.control import control_numbers

    correct, compared = control_numbers(tiny_root, "mesh4-join-agg", 2**31 + 7, per_client=4)
    assert correct is False
    assert compared["answers_wrong"]["value"] > 0 or compared["max_rel_gap"]["value"] > 1e-12
