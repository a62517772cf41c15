"""The four-chip build cell, traced, on four of the CPU's virtual
devices: its configuration comes from BENCHMARK.json, the chunk size is
lowered in the tiny copy so that lineitem takes several chunks, and the
run reads the sharded exchange's spans and counters."""

import json

from benchtools import REPO, run

SPEC = json.loads((REPO / "BENCHMARK.json").read_text())
NEW = {"exchange_s.mesh", "plan_s.mesh", "exchange_fill.mesh", "sharded_roofline.mesh"}


def test_mesh4_build_config_and_metrics_are_declared():
    (cell,) = [w for w in SPEC["workloads"] if w["name"] == "mesh4-build"]
    assert cell["chips"] == 4 and cell["config"] == "tpch-sf1-mesh4"
    (config,) = [c for c in SPEC["configs"] if c["name"] == "tpch-sf1-mesh4"]
    conf = json.loads((REPO / config["file"]).read_text())["conf"]
    assert int(conf["hyperspace.tpu.mesh.devices"]) == 4
    declared = {m["name"] for m in SPEC["per_layer"] if "mesh4-build" in m.get("workloads", ())}
    assert NEW <= declared


def _several_chunks(root):
    f = root / "benchmark" / "configs" / "tpch-sf1-mesh4.json"
    config = json.loads(f.read_text())
    config["conf"]["hyperspace.index.build.chunkRows"] = 4096  # 12,000 rows: 2 chunks + a tail
    f.write_text(json.dumps(config))


def test_mesh4_build_reports_its_rate(tiny_root):
    _several_chunks(tiny_root)
    out = run(tiny_root, "mesh4-build")
    assert out["correct"] is True, out["compared"]
    assert out["device"]["count"] == 4
    assert out["metrics"]["build_rows_per_s"]["value"] > 0


def test_traced_mesh4_build_reads_the_exchange(tiny_root):
    _several_chunks(tiny_root)
    out = run(tiny_root, "mesh4-build", trace=True)
    assert out["correct"] is True, out["compared"]
    m = out["metrics"]
    for name in ("exchange_s.mesh", "plan_s.mesh", "ingest_s.build", "finalize_s.build"):
        assert m[name]["value"] > 0, name
    # each chunk sends D*D*cap slots, cap a power of two of at least a
    # quarter of a shard's rows: at most every slot holds a row
    assert 0 < m["exchange_fill.mesh"]["value"] <= 100
    # no TPU plane on the CPU: the device share finds nothing to read
    assert "sharded_roofline.mesh" not in m
