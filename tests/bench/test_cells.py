"""Each cell's command prints a last line with the contract's keys, and
its answers pass the comparison with the plain reference (CPU, tiny
scale)."""

import json

import pytest

from benchtools import REPO, run

SPEC = json.loads((REPO / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]


def _names(group, cell):
    return {m["name"] for m in SPEC[group] if cell in m.get("workloads", [cell])}


@pytest.mark.parametrize("cell", CELLS)
def test_cell_result_line(tiny_root, cell):
    out = run(tiny_root, cell)
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(out)[-1] == "compared"
    assert out["correct"] is True, out["compared"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == _names("end_to_end", cell)
    for m in out["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert set(out["device"]) >= {"platform", "kind", "count", "memory_peak_bytes"}
    for c in out["compared"].values():
        assert set(c) == {"value", "limit"}
    json.dumps(out)


@pytest.mark.parametrize("cell", ["sf1-point-lookup", "sf1-build"])
def test_traced_run_reports_per_layer_metrics(tiny_root, cell):
    out = run(tiny_root, cell, trace=True)
    assert out["correct"] is True, out["compared"]
    assert set(out["metrics"]) <= _names("per_layer", cell)
    assert {"busy_s", "window_s"} <= set(out["device"])
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    # the program's spans and counters are read on the CPU too; the
    # device's share is a chip reading and has no TPU plane here
    host_read = {"sf1-point-lookup": {"queue_wait_ms.point", "plan_ms.point", "batch_occupancy.point",
                                      "query_p50_ms", "query_p95_ms"},
                 "sf1-build": {"ingest_s.build", "finalize_s.build"}}[cell]
    assert host_read <= set(out["metrics"])
