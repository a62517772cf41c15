"""The trace reduction on intervals worked out by hand, and on a small
trace recorded on a TPU v5e with ``record_trace.py``."""

from pathlib import Path

import pytest

from benchtools import REPO  # noqa: F401  (puts the repo on sys.path)

from benchmark.common import trace_reduce as T

MS = 1e6  # ns


def test_busy_union_idle_and_gap_labels_by_hand():
    # window 0-100 ms; ops 10-20, 15-30 (overlap), 50-60, 95-120 (clipped)
    ops = [(10 * MS, 20 * MS, "a"), (15 * MS, 30 * MS, "b"), (50 * MS, 60 * MS, "a"),
           (95 * MS, 120 * MS, "c")]
    trace = {
        "ops": {"/device:TPU:0": ops},
        "modules": {"/device:TPU:0": [(10 * MS, 30 * MS, "jit_m1"), (50 * MS, 60 * MS, "jit_m2")]},
        "annotations": [(0, 100 * MS, T.WINDOW)],
    }
    # busy: 10-30 (20) + 50-60 (10) + 95-100 (5) = 35 ms of 100
    # gaps: 0-10, 30-50, 60-95
    spans = [(0, 12 * MS, "serve.plan", 2), (28 * MS, 70 * MS, "serve.execute", 1),
             (40 * MS, 55 * MS, "scan.host_legs", 2), (60 * MS, 95 * MS, "serve.queue_wait", 1)]
    out = T.reduce(trace, spans)
    assert out["busy_s"] == pytest.approx(0.035)
    assert out["window_s"] == pytest.approx(0.1)
    assert out["op_s"] == pytest.approx({"a": 0.02, "b": 0.015, "c": 0.005})
    assert out["module_s"] == pytest.approx({"jit_m1": 0.02, "jit_m2": 0.01})
    # 0-10: serve.plan (10); 30-50: serve.execute 20 vs host_legs 10;
    # 60-95: queue_wait 35 vs execute 10
    assert dict(out["idle_gaps"]) == pytest.approx(
        {"serve.plan": 0.01, "serve.execute": 0.02, "serve.queue_wait": 0.035}
    )
    assert out["device_ops"][0] == ["jit_m1", pytest.approx(0.02)]


def test_gap_label_tie_goes_to_the_deeper_span():
    assert T.label((0, 10), [(0, 10, "outer", 0), (0, 10, "inner", 3)]) == "inner"
    assert T.label((0, 10), []) == "host (no span)"


def test_two_devices_average():
    trace = {"ops": {"/device:TPU:0": [(0, 50, "x")], "/device:TPU:1": [(0, 100, "x")]},
             "modules": {}, "annotations": [(0, 100, T.WINDOW)]}
    out = T.reduce(trace)
    assert out["busy_s"] == pytest.approx(75e-9)


SMALL = Path(__file__).parent / "data" / "small.xplane.pb"


def test_recorded_tpu_trace():
    """``record_trace.py`` on one TPU v5e (my chip run, PR 22). Read by
    hand from the file: the window annotation spans 47,635,425 +
    34,161,396 ns; the device plane has two modules, the sort at
    46,651,184 + 1,268,048 ns and the sum at 69,911,077 + 7,748 ns, and
    the ops under them end at 47,919,078 and 69,918,824 ns. The device
    clock runs about 1 ms ahead of the host's (the sort starts before
    the host's execute call), so the window clips the sort to
    47,635,425-47,919,078 (283,653 ns of ops, 283,807 ns of module)."""
    raw = T.load(str(SMALL))
    assert list(raw["ops"]) == ["/device:TPU:0"]
    out = T.reduce(raw)
    assert out["window_s"] == pytest.approx(34_161_396e-9)
    assert out["busy_s"] == pytest.approx((283_653 + 7_744) * 1e-9)
    assert sorted(out["module_s"].values()) == pytest.approx([7_748e-9, 283_807e-9])
    assert out["idle_gaps"] == [["host (no span)", pytest.approx((34_161_396 - 291_397) * 1e-9)]]
    names = [n for _, _, n in raw["annotations"]]
    assert names.count("bench.submit") == names.count("bench.result") == 1
