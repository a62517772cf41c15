"""The per-layer metrics that read the program's stage spans, in traced
tiny runs of the point and build cells (CPU), and the program's spans in
the harness's profile, where its monotonic mapping places them."""

import time

import pytest

from benchtools import run

HOST_READ = {
    "sf1-point-lookup": {"classify_ms.point", "dispatch_ms.point", "launch_ms.point", "host_leg_ms.point"},
    "rf1-point-lookup": {"classify_ms.point", "dispatch_ms.point", "launch_ms.point", "host_leg_ms.point",
                         "delta_leg_ms.hybrid"},
    "sf1-build": {"drain_s.build", "bucket_merge_s.build"},
}


@pytest.mark.parametrize("cell", sorted(HOST_READ))
def test_traced_run_reports_the_stage_span_metrics(tiny_root, cell):
    out = run(tiny_root, cell, trace=True)
    assert out["correct"] is True, out["compared"]
    for name in HOST_READ[cell]:
        assert out["metrics"][name]["value"] > 0, name
    if cell == "sf1-build":
        finalize = out["metrics"]["finalize_s.build"]["value"]
        parts = out["metrics"]["drain_s.build"]["value"] + out["metrics"]["bucket_merge_s.build"]["value"]
        assert parts <= finalize


def test_program_spans_land_on_the_profile_where_the_harness_maps_them(tmp_path, monkeypatch):
    """Spans opened while the harness's profiler runs appear as host
    events of the same name, and ``Profiler.reduce``'s one-reading
    mapping of their monotonic times onto the profile's clock lands
    within 1 ms of those events."""
    from benchmark.common import harness, trace_reduce
    from hyperspace_tpu.telemetry.trace import span, start_trace

    names = ("serve.classify", "scan.device_dispatch", "scan.host_leg")
    profiler = harness.Profiler(tmp_path / "profile", True)
    with profiler:
        traces = []
        for _ in range(3):
            with start_trace("serve.query") as t:
                for name in names:
                    with span(name):
                        time.sleep(0.002)
            t.finish()
            traces.append(t)
    events = [ev for ev in trace_reduce.load(trace_reduce.xplane_file(str(tmp_path / "profile")),
                                             annotation_prefix="s")["annotations"] if ev[2] in names]
    mapped = []
    real_reduce = trace_reduce.reduce
    monkeypatch.setattr(trace_reduce, "reduce", lambda raw, spans: mapped.extend(spans) or real_reduce(raw, spans))
    profiler.reduce(traces)
    mapped = sorted((s, e, n) for s, e, n, _ in mapped if n in names)
    events = sorted(events)
    assert len(events) == len(mapped) == 3 * len(names)
    for (s0, e0, n0), (s1, e1, n1) in zip(events, mapped):
        assert n0 == n1
        assert abs(s0 - s1) < 1e6 and abs(e0 - e1) < 1e6  # ns
