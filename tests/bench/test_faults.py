"""The comparison fails what it must: a wrong answer, half of a batch
left out, a build that leaves the index as it was, an altered index row,
and a moved fallback counter each make ``correct`` false; the control
(the reference in float32) fails every cell."""

import numpy as np
import pytest

from benchtools import run


def test_wrong_answer_makes_correct_false(tiny_root, monkeypatch):
    from hyperspace_tpu.serve import batcher

    real = batcher.execute_batch

    def altered(requests):
        out = real(requests)
        if out:
            b = out[0]
            col = b.columns["l_extendedprice"]
            if len(col.data):
                col.data = col.data.copy()
                col.data[0] += 0.01
        return out

    monkeypatch.setattr(batcher, "execute_batch", altered)
    out = run(tiny_root, "sf1-point-lookup")
    assert out["correct"] is False
    assert out["compared"]["max_rel_gap"]["value"] > 0


def test_half_of_a_batch_left_out(tiny_root, monkeypatch):
    from hyperspace_tpu.serve import batcher

    real = batcher.execute_batch

    def half(requests):
        out = real(requests)
        if out and len(out) > 1:
            out = out[: len(out) // 2] + [b.take(np.zeros(0, np.int64)) for b in out[len(out) // 2:]]
        return out

    monkeypatch.setattr(batcher, "execute_batch", half)
    out = run(tiny_root, "sf1-point-lookup")
    assert out["correct"] is False
    assert out["compared"]["answers_wrong"]["value"] > 0


def test_build_that_leaves_the_index_unchanged(tiny_root, monkeypatch):
    from hyperspace_tpu.hyperspace import Hyperspace

    real = Hyperspace.create_index

    def only_warmup(self, df, config):
        if "_warm" in config.index_name:
            return real(self, df, config)

    monkeypatch.setattr(Hyperspace, "create_index", only_warmup)
    out = run(tiny_root, "sf1-build")
    assert out["correct"] is False
    assert out["compared"]["rows_wrong"]["value"] > 0


def test_altered_index_row(tiny_root, monkeypatch):
    from hyperspace_tpu.storage import layout

    real = layout.write_batch

    def altered(path, batch, *a, **k):
        col = batch.columns.get("l_quantity")
        if col is not None and len(col.data):
            col.data = col.data.copy()
            col.data[-1] += 1.0
        return real(path, batch, *a, **k)

    monkeypatch.setattr(layout, "write_batch", altered)
    out = run(tiny_root, "sf1-build")
    assert out["correct"] is False
    assert out["compared"]["rows_wrong"]["value"] > 0


def test_fallback_counter_fails_the_run(tiny_root, monkeypatch):
    from hyperspace_tpu.serve import batcher
    from hyperspace_tpu.telemetry.metrics import metrics

    real = batcher.execute_batch

    def counted(requests):
        metrics.incr("scan.resident.device_failed")
        return real(requests)

    monkeypatch.setattr(batcher, "execute_batch", counted)
    out = run(tiny_root, "sf1-point-lookup")
    assert out["correct"] is False
    assert out["compared"]["fallback_counters"]["value"] > 0


def test_device_path_not_taken_fails_the_run(tiny_root, monkeypatch):
    monkeypatch.setenv("HYPERSPACE_TPU_HBM", "off")
    out = run(tiny_root, "sf1-point-lookup")
    assert out["correct"] is False
    assert out["compared"]["device_paths_missing"]["value"] > 0


@pytest.mark.parametrize("cell", ["rf1-point-lookup", "sf1-point-lookup", "sf1-build"])
def test_control_is_not_correct(tiny_root, cell):
    from benchmark.common.control import control_numbers

    correct, compared = control_numbers(tiny_root, cell, 2**31 + 11, per_client=2)
    assert correct is False, compared
