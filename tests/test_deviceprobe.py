"""The in-process first device touch, and the rule that a device fault
raises where the device is required (TPU platform or an explicit request)
instead of routing to the host."""

import pytest

from hyperspace_tpu.utils import deviceprobe
from hyperspace_tpu.utils.deviceprobe import DeviceFault


@pytest.fixture(autouse=True)
def fresh_latch():
    saved = dict(deviceprobe._FIRST_TOUCH)
    deviceprobe._FIRST_TOUCH.clear()
    yield
    deviceprobe._FIRST_TOUCH.clear()
    deviceprobe._FIRST_TOUCH.update(saved)


def _failing_device_put(monkeypatch):
    import jax

    def boom(*a, **k):
        raise RuntimeError("backend init failed: no device")

    monkeypatch.setattr(jax, "device_put", boom)


def test_first_touch_ok_on_cpu_backend():
    # conftest pins the CPU backend: the touch completes immediately
    assert deviceprobe.first_device_touch_ok() is True
    assert deviceprobe.latched_verdict() is True


def test_failed_touch_latches_false_where_device_optional(monkeypatch):
    _failing_device_put(monkeypatch)
    assert deviceprobe.first_device_touch_ok() is False
    # latched: the restored backend is not touched again
    monkeypatch.undo()
    assert deviceprobe.first_device_touch_ok() is False
    assert deviceprobe.latched_verdict() is False


def test_failed_touch_raises_when_device_requested(monkeypatch):
    _failing_device_put(monkeypatch)
    with pytest.raises(DeviceFault, match="backend init failed"):
        deviceprobe.first_device_touch_ok(required=True)
    # the verdict is latched for the process either way
    assert deviceprobe.latched_verdict() is False


def test_failed_touch_raises_on_tpu_platform(monkeypatch):
    from hyperspace_tpu import ops

    _failing_device_put(monkeypatch)
    monkeypatch.setattr(ops, "is_tpu_platform", lambda: True)
    with pytest.raises(DeviceFault, match="no device"):
        deviceprobe.first_device_touch_ok()


def test_require_platform_raises_when_absent():
    # the CPU backend is pinned: a TPU request is an error, not a default
    with pytest.raises(DeviceFault, match="tpu device requested"):
        deviceprobe.require_platform("tpu")
    assert deviceprobe.require_platform("cpu")[0].platform == "cpu"


@pytest.mark.parametrize("engine", ["device", "auto"])
def test_build_on_failed_device(tmp_path, monkeypatch, engine):
    """build.engine=device asks for the device: a failed first touch
    raises. An auto build on an accelerator platform that does not
    require the device routes host-side instead, counted."""
    import numpy as np

    from hyperspace_tpu import constants as C
    from hyperspace_tpu import ops
    from hyperspace_tpu.config import HyperspaceConf
    from hyperspace_tpu.hyperspace import Hyperspace
    from hyperspace_tpu.index.index_config import IndexConfig
    from hyperspace_tpu.session import HyperspaceSession
    from hyperspace_tpu.storage import parquet_io
    from hyperspace_tpu.storage.columnar import Column, ColumnarBatch
    from hyperspace_tpu.telemetry.metrics import metrics

    deviceprobe._FIRST_TOUCH.update(ok=False, error="RuntimeError('no device')")
    n = 1 << 17
    batch = ColumnarBatch({
        "k": Column("int64", np.arange(n, dtype=np.int64)),
        "v": Column("int64", np.arange(n, dtype=np.int64)),
    })
    parquet_io.write_parquet(tmp_path / "src" / "p0.parquet", batch)
    conf = HyperspaceConf({
        C.INDEX_SYSTEM_PATH: str(tmp_path / "idx"),
        C.INDEX_NUM_BUCKETS: 8,
        C.BUILD_MODE: C.BUILD_MODE_STREAMING,
        C.BUILD_CHUNK_ROWS: n // 4,
        C.BUILD_ENGINE: engine,
    })
    session = HyperspaceSession(conf)
    metrics.reset()
    build = lambda: Hyperspace(session).create_index(  # noqa: E731
        session.read.parquet(str(tmp_path / "src")), IndexConfig("i", ["k"], ["v"])
    )
    if engine == "device":
        with pytest.raises(DeviceFault, match="no device"):
            build()
        assert metrics.counter("build.engine.device") == 0
        assert metrics.counter("build.engine.host") == 0
        return
    # not a CPU platform (auto would route host without a touch) and not
    # a TPU (where a failed touch raises)
    monkeypatch.setattr(ops, "configured_platform", lambda: "gpu")
    build()
    counters = metrics.snapshot()["counters"]
    assert counters.get("build.engine.device_unreachable", 0) == 1
    assert counters.get("build.engine.device", 0) == 0
    assert counters.get("build.engine.host", 0) >= 1


@pytest.mark.parametrize("mode", ["force", "auto"])
def test_resident_prefetch_on_failed_device(tmp_path, monkeypatch, mode):
    """HYPERSPACE_TPU_HBM=force asks for the device: a prefetch whose
    first touch fails raises. In auto mode on a CPU platform the prefetch
    declines instead, counted hbm.device_unreachable."""
    import numpy as np

    from hyperspace_tpu import constants as C
    from hyperspace_tpu.config import HyperspaceConf
    from hyperspace_tpu.exec.hbm_cache import hbm_cache
    from hyperspace_tpu.hyperspace import Hyperspace
    from hyperspace_tpu.index.index_config import IndexConfig
    from hyperspace_tpu.session import HyperspaceSession
    from hyperspace_tpu.storage import parquet_io
    from hyperspace_tpu.storage.columnar import Column, ColumnarBatch
    from hyperspace_tpu.telemetry.metrics import metrics

    n = 4096
    parquet_io.write_parquet(
        tmp_path / "src" / "p0.parquet",
        ColumnarBatch({
            "k": Column("int64", np.arange(n, dtype=np.int64)),
            "v": Column("int64", np.arange(n, dtype=np.int64)),
        }),
    )
    session = HyperspaceSession(HyperspaceConf({
        C.INDEX_SYSTEM_PATH: str(tmp_path / "idx"),
        C.INDEX_NUM_BUCKETS: 4,
    }))
    hs = Hyperspace(session)
    hs.create_index(
        session.read.parquet(str(tmp_path / "src")), IndexConfig("i", ["k"], ["v"])
    )
    deviceprobe._FIRST_TOUCH.update(ok=False, error="RuntimeError('no device')")
    monkeypatch.setenv("HYPERSPACE_TPU_HBM", mode)
    hbm_cache.reset()
    metrics.reset()
    try:
        if mode == "force":
            with pytest.raises(DeviceFault, match="no device"):
                hs.prefetch_index("i", ["k"])
        else:
            assert hs.prefetch_index("i", ["k"]) is False
            assert metrics.counter("hbm.device_unreachable") == 1
        assert hbm_cache.snapshot()["tables"] == 0
    finally:
        hbm_cache.reset()
