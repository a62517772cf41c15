"""A copy of the benchmark at a tiny scale, on the CPU: the harness is
found by name under a temporary root, the TPC-H configurations are cut
to 0.002 of SF1 and the warm-ups to a second. The device paths are
asked for explicitly (residency forced, Pallas kernels interpreted, the
zone gate and the residency row floor off), as a chip would take them at
SF1; the chip check of run.py is the only part left out."""

import pytest

from benchtools import tiny_copy


@pytest.fixture
def cpu_device_paths(monkeypatch):
    from hyperspace_tpu.exec.hbm_cache import hbm_cache

    monkeypatch.setenv("HYPERSPACE_TPU_KERNELS", "interpret")
    monkeypatch.setenv("HYPERSPACE_TPU_HBM", "force")
    monkeypatch.setenv("HYPERSPACE_TPU_HBM_MIN_ROWS", "1")
    monkeypatch.setenv("HYPERSPACE_TPU_HBM_MAX_BLOCK_FRAC", "1.0")
    hbm_cache.reset()
    yield
    hbm_cache.reset()


@pytest.fixture
def tiny_root(tmp_path, cpu_device_paths):
    return tiny_copy(tmp_path / "root")
