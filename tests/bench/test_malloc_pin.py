"""A config's "malloc" entry pins glibc's allocator thresholds in the
run's own process, through mallopt."""

import json

import pytest

from benchtools import REPO

from benchmark.run import MALLOPT, pin_malloc


def test_the_mesh_config_pins_both_thresholds():
    config = json.loads((REPO / "benchmark" / "configs" / "tpch-sf1-mesh4.json").read_text())
    assert set(config["malloc"]) == set(MALLOPT)
    assert config["malloc"]["mmap_threshold"] == 32 << 20


def test_glibc_takes_the_mesh_config_thresholds():
    config = json.loads((REPO / "benchmark" / "configs" / "tpch-sf1-mesh4.json").read_text())
    pin_malloc(config["malloc"])


def test_a_config_without_malloc_leaves_the_allocator_alone(monkeypatch):
    import ctypes

    def no_libc(name):
        raise AssertionError(f"{name} loaded")

    monkeypatch.setattr(ctypes, "CDLL", no_libc)
    pin_malloc({})


def test_an_unknown_parameter_fails_the_run():
    with pytest.raises(KeyError):
        pin_malloc({"arena_max": 2})


@pytest.mark.parametrize("name", ["tpch-sf1", "tpch-sf1-rf1"])
def test_the_one_chip_configs_keep_glibc_defaults(name):
    config = json.loads((REPO / "benchmark" / "configs" / f"{name}.json").read_text())
    assert "malloc" not in config
