"""Test harness configuration.

The multi-chip analog of the reference's ``local[4]`` Spark
(SparkInvolvedSuite.scala:26-47) is an 8-device virtual CPU mesh: sharding,
all_to_all repartitioning, and bucket alignment are exercised for real on
one host. Env vars must be set before jax is imported anywhere.
"""

import os

# Force-assign: the tests run on the CPU whatever the environment presets
# (a TPU host's default platform is the chip); both the env var AND the
# config are pinned before any test imports jax.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture
def tmp_workspace(tmp_path, monkeypatch):
    """A scratch workspace directory; index system path defaults beneath it."""
    monkeypatch.chdir(tmp_path)
    return tmp_path
