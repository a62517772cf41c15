"""Ahead-of-time compiles of the main path's kernels for a described
TPU v5e, at real widths. Nothing runs: this catches what the chip's
compiler refuses (tiling, VMEM, Mosaic legalization) at no chip time.

The topology is described inside a module-scoped fixture, never at
import: only one process may hold the TPU library, and every xdist
worker imports this file.
"""

import numpy as np
import pytest

from hyperspace_tpu.ops import kernels as K
from hyperspace_tpu.plan.expr import col, lit

# SF1 lineitem (6,001,215 rows) padded to the mask tile, and its join
# with SF1 orders (1,500,000 keys)
SF1_LINEITEM = 6_001_215
SF1_ORDERS = 1_500_000
SF1_PARTS = 200_000


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


@pytest.fixture(scope="module")
def one_chip():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    import jax

    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.fixture
def tpu_kernels(monkeypatch):
    # the kernels read the mode at build time: compile the real kernel,
    # not the interpreter
    monkeypatch.setenv("HYPERSPACE_TPU_KERNELS", "tpu")


@pytest.mark.parametrize("rows", [1 << 20, SF1_LINEITEM])
def test_mask_kernel_compiles(one_chip, tpu_kernels, rows):
    import jax.numpy as jnp

    tile = K.MASK_BLOCK_SUBLANES * K.LANES
    n128 = _round_up(rows, tile) // K.LANES
    bound = (col("k") >= lit(5)) & (col("k") < lit(100_000)) & (col("d") != lit(7))
    with K._x32():
        fn = K._build_mask_call(bound, ("k", "d"), n128)
        args = [_spec((n128, K.LANES), jnp.int32, one_chip) for _ in range(2)]
        text = fn.lower(args).compile().as_text()
    assert "tpu_custom_call" in text


def test_smj_kernel_compiles(one_chip, tpu_kernels):
    import jax.numpy as jnp

    l_tile = K.SMJ_L_SUBLANES * K.LANES
    r_tile = K.SMJ_R_SUBLANES * K.LANES
    n_l_sub = _round_up(SF1_LINEITEM, l_tile) // K.LANES
    n_r_tiles = _round_up(SF1_ORDERS, r_tile) // r_tile
    n_l_tiles = n_l_sub // K.SMJ_L_SUBLANES
    with K._x32():
        fn = K._build_smj_call(n_l_sub, n_r_tiles, 4)
        args = [_spec((n_l_tiles,), jnp.int32, one_chip) for _ in range(3)] + [
            _spec((n_l_sub, K.LANES), jnp.int32, one_chip),
            _spec((n_r_tiles * K.SMJ_R_SUBLANES, K.LANES), jnp.int32, one_chip),
        ]
        text = fn.lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def test_agg_over_join_epilogue_compiles(one_chip):
    # a 64K-row left side compiles in about 5 s; the SF1 width compiles
    # too, in about 12 s (1M rows, oddly, takes a minute)
    import jax
    import jax.numpy as jnp

    n_l = 1 << 16
    fn = jax.jit(K.agg_over_join_epilogue(n_l))
    args = (
        _spec((n_l // K.LANES, K.LANES), jnp.int32, one_chip),
        _spec((n_l // K.LANES, K.LANES), jnp.int32, one_chip),
        _spec((SF1_ORDERS + 1,), jnp.int64, one_chip),
        _spec((n_l,), jnp.int32, one_chip),
        _spec((SF1_PARTS,), jnp.int32, one_chip),
        _spec((SF1_PARTS,), jnp.int32, one_chip),
    )
    fn.lower(*args).compile()


def test_build_step_compiles(one_chip):
    # the device build engine's per-chunk kernel with the int32 composite
    # (ops.build._narrow_pack); at the 2M-row chunk it compiles in about
    # 20 s, so the test takes a small chunk
    import jax.numpy as jnp

    from hyperspace_tpu.ops.build import _single_perm_kernel_packed

    rows = 4096
    kernel = _single_perm_kernel_packed(
        (("l_orderkey", "int64"),), ("l_orderkey",), 200, True
    )
    kernel.lower(
        {"l_orderkey": _spec((rows,), jnp.int64, one_chip)},
        {},
        _spec((), jnp.int32, one_chip),
        _spec((1,), jnp.int64, one_chip),
        _spec((1,), jnp.int32, one_chip),
    ).compile()


@pytest.mark.parametrize("narrow", [True, False], ids=["int32", "int64"])
def test_staged_step_compiles(one_chip, narrow):
    # the staged build's per-chunk kernel, which keeps its sorted
    # composite on device and takes the real row count as the n_valid
    # operand (full chunks and the partial tail), at a small chunk for
    # the same reason
    import jax.numpy as jnp

    from hyperspace_tpu.ops.build import _single_staged_kernel_packed

    rows = 4096
    kernel = _single_staged_kernel_packed(
        (("l_orderkey", "int64"),), ("l_orderkey",), 200, narrow
    )
    kernel.lower(
        {"l_orderkey": _spec((rows,), jnp.int64, one_chip)},
        {},
        _spec((), jnp.int32, one_chip),
        _spec((1,), jnp.int64, one_chip),
        _spec((1,), jnp.int32, one_chip),
    ).compile()


@pytest.mark.parametrize("narrow", [True, False], ids=["int32", "int64"])
def test_run_merge_compiles(one_chip, narrow):
    # the staged build's device run merge (one stable sort of two
    # re-packed runs), at a small chunk for the same reason
    import jax.numpy as jnp

    from hyperspace_tpu.ops.build import _staged_merge_fn

    r, rows, buckets = 2, 4096, 200
    _staged_merge_fn(1, narrow).lower(
        _spec((r, rows), jnp.int64, one_chip),
        _spec((r, rows), jnp.int32, one_chip),
        _spec((r, buckets), jnp.int32, one_chip),
        _spec((r, 1), jnp.int64, one_chip),
        _spec((r, 1), jnp.int32, one_chip),
        _spec((1,), jnp.int64, one_chip),
        _spec((1,), jnp.int32, one_chip),
    ).compile()
