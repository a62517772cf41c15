"""A bucket mesh from the session's conf (``hyperspace.tpu.mesh.devices``)
and the streaming index build sharded over it: the ICI all_to_all path
of ``Hyperspace.create_index`` on four of the 8 virtual CPU devices,
against the one-device build, the benchmark's plain reference and
pyarrow, with the sharded path's counters, spans and program name."""

import sys
from pathlib import Path

import numpy as np
import pytest

from hyperspace_tpu import constants as C
from hyperspace_tpu.config import HyperspaceConf
from hyperspace_tpu.exceptions import HyperspaceException
from hyperspace_tpu.hyperspace import Hyperspace
from hyperspace_tpu.index.index_config import IndexConfig
from hyperspace_tpu.parallel.mesh import make_mesh
from hyperspace_tpu.plan.expr import col, lit
from hyperspace_tpu.plan.ir import IndexScan
from hyperspace_tpu.session import HyperspaceSession
from hyperspace_tpu.storage import layout
from hyperspace_tpu.telemetry.metrics import metrics
from hyperspace_tpu.telemetry.recorder import flight_recorder

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

INDEXED = ["l_orderkey"]
INCLUDED = ["l_partkey", "l_quantity", "l_extendedprice", "l_discount", "l_shipdate"]
NUM_BUCKETS = 200
CHUNK_ROWS = 4096  # 12,000 rows at the scale below: two full chunks and a tail
NEW_COUNTERS = (
    "build.engine.device",
    "build.engine.sharded",
    "build.sharded.rows",
    "build.sharded.slots",
    "build.sharded.row_id_chunks",
)


# --- the conf key -----------------------------------------------------------


def test_unset_key_keeps_the_single_device_paths():
    assert HyperspaceSession(HyperspaceConf()).mesh is None
    assert HyperspaceSession(HyperspaceConf({C.TPU_MESH_DEVICES: 1})).mesh is None


def test_key_builds_a_mesh_on_the_bucket_axis():
    mesh = HyperspaceSession(HyperspaceConf({C.TPU_MESH_DEVICES: "4"})).mesh
    assert mesh.devices.size == 4
    assert mesh.axis_names == (C.TPU_MESH_BUCKET_AXIS_DEFAULT,)
    named = HyperspaceSession(
        HyperspaceConf({C.TPU_MESH_DEVICES: 2, C.TPU_MESH_BUCKET_AXIS: "b"})
    ).mesh
    assert named.axis_names == ("b",) and named.devices.size == 2


@pytest.mark.parametrize("value", [16, "0", "-2", "four"])
def test_a_mesh_the_host_cannot_give_raises(value):
    with pytest.raises((ValueError, HyperspaceException)):
        HyperspaceSession(HyperspaceConf({C.TPU_MESH_DEVICES: value}))


def test_an_explicit_mesh_wins_over_the_key():
    given = make_mesh(2)
    assert HyperspaceSession(HyperspaceConf({C.TPU_MESH_DEVICES: 4}), mesh=given).mesh is given


# --- the sharded streaming build --------------------------------------------


def _session(root: Path, name: str, **conf) -> HyperspaceSession:
    values = {
        C.INDEX_SYSTEM_PATH: str(root / name),
        C.INDEX_NUM_BUCKETS: NUM_BUCKETS,
        C.BUILD_MODE: "streaming",
        C.BUILD_ENGINE: "device",
        C.BUILD_CHUNK_ROWS: CHUNK_ROWS,
    }
    values.update(conf)
    return HyperspaceSession(HyperspaceConf(values))


def _index_files(root: Path, name: str) -> list:
    return sorted((root / name / "li").glob("v__=*/*.tcb"))


def _buckets(files) -> dict:
    """{bucket: rows in key order, ties in a fixed order}."""
    out = {}
    for f in files:
        batch = layout.read_batch(f)
        cols = [np.asarray(batch.columns[c].data) for c in INDEXED + INCLUDED]
        order = np.lexsort(cols[::-1])
        keys = cols[0]
        assert np.all(keys[1:] >= keys[:-1]), f  # the build's key order
        out[layout.bucket_of_file(f)] = np.stack([c[order].astype(np.float64) for c in cols])
    return out


@pytest.fixture(scope="module")
def built(tmp_path_factory):
    from benchmark.common import tpch

    root = tmp_path_factory.mktemp("mesh_build")
    lineitem, _ = tpch.tpch_tables(0.002, 2**31 + 11)
    tpch.write_files(lineitem, root / "lineitem", 8)
    flight_recorder.reset()
    before = dict(metrics.snapshot()["counters"])
    mesh_session = _session(root, "mesh", **{C.TPU_MESH_DEVICES: 4})
    assert mesh_session.mesh.devices.size == 4
    hs = Hyperspace(mesh_session)
    hs.create_index(mesh_session.read.parquet(str(root / "lineitem")), IndexConfig("li", INDEXED, INCLUDED))
    after = dict(metrics.snapshot()["counters"])
    moved = {k: after.get(k, 0) - before.get(k, 0) for k in NEW_COUNTERS}
    (trace,) = [t for t in flight_recorder.last() if t.root.labels.get("index") == "li"]
    one = _session(root, "one")
    assert one.mesh is None
    before = dict(metrics.snapshot()["counters"])
    Hyperspace(one).create_index(one.read.parquet(str(root / "lineitem")), IndexConfig("li", INDEXED, INCLUDED))
    after = dict(metrics.snapshot()["counters"])
    (one_trace,) = [t for t in flight_recorder.last() if t.root.labels.get("index") == "li" and t is not trace]
    # the session without the key takes the single-device path: no
    # sharded counter or span
    assert all(after.get(k, 0) == before.get(k, 0) for k in NEW_COUNTERS[1:])
    assert not {"build.sharded_plan", "build.sharded_exchange"} & set(one_trace.spans())
    return root, lineitem, mesh_session, moved, trace


def test_sharded_build_holds_the_one_device_builds_rows(built):
    root, lineitem, _, _, _ = built
    assert lineitem.num_rows > 2 * CHUNK_ROWS
    mesh, one = _buckets(_index_files(root, "mesh")), _buckets(_index_files(root, "one"))
    assert sorted(mesh) == sorted(one)
    for b in one:
        np.testing.assert_array_equal(mesh[b], one[b])


def test_sharded_build_files_are_byte_equal_to_the_one_device_builds(built):
    root, _, _, _, _ = built
    mesh, one = _index_files(root, "mesh"), _index_files(root, "one")
    assert [layout.bucket_of_file(f) for f in mesh] == [layout.bucket_of_file(f) for f in one]
    for m, o in zip(mesh, one):
        assert m.read_bytes() == o.read_bytes(), m.name


@pytest.mark.parametrize("indexed", [["k"], ["s"], ["x"]])
def test_sharded_streaming_build_keeps_ties_in_the_one_device_order(tmp_path, indexed):
    """Duplicate keys across shards and chunks, a first stretch of one key
    (its shard sends nothing to three devices), string keys and a float64
    key with no pack plan: the mesh build's files equal the one-device
    build's byte for byte, so ties keep the source-row order."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    rng = np.random.default_rng(17)
    n = 2600  # 1024-row chunks: two full chunks and a tail
    k = rng.integers(0, 40, n).astype(np.int64)
    k[:600] = 7
    x = rng.integers(-3, 4, n).astype(np.float64) * 0.5
    x[rng.random(n) < 0.1] = -0.0
    table = pa.table(
        {
            "k": k,
            "s": rng.choice(["AIR", "MAIL", "RAIL", "SHIP"], n),
            "x": x,
            "seq": np.arange(n, dtype=np.int64),
            "p": np.where(rng.random(n) < 0.2, -0.0, rng.random(n)),
        }
    )
    src = tmp_path / "t"
    src.mkdir()
    for i, start in enumerate(range(0, n, 700)):
        pq.write_table(table.slice(start, 700), src / f"part-{i}.parquet")
    included = [c for c in table.column_names if c not in indexed]
    before = metrics.snapshot()["counters"].get("build.sharded.row_id_chunks", 0)
    for name, conf in (("mesh", {C.TPU_MESH_DEVICES: 4}), ("one", {})):
        s = _session(tmp_path, name, **{C.BUILD_CHUNK_ROWS: 1024}, **conf)
        Hyperspace(s).create_index(s.read.parquet(str(src)), IndexConfig("li", indexed, included))
    assert metrics.snapshot()["counters"]["build.sharded.row_id_chunks"] - before == 3
    mesh, one = _index_files(tmp_path, "mesh"), _index_files(tmp_path, "one")
    assert mesh and [layout.bucket_of_file(f) for f in mesh] == [layout.bucket_of_file(f) for f in one]
    for m, o in zip(mesh, one):
        assert m.read_bytes() == o.read_bytes(), m.name


def test_sharded_build_passes_the_plain_reference(built):
    from benchmark.configs import tpch_reference as ref

    root, lineitem, _, _, _ = built
    got = ref.index_numbers(_index_files(root, "mesh"), ref.concat([lineitem]), INDEXED[0], INCLUDED, NUM_BUCKETS)
    assert got == {"rows_wrong": 0, "rows_misbucketed": 0, "order_breaks": 0}


def test_sharded_build_counts_and_spans(built):
    _, lineitem, _, moved, trace = built
    chunks = -(-lineitem.num_rows // CHUNK_ROWS)
    assert moved["build.engine.device"] == moved["build.engine.sharded"] == chunks
    assert moved["build.sharded.rows"] == lineitem.num_rows
    assert moved["build.sharded.slots"] >= lineitem.num_rows
    # every chunk crossed the exchange as sort words and row ids
    assert moved["build.sharded.row_id_chunks"] == chunks
    # what a slot carried when every index column crossed: the columns,
    # the int32 bucket and the validity byte
    column_slot_bytes = sum(lineitem.schema.field(c).type.bit_width // 8 for c in INDEXED + INCLUDED) + 4 + 1
    ingest = trace.find("build.ingest_dispatch")
    plans = [c for c in ingest.children if c.name == "build.sharded_plan"]
    exchanges = [c for c in ingest.children if c.name == "build.sharded_exchange"]
    # the full chunks run in the ingest loop; the tail in finalize
    assert len(plans) == len(exchanges) == chunks - 1
    for p, e in zip(plans, exchanges):
        assert set(p.labels) >= {"rows", "cap", "shard_rows", "h2d_bytes"}
        assert set(e.labels) >= {"cap", "narrow", "d2h_bytes", "payload_bytes"}
        assert 0 < e.labels["payload_bytes"] < 4 * 4 * e.labels["cap"] * column_slot_bytes
        assert p.labels["rows"] == CHUNK_ROWS and e.labels["cap"] == p.labels["cap"]
        assert p.labels["h2d_bytes"] > 0 and e.labels["d2h_bytes"] > 0
        assert p.t1 <= e.t0


def test_filter_on_the_mesh_built_index_matches_pyarrow(built):
    import pyarrow.compute as pc

    root, lineitem, session, _, _ = built
    session.enable_hyperspace()
    keys = lineitem.column("l_orderkey").to_numpy()
    for k in (int(keys[0]), int(keys[len(keys) // 2]), int(keys[-1])):
        cols = ["l_orderkey", "l_partkey", "l_quantity"]
        q = session.read.parquet(str(root / "lineitem")).filter(col("l_orderkey") == lit(k)).select(*cols)
        assert q.optimized_plan().collect(lambda n: isinstance(n, IndexScan))  # the rewrite fired
        got = q.collect()
        want = lineitem.filter(pc.equal(lineitem.column("l_orderkey"), k))
        g = np.stack([np.asarray(got.columns[c].data, dtype=np.float64) for c in cols])
        w = np.stack([want.column(c).to_numpy().astype(np.float64) for c in cols])
        assert g.shape == w.shape and g.shape[1] > 0
        np.testing.assert_array_equal(g[:, np.lexsort(g[::-1])], w[:, np.lexsort(w[::-1])])


# --- the program's name -----------------------------------------------------


def test_sharded_program_carries_its_name():
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    from hyperspace_tpu.ops import build as B

    mesh = make_mesh(4)
    row = NamedSharding(mesh, PartitionSpec(mesh.axis_names[0]))
    S = jax.ShapeDtypeStruct
    fn = B._sharded_build_fn(mesh, mesh.axis_names[0], (("k", "int64"),), ("k",), (), 8, 64, None, ())
    text = fn.lower({"k": S((1024,), jnp.int64, sharding=row)}, S((1024,), jnp.bool_, sharding=row), {}).as_text()
    assert text.split("module @", 1)[1].split(" ", 1)[0] == "jit_build_sharded_exchange"
    # the blocks are slices of the destination-sorted shard, not scatters
    assert "scatter" not in text
