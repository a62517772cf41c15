"""Columnar substrate + TCB layout tests."""

import numpy as np
import pytest

from hyperspace_tpu.exceptions import HyperspaceException
from hyperspace_tpu.storage import layout, parquet_io
from hyperspace_tpu.storage.columnar import (
    Column,
    ColumnarBatch,
    unify_dictionaries,
)


def sample_batch():
    return ColumnarBatch.from_pydict(
        {
            "k": np.arange(10, dtype=np.int64),
            "v": np.linspace(0, 1, 10).astype(np.float32),
            "s": np.array(["b", "a", "c", "a", "b", "a", "c", "b", "a", "d"], dtype=object),
        },
        schema={"k": "int64", "v": "float32", "s": "string"},
    )


def test_dictionary_encoding_is_order_preserving():
    b = sample_batch()
    s = b.columns["s"]
    # codes sort order == string sort order
    order_by_codes = np.argsort(s.data, kind="stable")
    order_by_strings = np.argsort(b.to_pydict()["s"].astype(str), kind="stable")
    assert list(order_by_codes) == list(order_by_strings)
    assert list(s.to_values()) == ["b", "a", "c", "a", "b", "a", "c", "b", "a", "d"]


def test_batch_ops():
    b = sample_batch()
    assert b.num_rows == 10
    assert b.schema() == {"k": "int64", "v": "float32", "s": "string"}
    sel = b.select(["k", "s"])
    assert sel.column_names == ["k", "s"]
    with pytest.raises(HyperspaceException):
        b.select(["nope"])
    t = b.take(np.array([0, 2, 4]))
    assert list(t.to_pydict()["k"]) == [0, 2, 4]
    assert list(t.to_pydict()["s"]) == ["b", "c", "b"]


def test_concat_unifies_dictionaries():
    b1 = ColumnarBatch.from_pydict({"s": np.array(["x", "a"], dtype=object)}, {"s": "string"})
    b2 = ColumnarBatch.from_pydict({"s": np.array(["m", "x"], dtype=object)}, {"s": "string"})
    c = ColumnarBatch.concat([b1, b2])
    assert list(c.to_pydict()["s"]) == ["x", "a", "m", "x"]
    s = c.columns["s"]
    # equal strings share a code after unification
    assert s.data[0] == s.data[3]
    # and codes still sort like strings
    assert list(np.argsort(s.data, kind="stable")) == [1, 2, 0, 3]


def test_unify_dictionaries_missing_value():
    c1 = Column.from_values(np.array(["a", "b"], dtype=object), "string")
    c2 = Column.from_values(np.array(["c"], dtype=object), "string")
    u1, u2 = unify_dictionaries([c1, c2])
    assert list(u1.to_values()) == ["a", "b"]
    assert list(u2.to_values()) == ["c"]
    assert u1.vocab is u2.vocab or list(u1.vocab) == list(u2.vocab)


@pytest.mark.parametrize("vocab", ["own", "shared"])
def test_gather_concat_equals_concat_take(vocab):
    """Rows gathered straight from several batches equal the concat's
    take, string codes and vocab included, whether the batches carry
    their own vocabs or already share one (then it is kept as is)."""
    rng = np.random.default_rng(5)
    parts = [
        ColumnarBatch.from_pydict(
            {
                "k": rng.integers(0, 100, n).astype(np.int64),
                "s": rng.choice(["a", "b", "x%d" % i], n).astype(object),
            },
            {"k": "int64", "s": "string"},
        )
        for i, n in enumerate([7, 0, 5, 9])
    ]
    if vocab == "shared":
        shared = unify_dictionaries([p.columns["s"] for p in parts])
        parts = [
            ColumnarBatch({"k": p.columns["k"], "s": u})
            for p, u in zip(parts, shared)
        ]
    idx = rng.permutation(21)[:15]
    got = ColumnarBatch.gather_concat(parts, idx)
    want = ColumnarBatch.concat(parts).take(idx)
    for name in ("k", "s"):
        np.testing.assert_array_equal(got.columns[name].data, want.columns[name].data)
    assert list(got.columns["s"].vocab) == list(want.columns["s"].vocab)
    if vocab == "shared":
        assert got.columns["s"].vocab is parts[0].columns["s"].vocab


def test_concat_schema_mismatch():
    b1 = ColumnarBatch.from_pydict({"a": np.arange(2)})
    b2 = ColumnarBatch.from_pydict({"b": np.arange(2)})
    with pytest.raises(HyperspaceException):
        ColumnarBatch.concat([b1, b2])


def test_tcb_round_trip(tmp_path):
    b = sample_batch()
    p = tmp_path / "b00000-abc.tcb"
    layout.write_batch(p, b, sorted_by=["k"], bucket=0, extra={"indexName": "i"})
    footer = layout.read_footer(p)
    assert footer["numRows"] == 10
    assert footer["sortedBy"] == ["k"]
    assert footer["bucket"] == 0
    k_meta = next(m for m in footer["columns"] if m["name"] == "k")
    assert (k_meta["min"], k_meta["max"]) == (0, 9)
    assert k_meta["offset"] % 128 == 0
    back = layout.read_batch(p)
    assert back.schema() == b.schema()
    np.testing.assert_array_equal(back.columns["k"].data, b.columns["k"].data)
    np.testing.assert_array_equal(back.columns["v"].data, b.columns["v"].data)
    assert list(back.to_pydict()["s"]) == list(b.to_pydict()["s"])
    # projection read
    proj = layout.read_batch(p, columns=["v"])
    assert proj.column_names == ["v"]
    with pytest.raises(HyperspaceException):
        layout.read_batch(p, columns=["zzz"])


def test_tcb_alignment_and_magic(tmp_path):
    p = tmp_path / "x.tcb"
    layout.write_batch(p, ColumnarBatch.from_pydict({"a": np.arange(3, dtype=np.int8)}))
    raw = p.read_bytes()
    assert raw[-4:] == b"TCB1"
    bad = tmp_path / "bad.tcb"
    bad.write_bytes(b"junkjunkjunkjunk")
    with pytest.raises(HyperspaceException):
        layout.read_footer(bad)


def test_bucket_file_names():
    name = layout.bucket_file_name(7)
    assert layout.bucket_of_file("/some/dir/" + name) == 7
    with pytest.raises(HyperspaceException):
        layout.bucket_of_file("part-0.parquet")


def test_prune_by_min_max(tmp_path):
    for i, (lo, hi) in enumerate([(0, 9), (10, 19), (20, 29)]):
        layout.write_batch(
            tmp_path / f"b{i:05d}-x.tcb",
            ColumnarBatch.from_pydict({"k": np.arange(lo, hi + 1, dtype=np.int64)}),
        )
    paths = sorted(tmp_path.glob("*.tcb"))
    kept = layout.prune_by_min_max(paths, "k", 12, 15)
    assert [p.name[:6] for p in kept] == ["b00001"]
    kept = layout.prune_by_min_max(paths, "k", None, 9)
    assert [p.name[:6] for p in kept] == ["b00000"]
    # unknown column: no pruning
    assert len(layout.prune_by_min_max(paths, "zzz", 0, 0)) == 3


def test_parquet_round_trip(tmp_path):
    b = sample_batch()
    p = tmp_path / "data.parquet"
    parquet_io.write_parquet(p, b)
    back = parquet_io.read_parquet([p])
    assert back.num_rows == 10
    np.testing.assert_array_equal(back.columns["k"].data, b.columns["k"].data)
    assert list(back.to_pydict()["s"]) == list(b.to_pydict()["s"])
    proj = parquet_io.read_parquet([p], columns=["k"])
    assert proj.column_names == ["k"]


def test_parquet_multi_file_concat(tmp_path):
    b1 = ColumnarBatch.from_pydict({"k": np.arange(3, dtype=np.int64)})
    b2 = ColumnarBatch.from_pydict({"k": np.arange(3, 5, dtype=np.int64)})
    parquet_io.write_parquet(tmp_path / "a.parquet", b1)
    parquet_io.write_parquet(tmp_path / "b.parquet", b2)
    back = parquet_io.read_parquet([tmp_path / "a.parquet", tmp_path / "b.parquet"])
    assert list(back.to_pydict()["k"]) == [0, 1, 2, 3, 4]


def test_csv_read(tmp_path):
    p = tmp_path / "d.csv"
    p.write_text("a,b\n1,x\n2,y\n")
    b = parquet_io.read_csv([p])
    assert list(b.to_pydict()["a"]) == [1, 2]
    assert list(b.to_pydict()["b"]) == ["x", "y"]


def test_device_arrays():
    import jax.numpy as jnp

    b = sample_batch()
    arrs = b.device_arrays(["k", "s"])
    assert isinstance(arrs["k"], jnp.ndarray)
    assert arrs["s"].dtype == jnp.int32


def test_null_strings_preserved_distinct_from_empty(tmp_path):
    # NULL vs "" must survive ingest + TCB round-trip (code -1 = NULL).
    import pyarrow as pa

    table = pa.table({"s": pa.array(["a", None, "", "a"])})
    b = ColumnarBatch.from_arrow(table)
    vals = list(b.to_pydict()["s"])
    assert vals == ["a", None, "", "a"]
    p = tmp_path / "n.tcb"
    layout.write_batch(p, b)
    back = layout.read_batch(p)
    assert list(back.to_pydict()["s"]) == ["a", None, "", "a"]


def test_reencode_empty_vocab():
    c = Column.from_values(np.array(["a", "b"], dtype=object), "string")
    r = c.reencode(np.array([], dtype=object))
    assert list(r.data) == [-1, -1]
