"""Index-build kernel tests: hash parity, partition+sort correctness vs a
pandas oracle, multi-device == single-device, end-to-end build+scan row
parity (the off/on oracle pattern of E2EHyperspaceRulesTest.scala:1004-1019).
"""

import numpy as np
import pytest

from hyperspace_tpu.exec.scan import index_scan
from hyperspace_tpu.index.builder import resolve_index_columns, write_index_data
from hyperspace_tpu.ops import hashing
from hyperspace_tpu.ops.build import build_partition_single, build_partition_sharded
from hyperspace_tpu.parallel.mesh import make_mesh, owner_of_bucket
from hyperspace_tpu.plan.expr import col
from hyperspace_tpu.storage import layout
from hyperspace_tpu.storage.columnar import ColumnarBatch
from hyperspace_tpu.exceptions import HyperspaceException


def sample(n=1000, seed=0):
    rng = np.random.default_rng(seed)
    return ColumnarBatch.from_pydict(
        {
            "orderkey": rng.integers(0, 10**12, n).astype(np.int64),
            "qty": rng.integers(0, 50, n).astype(np.int32),
            "price": rng.random(n).astype(np.float32),
            "flag": rng.choice([b"A", b"N", b"R"], n).astype(object),
        },
        schema={"orderkey": "int64", "qty": "int32", "price": "float32", "flag": "string"},
    )


def test_hash_host_device_parity():
    b = sample(500)
    for cols in (["orderkey"], ["orderkey", "flag"], ["flag"], ["price", "qty"]):
        host = hashing.bucket_ids_host([hashing.key_repr(b.columns[c]) for c in cols], 64)
        from hyperspace_tpu.ops.build import device_bucket_ids, vocab_hashes
        import jax.numpy as jnp

        arrays = b.device_arrays(cols)
        vh = {
            c: jnp.asarray(vocab_hashes(b.columns[c]))
            for c in cols
            if b.columns[c].dtype_str == "string"
        }
        dev = device_bucket_ids(arrays, b.schema(), cols, vh, 64)
        np.testing.assert_array_equal(host, np.asarray(dev))


def test_hash_is_value_stable_across_batches():
    # Same values in different batches (different vocab layouts) must land in
    # the same bucket — this is what makes bucketed joins and hybrid-scan
    # shuffles line up.
    b1 = ColumnarBatch.from_pydict({"s": np.array(["x", "a", "q"], dtype=object)}, {"s": "string"})
    b2 = ColumnarBatch.from_pydict({"s": np.array(["q", "zz", "x"], dtype=object)}, {"s": "string"})
    h1 = hashing.bucket_ids_host([hashing.key_repr(b1.columns["s"])], 32)
    h2 = hashing.bucket_ids_host([hashing.key_repr(b2.columns["s"])], 32)
    assert h1[0] == h2[2]  # "x"
    assert h1[2] == h2[0]  # "q"


def test_single_device_partition_sort():
    b = sample(2000)
    nb = 16
    out, counts = build_partition_single(b, ["orderkey"], nb)
    assert counts.sum() == 2000
    host_bucket = hashing.bucket_ids_host([hashing.key_repr(b.columns["orderkey"])], nb)
    # bucket sizes match host hash
    np.testing.assert_array_equal(counts, np.bincount(host_bucket, minlength=nb))
    # within each bucket, orderkey ascending; bucket ids grouped ascending
    out_bucket = hashing.bucket_ids_host([hashing.key_repr(out.columns["orderkey"])], nb)
    assert (np.diff(out_bucket) >= 0).all()
    offsets = np.concatenate([[0], np.cumsum(counts)]).astype(int)
    keys = out.columns["orderkey"].data
    for bkt in range(nb):
        seg = keys[offsets[bkt] : offsets[bkt + 1]]
        assert (np.diff(seg) >= 0).all()
    # row multiset preserved
    assert sorted(keys.tolist()) == sorted(b.columns["orderkey"].data.tolist())


@pytest.mark.parametrize(
    "keys",
    [
        ["orderkey"],  # 40-bit keys: the int64 packed composite
        ["qty"],  # narrow keys: the int32 packed composite
        ["price"],  # float32 keys have no pack plan: multi-operand sort
        ["qty", "orderkey"],  # two keys, int64 composite, ties on qty
    ],
)
def test_sharded_build_matches_single(tmp_path, keys):
    b = sample(777)  # deliberately not divisible by 8
    nb = 12
    mesh = make_mesh(8)
    per_device, global_counts = build_partition_sharded(b, keys, nb, mesh)
    host_bucket = hashing.bucket_ids_host(
        [hashing.key_repr(b.columns[k]) for k in keys], nb
    )
    np.testing.assert_array_equal(global_counts, np.bincount(host_bucket, minlength=nb))
    # each device holds exactly its owned buckets, grouped and sorted
    all_rows = []
    for d, (dev_batch, bucket_ids) in enumerate(per_device):
        if dev_batch.num_rows == 0:
            continue
        assert set(np.unique(bucket_ids) % 8) == {d}
        assert all(owner_of_bucket(int(x), 8) == d for x in np.unique(bucket_ids))
        assert (np.diff(bucket_ids) >= 0).all()
        for bkt in np.unique(bucket_ids):
            seg = list(zip(*(dev_batch.columns[k].data[bucket_ids == bkt] for k in keys)))
            assert seg == sorted(seg)
        all_rows.extend(
            zip(dev_batch.columns["orderkey"].data.tolist(), dev_batch.columns["qty"].data.tolist())
        )
    assert sorted(all_rows) == sorted(
        zip(b.columns["orderkey"].data.tolist(), b.columns["qty"].data.tolist())
    )


def tie_sample(n=2600, seed=5):
    """Keys with heavy duplicates (ties across shards), a first stretch of
    one key (its source shard sends nothing to the other devices), the
    same keys far from zero (a narrow pack that the zero padding rows
    would not fit), a float64 key with -0.0 (no pack plan: the unpacked
    exchange) and a row-sequence column that shows the order of ties."""
    rng = np.random.default_rng(seed)
    k = rng.integers(0, 40, n).astype(np.int64)
    k[: n // 5] = 7
    x = rng.integers(-3, 4, n).astype(np.float64) * 0.5
    x[rng.random(n) < 0.1] = -0.0
    return ColumnarBatch.from_pydict(
        {
            "k": k,
            "far": k + 10**12,
            "s": rng.choice([b"AIR", b"MAIL", b"RAIL", b"SHIP"], n).astype(object),
            "x": x,
            "seq": np.arange(n, dtype=np.int64),
            "p": np.where(rng.random(n) < 0.2, -0.0, rng.random(n)),
        },
        schema={"k": "int64", "far": "int64", "s": "string", "x": "float64", "seq": "int64", "p": "float64"},
    )


@pytest.mark.parametrize("n_devices", [4, 8])
@pytest.mark.parametrize("keys", [["k"], ["far"], ["s"], ["x"], ["s", "k"]])
def test_sharded_rows_equal_single_bucket_by_bucket(keys, n_devices):
    """Each device's rows of bucket b are the one-device build's rows of
    bucket b, in the same order (ties in row order) and the same bytes."""
    b = tie_sample()
    nb = 12
    single, counts = build_partition_single(b, keys, nb)
    per_device, global_counts = build_partition_sharded(b, keys, nb, make_mesh(n_devices))
    np.testing.assert_array_equal(global_counts, counts)
    offsets = np.concatenate([[0], np.cumsum(counts)])
    for bkt in range(nb):
        dev_batch, bucket_ids = per_device[owner_of_bucket(bkt, n_devices)]
        got = dev_batch.take(np.flatnonzero(bucket_ids == bkt))
        want = single.take(np.arange(offsets[bkt], offsets[bkt + 1]))
        for name in b.column_names:
            g, w = got.columns[name], want.columns[name]
            if g.vocab is not None:
                assert g.to_values().tolist() == w.to_values().tolist()
            else:
                assert g.data.dtype == w.data.dtype
                assert g.data.tobytes() == w.data.tobytes(), (bkt, name)


@pytest.mark.parametrize("engine", ["device", "host"])
def test_write_index_data_and_scan_row_parity(tmp_path, engine):
    b = sample(1500, seed=3)
    nb = 8
    files = write_index_data(b, ["orderkey"], nb, tmp_path / "v__=0", engine=engine)
    assert files
    for f in files:
        footer = layout.read_footer(f)
        assert footer["sortedBy"] == ["orderkey"]
        assert footer["bucket"] == layout.bucket_of_file(f)
    # off/on oracle: filter through the index == filter via pandas
    df = b.to_pandas()
    key = int(df["orderkey"].iloc[42])
    expected = df[df["orderkey"] == key].sort_values(["orderkey", "qty"]).reset_index(drop=True)
    got = index_scan(files, ["orderkey", "qty", "flag"], col("orderkey") == key)
    got_df = got.to_pandas().sort_values(["orderkey", "qty"]).reset_index(drop=True)
    assert len(got_df) == len(expected)
    assert got_df["orderkey"].tolist() == expected["orderkey"].tolist()
    assert got_df["qty"].tolist() == expected["qty"].tolist()
    assert got_df["flag"].tolist() == expected["flag"].tolist()
    # range query parity
    lo, hi = np.percentile(df["orderkey"], [30, 60]).astype(np.int64)
    expected = df[(df["orderkey"] > lo) & (df["orderkey"] <= hi)]
    got = index_scan(files, ["orderkey"], (col("orderkey") > int(lo)) & (col("orderkey") <= int(hi)))
    assert sorted(got.columns["orderkey"].data.tolist()) == sorted(expected["orderkey"].tolist())


def test_scan_bucket_pruning(tmp_path):
    from hyperspace_tpu.exec.scan import buckets_for_predicate
    from hyperspace_tpu.plan.expr import is_in

    b = ColumnarBatch.from_pydict({"k": np.arange(1000, dtype=np.int64)})
    files = write_index_data(b, ["k"], 10, tmp_path / "v__=0")
    dtypes = {"k": "int64"}
    # equality predicate pins the hash bucket: exactly one bucket read
    bkts = buckets_for_predicate(col("k") == 500, ["k"], dtypes, 10)
    assert len(bkts) == 1
    got = index_scan(
        files, ["k"], col("k") == 500,
        indexed_columns=["k"], dtypes=dtypes, num_buckets=10,
    )
    assert got.columns["k"].data.tolist() == [500]
    # IN-list prunes to its buckets; range predicates don't pin
    assert buckets_for_predicate(is_in(col("k"), [1, 2, 3]), ["k"], dtypes, 10)
    assert buckets_for_predicate(col("k") > 5, ["k"], dtypes, 10) is None
    # parity with an unpruned scan
    got2 = index_scan(files, ["k"], col("k") == 500)
    assert got2.columns["k"].data.tolist() == [500]


def test_string_predicates_through_index(tmp_path):
    b = sample(800, seed=5)
    files = write_index_data(b, ["flag"], 4, tmp_path / "v__=0")
    df = b.to_pandas()
    got = index_scan(files, ["orderkey", "flag"], col("flag") == "N")
    expected = df[df["flag"] == "N"]
    assert sorted(got.columns["orderkey"].data.tolist()) == sorted(
        expected["orderkey"].tolist()
    )
    got = index_scan(files, ["flag"], col("flag") > "A")
    expected = df[df["flag"] > "A"]
    assert len(got.columns["flag"].data) == len(expected)


def test_resolve_index_columns():
    assert resolve_index_columns(["Query", "qty"], ["query"], ["QTY"]) == (
        ["Query"],
        ["qty"],
    )
    with pytest.raises(HyperspaceException):
        resolve_index_columns(["a"], ["zzz"], [])


def test_sharded_mesh_single_device_path(tmp_path):
    # mesh of 1 device falls back to the single kernel inside write_index_data
    b = sample(100)
    mesh = make_mesh(1)
    files = write_index_data(b, ["orderkey"], 4, tmp_path / "v", mesh=mesh)
    total = sum(layout.read_footer(f)["numRows"] for f in files)
    assert total == 100


def test_sharded_write_index_data(tmp_path):
    b = sample(500, seed=9)
    mesh = make_mesh(8)
    files = write_index_data(b, ["orderkey"], 16, tmp_path / "v", mesh=mesh)
    single = write_index_data(b, ["orderkey"], 16, tmp_path / "v1", engine="device")
    # same buckets, same per-bucket contents
    def contents(fs):
        out = {}
        for f in fs:
            fb = layout.read_batch(f)
            out.setdefault(layout.bucket_of_file(f), []).append(fb.columns["orderkey"].data)
        return {k: np.sort(np.concatenate(v)).tolist() for k, v in out.items()}

    assert contents(files) == contents(single)


@pytest.mark.parametrize("engine", ["device", "host"])
def test_float64_exact_through_build(tmp_path, engine):
    # float64 must survive the build bit-exactly (ops.floatbits transport);
    # includes negatives, -0.0, tiny/huge magnitudes.
    vals = np.array(
        [3421.33, -3421.33, 0.0, -0.0, 1e-300, -1e300, 123456789.000000001],
        dtype=np.float64,
    )
    n = 640
    rng = np.random.default_rng(0)
    price = np.concatenate([vals, (rng.random(n - len(vals)) * 1e6)])
    b = ColumnarBatch.from_pydict(
        {"k": rng.integers(0, 50, n).astype(np.int64), "price": price},
        schema={"k": "int64", "price": "float64"},
    )
    files = write_index_data(b, ["k"], 8, tmp_path / "v", engine=engine)
    got = index_scan(files, ["price"])
    got_sorted = np.sort(got.columns["price"].data)
    exp_sorted = np.sort(np.where(price == 0.0, 0.0, price))
    np.testing.assert_array_equal(
        got_sorted.view(np.int64), exp_sorted.view(np.int64)
    )


@pytest.mark.parametrize("engine", ["device", "host"])
def test_float64_as_indexed_key(tmp_path, engine):
    from hyperspace_tpu.ops.floatbits import (
        f64_to_ordered_i64,
        ordered_i64_to_f64,
    )

    x = np.array([-2.0, -1.0, -0.0, 0.0, 0.5, 1.0, np.inf, -np.inf], dtype=np.float64)
    o = f64_to_ordered_i64(x)
    # order preserved
    assert list(np.argsort(o, kind="stable")) == list(np.argsort(np.where(x == 0, 0.0, x), kind="stable"))
    back = ordered_i64_to_f64(o)
    np.testing.assert_array_equal(back, np.where(x == 0.0, 0.0, x))
    # end-to-end: index on a float64 column
    rng = np.random.default_rng(1)
    price = (rng.random(500) * 100).round(3)
    price[7] = 42.125
    b = ColumnarBatch.from_pydict({"price": price, "v": np.arange(500, dtype=np.int64)},
                                  schema={"price": "float64", "v": "int64"})
    files = write_index_data(b, ["price"], 4, tmp_path / "v", engine=engine)
    got = index_scan(files, ["v"], col("price") == 42.125,
                     indexed_columns=["price"], dtypes=b.schema(), num_buckets=4)
    expected = np.flatnonzero(price == 42.125)
    assert sorted(got.columns["v"].data.tolist()) == sorted(expected.tolist())


def test_device_mask_padded_paths(tmp_path):
    # Exercise the jitted device-mask path explicitly (production gate is
    # min_device_rows; tests force it with min_device_rows=1): cache miss,
    # cache hit across files with identical dictionaries, string predicate,
    # and the f64 host fallback.
    from hyperspace_tpu.exec import scan as scan_mod

    b = sample(1200, seed=21)
    files = write_index_data(b, ["orderkey"], 4, tmp_path / "v")
    df = b.to_pandas()
    scan_mod._mask_fn_cache.clear()
    pred = col("qty") > 25
    got = index_scan(files, ["orderkey"], pred, min_device_rows=1)
    assert sorted(got.columns["orderkey"].data.tolist()) == sorted(
        df[df["qty"] > 25]["orderkey"].tolist()
    )
    # same predicate + same shapes across several files: few compiled fns
    n_fns = len(scan_mod._mask_fn_cache)
    assert n_fns >= 1
    index_scan(files, ["orderkey"], pred, min_device_rows=1)
    assert len(scan_mod._mask_fn_cache) == n_fns  # pure cache hits
    # string predicate through the device path
    got = index_scan(files, ["orderkey", "flag"], col("flag") == "N", min_device_rows=1)
    assert sorted(got.columns["orderkey"].data.tolist()) == sorted(
        df[df["flag"] == "N"]["orderkey"].tolist()
    )
    # f64 predicate: host fallback, still exact
    b2 = ColumnarBatch.from_pydict(
        {"k": np.arange(600, dtype=np.int64), "p": (np.arange(600) * 1.1)},
        schema={"k": "int64", "p": "float64"},
    )
    files2 = write_index_data(b2, ["k"], 4, tmp_path / "v2")
    got = index_scan(files2, ["k"], col("p") > 300.0, min_device_rows=1)
    exp = np.flatnonzero(np.arange(600) * 1.1 > 300.0)
    assert sorted(got.columns["k"].data.tolist()) == sorted(exp.tolist())


def test_device_arrays_f64_encoding_round_trip():
    from hyperspace_tpu.storage.columnar import decode_device_array

    vals = np.array([3421.33, -1.5, 0.0, -0.0, 1e300], dtype=np.float64)
    b = ColumnarBatch.from_pydict({"p": vals}, schema={"p": "float64"})
    arrs = b.device_arrays(["p"])
    import jax.numpy as jnp

    assert arrs["p"].dtype == jnp.int64  # encoded, never raw f64
    back = decode_device_array("float64", np.asarray(arrs["p"]))
    np.testing.assert_array_equal(
        back.view(np.int64), np.where(vals == 0.0, 0.0, vals).view(np.int64)
    )


def test_empty_bucket_lookup_returns_empty(tmp_path):
    """An equality key hashing to a bucket with no rows (hence no file)
    returns an empty result in the index schema — regression: it crashed
    with 'index_scan over zero files with no schema'."""
    from hyperspace_tpu.ops.hashing import bucket_of_values

    b = ColumnarBatch.from_pydict(
        {"k": np.array([1, 2] * 50, dtype=np.int64),
         "v": np.arange(100, dtype=np.int64)}
    )
    nb = 64
    files = write_index_data(b, ["k"], nb, tmp_path / "v")
    used = {layout.bucket_of_file(f) for f in files}
    probe = next(
        k for k in range(3, 10_000)
        if bucket_of_values([k], ["int64"], nb) not in used
    )
    got = index_scan(
        files, ["k", "v"], col("k") == probe,
        indexed_columns=["k"], dtypes={"k": "int64", "v": "int64"}, num_buckets=nb,
    )
    assert got.num_rows == 0
    assert got.schema() == {"k": "int64", "v": "int64"}


def test_pack_sort_keys_matches_lexsort():
    """The bit-packed composite's ascending order must equal lexsort's
    (bucket primary, then keys in order), including negative encodings
    (float ordered-int64) and multi-key packs; unpackable widths -> None."""
    import numpy as np

    from hyperspace_tpu.ops.build import _pack_sort_keys

    rng = np.random.default_rng(4)
    n = 5000
    k1 = rng.integers(-500, 500, n)  # negatives (f64 ordered-i64 analog)
    k2 = rng.integers(0, 37, n)
    bucket = rng.integers(0, 16, n)
    comp = _pack_sort_keys([k1, k2], bucket, 16)
    assert comp is not None
    got = np.argsort(comp, kind="stable")
    exp = np.lexsort((k2, k1, bucket))
    np.testing.assert_array_equal(got, exp)
    # no bucket: keys only
    comp2 = _pack_sort_keys([k1, k2], None, 0)
    np.testing.assert_array_equal(
        np.argsort(comp2, kind="stable"), np.lexsort((k2, k1))
    )
    # width overflow falls back
    wide = np.array([np.iinfo(np.int64).min, np.iinfo(np.int64).max])
    assert _pack_sort_keys([wide, wide], None, 0) is None


def test_pack_sort_keys_uint64_beyond_int64_falls_back():
    import numpy as np

    from hyperspace_tpu.ops.build import _pack_sort_keys

    big = np.array([2**63 + 5, 2**63 + 1, 2**63 + 9], dtype=np.uint64)
    assert _pack_sort_keys([big], None, 0) is None
    assert _pack_sort_keys([big, big], None, 0) is None


def test_float_key_zero_tie_order_matches_host_twin():
    """f32/f64 key columns containing both -0.0 and +0.0: the device sort
    must treat them as EQUAL ties kept in input order, exactly like the
    host twin (lax.sort would otherwise order -0.0 strictly first)."""
    import numpy as np

    from hyperspace_tpu.ops.build import (
        build_partition_host,
        build_partition_single,
    )
    from hyperspace_tpu.storage.columnar import Column, ColumnarBatch

    for dt, np_dt in (("float32", np.float32), ("float64", np.float64)):
        vals = np.array(
            [0.0, -0.0, 1.5, -0.0, 0.0, -1.5, 0.0], dtype=np_dt
        )
        b = ColumnarBatch(
            {
                "k": Column(dt, vals),
                "v": Column("int64", np.arange(len(vals))),
            }
        )
        dev, dc = build_partition_single(b, ["k"], 4)
        host, hc = build_partition_host(b, ["k"], 4)
        np.testing.assert_array_equal(dc, hc)
        np.testing.assert_array_equal(
            dev.columns["v"].data, host.columns["v"].data, err_msg=dt
        )
        # bytes identical too (-0.0 canonicalized the same way)
        assert dev.columns["k"].data.tobytes() == host.columns["k"].data.tobytes()
