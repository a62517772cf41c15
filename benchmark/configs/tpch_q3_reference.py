"""The plain reference of the TPC-H Q3 deployment: a copy of
``tpch_reference.py`` (the point lookup and the covering index) plus
TPC-H Q3, the Shipping Priority query (v3.0.1 §2.4.3), in numpy over the
generated tables, importing nothing of the program and taking nothing it
made.

``dtype`` is the precision of the DECIMAL columns. The configuration
states float64; the control computes the same reference in float32.
"""

from __future__ import annotations

from pathlib import Path

import numpy as np

SEED = np.uint32(0x9E3779B9)


# the string columns a query shape here reads (Q3's SEGMENT)
STRINGS = {"c_mktsegment"}
# Q3's LIMIT
LIMIT = 10


def columns_of(table) -> dict:
    """{column: numpy array} of a pyarrow table's numeric and date
    columns, dates as int32 days, and of the string columns in
    ``STRINGS`` as Python strings."""
    import pyarrow as pa

    out = {}
    for name in table.column_names:
        arr = table.column(name)
        if pa.types.is_string(arr.type):
            if name in STRINGS:
                out[name] = np.array(arr.to_pylist(), dtype=object)
            continue
        if pa.types.is_date32(arr.type):
            arr = arr.cast(pa.int32())
        out[name] = arr.to_numpy()
    return out


def concat(tables) -> dict:
    cols = [columns_of(t) for t in tables]
    return {c: np.concatenate([d[c] for d in cols]) for c in cols[0]}


class Reference:
    """The query shapes over ``tables`` ({table: {column: array}}, every
    table the configuration lists); the point lookup reads ``lineitem``."""

    def __init__(self, tables: dict, dtype=np.float64):
        def cast(cols):
            return {k: v.astype(dtype) if v.dtype.kind == "f" else v for k, v in cols.items()}

        self.t = {name: cast(cols) for name, cols in tables.items()}
        self.li = self.t["lineitem"]
        self.dtype = dtype
        self._li_order = None
        self._q3 = None

    # -- the query shapes ----------------------------------------------------
    def point(self, key: int) -> dict:
        if self._li_order is None:
            o = np.argsort(self.li["l_orderkey"], kind="stable")
            self._li_order = (o, self.li["l_orderkey"][o])
        o, keys = self._li_order
        lo, hi = np.searchsorted(keys, key, "left"), np.searchsorted(keys, key, "right")
        rows = o[lo:hi]
        cols = ["l_orderkey", "l_partkey", "l_quantity", "l_extendedprice", "l_shipdate"]
        return {c: self.li[c][rows] for c in cols}

    def _q3_join(self):
        """The parameter-free half of Q3's join: each line's orders row
        (-1 where the order key is absent; order keys are unique) and each
        line's value l_extendedprice * (1 - l_discount)."""
        if self._q3 is None:
            od, li = self.t["orders"], self.t["lineitem"]
            by_key = np.argsort(od["o_orderkey"], kind="stable")
            keys = od["o_orderkey"][by_key]
            at = np.minimum(np.searchsorted(keys, li["l_orderkey"]), max(len(keys) - 1, 0))
            hit = (keys[at] == li["l_orderkey"]) if len(keys) else np.zeros(len(at), bool)
            row = np.where(hit, by_key[at], -1)
            value = li["l_extendedprice"] * (self.dtype(1) - li["l_discount"])
            self._q3 = (row, value)
        return self._q3

    def q3(self, segment: str, date: int) -> dict:
        """TPC-H Q3: for the customers of ``segment``, their orders placed
        before ``date`` with lines shipped after it, the revenue
        sum(l_extendedprice * (1 - l_discount)) per (l_orderkey,
        o_orderdate, o_shippriority), ordered by revenue descending and
        o_orderdate; the first LIMIT rows, and after them every row tied
        with the LIMIT-th on (revenue, o_orderdate), so that a comparison
        can accept any order among exact ties at the cut. Rows tied on
        both keys follow in l_orderkey order. A customer key listed twice
        joins twice (inner-join semantics)."""
        cu, od, li = self.t["customer"], self.t["orders"], self.t["lineitem"]
        row, value = self._q3_join()
        seg_keys, mult = np.unique(cu["c_custkey"][cu["c_mktsegment"] == segment], return_counts=True)
        pos = np.minimum(np.searchsorted(seg_keys, od["o_custkey"]), max(len(seg_keys) - 1, 0))
        hit = (seg_keys[pos] == od["o_custkey"]) if len(seg_keys) else np.zeros(len(pos), bool)
        times = np.where(hit & (od["o_orderdate"] < date), mult[pos] if len(seg_keys) else 0, 0)
        lines = np.flatnonzero((li["l_shipdate"] > date) & (row >= 0))
        lines = lines[times[row[lines]] > 0]
        rows = row[lines]
        contrib = value[lines] * times[rows].astype(self.dtype)
        revenue = np.zeros(len(od["o_orderkey"]), dtype=self.dtype)
        np.add.at(revenue, rows, contrib)
        groups = np.unique(rows)
        revenue = revenue[groups]
        okey, odate = od["o_orderkey"][groups], od["o_orderdate"][groups]
        rank = np.lexsort((okey, odate, -revenue))
        cut = len(rank)
        if cut > LIMIT:
            last = rank[LIMIT - 1]
            tied = (revenue[rank] == revenue[last]) & (odate[rank] == odate[last])
            cut = max(LIMIT, int(np.flatnonzero(tied).max()) + 1)
        rank = rank[:cut]
        return {
            "l_orderkey": okey[rank],
            "revenue": revenue[rank],
            "o_orderdate": odate[rank],
            "o_shippriority": od["o_shippriority"][groups][rank],
        }

    def answer(self, shape: str, params: dict) -> dict:
        if shape == "point":
            return self.point(int(params["key"]))
        if shape == "q3":
            return self.q3(params["segment"], int(params["date"]))
        raise KeyError(f"unknown query shape {shape!r}")


# -- the covering index ------------------------------------------------------
def _fmix32(h):
    h = h.astype(np.uint32)
    h ^= h >> np.uint32(16)
    h = (h * np.uint32(0x85EBCA6B)).astype(np.uint32)
    h ^= h >> np.uint32(13)
    h = (h * np.uint32(0xC2B2AE35)).astype(np.uint32)
    h ^= h >> np.uint32(16)
    return h


def bucket_of(keys: np.ndarray, num_buckets: int) -> np.ndarray:
    """The index's bucket of each int64 key: the murmur3 finalizer over
    the key's two 32-bit halves, seeded 0x9E3779B9, modulo the bucket
    count (docs/03's bucketing rule; hyperspace_tpu/ops/hashing.py)."""
    u = keys.astype(np.int64).view(np.uint64)
    lo = (u & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    hi = (u >> np.uint64(32)).astype(np.uint32)
    with np.errstate(over="ignore"):
        h = _fmix32(np.full(len(keys), SEED, np.uint32) ^ _fmix32(lo ^ _fmix32(hi)))
    return (h % np.uint32(num_buckets)).astype(np.int64)


def _row_hashes(cols: dict, names) -> np.ndarray:
    """A 64-bit hash of each row's bit patterns (splitmix64 finalizer
    folded over the columns)."""
    h = np.zeros(len(cols[names[0]]), np.uint64)
    with np.errstate(over="ignore"):
        for c in names:
            v = cols[c]
            bits = v.astype(np.float64).view(np.uint64) if v.dtype.kind == "f" else v.astype(np.int64).view(np.uint64)
            h = (h ^ bits) * np.uint64(0x9E3779B97F4A7C15)
            h ^= h >> np.uint64(30)
            h *= np.uint64(0xBF58476D1CE4E5B9)
            h ^= h >> np.uint64(27)
            h *= np.uint64(0x94D049BB133111EB)
            h ^= h >> np.uint64(31)
    return h


def rows_wrong(got: dict, want: dict, names) -> int:
    """Rows of ``want`` that ``got`` does not hold, as multisets of rows
    (by a 64-bit hash of each row's values; a wrong row that matched a
    right one's hash is a 2**-64 chance), plus rows ``got`` holds beyond
    ``want``'s count."""
    ug, cg = np.unique(_row_hashes(got, names), return_counts=True)
    uw, cw = np.unique(_row_hashes(want, names), return_counts=True)
    _, ig, iw = np.intersect1d(ug, uw, assume_unique=True, return_indices=True)
    held = int(np.minimum(cg[ig], cw[iw]).sum())
    n_got, n_want = int(cg.sum()), int(cw.sum())
    return (n_want - held) + max(n_got - n_want, 0)


def index_numbers(files, source: dict, indexed: str, included, num_buckets: int) -> dict:
    """What one built covering index holds against the source rows:
    ``rows_wrong`` rows of the source not held exactly (or held too
    often), ``rows_misbucketed`` rows in a bucket their key does not hash
    to, ``order_breaks`` adjacent rows of one bucket out of key order."""
    from benchmark.common import tcb

    names = [indexed, *included]
    parts, misbucketed, breaks = [], 0, 0
    for f in files:
        cols, footer = tcb.read(Path(f), names)
        k = cols[indexed]
        misbucketed += int(np.count_nonzero(bucket_of(k, num_buckets) != tcb.bucket_of_file(footer, f)))
        breaks += int(np.count_nonzero(k[1:] < k[:-1]))
        parts.append(cols)
    got = {c: np.concatenate([p[c] for p in parts]) for c in names} if parts else {
        c: source[c][:0] for c in names}
    return {
        "rows_wrong": rows_wrong(got, source, names),
        "rows_misbucketed": misbucketed,
        "order_breaks": breaks,
    }
