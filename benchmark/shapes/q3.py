"""``q3``: TPC-H Q3, the Shipping Priority query (v3.0.1 §2.4.3), written
in the spec's FROM order: the customers of one market segment, their
orders placed before a day, those orders' lines shipped after it; the
revenue sum(l_extendedprice * (1 - l_discount)) per (l_orderkey,
o_orderdate, o_shippriority), ordered by revenue descending and
o_orderdate, the first 10.

Its answer is ordered, so it brings its own ``compare``: rank by rank,
a row count, key, date or priority that differs is wrong, and revenues
count as a relative gap; two rows may trade places only where the
reference holds them exactly tied on (revenue, o_orderdate). The
reference lists, after its first 10, every row tied with the 10th, so
that any of them may fill a tied place."""

import numpy as np

COLUMNS = ["l_orderkey", "revenue", "o_orderdate", "o_shippriority"]
KEYS = []
LIMIT = 10
TINY = np.finfo(np.float64).tiny


def dataframe(session, paths, params):
    from hyperspace_tpu.dataframe import desc
    from hyperspace_tpu.plan.aggregates import agg_sum
    from hyperspace_tpu.plan.expr import col, lit

    cu = session.read.parquet(str(paths["customer"]))
    od = session.read.parquet(str(paths["orders"]))
    li = session.read.parquet(str(paths["lineitem"]))
    date = int(params["date"])
    return (
        cu.filter(col("c_mktsegment") == lit(params["segment"]))
        .join(od.filter(col("o_orderdate") < lit(date)), col("c_custkey") == col("o_custkey"))
        .join(li.filter(col("l_shipdate") > lit(date)), col("l_orderkey") == col("o_orderkey"))
        .group_by("l_orderkey", "o_orderdate", "o_shippriority")
        .agg(agg_sum(col("l_extendedprice") * (lit(1) - col("l_discount")), "revenue"))
        .order_by(desc("revenue"), "o_orderdate")
        .limit(LIMIT)
    )


def compare(got, want):
    """(wrong, gap) of one answer against the reference's."""
    n = min(LIMIT, len(want["l_orderkey"]))
    if len(got["l_orderkey"]) != n:
        return True, 0.0
    wkey = want["l_orderkey"].astype(np.int64)
    wrev = want["revenue"].astype(np.float64)
    wdate = want["o_orderdate"].astype(np.int64)
    seen = set()
    gap = 0.0
    for i in range(n):
        key = int(got["l_orderkey"][i])
        # the reference row this rank holds: the same key at this rank, or
        # a row exactly tied with this rank's on (revenue, o_orderdate)
        at = np.flatnonzero((wkey == key) & (wrev == wrev[i]) & (wdate == wdate[i]))
        if key in seen or not len(at):
            return True, gap
        j = int(at[0])
        seen.add(key)
        if int(got["o_orderdate"][i]) != wdate[j] or int(got["o_shippriority"][i]) != int(
            want["o_shippriority"][j]
        ):
            return True, gap
        g, w = float(got["revenue"][i]), float(wrev[j])
        if g != w:
            gap = max(gap, abs(g - w) / max(abs(w), TINY))
    return False, gap
