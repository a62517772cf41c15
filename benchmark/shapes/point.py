"""``point``: the lineitem rows of one order key, five columns
(``l_orderkey = key``; YCSB's read through the covering index)."""

COLUMNS = ["l_orderkey", "l_partkey", "l_quantity", "l_extendedprice", "l_shipdate"]
KEYS = COLUMNS


def dataframe(session, paths: dict, params: dict):
    from hyperspace_tpu.plan.expr import col, lit

    li = session.read.parquet(str(paths["lineitem"]))
    return li.filter(col("l_orderkey") == lit(int(params["key"]))).select(*COLUMNS)
