"""The query shapes the traffic mixes name, as DataFrames of the program
(the entry the window drives), with the columns an answer is compared on
and the key columns it is ordered by. The same shapes, computed without
the program, are in ``configs/tpch_reference.py``."""

from __future__ import annotations

POINT_COLUMNS = ["l_orderkey", "l_partkey", "l_quantity", "l_extendedprice", "l_shipdate"]

# shape -> (columns compared, ordering keys)
SHAPES = {
    "point": (POINT_COLUMNS, POINT_COLUMNS),
}


def dataframe(session, paths: dict, shape: str, params: dict):
    """The program's DataFrame for one request."""
    from hyperspace_tpu.plan.expr import col, lit

    li = session.read.parquet(str(paths["lineitem"]))
    if shape == "point":
        return li.filter(col("l_orderkey") == lit(int(params["key"]))).select(*POINT_COLUMNS)
    raise KeyError(f"unknown query shape {shape!r}")


def arrays(batch, columns) -> dict:
    """{column: numpy array} of the program's answer (a ColumnarBatch)."""
    import numpy as np

    return {c: np.asarray(batch.columns[c].data) for c in columns}
