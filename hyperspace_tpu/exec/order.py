"""ORDER BY and LIMIT on the host: the exact semantics the device top-k
arm (exec.join_residency) is held to.

Rows order by each key in turn, ascending or descending; NULLs (string
code -1, float NaN) come first when ascending and last when descending,
Spark's default. The sort is stable, so rows equal on every key keep the
child's order. String codes order like their strings within one batch
(storage.columnar's order-preserving dictionary).
"""

from __future__ import annotations

from typing import Sequence

import numpy as np

from ..storage.columnar import ColumnarBatch, is_string


def _nulls(data: np.ndarray, dtype_str: str) -> np.ndarray:
    if is_string(dtype_str):
        return data < 0
    if data.dtype.kind == "f":
        return np.isnan(data)
    return np.zeros(len(data), dtype=bool)


def sort_order(batch: ColumnarBatch, keys: Sequence[str], ascending: Sequence[bool]) -> np.ndarray:
    """The row permutation of ``ORDER BY keys``."""
    order = np.arange(batch.num_rows)
    for key, asc in reversed(list(zip(keys, ascending))):
        col = batch.columns[key]
        x = col.data[order]
        null = _nulls(x, col.dtype_str)
        _, inv = np.unique(x[~null], return_inverse=True)
        top = int(inv.max()) + 1 if len(inv) else 0
        rank = np.empty(len(x), dtype=np.int64)
        rank[~null] = inv if asc else top - 1 - inv
        rank[null] = -1 if asc else top
        order = order[np.argsort(rank, kind="stable")]
    return order


def sort_batch(batch: ColumnarBatch, keys: Sequence[str], ascending: Sequence[bool]) -> ColumnarBatch:
    if batch.num_rows < 2:
        return batch
    return batch.take(sort_order(batch, keys, ascending))


def limit_batch(batch: ColumnarBatch, n: int) -> ColumnarBatch:
    if batch.num_rows <= n:
        return batch
    return batch.take(np.arange(n))
