"""The least bytes a piece of work has to move, reckoned from the query
and the source schema alone, so that it stays the same whatever
implements the program."""

from __future__ import annotations

# bytes per value of the indexed keys (common/tpch.py)
WIDTH = {"l_orderkey": 8, "o_orderkey": 8}


def build_sort_bytes(rows: int, key: str = "l_orderkey") -> int:
    """The indexed key, read once and written once per row."""
    return 2 * rows * WIDTH[key]
