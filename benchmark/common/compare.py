"""The comparison that decides ``correct``: answers against the plain
reference, and each number compared against its limit."""

from __future__ import annotations

import numpy as np

TINY = np.finfo(np.float64).tiny


def compare_answer(got: dict, want: dict, columns, keys) -> tuple:
    """(wrong, gap) of one answer. ``wrong``: the row count or an exact
    (integer or date) column differs. ``gap``: the largest relative gap
    of a float value, |got - want| / |want|, rows ordered by ``keys``,
    or, with no ``keys``, in the order each answer gives them (an answer
    whose order is part of it)."""
    n = len(want[columns[0]])
    if len(got[columns[0]]) != n:
        return True, 0.0
    if n == 0:
        return False, 0.0
    if keys:
        ga = np.lexsort([got[k] for k in reversed(keys)])
        wa = np.lexsort([want[k] for k in reversed(keys)])
    else:
        ga = wa = np.arange(n)
    wrong, gap = False, 0.0
    for c in columns:
        x, y = got[c][ga], want[c][wa]
        if x.dtype.kind == "f" or y.dtype.kind == "f":
            x, y = x.astype(np.float64), y.astype(np.float64)
            d = np.abs(x - y)
            rel = np.where(d == 0, 0.0, d / np.maximum(np.abs(y), TINY))
            gap = max(gap, float(rel.max()))
        elif not np.array_equal(x.astype(np.int64), y.astype(np.int64)):
            wrong = True
    return wrong, gap


def verdict(numbers: dict, limits: dict) -> tuple:
    """(correct, {name: {"value", "limit"}}) — each number must be at or
    under its limit; a number with no limit is an error in the traffic
    file, not a pass."""
    compared = {}
    ok = True
    for name, value in numbers.items():
        limit = limits[name]
        compared[name] = {"value": value, "limit": limit}
        ok &= value <= limit
    return bool(ok), compared
