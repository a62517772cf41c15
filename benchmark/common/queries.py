"""The query shapes the traffic mixes name. Shape ``<name>`` is the file
``benchmark/shapes/<name>.py``, which ``harness.Bench.shapes`` finds by
name as it finds the metric readers, and defines:

- ``COLUMNS``: the columns an answer is compared on;
- ``KEYS``: the columns its rows are ordered by before comparing;
- ``dataframe(session, paths, params)``: the program's DataFrame for one
  request (the entry the window drives), ``paths`` holding every table
  the configuration lists;
- optionally ``compare(got, want) -> (wrong, gap)``, for an answer whose
  row order is part of it (``ORDER BY ... LIMIT``); by default
  ``compare.compare_answer`` over ``COLUMNS``, rows ordered by ``KEYS``.

The same shapes, computed without the program, are in the reference
module the configuration names."""

from __future__ import annotations

from . import compare


class Shape:
    """One query shape, loaded from its file."""

    def __init__(self, module):
        self.columns = list(module.COLUMNS)
        self.keys = list(module.KEYS)
        self.dataframe = module.dataframe
        self._compare = getattr(module, "compare", None)

    def compare(self, got: dict, want: dict) -> tuple:
        if self._compare is not None:
            return self._compare(got, want)
        return compare.compare_answer(got, want, self.columns, self.keys)


def arrays(batch, columns) -> dict:
    """{column: numpy array} of the program's answer (a ColumnarBatch)."""
    import numpy as np

    return {c: np.asarray(batch.columns[c].data) for c in columns}
