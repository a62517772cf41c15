"""The one general request generator: a traffic mix is a JSON file of
parameters (``traffic/<mix>.json``), and this turns it into one seeded
request stream per client.

A mix lists ``requests``, each a query shape with its parameters. A
parameter is a literal or a draw:

- ``{"zipf": theta, "over": "table.column"}``: a key drawn Zipf(theta)
  over the distinct values of the column present in the table (base and
  appended), popularity ranked by a permutation seeded by the run's seed;
- ``{"choice": [v, ...]}``: one of the values, uniformly (TPC-H's
  substitution parameters picked from a list, such as Q3's SEGMENT);
- ``{"uniform": [lo, hi]}``: an integer uniformly in lo..hi, both
  included; a pair of ISO dates (``"1995-03-01"``) gives a day, as the
  number of days since 1970-01-01, the representation of a date in the
  shapes and the references (Q3's DATE).

Every draw of a client comes from its own seeded generator, so a
client's requests repeat from the seed.

Each client cycles through ``requests``, client c starting at request
c, so that the clients start on different shapes.
"""

from __future__ import annotations

import numpy as np

from .keys import Zipf, stream


class Keyspace:
    """What the Zipf draws rank: the distinct keys of one column."""

    def __init__(self, values: np.ndarray, seed: int):
        self.keys = np.unique(values)
        self.order = stream(seed, 2).permutation(len(self.keys))
        self._zipf = {}

    def zipf(self, theta: float) -> Zipf:
        if theta not in self._zipf:
            self._zipf[theta] = Zipf(len(self.keys), theta)
        return self._zipf[theta]


class Stream:
    """Client ``client``'s requests in ``phase`` (0 the window, 1 the
    warm-up), each ``(shape, params)``."""

    BLOCK = 1024

    def __init__(self, mix: dict, keyspaces: dict, seed: int, client: int, phase: int):
        self.requests = mix["requests"]
        self.keyspaces = keyspaces
        self.rng = stream(seed, 4, phase, client)
        self.i = client
        self._blocks: dict = {}

    def _zipf_key(self, spec: dict) -> int:
        key = (spec["over"], spec["zipf"])
        buf = self._blocks.get(key)
        if not buf:
            ks = self.keyspaces[spec["over"]]
            ranks = ks.zipf(spec["zipf"]).ranks(self.rng, self.BLOCK)
            buf = self._blocks[key] = list(ks.keys[ks.order[ranks]])[::-1]
        return int(buf.pop())

    def _draw(self, spec):
        if not isinstance(spec, dict):
            return spec
        if "zipf" in spec:
            return self._zipf_key(spec)
        if "choice" in spec:
            values = spec["choice"]
            return values[int(self.rng.integers(len(values)))]
        if "uniform" in spec:
            lo, hi = (_day(v) if isinstance(v, str) else int(v) for v in spec["uniform"])
            return int(self.rng.integers(lo, hi + 1))
        raise KeyError(f"unknown parameter draw {spec!r}")

    def __iter__(self):
        return self

    def __next__(self) -> tuple:
        r = self.requests[self.i % len(self.requests)]
        self.i += 1
        return r["shape"], {k: self._draw(v) for k, v in r.get("params", {}).items()}


def _day(iso: str) -> int:
    """Days since 1970-01-01 of an ISO date."""
    return int(np.datetime64(iso, "D").astype(np.int64))


def key_columns(mix: dict) -> set:
    return {
        v["over"]
        for r in mix["requests"]
        for v in r.get("params", {}).values()
        if isinstance(v, dict) and "over" in v
    }
