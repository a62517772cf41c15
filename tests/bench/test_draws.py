"""The request generator's parameter draws: ``choice`` and ``uniform``
repeat per (seed, client, phase), stay in range and reach every value,
and the ``point`` mix's stream is the one the cells have measured. Shapes
are files found by name, and an ordered answer can be compared in the
order returned."""

import hashlib
import json

import numpy as np
import pytest

from benchtools import REPO

SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD"]
MIX = {"requests": [{"shape": "q", "params": {
    "segment": {"choice": SEGMENTS},
    "date": {"uniform": ["1995-03-01", "1995-03-31"]},
    "n": {"uniform": [1, 50]},
    "fixed": 7,
}}]}
DAY0 = int(np.datetime64("1995-03-01", "D").astype(np.int64))


def _draws(seed, client, phase, n=400):
    from benchmark.common.traffic import Stream

    s = Stream(MIX, {}, seed, client, phase)
    return [next(s)[1] for _ in range(n)]


def test_draws_repeat_per_seed_client_and_phase():
    a = _draws(2**33 + 5, 3, 0)
    assert a == _draws(2**33 + 5, 3, 0)
    for other in [(2**33 + 6, 3, 0), (2**33 + 5, 4, 0), (2**33 + 5, 3, 1)]:
        assert _draws(*other) != a, other


def test_draws_stay_in_range_and_reach_every_value():
    got = _draws(2**31 + 17, 0, 0, n=2000)
    segments = [p["segment"] for p in got]
    days = np.array([p["date"] for p in got])
    ns = np.array([p["n"] for p in got])
    assert set(segments) == set(SEGMENTS)
    assert days.min() == DAY0 and days.max() == DAY0 + 30  # March 1 to 31, both included
    assert set(ns) == set(range(1, 51))
    assert all(p["fixed"] == 7 for p in got)
    assert all(isinstance(v, (int, str)) for p in got for v in p.values())


def test_unknown_draw_is_an_error():
    from benchmark.common.traffic import Stream

    with pytest.raises(KeyError, match="normal"):
        next(Stream({"requests": [{"shape": "q", "params": {"x": {"normal": 1}}}]}, {}, 1, 0, 0))


# sha256 of the first 2,500 keys of the point mix's client streams, made by
# the generator of the commit before ``choice`` and ``uniform`` were added
POINT = {
    (0, 0): "6b9d05e1f951adfeb71f3191c69a075502dabb3e3041dd364d97a2bd7cbdea28",
    (3, 0): "8b5b45dc1f76a7f33a824b86fe2740867f08d6b66ce73c2b1c5483cfd94af01c",
    (31, 1): "84754b8142ee652f47c396514b949ff4f3065d5a5b22ee8fa4d01ed6237cb6de",
}


@pytest.mark.parametrize("client,phase", sorted(POINT))
def test_point_stream_is_unchanged(client, phase):
    from benchmark.common.traffic import Keyspace, Stream

    mix = json.loads((REPO / "benchmark/traffic/point-zipf-closed32.json").read_text())
    seed = 2**33 + 1
    s = Stream(mix, {"orders.o_orderkey": Keyspace(np.arange(1, 5000) * 4, seed)}, seed, client, phase)
    reqs = [next(s) for _ in range(2500)]
    assert {shape for shape, _ in reqs} == {"point"}
    keys = np.array([p["key"] for _, p in reqs], np.int64)
    assert hashlib.sha256(keys.tobytes()).hexdigest() == POINT[(client, phase)]


def test_shapes_are_found_by_name():
    from benchmark.common import compare
    from benchmark.common.harness import Bench

    mix = json.loads((REPO / "benchmark/traffic/point-zipf-closed32.json").read_text())
    shapes = Bench(REPO).shapes(mix)
    assert list(shapes) == ["point"]
    point = shapes["point"]
    assert point.columns == point.keys == ["l_orderkey", "l_partkey", "l_quantity", "l_extendedprice",
                                           "l_shipdate"]
    want = {c: np.array([3, 1, 2], np.int64) for c in point.columns}
    got = {c: np.array([1, 2, 3], np.int64) for c in point.columns}
    assert point.compare(got, want) == compare.compare_answer(got, want, point.columns, point.keys) == (False, 0.0)
    assert Bench(REPO).shapes({"kind": "builds"}) == {}


def test_ordered_answer_is_compared_in_the_order_returned():
    from benchmark.common import compare

    want = {"k": np.array([1, 2, 3]), "v": np.array([3.0, 2.0, 1.0])}
    swapped = {"k": np.array([2, 1, 3]), "v": np.array([2.0, 3.0, 1.0])}
    assert compare.compare_answer(swapped, want, ["k", "v"], ["k"]) == (False, 0.0)
    wrong, gap = compare.compare_answer(swapped, want, ["k", "v"], [])
    assert wrong and gap == pytest.approx(0.5)
    assert compare.compare_answer(want, want, ["k", "v"], []) == (False, 0.0)
