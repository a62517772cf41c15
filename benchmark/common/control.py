"""The control of ``correct``: the plain reference put in the program's
place, computed one precision below what the configuration states
(float32 for its float64 DECIMAL columns), and judged by the cell's own
comparison and limits. A sound comparison finds it not correct.

For a query cell the control answers the requests the cell's clients
would send (their first ``per_client`` each, from the run's seed); for
the build cell it is the covering index as the reference builds it in
float32. The benchmark's own runs do not run it."""

from __future__ import annotations

import shutil
from pathlib import Path

import numpy as np

from . import compare
from .harness import WORK, Bench
from .system import Deployment
from .traffic import Keyspace, Stream, key_columns


def control_numbers(root: Path, workload: str, seed: int, per_client: int = 8) -> tuple:
    """(correct, compared) of the control on one seed."""
    bench = Bench(root)
    cell = bench.cell(workload)
    config = bench.config(cell["config"])
    traffic = bench.traffic(cell["traffic"])
    ref_mod = bench.reference(config)
    work = Path(root) / WORK
    shutil.rmtree(work, ignore_errors=True)
    dep = Deployment(config, seed, work)
    try:
        dep.generate()
        dep.append()
        if traffic["kind"] == "builds":
            spec = dep.index_spec(traffic["index"])
            source = ref_mod.concat(dep.tables[spec["table"]])
            names = [*spec["indexed"], *spec["included"]]
            got = {c: source[c].astype(np.float32) if source[c].dtype.kind == "f" else source[c]
                   for c in names}
            numbers = {"rows_wrong": ref_mod.rows_wrong(got, source, names),
                       "rows_misbucketed": 0, "order_breaks": 0}
        else:
            shapes = bench.shapes(traffic)
            tables = {t: ref_mod.concat(dep.tables[t]) for t in config["tables"]}
            want_ref = ref_mod.Reference(tables)
            ctrl_ref = ref_mod.Reference(tables, dtype=np.float32)
            keyspaces = {c: Keyspace(dep.column(*c.split(".")), seed) for c in key_columns(traffic)}
            wrong, gap, seen = 0, 0.0, {}
            for c in range(traffic["clients"]):
                s = Stream(traffic, keyspaces, seed, c, 0)
                for _ in range(per_client):
                    shape, params = next(s)
                    key = (shape, tuple(sorted(params.items())))
                    if key not in seen:
                        seen[key] = shapes[shape].compare(
                            ctrl_ref.answer(shape, params), want_ref.answer(shape, params))
                    wrong, gap = wrong + seen[key][0], max(gap, seen[key][1])
            numbers = {"answers_failed": 0, "answers_wrong": wrong, "max_rel_gap": gap}
        numbers.update(fallback_counters=0, device_paths_missing=0)
        return compare.verdict(numbers, traffic["limits"])
    finally:
        shutil.rmtree(work, ignore_errors=True)
