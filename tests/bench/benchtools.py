"""Helpers of the benchmark's CPU tests: a copy of the benchmark at a
tiny scale under a temporary root, and one run of a cell there."""

import json
import shutil
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

SCALE = 0.002


def tiny_copy(dest: Path) -> Path:
    shutil.copytree(REPO / "benchmark", dest / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", dest / "BENCHMARK.json")
    for f in (dest / "benchmark" / "configs").glob("*.json"):
        c = json.loads(f.read_text())
        c["scale_factor"] = SCALE
        f.write_text(json.dumps(c))
    for f in (dest / "benchmark" / "traffic").glob("*.json"):
        t = json.loads(f.read_text())
        w = t["warmup"]
        if "seconds" in w:
            w["seconds"] = 0.5
        if "burst_sizes" in w:
            w["burst_sizes"] = w["burst_sizes"][:3]
        f.write_text(json.dumps(t))
    return dest


class Clock:
    """The compile clock's reading without JAX's monitoring hooks (a
    test process registers listeners once per run otherwise)."""

    def reading(self):
        return {"compiles": 0, "compile_s": 0.0, "cache_hits": 0, "cache_misses": 0}


def run(root, workload, trace=False, seconds=1.0, seed=2**31 + 7):
    from benchmark.common.harness import run_cell

    return run_cell(root, workload, seed, seconds, trace, Clock())
