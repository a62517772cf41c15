"""Run one benchmark cell once, on the chips of the machine it starts on.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads, warms up, measures for ``--seconds``, checks what the window
produced against the plain reference, and prints one JSON result line
last on stdout. Exits non-zero with no result line when JAX finds no TPU
or fewer chips than the cell asks for.
"""

from __future__ import annotations

import argparse
import ctypes
import importlib.util
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
# glibc's mallopt parameters a config may pin under "malloc"
MALLOPT = {"trim_threshold": -1, "mmap_threshold": -3}


def pin_malloc(params: dict) -> None:
    """Fix the glibc allocator thresholds that the config names. A fixed
    mmap threshold also stops glibc from moving it as large buffers are
    freed, which leaves each process mapping and trimming alike."""
    if not params:
        return
    libc = ctypes.CDLL("libc.so.6")
    for key, value in params.items():
        if libc.mallopt(MALLOPT[key], int(value)) != 1:
            raise RuntimeError(f"mallopt refused {key}={value}")


def main(argv=None) -> int:
    t0 = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT))
    from benchmark.common.harness import Bench, log  # noqa: E402

    if importlib.util.find_spec("hyperspace_tpu") is None:
        log(f"no run: the system under test (hyperspace_tpu) is not in {ROOT}")
        return 2

    bench = Bench(ROOT)
    cell = bench.cell(args.workload)
    config = bench.config(cell["config"])
    # the compile cache lives inside the checkout, at a fixed path
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    for k, v in config.get("env", {}).items():
        os.environ[k] = str(v)
    pin_malloc(config.get("malloc", {}))

    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # no eviction: a size limit from the environment (JAX_COMPILATION_CACHE_
    # MAX_SIZE) smaller than the cell's programs makes every run miss them all
    jax.config.update("jax_compilation_cache_max_size", -1)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        log(f"no run: JAX finds {len(devices)} {devices[0].platform} device(s); "
            f"the cell needs {cell['chips']} TPU chip(s)")
        return 2

    from benchmark.common.compile_clock import CompileClock
    from benchmark.common.harness import run_cell

    clock = CompileClock()
    result = run_cell(ROOT, args.workload, args.seed, args.seconds, bool(args.trace), clock, t0)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
