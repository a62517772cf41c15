"""Share of the HBM roofline of the sharded build program: the least
time the window's builds need at the chip's peak HBM bandwidth over the
summed device time of the ``jit_build_sharded_exchange`` modules on all
device planes. The least bytes are every row's indexed and included
columns of ``li_orderkey``, read once and written once, from the source
schema alone. Both sides are summed over the chips, so the share is the
per-chip least time over the per-chip device time."""

import re

from benchmark.common import peaks

PROGRAM = re.compile(r"^jit_build_sharded_exchange")

# bytes per value of the index's columns (common/tpch.py): l_orderkey,
# l_partkey, l_quantity, l_extendedprice, l_discount 8 each; l_shipdate 4
ROW_BYTES = 5 * 8 + 4


def least_bytes(rows: int) -> int:
    return 2 * rows * ROW_BYTES


def read(run):
    p = run.profile
    if not p or not run.builds:
        return None
    device_s = sum(s for name, s in p["module_s"].items() if PROGRAM.search(name))
    if device_s <= 0:
        return None
    least = sum(least_bytes(b[3]) for b in run.builds)
    return 100.0 * least / peaks.peak(run.device_kind, "hbm_bytes_per_s") / device_s
