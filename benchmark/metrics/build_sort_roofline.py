"""Share of the HBM roofline of the build's device bucketize+sort: the
least time the window's builds need at the chip's peak HBM bandwidth
(the indexed key, read once and written once per row; benchmark/common/
roofline.py) over the summed device time of the build programs in the
trace."""

import re

from benchmark.common import peaks, roofline

# the build's bucketize+sort and run-merge programs are jitted from
# functions named ``kernel`` (ops/build.py); the trace's module line
# shows them as ``jit_kernel(<hash>)``
PROGRAMS = re.compile(r"^jit_kernel\(|bucket|sort|radix", re.I)


def read(run):
    p = run.profile
    if not p or not run.builds:
        return None
    device_s = sum(s for name, s in p["module_s"].items() if PROGRAMS.search(name))
    if device_s <= 0:
        return None
    least = sum(roofline.build_sort_bytes(b[3]) for b in run.builds)
    return 100.0 * least / peaks.peak(run.device_kind, "hbm_bytes_per_s") / device_s
