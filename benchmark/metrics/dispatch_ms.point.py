"""Median of the program's ``scan.device_dispatch`` span: one resident
mask+count dispatch, batched or single, from the launch until the counts
are on the host."""

import statistics


def read(run):
    d = run.spans.get("scan.device_dispatch")
    return statistics.median(d) * 1e3 if d else None
