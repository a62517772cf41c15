"""Median of the program's ``scan.device_launch`` span: the call of the
jitted count program until it returns its unsynced result (the host-side
launch, without the wait and the D2H)."""

import statistics


def read(run):
    d = run.spans.get("scan.device_launch")
    return statistics.median(d) * 1e3 if d else None
