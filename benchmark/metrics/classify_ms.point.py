"""Median of the program's ``serve.classify`` span (file pruning,
residency lookup, zone gate and predicate narrowing, at submit)."""

import statistics


def read(run):
    d = run.spans.get("serve.classify")
    return statistics.median(d) * 1e3 if d else None
