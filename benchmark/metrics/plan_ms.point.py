"""Median of the program's ``serve.plan`` span (rewrite rules and plan
cache, at submit)."""

import statistics


def read(run):
    d = run.spans.get("serve.plan")
    return statistics.median(d) * 1e3 if d else None
