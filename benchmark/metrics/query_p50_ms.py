"""Median latency, submit to result, of all requests sent in the window;
a failed request counts as infinitely late."""

import numpy as np


def read(run):
    if run.traffic["kind"] != "queries" or not run.records:
        return None
    lat = [(r.t1 - r.t0) * 1e3 if r.ok else np.inf for r in run.records]
    return float(np.percentile(lat, 50))
