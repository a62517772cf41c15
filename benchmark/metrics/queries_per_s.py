"""Completed queries over the whole window: from its start to the end of
the last request sent in it."""


def read(run):
    if run.traffic["kind"] != "queries" or run.window_s <= 0:
        return None
    return sum(r.ok for r in run.records) / run.window_s
