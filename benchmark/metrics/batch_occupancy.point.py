"""Queries per batched device dispatch: completed tickets over the
``serve.batch.dispatches`` counter of the window."""


def read(run):
    n = run.counters.get("serve.batch.dispatches", 0)
    return sum(r.ok for r in run.records) / n if n else None
