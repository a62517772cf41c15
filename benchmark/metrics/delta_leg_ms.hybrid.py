"""Milliseconds of the program's ``scan.delta_leg`` spans (the hybrid
scan's appended side, sliced from the host-held batch) per completed
query."""


def read(run):
    d = run.spans.get("scan.delta_leg")
    done = sum(r.ok for r in run.records)
    return sum(d) * 1e3 / done if d and done else None
