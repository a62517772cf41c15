"""Milliseconds of the program's ``scan.host_leg`` spans (the exact host
read and predicate re-evaluation of the counted blocks) per completed
query."""


def read(run):
    d = run.spans.get("scan.host_leg")
    done = sum(r.ok for r in run.records)
    return sum(d) * 1e3 / done if d and done else None
