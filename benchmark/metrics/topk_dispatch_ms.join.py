"""Median of the program's ``scan.join_topk.dispatch`` span: one
filtered join-aggregate top-k on the mesh, from its operands through
the launch, the wait, the D2H of the chips' candidates and the host's
final choice."""

import statistics


def read(run):
    d = run.spans.get("scan.join_topk.dispatch")
    return statistics.median(d) * 1e3 if d else None
