"""Mean seconds of the program's ``build.drain`` span per build: inside
``build.finalize``, the wait for the in-flight device merges, their D2H
and gather, and the spill writes."""

import statistics


def read(run):
    d = run.spans.get("build.drain")
    return statistics.mean(d) if d else None
