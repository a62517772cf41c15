"""Mean seconds of the program's ``build.finalize`` span per build (the
per-bucket merge and write of the index files)."""

import statistics


def read(run):
    d = run.spans.get("build.finalize")
    return statistics.mean(d) if d else None
