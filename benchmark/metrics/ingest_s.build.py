"""Mean seconds of the program's ``build.ingest_dispatch`` span per build
(index/stream_builder.py: decode, upload and device bucketize+sort)."""

import statistics


def read(run):
    d = run.spans.get("build.ingest_dispatch")
    return statistics.mean(d) if d else None
