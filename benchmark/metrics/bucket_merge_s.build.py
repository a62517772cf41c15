"""Mean seconds of the program's ``build.bucket_merge`` span per build:
inside ``build.finalize``, the final per-bucket read, merge and write of
the index files."""

import statistics


def read(run):
    d = run.spans.get("build.bucket_merge")
    return statistics.mean(d) if d else None
