"""Mean seconds a build spends in the program's ``build.sharded_exchange``
spans (ops/build.py: the sharded bucketize, ICI all_to_all and per-chip
sort, the D2H of the sorted row ids, and the host's take of each
device's rows from the chunk), summed over the build's chunks."""


def read(run):
    d = run.spans.get("build.sharded_exchange")
    builds = run.spans.get("build.index")
    return sum(d) / len(builds) if d and builds else None
