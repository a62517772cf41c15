"""Mean seconds a build spends in the program's ``build.sharded_plan``
spans (ops/build.py: the host bucket hash, the exchange capacity, the
pack plan and the H2D puts before the sharded program), summed over the
build's chunks."""


def read(run):
    d = run.spans.get("build.sharded_plan")
    builds = run.spans.get("build.index")
    return sum(d) / len(builds) if d and builds else None
