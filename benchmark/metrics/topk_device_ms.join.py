"""Device milliseconds of the filtered join-aggregate top-k program
(``jit_mesh_join_topk``, in the trace's module line) per completed
query, on each chip: the program's device time summed over the chips'
planes, over the chips, over the queries completed in the window."""

import re

PROGRAM = re.compile(r"^jit_mesh_join_topk")


def read(run):
    p = run.profile
    done = sum(r.ok for r in run.records)
    if not p or not done:
        return None
    device_s = sum(s for name, s in p["module_s"].items() if PROGRAM.search(name))
    return device_s * 1e3 / run.cell["chips"] / done if device_s > 0 else None
