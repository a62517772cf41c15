"""Device milliseconds of the resident count programs
(``jit_resident_counts*`` and ``jit_resident_hybrid_counts*``, in the
trace's module line) per completed query."""

import re

PROGRAMS = re.compile(r"^jit_resident_.*counts")


def read(run):
    p = run.profile
    done = sum(r.ok for r in run.records)
    if not p or not done:
        return None
    device_s = sum(s for name, s in p["module_s"].items() if PROGRAMS.search(name))
    return device_s * 1e3 / done if device_s > 0 else None
