"""Share of the all_to_all's exchange slots that carry a row in the
window: 100 x ``build.sharded.rows`` / ``build.sharded.slots`` (each
chunk sends ``D x D x cap`` slots, ``cap`` a power of two)."""


def read(run):
    slots = run.counters.get("build.sharded.slots", 0)
    return 100.0 * run.counters.get("build.sharded.rows", 0) / slots if slots else None
