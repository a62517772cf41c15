"""Bytes the filtered join-aggregate top-k brought home per completed
query: the window's move of the ``scan.join_topk.d2h_bytes`` counter
over the queries completed in it."""


def read(run):
    done = sum(r.ok for r in run.records)
    moved = run.counters.get("scan.join_topk.d2h_bytes", 0)
    return moved / done if done and moved else None
