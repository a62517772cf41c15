"""Median wait of a request in the server's queue: ticket
``started_at - submitted_at`` (serve/server.py)."""

import statistics


def read(run):
    waits = [r.ticket.wait_s for r in run.records if r.ok and r.ticket is not None and r.ticket.wait_s is not None]
    return statistics.median(waits) * 1e3 if waits else None
