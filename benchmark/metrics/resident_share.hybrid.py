"""Share of the window's hybrid queries served by the resident hybrid
path (``scan.path.resident_hybrid``); every query of a cell on an
appended configuration is a hybrid scan."""


def read(run):
    n = sum(r.ok for r in run.records)
    if not run.config.get("append") or not n:
        return None
    return 100.0 * run.counters.get("scan.path.resident_hybrid", 0) / n
