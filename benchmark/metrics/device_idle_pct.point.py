"""Share of the traced window in which no operation ran on the device:
1 - busy / window, busy the union of the device's op intervals."""


def read(run):
    p = run.profile
    if not p or p["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - p["busy_s"] / p["window_s"])
