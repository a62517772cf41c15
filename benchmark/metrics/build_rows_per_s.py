"""Source rows of the builds completed, over the time from the window's
start to the end of the last build."""


def read(run):
    if not run.builds or run.window_s <= 0:
        return None
    return sum(b[3] for b in run.builds) / run.window_s
