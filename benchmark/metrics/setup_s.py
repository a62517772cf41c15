"""Set-up seconds: from the start of the run to the start of the window
(data, indexes, warm-up and any compile)."""


def read(run):
    return run.setup_s
