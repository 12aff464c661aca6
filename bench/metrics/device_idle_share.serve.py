"""Share of the traced window in which no operation ran on the chip."""


def read(run):
    if run.trace is None:
        return None
    busy, window = run.trace.busy_seconds(), run.trace.window_seconds()
    if window <= 0 or busy <= 0:
        return None
    return 100.0 * (1.0 - busy / window)
