"""Programs built (compiled, or read from the persistent cache) inside the
measured window: the serve loop should build none."""


def read(run):
    return float(run.compiles_in_window)
