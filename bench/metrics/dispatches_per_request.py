"""XLA dispatches over all PEs (``PEStats.invokes``) per request retired
in the window."""


def read(run):
    return run.counters.invokes / run.retired if run.retired else None
