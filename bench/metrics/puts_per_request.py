"""Frames put on the fabric (``TrafficStats.puts``) per request retired in
the window."""


def read(run):
    return run.counters.puts / run.retired if run.retired else None
