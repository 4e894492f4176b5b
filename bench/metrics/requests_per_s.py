"""Requests retired in the window per second of window (a gather request
or a whole chase)."""

from bench.stats import rate


def read(run):
    return rate(run.retired, run.window_s)
