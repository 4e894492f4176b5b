"""Median latency, submit to the host seeing the retired result, over
every request retired in the window."""

from bench.stats import percentile


def read(run):
    return percentile(run.latencies_ms, 50)
