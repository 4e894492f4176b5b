"""Scheduler rounds the harness drove (``EmbedShardService.tick``, or one
poll of every live PE) per request retired in the window."""


def read(run):
    return run.counters.ticks / run.retired if run.retired else None
