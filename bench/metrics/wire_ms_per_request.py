"""Host self time of the wire layer's flush (``pe/flush``: coalescing and
putting queued frames and region writes) per request retired in the
traced window, in ms."""

from bench.program_spans import LAYERS, ms_per_request


def read(run):
    return ms_per_request(run, LAYERS["wire"])
