"""Host time waiting on and copying between host and device (``pe/sync``:
each dispatch output to the host; ``pe/h2d``: a rewritten region put back
on the device) per request retired in the traced window, in ms."""

from bench.program_spans import LAYERS, ms_per_request


def read(run):
    return ms_per_request(run, LAYERS["sync"])
