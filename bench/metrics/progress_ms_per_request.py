"""Host self time of the progress engine (``pe/poll``, ``pe/ingest``:
inbox drain, lanes, credit return, frame routing) per request retired in
the traced window, in ms."""

from bench.program_spans import LAYERS, ms_per_request


def read(run):
    return ms_per_request(run, LAYERS["progress"])
