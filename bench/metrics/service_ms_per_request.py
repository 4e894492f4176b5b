"""Host self time of the service (``svc/tick``, ``svc/admit``,
``svc/retire``: admission, retirement and the scheduler round around the
polls) per request retired in the traced window, in ms."""

from bench.program_spans import LAYERS, ms_per_request


def read(run):
    return ms_per_request(run, LAYERS["service"])
