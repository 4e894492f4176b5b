"""Host self time of the execution layer (``pe/exec``, ``pe/decode``,
``pe/dispatch``, ``pe/actions``, ``pe/write_region``: payload decode, the
call of the executable, the action rows, the region copy) per request
retired in the traced window, in ms."""

from bench.program_spans import LAYERS, ms_per_request


def read(run):
    return ms_per_request(run, LAYERS["exec_host"])
