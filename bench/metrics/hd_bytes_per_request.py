"""Bytes between host and device per request retired in the traced
window: regions put on the device, host arguments of dispatches and
dispatch outputs copied back (``PEStats.h2d_bytes + d2h_bytes``, which the
program's ``pe/h2d``, ``pe/dispatch`` and ``pe/sync`` spans carry)."""

from bench.program_spans import HD_SPANS, program_totals


def read(run):
    tot = program_totals(run)
    if not tot or not run.retired:
        return None
    return sum(tot[n][2] for n in HD_SPANS if n in tot) / run.retired
