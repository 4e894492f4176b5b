"""Regions' bytes put on the device (``pe/h2d`` spans: a whole region on
a host rewrite, or a block of recycled completion-queue slots reset) per
request retired in the traced window."""

from bench.program_spans import program_totals


def read(run):
    tot = program_totals(run)
    if not tot or not run.retired:
        return None
    return tot.get("pe/h2d", (0, 0.0, 0))[0] / run.retired
