"""Update results kept on the device as their region's value (the
``pe/write_region`` spans of a program that keeps them there, each with a
``region`` argument) per request retired in the traced window."""

from bench.program_spans import program_totals


def read(run):
    tot = program_totals(run)
    if not tot or not run.retired or "pe/write_region" not in tot:
        return None
    return tot["pe/write_region"][0] / run.retired
