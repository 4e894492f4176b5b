"""Share of the traced window in which no operation ran on the device:
1 - (union of device-op intervals) / window, in percent."""


def read(run):
    t = run.trace
    return None if t is None else t.idle_pct
