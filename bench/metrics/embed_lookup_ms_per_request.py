"""Device time of the ``embed_lookup`` kernel per request retired in the
traced window: the union of the device operations the trace names
``embed_lookup`` (the Pallas custom call of the gatherer's TPU slice).
A gather path without that kernel reads nothing."""

KERNEL = "embed_lookup"


def read(run):
    t = run.trace
    if t is None or not run.retired or t.op_device_s.get(KERNEL, 0.0) <= 0:
        return None
    return t.op_device_s[KERNEL] * 1e3 / run.retired
