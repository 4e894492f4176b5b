"""Payloads retired per XLA dispatch over all PEs in the window
(``PEStats.invoked_payloads`` / ``PEStats.invokes``)."""


def read(run):
    c = run.counters
    return c.invoked_payloads / c.invokes if c.invokes else None
