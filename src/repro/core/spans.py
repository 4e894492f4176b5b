"""Host spans inside the runtime, on the profiler's clock.

A span is a ``jax.profiler.TraceAnnotation``: it lands on the host plane
of the profiler's trace, on the clock of the device planes, so a
reduction can name what the host was doing while the device idled.  While
spans are on, each closed span also adds to per-name totals kept here
(count, self time: its duration less its child spans', and its ``bytes``
argument), which :func:`totals` returns for readers in this process.

Spans are on while a profiler session records host events, unless
:func:`enable` forces them on or off.  The outermost sites (a scheduler
tick, a poll, a flush) call :func:`follow`, which looks at the profiler
once; every other site reads :data:`enabled`, so with spans off a site
costs one flag read: no annotation, no arguments, no clock read::

    with spans.span("pe/exec", n=n) if spans.enabled else spans.NULL:

The runtime is driven from one thread, and the open spans form one stack.
"""

from __future__ import annotations

import contextlib
import time

from jax.profiler import TraceAnnotation

NULL = contextlib.nullcontext()
enabled = False  # read by every site
_forced: bool | None = None  # None: follow the profiler
_totals: dict[str, list[int]] = {}  # name -> [count, self ns, bytes]
_open: list["Span"] = []


def _set(on: bool) -> None:
    global enabled
    if on and not enabled:
        _totals.clear()  # the totals cover one session
    enabled = on


def enable(on: bool | None) -> None:
    """Force spans on (``True``) or off (``False``), or let them follow the
    profiler (``None``, the default)."""
    global _forced
    _forced = on
    _set(TraceAnnotation.is_enabled() if on is None else on)


def follow() -> bool:
    """At an outermost site: turn spans on while the profiler records host
    events (unless forced); returns :data:`enabled`."""
    if _forced is None:
        _set(TraceAnnotation.is_enabled())
    return enabled


def totals() -> dict[str, tuple[int, float, int]]:
    """Per span name, since spans last turned on: (count, self seconds,
    bytes)."""
    return {n: (c, ns / 1e9, b) for n, (c, ns, b) in _totals.items()}


class Span:
    """One span; ``set`` adds arguments known only once its work is done."""

    __slots__ = ("name", "nbytes", "note", "t0", "child_ns")

    def __init__(self, name: str, args: dict) -> None:
        self.name = name
        self.nbytes = args.get("bytes", 0)
        self.note = TraceAnnotation(name, **args)

    def set(self, **args) -> None:
        self.nbytes = args.get("bytes", self.nbytes)
        self.note.set_metadata(**args)

    def __enter__(self) -> "Span":
        self.note.__enter__()
        _open.append(self)
        self.child_ns = 0
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        ns = time.perf_counter_ns() - self.t0
        _open.pop()
        if _open:
            _open[-1].child_ns += ns
        tot = _totals.setdefault(self.name, [0, 0, 0])
        tot[0] += 1
        tot[1] += ns - self.child_ns
        tot[2] += self.nbytes
        self.note.__exit__(*exc)


def span(name: str, **args) -> Span:
    """A span named ``name`` (no ``#``), its arguments in ``args``."""
    return Span(name, args)
