"""The program's own spans (``repro.core.spans``) in a traced run.

Per span name: its count and its self time, the span's duration less the
part of it its child spans cover; and the bytes that cross between host
and device, which the spans of dispatches, region puts and syncs carry.
Five layers of host time are read from the self times, each per request
retired (``bench/metrics/*_ms_per_request.py``).

Two sources give these numbers.  The metric readers take the program's
totals, kept in its process over the profiler session, because the
harness removes the trace once it is reduced.  :func:`reduce` takes a
trace kept with ``run.py --trace-dir`` and computes the same self times
from its host plane inside ``bench/window``; it also names each idle gap
of the device by the innermost span of either kind (``bench/`` or the
program's), and measures how much device time lies between a dispatch
and the end of its sync: as recorded, and with the device plane moved by
the offset (``device_offset_s``, added to its times) that puts the most
there, which estimates how far the profiler's device clock sits from the
host's::

    python3 bench/program_spans.py <trace.xplane.pb>

A trace with no device plane (the CPU's) gives the self times alone.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

import numpy as np

if __name__ == "__main__":
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench.tracing import DEVICE_LINE, DEVICE_PLANE, WINDOW, clip, innermost, union  # noqa: E402

LAYERS = {  # layer -> the spans whose self time is its host time
    "service": ("svc/tick", "svc/admit", "svc/retire"),
    "progress": ("pe/poll", "pe/ingest"),
    "exec_host": ("pe/exec", "pe/decode", "pe/dispatch", "pe/actions", "pe/write_region"),
    "sync": ("pe/sync", "pe/h2d"),
    "wire": ("pe/flush",),
}
UNMETERED = ("pe/resolve", "pe/compile")  # in no layer: near 0 in a window
PREFIXES = ("svc/", "pe/")  # the program's span names
HD_SPANS = ("pe/dispatch", "pe/h2d", "pe/sync")  # their ``bytes`` cross
SHIFTS = np.arange(-3e6, 3e6 + 1, 1e4)  # device-plane offsets tried, ns


def program_totals(run) -> dict | None:
    """The program's span totals over the traced window, ``{name: (count,
    self seconds, bytes)}``; ``None`` in a run whose trace did not reduce
    (no device plane), or for a program that keeps no spans."""
    if run.trace is None:
        return None
    try:
        from repro.core import spans
    except ImportError:
        return None
    return spans.totals() or None


def ms_per_request(run, names) -> float | None:
    """Self time of the spans ``names`` per request retired, in ms."""
    tot = program_totals(run)
    if not tot or not run.retired or not any(n in tot for n in names):
        return None
    return 1e3 * sum(tot[n][1] for n in names if n in tot) / run.retired


def nest(events: list) -> list:
    """``events`` are ``(name, thread, start, end)``; returns for each, by
    thread and start, ``(name, parent, duration, self)``: the parent is the
    innermost event of the same thread holding it (``None`` at the top)."""
    out, stacks = [], {}
    for name, thread, s, e in sorted(events, key=lambda ev: (ev[1], ev[2], -ev[3])):
        stack = stacks.setdefault(thread, [])
        while stack and stack[-1][1] <= s:
            stack.pop()
        rec = [name, stack[-1][0][0] if stack else None, e - s, e - s]
        if stack:
            stack[-1][0][3] -= e - s
        stack.append((rec, e))
        out.append(rec)
    return [tuple(r) for r in out]


def self_times(events: list) -> dict:
    """``{name: (count, self)}`` over ``events`` (see :func:`nest`)."""
    tot: dict = {}
    for name, _, _, own in nest(events):
        c, t = tot.get(name, (0, 0))
        tot[name] = (c + 1, t + own)
    return tot


def _below(b: tuple, t: np.ndarray) -> np.ndarray:
    """For each time in ``t``, the length of the union ``b`` (disjoint
    sorted intervals ``(starts, ends)``) that lies before it."""
    bs, be = b
    if not len(bs):
        return np.zeros(len(t))
    before = np.concatenate([[0.0], np.cumsum(be - bs)])
    i = np.searchsorted(bs, t, "right")  # intervals starting at or before t
    j = np.maximum(i - 1, 0)
    part = np.clip(t - bs[j], 0.0, be[j] - bs[j])
    return np.where(i > 0, before[j] + part, 0.0)


def _overlap(a: tuple, b: tuple, shift: float = 0.0) -> float:
    """Length of the intersection of two unions of disjoint sorted
    intervals, ``a`` moved by ``shift``."""
    return float(np.sum(_below(b, a[1] + shift) - _below(b, a[0] + shift)))


def read_trace(path: str) -> tuple[list, list, tuple | None]:
    """From one ``.xplane.pb``: the host events ``(name, thread, start,
    end)`` that start inside ``bench/window`` (names cut at ``#``), each
    device's operations as ``(starts, ends)``, and the window (``None``
    without one)."""
    from jax.profiler import ProfileData

    host, ops = [], []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                host += [(ev.name.split("#", 1)[0], (plane.name, i), ev.start_ns, ev.end_ns)
                         for ev in line.events]
        elif DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                evs = list(line.events) if line.name == DEVICE_LINE else []
                if evs:
                    ops.append(([ev.start_ns for ev in evs], [ev.end_ns for ev in evs]))
    windows = [(s, e) for n, _, s, e in host if n == WINDOW]
    if not windows:
        return [], ops, None
    w0, w1 = windows[0]
    return [ev for ev in host if w0 <= ev[2] < w1 and ev[0] != WINDOW], ops, (w0, w1)


def reduce(path: str) -> dict | None:
    """One ``.xplane.pb`` reduced; ``None`` without a ``bench/window``."""
    inside, chips, window = read_trace(path)
    if window is None:
        return None
    w0, w1 = window
    program = [ev for ev in inside if ev[0].startswith(PREFIXES)]
    tot = self_times(program)
    out = {
        "window_s": (w1 - w0) / 1e9,
        "spans": {n: [c, t / 1e9] for n, (c, t) in sorted(tot.items())},
        "tick_s": sum(e - s for n, _, s, e in program if n == "svc/tick") / 1e9,
    }
    if not chips:
        return out
    named = [(n, s, e) for n, _, s, e in inside if n.startswith("bench/") or n in tot]
    cover = _dispatch_to_sync(program)
    gaps, busy, covered, aligned, offsets = {}, 0.0, 0.0, 0.0, []
    for starts, ends in chips:
        us, ue = union(*clip(np.array(starts, float), np.array(ends, float), w0, w1))
        busy += float(np.sum(ue - us))
        covered += _overlap((us, ue), cover)
        # the profiler aligns each device plane to the host's clock up to an
        # offset: the shift that puts the most device time inside the
        # dispatch-to-sync intervals estimates it
        shift = max(SHIFTS, key=lambda d: _overlap((us, ue), cover, d))
        offsets.append(shift / 1e9)
        aligned += _overlap((us, ue), cover, shift)
        gs, ge = np.concatenate([[w0], ue]), np.concatenate([us, [w1]])
        ok = ge > gs
        gs, ge = gs[ok], ge[ok]
        for name, d in zip(innermost(named, (gs + ge) / 2), (ge - gs).tolist()):
            gaps[name] = gaps.get(name, 0.0) + d / 1e9
    idle = sum(gaps.values())
    bare = sum(v for n, v in gaps.items() if n not in tot)
    out.update(
        busy_s=busy / len(chips) / 1e9,
        idle_gaps=sorted(([n, v / len(chips)] for n, v in gaps.items()), key=lambda g: -g[1]),
        idle_unattributed_share=bare / idle if idle else 0.0,
        device_in_dispatch_share=covered / busy if busy else None,
        device_offset_s=offsets,
        device_in_dispatch_share_aligned=aligned / busy if busy else None,
    )
    return out


def _dispatch_to_sync(program: list) -> tuple:
    """The union of the intervals from each ``pe/dispatch`` start to the
    end of the next ``pe/sync`` to start on its thread."""
    spans_s, spans_e = [], []
    by_thread: dict = {}
    for name, thread, s, e in sorted(program, key=lambda ev: ev[2]):
        if name in ("pe/dispatch", "pe/sync"):
            by_thread.setdefault(thread, []).append((name, s, e))
    for evs in by_thread.values():
        start = None
        for name, s, e in evs:
            if name == "pe/dispatch" and start is None:
                start = s
            elif name == "pe/sync" and start is not None:
                spans_s.append(start)
                spans_e.append(e)
                start = None
    return union(np.array(spans_s, float), np.array(spans_e, float))


if __name__ == "__main__":
    print(json.dumps(reduce(sys.argv[1])))
