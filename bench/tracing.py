"""Host spans around the calls the harness makes, the profiler around the
window, and the reduction of its trace to device busy time, idle gaps by
what the host was doing, and device time per operation name.

Spans are ``jax.profiler.TraceAnnotation``s named ``bench/...``; when the
run is not traced they cost one ``nullcontext``.  Each PE instance's
``poll``, its two halves ``poll_begin`` and ``poll_complete`` (which
``EmbedShardService.tick`` calls for every PE in turn), and ``flush`` are
wrapped, so the idle gaps between device operations are named by the
call the host was in and the PE's role (``bench/poll_begin server``,
``bench/poll_complete client``, ``bench/poll server``, ...).  A kernel's
device time is keyed on its operation's name in the trace
(``embed_lookup``, the Pallas custom call), not on a span.
"""

from __future__ import annotations

import contextlib
import functools
import glob
import re
from dataclasses import dataclass

import numpy as np

WINDOW = "bench/window"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
DEVICE_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
TOP = 10

_NULL = contextlib.nullcontext()


class Spans:
    """``spans(name)`` is a host span when ``on``, else a no-op."""

    def __init__(self, on: bool) -> None:
        self.on = on
        if on:
            from jax.profiler import TraceAnnotation

            self._annotation = TraceAnnotation

    def __call__(self, name: str):
        return self._annotation(name) if self.on else _NULL

    def wrap(self, cluster) -> None:
        """Put a span around every PE's ``poll``, ``poll_begin``,
        ``poll_complete`` and ``flush``, named by the method and the PE's
        role (``server`` or ``client``)."""
        if not self.on:
            return
        for pe in cluster.pes():
            role = "client" if pe is cluster.client else "server"
            for method in ("poll", "poll_begin", "poll_complete", "flush"):
                inner = getattr(pe, method)
                setattr(pe, method, self._spanned(f"bench/{method} {role}", inner))

    def _spanned(self, name, inner):
        @functools.wraps(inner)
        def call(*args, **kwargs):
            with self._annotation(name):
                return inner(*args, **kwargs)

        return call


@contextlib.contextmanager
def profiled(log_dir: str):
    """The JAX profiler on for the block, host annotations and device
    activity only (no Python tracer)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def find_trace(log_dir: str) -> str | None:
    found = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))
    return found[-1] if found else None


@dataclass
class TraceSummary:
    window_s: float  # length of the bench/window span
    busy_s: float  # union of device-op intervals in the window, mean over chips
    op_device_s: dict  # operation name (``op_name``) -> union of its intervals
    device_ops: list  # [[op name, seconds]], most time first
    idle_gaps: list  # [[host span during the gap, seconds]], most time first
    chips: int

    @property
    def idle_pct(self) -> float:
        return 100.0 * (1.0 - self.busy_s / self.window_s)


def union(starts: np.ndarray, ends: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Merge intervals into disjoint ones, sorted by start."""
    if len(starts) == 0:
        return starts, ends
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    reach = np.maximum.accumulate(e)
    new = np.ones(len(s), bool)
    new[1:] = s[1:] > reach[:-1]
    idx = np.flatnonzero(new)
    return s[idx], np.append(reach[idx[1:] - 1], reach[-1])


def clip(starts, ends, lo, hi):
    s, e = np.clip(starts, lo, hi), np.clip(ends, lo, hi)
    keep = e > s
    return s[keep], e[keep]


def innermost(spans: list, t: np.ndarray) -> list:
    """For each time in ``t``, the name of the shortest span holding it
    (``"no span"`` where none does).  ``spans`` is [(name, start, end)]."""
    out = ["no span"] * len(t)
    best = np.full(len(t), np.inf)
    for name, s, e in spans:
        lo, hi = np.searchsorted(t, s, "left"), np.searchsorted(t, e, "right")
        if hi > lo:
            sel = np.arange(lo, hi)[best[lo:hi] > e - s]
            best[sel] = e - s
            for i in sel.tolist():
                out[i] = name
    return out


def op_name(hlo: str) -> str:
    """``%embed_lookup.6 = f32[16,128] custom-call(...)`` -> ``embed_lookup``."""
    head = hlo.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def module_names(modules: list, mids: np.ndarray) -> list:
    """The executable (``jit_mapped``, ...) running at each op midpoint."""
    if not modules:
        return ["?"] * len(mids)
    starts = np.array([m[1] for m in modules])
    ends = np.array([m[2] for m in modules])
    names = [m[0].split("(", 1)[0] for m in modules]
    k = np.searchsorted(starts, mids, "right") - 1
    return [names[j] if j >= 0 and mids[i] <= ends[j] else "?" for i, j in enumerate(k.tolist())]


def reduce_trace(path: str) -> TraceSummary | None:
    """Reduce one ``.xplane.pb``; ``None`` when it holds no window span or
    no device operation inside the window."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    spans, chips = [], []
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench/"):
                        spans.append((ev.name, ev.start_ns, ev.end_ns))
        elif DEVICE_PLANE.match(plane.name):
            ops, modules = [], []
            for line in plane.lines:
                if line.name == DEVICE_LINE:
                    ops = [(ev.name, ev.start_ns, ev.end_ns) for ev in line.events]
                elif line.name == MODULE_LINE:
                    modules = [(ev.name, ev.start_ns, ev.end_ns) for ev in line.events]
            chips.append((ops, sorted(modules, key=lambda m: m[1])))
    windows = [(s, e) for n, s, e in spans if n == WINDOW]
    if not windows:
        return None
    w0, w1 = windows[0]
    inner = sorted((s for s in spans if s[0] != WINDOW and s[2] > w0 and s[1] < w1),
                   key=lambda s: s[1])
    busy, op_dev, op_time, gap_time, used = 0.0, {}, {}, {}, 0
    for ops, modules in chips:
        if not ops:
            continue
        s = np.array([o[1] for o in ops], np.float64)
        e = np.array([o[2] for o in ops], np.float64)
        keep = (e > w0) & (s < w1)
        if not keep.any():
            continue
        used += 1
        s, e = s[keep], e[keep]
        bases = [op_name(o[0]) for o, k in zip(ops, keep) if k]
        names = [f"{mod}/{n}" for mod, n in zip(module_names(modules, (s + e) / 2), bases)]
        for n, d in zip(names, (np.minimum(e, w1) - np.maximum(s, w0)).tolist()):
            op_time[n] = op_time.get(n, 0.0) + d
        us, ue = union(*clip(s, e, w0, w1))
        busy += float(np.sum(ue - us))
        by_name: dict = {}
        for k, n in enumerate(bases):
            by_name.setdefault(n, []).append(k)
        for n, ks in by_name.items():
            a, b = union(*clip(s[ks], e[ks], w0, w1))
            op_dev[n] = op_dev.get(n, 0.0) + float(np.sum(b - a))
        # idle gaps, each named by the innermost host span at its midpoint
        gs = np.concatenate([[w0], ue])
        ge = np.concatenate([us, [w1]])
        ok = ge > gs
        gs, ge = gs[ok], ge[ok]
        for name, d in zip(innermost(inner, (gs + ge) / 2), (ge - gs).tolist()):
            gap_time[name] = gap_time.get(name, 0.0) + d
    if not used:
        return None

    def top(d: dict) -> list:
        return [[n, v / used / 1e9] for n, v in sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]

    return TraceSummary(
        window_s=(w1 - w0) / 1e9,
        busy_s=busy / used / 1e9,
        op_device_s={n: v / used / 1e9 for n, v in op_dev.items()},
        device_ops=top(op_time),
        idle_gaps=top(gap_time),
        chips=used,
    )
