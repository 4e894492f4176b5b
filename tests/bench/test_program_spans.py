"""The program's spans in a traced run: their nesting and self times on
the CPU's profiler trace, the agreement of the program's own totals with
the trace, and the readers of the five host-time metrics and the
host-device bytes."""

import dataclasses
import sys

import pytest
from conftest import CELLS, DATA, PEAKS, tiny

from bench import program_spans, spec
from bench.cells import Counters
from bench.run import RunData, measure
from bench.tracing import find_trace, reduce_trace

READERS = ["service_ms_per_request", "progress_ms_per_request", "exec_host_ms_per_request",
           "sync_ms_per_request", "wire_ms_per_request", "hd_bytes_per_request"]


def test_self_time_is_duration_less_children():
    events = [("svc/tick", 0, 0, 100), ("pe/poll", 0, 10, 60), ("pe/exec", 0, 20, 50),
              ("pe/sync", 0, 40, 50), ("pe/poll", 0, 60, 90), ("pe/poll", 1, 5, 15)]
    nested = program_spans.nest(events)
    parents = {(n, p) for n, p, _, _ in nested}
    assert parents == {("svc/tick", None), ("pe/poll", "svc/tick"), ("pe/exec", "pe/poll"),
                       ("pe/sync", "pe/exec"), ("pe/poll", None)}
    assert program_spans.self_times(events) == {
        "svc/tick": (1, 20), "pe/poll": (3, 20 + 30 + 10), "pe/exec": (1, 20), "pe/sync": (1, 10)}


@pytest.fixture(scope="module")
def traced(compiles, cpu, tmp_path_factory):
    """A tiny loaded cell traced on the CPU, its trace kept, with the
    program's totals read right after the window."""
    from repro.core import spans

    trace_dir = tmp_path_factory.mktemp("trace")
    m = measure(tiny(CELLS[0]), 11, 0.5, cpu, PEAKS, compiles, traced=True,
                trace_dir=str(trace_dir))
    return m, spans.totals(), find_trace(str(trace_dir))


def test_spans_nest_under_the_profiler(traced):
    _, _, path = traced
    inside, chips, window = program_spans.read_trace(path)
    assert window is not None and chips == []  # the CPU has no device plane
    program = [ev for ev in inside if ev[0].startswith(program_spans.PREFIXES)]
    nested = program_spans.nest(program)
    pairs = {(n, p) for n, p, _, _ in nested}
    for pair in [("svc/tick", None), ("svc/admit", "svc/tick"), ("pe/poll", "svc/tick"),
                 ("pe/ingest", "pe/poll"), ("pe/exec", "pe/poll"), ("pe/dispatch", "pe/exec"),
                 ("pe/sync", "pe/exec"), ("pe/decode", "pe/exec"), ("pe/flush", "pe/poll"),
                 ("pe/flush", "svc/tick"), ("svc/retire", "svc/tick")]:
        assert pair in pairs, pair
    assert all(own >= 0 for _, _, _, own in nested)
    top = sum(d for _, p, d, _ in nested if p is None)
    assert sum(own for _, _, _, own in nested) == top  # the self times partition the ticks


def test_harness_spans_name_each_half_of_a_poll(traced):
    _, _, path = traced
    inside, _, _ = program_spans.read_trace(path)
    harness = {ev[0] for ev in inside if ev[0].startswith("bench/")}
    assert {f"bench/{half} {role}" for half in ("poll_begin", "poll_complete")
            for role in ("server", "client")} <= harness


def test_program_totals_agree_with_the_trace(traced):
    m, totals, path = traced
    red = program_spans.reduce(path)
    assert {n: c for n, (c, _, _) in totals.items()} == {n: c for n, (c, _) in red["spans"].items()}
    assert sum(t for _, t in red["spans"].values()) == pytest.approx(red["tick_s"])
    # the program's clock: its self times add up to the ticks the trace holds
    assert sum(t for _, t, _ in totals.values()) == pytest.approx(red["tick_s"], rel=0.02)
    assert 0 < red["tick_s"] <= red["window_s"]
    assert m.run.trace is None  # so the readers read nothing on the CPU


def run_with(trace, retired=10) -> RunData:
    return RunData(cell=spec.load_cell(CELLS[0]), peaks={}, setup_s=0.0, window_s=1.0,
                   latencies_ms=[], retired=retired, counters=Counters(), trace=trace)


def test_readers(traced, monkeypatch):
    _, totals, _ = traced
    from repro.core import spans

    monkeypatch.setattr(spans, "totals", lambda: totals)
    # any reduced trace stands for the chip's
    run = run_with(reduce_trace(str(DATA / "gather-k16-c1.xplane.pb")))
    values = {r: spec.metric_reader(f"{r}.load")(run) for r in READERS}
    assert all(v is not None and v >= 0 for v in values.values())
    host = sum(values[r] for r in READERS[:5]) + sum(
        totals[n][1] for n in program_spans.UNMETERED if n in totals) * 1e3 / run.retired
    assert host == pytest.approx(sum(t for _, t, _ in totals.values()) * 1e3 / run.retired)
    assert values["hd_bytes_per_request"] == sum(
        totals[n][2] for n in program_spans.HD_SPANS) / run.retired
    # read nothing: untraced, no request retired, or a program without spans
    for nothing in (run_with(None), dataclasses.replace(run, retired=0)):
        assert all(spec.metric_reader(r)(nothing) is None for r in READERS)
    import repro.core

    monkeypatch.delattr(repro.core, "spans")
    monkeypatch.setitem(sys.modules, "repro.core.spans", None)
    assert all(spec.metric_reader(r)(run) is None for r in READERS)


CHIP_TRACE = DATA / "gather-uniform27-c1-spans.xplane.pb"  # 0.2 s of the single
# cell on one TPU v5 lite, traced with the program's spans (run.py --trace 1)


def test_reduction_of_a_chip_trace():
    red = program_spans.reduce(str(CHIP_TRACE))
    assert red["window_s"] == pytest.approx(0.200073557)
    assert red["busy_s"] == pytest.approx(reduce_trace(str(CHIP_TRACE)).busy_s)
    assert red["busy_s"] == pytest.approx(0.002221777)
    counts = {n: c for n, (c, _) in red["spans"].items()}
    assert counts == {"pe/actions": 101, "pe/decode": 126, "pe/dispatch": 126, "pe/exec": 126,
                      "pe/flush": 151, "pe/h2d": 25, "pe/ingest": 225, "pe/poll": 225,
                      "pe/resolve": 226, "pe/sync": 126, "pe/write_region": 25,
                      "svc/admit": 25, "svc/retire": 25, "svc/tick": 25}
    assert red["spans"]["pe/sync"][1] == pytest.approx(0.10245314)
    assert red["spans"]["pe/dispatch"][1] == pytest.approx(0.0459852)
    assert sum(t for _, t in red["spans"].values()) == pytest.approx(red["tick_s"])
    assert red["tick_s"] == pytest.approx(0.199509245)
    # the device idles while the host waits in the dispatch outputs' syncs
    gap, seconds = red["idle_gaps"][0]
    assert gap == "pe/sync" and seconds == pytest.approx(0.196629702)
    assert sum(s for _, s in red["idle_gaps"]) == pytest.approx(red["window_s"] - red["busy_s"])
    assert red["idle_unattributed_share"] == pytest.approx(4.869301656e-05)
    # the device plane reads 0.13 ms early against the host's: moved by
    # that, every device operation lies between a dispatch and its sync
    assert red["device_in_dispatch_share"] == pytest.approx(0.608072277)
    assert red["device_offset_s"] == [pytest.approx(0.00013)]
    assert red["device_in_dispatch_share_aligned"] == pytest.approx(1.0)


def test_chip_trace_names_executables_after_their_ifunc():
    ops = {n.split("/")[0] for n, _ in reduce_trace(str(CHIP_TRACE)).device_ops}
    assert ops == {"jit_call_gatherer", "jit_folded_gather_return"}
