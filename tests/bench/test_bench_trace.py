"""The trace reduction, on a trace recorded on one TPU v5 lite: 0.3 s of a
single-request gather of 16 keys over a 4,194,304 x 128 f32 table in 8
shards under the profiler (``run.py --trace 1``)."""

import dataclasses
from pathlib import Path

import pytest

from bench import spec, tracing
from bench.cells import Counters
from bench.run import RunData

TRACE = Path(__file__).parent / "data" / "gather-k16-c1.xplane.pb"


@pytest.fixture(scope="module")
def summary():
    return tracing.reduce_trace(str(TRACE))


def test_window_busy_and_idle(summary):
    assert summary.chips == 1
    assert summary.window_s == pytest.approx(0.303830125)
    assert summary.busy_s == pytest.approx(0.067340369)
    assert summary.idle_pct == pytest.approx(77.83617769962738)


def test_device_time_by_operation(summary):
    dev = summary.op_device_s
    kernel = sum(v for n, v in summary.device_ops if n.endswith("/embed_lookup"))
    assert dev["embed_lookup"] == pytest.approx(kernel)
    assert dev["embed_lookup"] == pytest.approx(0.065003256, rel=1e-3)
    assert max(dev.values()) <= summary.busy_s <= sum(dev.values())


def test_breakdown(summary):
    op, seconds = summary.device_ops[0]
    assert op == "jit_call/embed_lookup" and seconds == pytest.approx(0.065003256)
    assert len(summary.device_ops) == tracing.TOP
    gaps = dict(summary.idle_gaps)
    assert set(gaps) == {"bench/poll server", "bench/poll client"}
    assert sum(gaps.values()) == pytest.approx(summary.window_s - summary.busy_s)


def test_kernel_and_device_readers_on_the_trace(summary):
    cell = spec.load_cell("gather-uniform27-c1")
    cell = dataclasses.replace(cell, traffic=dict(cell.traffic, keys_per_request=16))
    run = RunData(cell=cell, peaks={"hbm_bytes_per_s": 819e9},
                  setup_s=0.0, window_s=summary.window_s, latencies_ms=[], retired=17,
                  counters=Counters(), trace=summary)
    assert spec.metric_reader("device_idle_pct.single")(run) == pytest.approx(77.836, abs=1e-3)
    ms = spec.metric_reader("embed_lookup_ms_per_request.single")(run)
    assert ms == pytest.approx(summary.op_device_s["embed_lookup"] * 1e3 / 17)
    assert 0 < spec.metric_reader("embed_lookup_roofline")(run) < 100


def test_a_trace_without_the_window_reads_nothing(tmp_path):
    assert tracing.find_trace(str(tmp_path)) is None
