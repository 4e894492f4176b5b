"""The arithmetic of the benchmark: percentiles and rates, interval
unions, the per-layer readers, and the shape of BENCHMARK.json."""

import dataclasses
import json
import re

import numpy as np
import pytest
from conftest import CELLS, ROOT

from bench import spec, stats, tracing
from bench.cells import Counters
from bench.run import RunData


@pytest.mark.parametrize(
    "values,q,want",
    [([5.0], 50, 5.0), ([1, 2, 3, 4], 50, 2), ([1, 2, 3, 4], 95, 4),
     (list(range(1, 101)), 95, 95), (list(range(100, 0, -1)), 50, 50),
     (list(range(1, 21)), 95, 19)],
)
def test_percentile_is_nearest_rank(values, q, want):
    assert stats.percentile(values, q) == want


def test_percentile_and_rate_of_nothing():
    assert stats.percentile([], 95) is None
    assert stats.rate(0, 10.0) is None
    assert stats.rate(5, 0.0) is None
    assert stats.rate(1500, 10.0) == 150.0
    with pytest.raises(ValueError):
        stats.percentile([1.0], 0)


def test_union_merges_overlap_and_nesting():
    s = np.array([5.0, 0.0, 1.0, 10.0, 12.0])
    e = np.array([6.0, 3.0, 2.0, 11.0, 13.0])
    us, ue = tracing.union(s, e)
    assert us.tolist() == [0.0, 5.0, 10.0, 12.0]
    assert ue.tolist() == [3.0, 6.0, 11.0, 13.0]
    cs, ce = tracing.clip(us, ue, 2.0, 10.5)
    assert float(np.sum(ce - cs)) == 1.0 + 1.0 + 0.5


def test_innermost_span_names_each_time():
    spans = [("bench/tick", 0, 100), ("bench/poll server", 10, 20), ("bench/poll client", 30, 40)]
    names = tracing.innermost(spans, np.array([5.0, 15.0, 35.0, 50.0, 150.0]))
    assert names == ["bench/tick", "bench/poll server", "bench/poll client", "bench/tick", "no span"]


def test_op_name_drops_the_hlo_signature():
    hlo = ('%embed_lookup.6 = f32[16,128]{1,0:T(8,128)S(1)} custom-call(s32[1]{0:T(128)} '
           '%bitcast.53), custom_call_target="tpu_custom_call"')
    assert tracing.op_name(hlo) == "embed_lookup"
    assert tracing.op_name("%while.2 = (s32[]) while(%tuple.50)") == "while"


def run_data(cell_name, **kw):
    base = dict(cell=spec.load_cell(cell_name), peaks={"hbm_bytes_per_s": 819e9}, setup_s=20.0,
                window_s=10.0, latencies_ms=[float(x) for x in range(1, 101)], retired=100,
                counters=Counters(ticks=50, invokes=400, invoked_payloads=2000, puts=300))
    base.update(kw)
    return RunData(**base)


def test_readers_of_the_end_to_end_metrics():
    run = run_data(CELLS[0])
    assert spec.metric_reader("requests_per_s")(run) == 10.0
    assert spec.metric_reader("latency_p50_ms")(run) == 50.0
    assert spec.metric_reader("latency_p95_ms")(run) == 95.0
    assert spec.metric_reader("setup_s")(run) == 20.0


def test_readers_of_the_counters():
    run = run_data(CELLS[0])
    assert spec.metric_reader("ticks_per_request.load")(run) == 0.5
    assert spec.metric_reader("ticks_per_request.single")(run) == 0.5
    assert spec.metric_reader("payloads_per_dispatch.load")(run) == 5.0
    assert spec.metric_reader("dispatches_per_request.single")(run) == 4.0
    assert spec.metric_reader("puts_per_request.load")(run) == 3.0
    empty = run_data(CELLS[0], retired=0, latencies_ms=[], counters=Counters())
    for name in ("ticks_per_request.load", "payloads_per_dispatch.load", "puts_per_request.load",
                 "dispatches_per_request.single", "requests_per_s", "latency_p95_ms"):
        assert spec.metric_reader(name)(empty) is None, name


def test_trace_readers_read_nothing_without_a_trace():
    run = run_data(CELLS[0])
    for name in ("embed_lookup_ms_per_request.load", "embed_lookup_roofline.load",
                 "device_idle_pct.load", "device_idle_pct.single"):
        assert spec.metric_reader(name)(run) is None, name


def test_embed_lookup_roofline_counts_the_rows_a_request_needs():
    summary = tracing.TraceSummary(window_s=1.0, busy_s=0.5,
                                   op_device_s={"embed_lookup": 0.4, "while": 0.45},
                                   device_ops=[], idle_gaps=[], chips=1)
    run = run_data(CELLS[0], trace=summary)
    need = 27 * (2 * 128 * 4 + 4)  # rows read and written, ids read
    assert spec.metric_reader("embed_lookup_ms_per_request.load")(run) == pytest.approx(4.0)
    assert spec.metric_reader("embed_lookup_roofline.load")(run) == pytest.approx(
        100 * 100 * need / 819e9 / 0.4)
    assert spec.metric_reader("device_idle_pct.load")(run) == pytest.approx(50.0)
    other = dataclasses.replace(summary, op_device_s={"gather_rows": 0.1})
    assert spec.metric_reader("embed_lookup_roofline.load")(run_data(CELLS[0], trace=other)) is None


def test_a_split_metric_reads_its_base_reader():
    assert spec.metric_path("ticks_per_request.single") == spec.metric_path("ticks_per_request")
    assert spec.metric_path("ticks_per_request.load").name == "ticks_per_request.py"
    assert spec.metric_path("requests_per_s").name == "requests_per_s.py"


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_benchmark_json_is_whole():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert [w["name"] for w in bench["workloads"]] == CELLS
    files = {c["name"]: c["file"] for c in bench["configs"]}
    for w in bench["workloads"]:
        assert (ROOT / files[w["config"]]).is_file()
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
        cell = spec.load_cell(w["name"])
        assert "setup_s" in [m["name"] for m in cell.end_to_end]
        assert len(cell.end_to_end) >= 2 and cell.per_layer
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]), m["name"]
        assert spec.metric_path(m["name"]).is_file(), m["name"]
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        moved = next(x for x in bench["end_to_end"] if x["name"] == m["moves"])
        assert set(m["workloads"]) <= set(moved.get("workloads", CELLS)), m["name"]
