"""Each cell's closed loop at a tiny size on the CPU: it retires requests,
compiles nothing in its window, and every answer matches the reference.
And a run refuses to measure without a TPU."""

import json
import shutil
import subprocess
import sys

import pytest
from conftest import ALL_CELLS, CELLS, PEAKS, ROOT, tiny

from bench import spec
from bench.check import check_cell, passed
from bench.run import main, measure, result_line


@pytest.mark.parametrize("name", ALL_CELLS)
def test_cell_retires_correct_answers(name, compiles, cpu):
    cell = tiny(name)
    m = measure(cell, 2**31 + 7, 0.5, cpu, PEAKS, compiles)
    result = result_line(m, cpu, traced=False)
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] == len(m.records) > 0
    assert m.run.retired > 0 and m.missing == 0
    assert m.run.counters.jit_ms == 0.0  # nothing compiled in the window
    assert set(result["metrics"]) == {e["name"] for e in cell.end_to_end}
    assert list(result)[-1] == "checks"
    assert all(c["value"] == 0 and c["limit"] == 0 for c in result["checks"].values())
    # the control (the reference one step below the guarantee) fails
    control, failed = check_cell(m.cell, m.records, m.missing, control=True)
    assert not passed(control) and failed > 0


def test_same_seed_same_requests(compiles, cpu):
    runs = [measure(tiny("chase-d64-c1"), 5, 0.3, cpu, PEAKS, compiles) for _ in range(2)]
    starts = [[int(r.cell.traffic.request(x.index)) for x in r.records[:5]] for r in runs]
    assert starts[0] == starts[1]
    other = measure(tiny("chase-d64-c1"), 6, 0.3, cpu, PEAKS, compiles)
    assert [int(other.cell.traffic.request(x.index)) for x in other.records[:5]] != starts[0]


def test_traced_run_reports_the_counters(compiles, cpu, tmp_path):
    cell = tiny(CELLS[0])
    m = measure(cell, 3, 0.5, cpu, PEAKS, compiles, traced=True, trace_dir=str(tmp_path))
    result = result_line(m, cpu, traced=True)
    assert result["correct"]
    # the CPU has no TPU plane: only the counter metrics are read
    assert set(result["metrics"]) == {
        "ticks_per_request.load", "payloads_per_dispatch.load", "puts_per_request.load"}
    assert m.run.trace is None and "breakdown" not in result


def test_refuses_without_a_tpu(capsys):
    assert main(["--workload", CELLS[1], "--seed", "1", "--seconds", "1"]) == 2
    out = capsys.readouterr()
    assert out.out == "" and "needs 1 TPU" in out.err


def test_unknown_workload_is_an_error():
    with pytest.raises(KeyError):
        spec.load_cell("no-such-cell")


def test_refuses_in_a_bare_checkout(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's paths
    prints no result and exits non-zero."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for path in bench["paths"]:
        shutil.copytree(ROOT / path, tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, *bench["command"][1:], "--workload", CELLS[1],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120,
        env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu", "HOME": str(tmp_path)},
    )
    assert proc.returncode != 0 and proc.stdout == ""
