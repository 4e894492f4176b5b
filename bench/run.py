"""Run one cell of the benchmark once on the chip(s) JAX finds.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

In order: refuse unless JAX finds enough TPUs of a kind in ``peaks.json``;
turn on the persistent compile cache at ``.bench_jax_cache/`` in the
checkout; build the data from the seed and the system under test; warm up
until a pass of the cell's own traffic compiles nothing; measure a closed loop for
``--seconds`` (``--trace 1``: at most ``TRACE_CAP_S``, under the profiler);
drain what is in flight and compare every request retired since the
window opened against the plain reference; print one JSON line.

Standard error carries the window's compile count and, as its last
lines, each number compared with its limit.  Standard output's last line
is the result: ``correct``, ``attempted``, ``failed``, ``metrics``
(end-to-end with ``--trace 0``, per-layer with ``--trace 1``),
``device``, with ``--trace 1`` ``breakdown``, and ``checks`` last.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import spec  # noqa: E402
from bench.cells import Counters, build_cell  # noqa: E402
from bench.check import check_cell, passed  # noqa: E402
from bench.tracing import WINDOW, Spans, find_trace, profiled, reduce_trace  # noqa: E402

TRACE_CAP_S = 5.0  # a traced window measures at most this long
WARM_PASSES = 8  # closed-loop warm-up passes before giving up on a quiet one
CACHE_DIR = ROOT / ".bench_jax_cache"  # the benchmark's own, at a fixed path


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


@dataclass
class RunData:
    """What a metric reader (``metrics/<name>.py``) reads."""

    cell: spec.Cell
    peaks: dict  # the chip's row of peaks.json
    setup_s: float
    window_s: float
    latencies_ms: list  # every request retired in the window, submit -> seen
    retired: int
    counters: Counters  # window deltas
    trace: object = None  # tracing.TraceSummary of the window, traced runs


class CompileCounter:
    """JAX's lowerings, backend compiles and persistent-cache reads, as
    reported to ``jax.monitoring`` (the process's listeners stay)."""

    def __init__(self) -> None:
        import jax

        self.counts = collections.Counter()
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event: str, **_) -> None:
        if event.startswith("/jax/compilation_cache/"):
            self.counts[event.rsplit("/", 1)[-1]] += 1

    def _duration(self, event: str, duration: float, **_) -> None:
        if event.startswith("/jax/core/compile/"):
            self.counts[event.rsplit("/", 1)[-1]] += 1

    def snapshot(self) -> dict:
        return {
            "lowerings": self.counts["jaxpr_to_mlir_module_duration"],
            "backend_compiles": self.counts["backend_compile_duration"],
            "cache_hits": self.counts["cache_hits"],
            "cache_misses": self.counts["cache_misses"],
        }


def chip_devices(jax, chips: int, peaks: dict):
    """The devices the cell runs on, or ``None`` when JAX finds fewer than
    ``chips`` TPUs of a kind the table of peaks knows."""
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" or dev.device_kind not in peaks or len(devices) < chips:
        log(f"bench: needs {chips} TPU(s) of a kind in peaks.json {sorted(peaks)}; "
            f"JAX found {len(devices)} {dev.platform!r} device(s) of kind {dev.device_kind!r}")
        return None
    return devices[:chips]


def warm(cell, compiles: CompileCounter) -> int:
    """Every burst shape, then closed-loop passes of the cell's traffic
    until one compiles nothing; returns the passes run."""
    t = time.perf_counter()
    cell.warm_bursts()
    cell.setup_log["bursts_s"] = time.perf_counter() - t
    cell.fill()
    per_pass = 2 * cell.concurrency + 16
    for passes in range(1, WARM_PASSES + 1):
        before, jit0, n0 = compiles.snapshot(), cell.counters().jit_ms, len(cell.done)
        while len(cell.done) - n0 < per_pass:
            cell.step()
        if compiles.snapshot() == before and cell.counters().jit_ms == jit0:
            break
    return passes


def memory_peak(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", help="keep the profiler trace here (default: a "
                    "temporary directory, removed after it is read)")
    return ap.parse_args(argv)


@dataclass
class Measured:
    """A cell after its window and drain, with the system under test freed."""

    cell: object
    records: list  # Retired, every request retired from the window's start
    missing: int  # requests in flight that never retired
    run: RunData
    memory_peak_bytes: int | None


def start_chip(chips: int, peaks: dict):
    """The cell's devices with the compile cache on, or ``None`` (refused)."""
    t = time.perf_counter()
    import jax

    t_jax = time.perf_counter()
    devices = chip_devices(jax, chips, peaks)
    log(f"bench: start: harness imports {t - T_PROCESS:.3f} s, import jax "
        f"{t_jax - t:.3f} s, find the chips {time.perf_counter() - t_jax:.3f} s")
    if devices is not None:
        CACHE_DIR.mkdir(exist_ok=True)
        os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
        from repro.launch.compile_cache import enable_compile_cache

        enable_compile_cache()
    return devices


def measure(cell_spec: spec.Cell, seed: int, seconds: float, devices, peaks: dict,
            compiles: CompileCounter, traced: bool = False, trace_dir: str | None = None,
            before_window=None) -> Measured:
    """Build the cell's kind on ``devices``, warm up, measure ``seconds``
    (traced: at most ``TRACE_CAP_S`` under the profiler), drain, read the
    memory peak and free the system under test.  ``before_window(cell)``,
    for the tests, runs between warm-up and window."""
    spans = Spans(traced)
    t = time.perf_counter()
    cell = build_cell(cell_spec.config, cell_spec.traffic, seed, spans, devices)
    t_built = time.perf_counter()
    passes = warm(cell, compiles)
    log(f"bench: set-up {time.perf_counter() - T_PROCESS:.3f} s: start "
        f"{t - T_PROCESS:.3f} s, build {t_built - t:.3f} s, warm-up "
        f"{time.perf_counter() - t_built:.3f} s in {passes} closed pass(es); "
        f"{cell.setup_log}; compiles {compiles.snapshot()}, pe_jit_ms "
        f"{cell.counters().jit_ms:.1f}")
    if before_window is not None:
        before_window(cell)
    if traced:
        seconds = min(seconds, TRACE_CAP_S)
    own_dir = traced and trace_dir is None
    if own_dir:
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
    c0, k0, i0 = compiles.snapshot(), cell.counters(), len(cell.done)
    with profiled(trace_dir) if traced else contextlib.nullcontext():
        with spans(WINDOW):
            t0 = time.perf_counter()
            end = t0 + seconds
            while time.perf_counter() < end:
                cell.step()
            t1 = time.perf_counter()
    k1, c1, i1 = cell.counters(), compiles.snapshot(), len(cell.done)
    in_window = cell.done[i0:i1]
    window_compiles = {k: c1[k] - c0[k] for k in c1}
    done_at = [t0] + [r.t_done for r in in_window] + [t1]
    log(f"bench: window compiles {sum(window_compiles.values())} {window_compiles} "
        f"pe_jit_ms {k1.jit_ms - k0.jit_ms}; {len(in_window)} retired in {t1 - t0:.3f} s, "
        f"longest wait for a retirement {max(b - a for a, b in zip(done_at, done_at[1:])):.4f} s")
    cell.drain()
    missing = cell.in_flight()
    mem = memory_peak(devices)
    records = cell.done[i0:]
    cell.release()
    summary = None
    if traced:
        path = find_trace(trace_dir)
        summary = reduce_trace(path) if path else None
        if own_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    run = RunData(
        cell=cell_spec,
        peaks=peaks[devices[0].device_kind],
        setup_s=t0 - T_PROCESS,
        window_s=t1 - t0,
        latencies_ms=[(r.t_done - r.t_submit) * 1e3 for r in in_window],
        retired=len(in_window),
        counters=k1.minus(k0),
        trace=summary,
    )
    return Measured(cell, records, missing, run, mem)


def result_line(m: Measured, devices, traced: bool) -> dict:
    """The contract's result: metrics, device, breakdown, checks last."""
    checks, failed = check_cell(m.cell, m.records, m.missing)
    metrics = {}
    for entry in m.run.cell.per_layer if traced else m.run.cell.end_to_end:
        value = spec.metric_reader(entry["name"])(m.run)
        if value is not None:
            metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
              "memory_peak_bytes": m.memory_peak_bytes}
    result = {
        "correct": passed(checks) and len(m.records) > 0,
        "attempted": len(m.records) + m.missing,
        "failed": failed,
        "metrics": metrics,
        "device": device,
    }
    summary = m.run.trace
    if summary is not None:
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        result["breakdown"] = {"device_ops": summary.device_ops,
                               "idle_gaps": summary.idle_gaps}
    result["checks"] = {k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()}
    return result


def main(argv=None) -> int:
    args = parse(argv)
    cell_spec = spec.load_cell(args.workload)
    peaks = spec.load_peaks()
    devices = start_chip(cell_spec.chips, peaks)
    if devices is None:
        return 2
    traced = args.trace == 1
    m = measure(cell_spec, args.seed, args.seconds, devices, peaks, CompileCounter(),
                traced, args.trace_dir)
    result = result_line(m, devices, traced)
    for name, check in result["checks"].items():
        log(f"check {name} {check['value']} limit {check['limit']}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
