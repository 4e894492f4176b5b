"""Shared set-up of the benchmark's tests: the checkout root on the path
(for ``bench``), and cells cut to a size the CPU runs in seconds.

Besides the cells of BENCHMARK.json the tests drive two pointer-chase
cells that no run of the benchmark measures (their configuration and
traffic are in ``data/``), so the harness's chase loop stays proven for
the cell a later change adds."""

import dataclasses
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import spec  # noqa: E402

CELLS = ["gather-uniform27-c64", "gather-uniform27-c1"]  # BENCHMARK.json's
# test-only chase cells: name -> (traffic in data/, the cell whose metrics they report)
CHASES = {"chase-d64-c256": ("d64-c256", CELLS[0]), "chase-d64-c1": ("d64-c1", CELLS[1])}
ALL_CELLS = CELLS + list(CHASES)
PEAKS = {"cpu": {"hbm_bytes_per_s": 819e9}}  # the tests' stand-in for a chip row


def load(name: str) -> spec.Cell:
    """A cell of BENCHMARK.json, or one of the test-only chase cells."""
    if name not in CHASES:
        return spec.load_cell(name)
    traffic, like = CHASES[name]
    metrics = spec.load_cell(like)
    return spec.Cell(
        name, 1, spec.load_json(DATA / "chase-config.json"),
        spec.load_json(DATA / f"{traffic}.json"), metrics.end_to_end,
        [m for m in metrics.per_layer if not m["name"].startswith("embed_lookup")],
    )


def tiny(name: str) -> spec.Cell:
    """The cell, shrunk: 4 servers, a few thousand rows or entries, narrow
    rows, at most 4 requests in flight, and PEs on the host CPU
    (``cpu-bf2``)."""
    cell = load(name)
    config = dict(cell.config, n_servers=4, triple="cpu-bf2")
    if "rows" in config:
        config.update(rows=4 * 256, dim=16)
    else:
        config.update(entries=4 * 1024)
    traffic = dict(cell.traffic, concurrency=min(cell.traffic["concurrency"], 4))
    return dataclasses.replace(cell, config=config, traffic=traffic)


@pytest.fixture(scope="module")
def compiles():
    from bench.run import CompileCounter

    return CompileCounter()


@pytest.fixture(scope="module")
def cpu():
    import jax

    return jax.devices("cpu")[:1]
