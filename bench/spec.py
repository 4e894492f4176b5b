"""The benchmark's data: ``BENCHMARK.json`` at the checkout root, one JSON
file per configuration (``configs/``) and per traffic mix (``traffic/``),
the table of peaks, one reader module per metric (``metrics/``), and one
module per kind of service (``kinds/``).  Everything is found by the name
``BENCHMARK.json`` or a configuration gives it."""

from __future__ import annotations

import importlib.util
import json
import sys
from dataclasses import dataclass
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
KINDS_DIR = BENCH_DIR / "kinds"


def load_json(path: Path) -> dict:
    with open(path) as fp:
        return json.load(fp)


@dataclass(frozen=True)
class Cell:
    """One entry of ``workloads`` with its configuration and traffic mix."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list  # the BENCHMARK.json metric entries this cell reports
    per_layer: list


def _applies(metric: dict, cell: str, e2e_cells: dict) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    moves = metric.get("moves")
    return moves is None or cell in e2e_cells.get(moves, ())


def load_cell(name: str) -> Cell:
    """The cell ``name`` as ``BENCHMARK.json`` defines it; raises
    ``KeyError`` for a name it does not list."""
    bench = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(ROOT / configs[w["config"]]["file"])
    traffic = load_json(BENCH_DIR / "traffic" / f"{w['traffic']}.json")
    every = [c["name"] for c in bench["workloads"]]
    e2e_cells = {m["name"]: m.get("workloads", every) for m in bench["end_to_end"]}
    e2e = [m for m in bench["end_to_end"] if name in e2e_cells[m["name"]]]
    layer = [m for m in bench["per_layer"] if _applies(m, name, e2e_cells)]
    return Cell(name, int(w["chips"]), config, traffic, e2e, layer)


def load_peaks() -> dict:
    return load_json(BENCH_DIR / "peaks.json")


def metric_path(name: str) -> Path:
    """``metrics/<name>.py``, or, for a metric split by the end-to-end
    metric it moves (``ticks_per_request.load``), ``metrics/<name before
    the dot>.py`` where the split has no reader of its own."""
    path = BENCH_DIR / "metrics" / f"{name}.py"
    return path if path.is_file() else BENCH_DIR / "metrics" / f"{name.split('.', 1)[0]}.py"


def metric_reader(name: str):
    """The ``read(run) -> float | None`` function of :func:`metric_path`."""
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), metric_path(name)
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def kind_path(service: str) -> Path:
    """``kinds/<service>.py``: the module of a configuration's ``service``."""
    return KINDS_DIR / f"{service}.py"


def load_kind(service: str):
    """The kind module :func:`kind_path` names, loaded by path; raises
    ``KeyError`` naming the kinds there when it has none of ``service``."""
    path = kind_path(service)
    if service.startswith("_") or not path.is_file():
        kinds = sorted(p.stem for p in KINDS_DIR.glob("*.py") if not p.stem.startswith("_"))
        raise KeyError(f"no kind module for service {service!r} in {KINDS_DIR}: {kinds}")
    name = "bench_kind_" + service
    mod_spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    sys.modules[name] = mod  # check.check_cell finds ``check`` by the cell's class
    mod_spec.loader.exec_module(mod)
    return mod
