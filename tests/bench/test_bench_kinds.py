"""Kinds of service are modules found by a configuration's ``service``:
every configuration has one, an unknown service names the ones there
are, a kind written as a new file alone is built, measured and checked,
and each kind gets the cell's devices and refuses traffic it does not
drive."""

import json

import numpy as np
import pytest
from conftest import CELLS, DATA, PEAKS, ROOT, tiny

from bench import spec
from bench.cells import build_cell
from bench.check import check_cell, passed
from bench.kinds.embed_gather import GatherTraffic
from bench.run import measure, result_line
from bench.tracing import Spans

SERVICES = sorted(
    {spec.load_json(ROOT / c["file"])["service"]
     for c in json.loads((ROOT / "BENCHMARK.json").read_text())["configs"]}
    | {spec.load_json(DATA / "chase-config.json")["service"]})


@pytest.mark.parametrize("service", SERVICES)
def test_every_service_has_a_kind_module(service):
    assert spec.kind_path(service).parent == ROOT / "bench" / "kinds"
    kind = spec.load_kind(service)
    assert callable(kind.Cell) and callable(kind.check)


@pytest.mark.parametrize("service", ["no_such_service", "__init__"])
def test_an_unknown_service_names_the_kinds(service):
    with pytest.raises(KeyError, match="embed_gather.*pointer_chase"):
        spec.load_kind(service)


TOY = '''"""A kind for the tests: a request is one integer, answered with its
square on the cell's first device."""

import time

import numpy as np

from bench.cells import ClosedLoop, Counters, Retired, refuse_unless
from bench.traffic import POOL, rng_for

KIND = "square"
ARRIVALS = ("closed",)


class Cell(ClosedLoop):
    def __init__(self, config, traffic, seed, spans, devices):
        refuse_unless(traffic, KIND, ARRIVALS)
        super().__init__(int(traffic["concurrency"]), spans)
        self.devices = devices
        self.pool = rng_for(seed, 1).integers(0, config["limit"], POOL)
        self.setup_log = {}
        self._waiting = []

    def counters(self):
        return Counters(ticks=self.ticks)

    def in_flight(self):
        return len(self._waiting)

    def submit(self):
        self._waiting.append((self.next_index, time.perf_counter()))
        self.next_index += 1

    def warm_bursts(self):
        pass

    def step(self, resubmit=True):
        import jax

        self.ticks += 1
        waiting, self._waiting = self._waiting, []
        x = jax.device_put(np.array([self.pool[i % POOL] for i, _ in waiting]), self.devices[0])
        y = np.asarray(x * x)
        t = time.perf_counter()
        self.done += [Retired(i, int(v), t_submit, t) for (i, t_submit), v in zip(waiting, y)]
        if resubmit:
            for _ in waiting:
                self.submit()
        return len(waiting)

    def release(self):
        pass


def check(cell, records, missing, control=False):
    asked = np.array([cell.pool[r.index % POOL] for r in records], np.int64)
    got = asked * asked + 1 if control else np.array([r.answer for r in records], np.int64)
    wrong = int(np.sum(got != asked * asked))
    return {"squares_differing": (wrong, 0), "squares_missing": (missing, 0)}, wrong + missing
'''


@pytest.fixture
def toy_kinds(tmp_path, monkeypatch):
    """A directory of kinds that holds one new module, and the loader
    pointed at it."""
    (tmp_path / "toy_square.py").write_text(TOY)
    monkeypatch.setattr(spec, "KINDS_DIR", tmp_path)
    return tmp_path


def toy_cell(**traffic) -> spec.Cell:
    like = spec.load_cell(CELLS[0])
    return spec.Cell("toy-square-c4", 1, {"service": "toy_square", "limit": 1 << 15},
                     dict({"kind": "square", "arrival": "closed", "concurrency": 4}, **traffic),
                     like.end_to_end, [])


def test_a_kind_joins_with_a_new_file_alone(toy_kinds, compiles, cpu):
    cell = toy_cell()
    m = measure(cell, 2**31 + 3, 0.3, cpu, PEAKS, compiles)
    assert m.cell.devices is cpu  # measure's devices reach the kind's constructor
    result = result_line(m, cpu, traced=False)
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] == len(m.records) > 0 and m.missing == 0
    assert set(result["metrics"]) == {e["name"] for e in cell.end_to_end}
    assert result["checks"] == {"squares_differing": {"value": 0, "limit": 0},
                                "squares_missing": {"value": 0, "limit": 0}}
    control, failed = check_cell(m.cell, m.records, m.missing, control=True)
    assert not passed(control) and failed == len(m.records)


@pytest.mark.parametrize("traffic", [{"kind": "gather"}, {"arrival": "poisson"}])
def test_a_kind_refuses_traffic_it_does_not_drive(toy_kinds, traffic, cpu):
    cell = toy_cell(**traffic)
    with pytest.raises(ValueError, match="does not fit"):
        build_cell(cell.config, cell.traffic, 1, Spans(False), cpu)


@pytest.mark.parametrize("name", [CELLS[1], "chase-d64-c1"])
def test_the_cells_devices_reach_each_kind(name, cpu):
    cell = tiny(name)
    built = build_cell(cell.config, cell.traffic, 1, Spans(False), cpu)
    assert all(pe.device in cpu for pe in built.cluster.pes())
    with pytest.raises(ValueError, match="not on the cell's devices"):
        build_cell(cell.config, cell.traffic, 1, Spans(False), [])
    for traffic in (dict(cell.traffic, arrival="poisson"),
                    dict(cell.traffic, kind="gather" if "entries" in cell.config else "chase")):
        with pytest.raises(ValueError, match="does not fit"):
            build_cell(cell.config, traffic, 1, Spans(False), cpu)


def test_gather_bursts_warm_every_single_request_fold():
    """After the powers-of-two bursts, one request on each number of
    shards from 2 to one short of all, entering at server 0; the window's
    pool is untouched."""
    cell = spec.load_cell(CELLS[1])
    rows, servers = 8 * 1024, 8
    traffic = GatherTraffic(cell.traffic, rows, servers, 2**31 + 9)
    pool = traffic.pool.copy()
    bursts = traffic.bursts(1)
    shards = [sorted(set((b[0] // (rows // servers)).tolist())) for b in bursts[-(servers - 2):]]
    assert shards == [list(range(k)) for k in range(2, servers)]
    assert all(len(b) == 1 and b[0][0] // (rows // servers) == 0
               for b in bursts[-(servers - 2):])
    assert len(bursts) == servers + 1 + servers - 2
    assert np.array_equal(traffic.pool, pool)
    assert all(b.dtype == np.int32 and b.shape[1] == traffic.n_keys for b in bursts)
