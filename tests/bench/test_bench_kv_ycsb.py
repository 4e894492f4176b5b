"""The YCSB cells' kind at a tiny size on the CPU: every answer passes the
register check and the one-version-stale control fails it, the read-back
after the drain covers every updated record, a run with its write path
broken reads incorrect, and the kind refuses gather traffic.  Its copies
of YCSB's generators agree with the program's reference."""

import dataclasses

import numpy as np
import pytest
from conftest import PEAKS

from bench import spec
from bench.cells import build_cell
from bench.check import check_cell, passed
from bench.kinds import kv_ycsb
from bench.run import measure, result_line
from bench.tracing import Spans
from repro.core.pe.pe import PE
from repro.runtime import kv_reference

YCSB = ["ycsb-b-c64", "ycsb-a-c64"]


def tiny_ycsb(name: str) -> spec.Cell:
    """The cell on 4 servers of 256 records each, 8 requests in flight, PEs
    on the host CPU."""
    cell = spec.load_cell(name)
    config = dict(cell.config, records=4 * 256, n_servers=4, triple="cpu-bf2")
    return dataclasses.replace(cell, config=config, traffic=dict(cell.traffic, concurrency=8))


@pytest.fixture(scope="module", params=YCSB)
def measured(request, compiles, cpu):
    return measure(tiny_ycsb(request.param), 2**31 + 11, 0.5, cpu, PEAKS, compiles)


def test_cell_passes_the_check_and_its_control_fails(measured, cpu):
    m = measured
    result = result_line(m, cpu, traced=False)
    assert result["correct"] and result["failed"] == 0, result["checks"]
    assert set(result["checks"]) == {"stale_reads", "torn_fields", "lost_updates",
                                     "requests_missing"}
    assert m.run.retired > 0 and m.missing == 0 and m.run.counters.jit_ms == 0.0
    assert set(result["metrics"]) == {"requests_per_s", "latency_p95_ms", "setup_s"}
    control, failed = check_cell(m.cell, m.records, m.missing, control=True)
    assert not passed(control) and control["stale_reads"][0] > 0 and failed > 0


def test_the_read_back_covers_every_updated_record(measured):
    cell = measured.cell
    updated = {cell.traffic.op(r.index)[0] for r in cell.done if cell.traffic.op(r.index)[1] >= 0}
    assert updated and set(cell.readback) == updated
    # and it is the records as the servers hold them: a write of each lands there
    last = {}
    for r in cell.done:
        key, field = cell.traffic.op(r.index)
        if field >= 0:
            last[key, field] = r.index
    hits = sum(int(cell.readback[k].reshape(cell.fields, -1)[f][0]) == i
               for (k, f), i in last.items())
    assert hits >= 0.9 * len(last)


def unchanged_state(mp):
    mp.setattr(PE, "write_region", lambda self, name, value: None)


def dropped_acknowledged_write(mp):
    """Every update acknowledged, none kept: the shard's value is put
    on the device again from the host bytes."""
    inner = PE.write_region

    def write_region(self, name, value):
        if name != "embed_shard":
            inner(self, name, value)
        else:
            self._region_dev.pop(name, None)  # the old value, put again from the host

    mp.setattr(PE, "write_region", write_region)


@pytest.mark.parametrize("fault", [unchanged_state, dropped_acknowledged_write])
def test_a_broken_write_path_reads_incorrect(fault, compiles, cpu):
    with pytest.MonkeyPatch.context() as mp:
        m = measure(tiny_ycsb(YCSB[1]), 13, 0.3, cpu, PEAKS, compiles,
                    before_window=lambda cell: fault(mp))
    result = result_line(m, cpu, traced=False)
    assert not result["correct"] and result["failed"] > 0, result["checks"]


def test_the_kind_refuses_gather_traffic(cpu):
    cell = tiny_ycsb(YCSB[0])
    gather = spec.load_cell("gather-uniform27-c64").traffic
    for traffic in (gather, dict(cell.traffic, arrival="poisson")):
        with pytest.raises(ValueError, match="does not fit"):
            build_cell(cell.config, traffic, 1, Spans(False), cpu)
    with pytest.raises(ValueError, match="scrambled zipfian"):
        build_cell(cell.config, dict(cell.traffic, keys={"dist": "uniform"}), 1, Spans(False), cpu)


def test_the_kinds_generators_are_the_references():
    for seed in (1, 2**31 + 5):
        rng_a, rng_b = np.random.default_rng(seed), np.random.default_rng(seed)
        np.testing.assert_array_equal(kv_ycsb.scrambled_zipfian(rng_a, 4096, 1 << 23),
                                      kv_reference.scrambled_zipfian(rng_b, 4096, 1 << 23))
    vals = np.array([0, 7, 1 << 40, 10**10])
    np.testing.assert_array_equal(kv_ycsb.fnvhash64(vals), kv_reference.fnvhash64(vals))


def test_the_device_shards_are_the_reference_records(cpu):
    import jax

    make = kv_ycsb.shard_maker(64, 250, 25)
    with jax.default_device(cpu[0]):
        shard = np.asarray(make(np.uint32(128), np.uint32(0xDEADBEEF)))
    want = kv_ycsb.initial_records(np.arange(128, 192), 250, 25, 0xDEADBEEF)
    np.testing.assert_array_equal(shard, want)
    assert (want[:, ::25] < 0).all() and (kv_ycsb.update_values([0, 5], 25, 1)[:, 0] >= 0).all()
