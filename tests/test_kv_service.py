"""The key-value shard service against the plain reference
(``repro.runtime.kv_reference``), update results kept on the device, and
YCSB's generators against their published constants."""

import numpy as np
import pytest

from repro.core import Cluster, spans
from repro.core.xrdma import make_kv_update
from repro.runtime.kv_reference import (
    FNV_OFFSET_BASIS_64,
    FNV_PRIME_64,
    ZETAN,
    KVStore,
    Op,
    fnvhash64,
    register_check,
    scrambled_zipfian,
)
from repro.runtime.kv_service import KVShardService

I32 = np.int32
SERVERS, ROWS, WORDS, FIELDS = 4, 16, 20, 4  # 64 records of 4 fields of 5 words
FW = WORDS // FIELDS


def make_table(seed: int) -> np.ndarray:
    t = np.random.default_rng(seed).integers(0, 1 << 31, (SERVERS * ROWS, WORDS)).astype(I32)
    t[:, ::FW] |= np.int32(-(1 << 31))  # a field's first word is negative as made
    return t


def make_service(table: np.ndarray, batching: bool, slots: int = 8) -> KVShardService:
    cl = Cluster(n_servers=SERVERS, wire="ideal")
    svc = KVShardService(cl, [table[i * ROWS : (i + 1) * ROWS].copy() for i in range(SERVERS)],
                         fields=FIELDS, max_slots=slots)
    cl.set_batching(batching)
    svc.batching = batching
    return svc


def value(i: int) -> np.ndarray:
    return np.array([i, *np.random.default_rng(i).integers(0, 1 << 30, FW - 1)], I32)


@pytest.mark.parametrize("batching", [False, True])
def test_one_at_a_time_matches_the_sequential_store(batching):
    table = make_table(1)
    svc, store = make_service(table, batching), KVStore(table, FIELDS)
    rng = np.random.default_rng(2)
    for i in range(40):
        key = int(rng.integers(0, len(table)))
        if rng.random() < 0.5:
            field = int(rng.integers(0, FIELDS))
            svc.submit_update(key, field, value(i))
            want = store.update(key, field, value(i))
        else:
            svc.submit_read(key)
            want = store.read(key)
        svc.run()
        got = svc.finished.pop().rows
        assert got.dtype == I32 and got.shape == (1, WORDS)
        np.testing.assert_array_equal(got[0], want)
    assert all(pe.stats.forwards == 0 for pe in svc.cluster.pes())  # each op entered at its owner


def run_history(svc, ops) -> list[Op]:
    """Submit ``ops`` ((key, field or -1), index = position) in one burst,
    run, and return them as retired ops on the tick clock."""
    rid_of = {}
    for i, (key, field) in enumerate(ops):
        rid = svc.submit_read(key) if field < 0 else svc.submit_update(key, field, value(i))
        rid_of[rid] = (i, key, field)
    svc.run()
    out = []
    for req in svc.finished:
        i, key, field = rid_of[req.rid]
        out.append(Op(i, key, field, req.t_submit, req.t_done, req.rows[0]))
    svc.finished.clear()
    return sorted(out, key=lambda op: op.index)


def values_of(indices):
    return np.stack([value(int(i)) for i in indices]) if len(indices) else np.zeros((0, FW), I32)


@pytest.mark.parametrize("batching", [False, True])
def test_reads_and_updates_of_one_key_in_one_poll(batching):
    """A read, an update of the same record, a read again, all in one
    burst: at the owner the reads form one dispatch before the update's,
    so both see the record as it was, and the acknowledgement carries the
    write; nothing is dispatched twice, and the register check holds."""
    table = make_table(3)
    svc = make_service(table, batching)
    server = svc.cluster.servers[1]
    invokes = server.stats.invokes
    hist = run_history(svc, [(ROWS + 2, -1), (ROWS + 2, 1), (ROWS + 2, -1)])
    want = table[ROWS + 2].copy()
    if batching:
        np.testing.assert_array_equal(hist[0].answer, want)
        np.testing.assert_array_equal(hist[2].answer, want)
        assert server.stats.invokes - invokes == 2
    want[FW : 2 * FW] = value(1)
    np.testing.assert_array_equal(hist[1].answer, want)
    got = register_check(hist, hist, lambda k: table[k], values_of, FIELDS,
                         readback={ROWS + 2: server.region_rows("embed_shard", [2])[0]})
    assert got == {"stale_reads": 0, "torn_fields": 0, "lost_updates": 0, "failed": 0}


@pytest.mark.parametrize("batching", [False, True])
def test_concurrent_updates_of_one_field(batching):
    """Three updates of one field of one record in flight together: each
    is acknowledged with its own write, the last applied is read back, and
    the register check accepts the history."""
    table = make_table(4)
    svc = make_service(table, batching)
    key = 3 * ROWS + 7
    hist = run_history(svc, [(key, 2), (key, 2), (key, 2), (key, -1)])
    for op in hist[:3]:
        np.testing.assert_array_equal(op.answer.reshape(FIELDS, FW)[2], value(op.index))
    back = svc.cluster.servers[3].region_rows("embed_shard", [7])[0]
    np.testing.assert_array_equal(back.reshape(FIELDS, FW)[2], value(2))
    got = register_check(hist, hist, lambda k: table[k], values_of, FIELDS, readback={key: back})
    assert got["failed"] == 0 and got["lost_updates"] == 0


def test_register_check_finds_stale_torn_and_lost():
    table = make_table(5)
    init = table[9]

    def rec(*fields):
        r = init.copy()
        for f, i in fields:
            r[f * FW : (f + 1) * FW] = value(i)
        return r

    w1 = Op(1, 9, 0, 0.0, 1.0, rec((0, 1)))
    w2 = Op(2, 9, 0, 2.0, 3.0, rec((0, 2)))  # submitted after w1's acknowledgement
    hist = [w1, w2]
    check = lambda ops, back=None: register_check(  # noqa: E731
        hist, ops, lambda k: table[k], values_of, FIELDS, readback=back)
    fresh = Op(5, 9, -1, 4.0, 5.0, rec((0, 2)))
    concurrent = Op(6, 9, -1, 2.5, 5.0, rec((0, 1)))  # w2 not yet acknowledged
    assert check([fresh, concurrent])["failed"] == 0
    stale = Op(7, 9, -1, 4.0, 5.0, rec((0, 1)))  # w2 superseded w1 before it
    initial = Op(8, 9, -1, 4.0, 5.0, init)
    future = Op(9, 9, -1, 0.0, 0.5, rec((0, 2)))  # w2 was submitted later
    assert check([stale, initial, future]) == {
        "stale_reads": 3, "torn_fields": 0, "lost_updates": 0, "failed": 3}
    torn = rec((0, 2))
    torn[FW - 1] ^= 1
    assert check([Op(10, 9, -1, 4.0, 5.0, torn)])["torn_fields"] == 1
    lost_ack = Op(2, 9, 0, 2.0, 3.0, rec((0, 1)))  # its acknowledgement lacks its write
    assert check([lost_ack])["lost_updates"] == 1
    assert check([], {9: rec((0, 1))})["lost_updates"] == 1  # read back superseded
    assert check([], {9: rec((0, 2))})["lost_updates"] == 0


def test_update_result_stays_on_the_device():
    """An update's result is its region's value on the device: no host
    copy until one is read, the next dispatch puts nothing on the device,
    and the region is donated to the write."""
    cl = Cluster(n_servers=1, wire="ideal")
    server = cl.servers[0]
    table = make_table(6)[:ROWS]
    server.register_region("embed_shard", table.copy())
    server.register_cap("gather_meta", np.array([0, ROWS, 1], I32))
    cl.toolchain.publish(make_kv_update(ROWS, FIELDS, FW, WORDS))
    server.execl.apply_actions = lambda exe, out: None  # no CQ here: drop the RETURN

    def update(key, field, i):
        cl.client.send_ifunc("server0", "kv_update",
                             np.concatenate([[0, 0, 0, key, field], value(i)]).astype(I32))
        cl.client.flush()
        server.poll()

    update(3, 1, 0)  # installs the code and puts the shard on the device
    before = server.region_device("embed_shard")
    h2d, pulls = server.stats.h2d_bytes, server.stats.region_pulls
    spans.enable(True)
    try:
        update(5, 2, 1)
    finally:
        spans.enable(None)
    tot = spans.totals()
    assert before.is_deleted()  # donated to the write
    assert "pe/h2d" not in tot and tot["pe/write_region"][0] == 1
    assert server.stats.h2d_bytes - h2d == (5 + FW + 3) * 4  # the payload and the meta cap
    assert server.stats.device_stores == 2 and server.stats.region_pulls == pulls
    assert "embed_shard" in server.endpoint.device_ahead
    want = table.copy()
    want[3, FW : 2 * FW] = value(0)
    want[5, 2 * FW : 3 * FW] = value(1)
    np.testing.assert_array_equal(server.region_rows("embed_shard", [5, 3]), want[[5, 3]])
    assert server.stats.region_pulls == pulls  # a take of rows, no pull
    np.testing.assert_array_equal(server.region("embed_shard"), want)
    assert server.stats.region_pulls == pulls + 1
    assert server.stats.region_pull_bytes == want.nbytes
    assert "embed_shard" not in server.endpoint.device_ahead


def test_a_device_region_is_pulled_once_and_a_host_write_lands_on_it():
    cl = Cluster(n_servers=1, wire="ideal")
    pe = cl.servers[0]
    import jax

    pe.register_region("r", jax.device_put(np.arange(8, dtype=I32), pe.device))
    assert "r" in pe.endpoint.device_ahead
    cl.fabric.put_region(cl.client.name, pe.name, "r", 4, np.int32(-1).tobytes())
    np.testing.assert_array_equal(pe.region("r"), [0, -1, 2, 3, 4, 5, 6, 7])
    assert pe.stats.region_pulls == 1
    np.testing.assert_array_equal(np.asarray(pe.region_device("r")), [0, -1, 2, 3, 4, 5, 6, 7])


def java_fnvhash64(val: int) -> int:
    """``Utils.fnvhash64`` line by line, on Python's integers (Java's long
    arithmetic: products wrap at 64 bits)."""
    h = FNV_OFFSET_BASIS_64
    for _ in range(8):
        h ^= val & 0xFF
        val >>= 8
        h = (h * FNV_PRIME_64) % (1 << 64)
    signed = h - (1 << 64) if h >= 1 << 63 else h
    return abs(signed)


def test_fnvhash64_and_the_scrambled_zipfian_as_published():
    vals = [0, 1, 2, 255, 256, 123456789, 9_999_999_999, 10_000_000_000, (1 << 62) + 12345]
    assert fnvhash64(vals).tolist() == [java_fnvhash64(v) for v in vals]
    records = 1 << 23
    keys = scrambled_zipfian(np.random.default_rng(7), 1 << 20, records)
    assert keys.min() >= 0 and keys.max() < records
    # the hottest item (zipfian item 0) draws 1/ZETAN of the operations, the
    # next 0.5**0.99/ZETAN; FNV spreads them over the keys
    share = np.mean(keys == fnvhash64([0])[0] % (records + 1))
    assert share == pytest.approx(1 / ZETAN, rel=0.03)
    second = np.mean(keys == fnvhash64([1])[0] % (records + 1))
    assert second == pytest.approx(0.5**0.99 / ZETAN, rel=0.05)
