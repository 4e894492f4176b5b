"""The two-phase poll: ``EmbedShardService.tick`` begins every PE's poll
(take arrivals, dispatch them) before it completes any (wait for the
outputs, apply them, flush), so the PEs' round trips to the device
overlap.  Within a PE nothing reorders; across PEs a frame emitted in one
tick is handled in the next.  These tests pin the results to the take
oracle under every data plane, show the dispatches in flight at the first
wait, and close the hazard the split opens: a one-sided write landing in a
region whose fold is still in flight."""

import numpy as np
import pytest

from repro.core import Cluster, DataPlaneConfig, ReliabilityConfig, make_tsi
from repro.runtime.embed_service import EmbedShardService

I32 = np.int32
SERVERS, ROWS, DIM, KEYS = 8, 64, 8, 27

PLANES = {
    "framed": DataPlaneConfig.framed(),
    "zerocopy": DataPlaneConfig.zero_copy(eager_max=0),
    "rendezvous": DataPlaneConfig.rendezvous(rndv_min=1),
    "reliable": None,  # framed, with reliability on
}


def make_service(max_slots=64) -> EmbedShardService:
    cl = Cluster(n_servers=SERVERS, wire="ideal")
    return EmbedShardService(cl, vocab=SERVERS * ROWS, dim=DIM, n_keys=KEYS,
                             max_slots=max_slots, seed=3)


def every_shard(seed: int = 0) -> np.ndarray:
    """27 keys touching all 8 shards, the first on server 0."""
    rng = np.random.default_rng(seed)
    owners = np.concatenate([np.arange(SERVERS), rng.integers(0, SERVERS, KEYS - SERVERS)])
    return (owners * ROWS + rng.integers(0, ROWS, KEYS)).astype(I32)


def bits(a) -> np.ndarray:
    return np.asarray(a, np.float32).view(np.uint32)


@pytest.mark.parametrize("plane", list(PLANES))
@pytest.mark.parametrize("concurrency", [1, 64])
def test_two_phase_ticks_give_the_take_rows(concurrency, plane):
    svc = make_service(max_slots=concurrency)
    if plane == "reliable":
        svc.cluster.set_reliability(ReliabilityConfig.on())
    rng = np.random.default_rng(concurrency)
    batches = [rng.integers(0, SERVERS * ROWS, rng.integers(1, KEYS + 1)).astype(I32)
               for _ in range(concurrency + 8)]
    rep = svc.gather(batches, batching=True, dataplane=PLANES[plane])
    for got, want in zip(rep.results, svc.oracle(batches), strict=True):
        np.testing.assert_array_equal(bits(got), bits(want))
    assert svc.inflight_at_wait > 0
    assert not any(pe.in_flight for pe in svc.cluster.pes())


def test_forward_tick_has_every_gatherer_in_flight():
    """One 27-key request over 8 servers: the entry server's tick, then a
    tick with the 7 FORWARD gatherers and the fold of the entry's RETURN in
    flight together, then the fold of the 7 RETURNs."""
    svc = make_service()
    keys = every_shard()
    svc.gather([keys], batching=True)  # code on every PE, regions on the device
    svc.cluster.set_batching(True)
    svc.batching = True
    waits0 = sum(pe.stats.overlapped_waits for pe in svc.cluster.pes())
    svc.submit(keys)
    per_tick = []
    while svc.queue or svc.active:
        before = svc.inflight_at_wait
        svc.tick()
        per_tick.append(svc.inflight_at_wait - before)
    assert per_tick == [1, SERVERS, 1]
    np.testing.assert_array_equal(bits(svc.finished[-1].rows), bits(svc.table[keys]))
    # each of the 7 gatherers waited while a later PE's dispatch was in flight
    assert sum(pe.stats.overlapped_waits for pe in svc.cluster.pes()) - waits0 == SERVERS - 1


def test_begin_leaves_a_dispatch_in_flight_and_poll_leaves_none():
    svc = make_service()
    cl = svc.cluster
    cl.set_batching(True)
    server = cl.servers[0]
    for _ in range(2):
        fut = cl.client.submit("server0", "gatherer", svc._pad(np.array([5], I32)),
                               svc.cq, expected=1)
        cl.client.flush()
        assert server.poll_begin() == 1
        assert server.in_flight == 1
        assert not cl.client.endpoint.inbox  # its RETURN waits for the completion
        server.poll_complete()
        assert server.in_flight == 0
        cl.run_until(fut.done)
        np.testing.assert_array_equal(bits(fut.result()[0]), bits(svc.table[5]))
    cl.client.submit("server0", "gatherer", svc._pad(np.array([6], I32)), svc.cq, expected=1)
    cl.client.flush()
    assert server.poll() == 1
    assert server.in_flight == 0


def test_one_sided_return_during_a_pending_fold_is_kept():
    """A framed RETURN's fold is in flight at the client when another
    server's zero-copy RETURN lands in the same CQ slab: the fold's result
    is already the slab's value, so the one-sided write pulls it and lands
    on top; the fold runs once and neither request's rows are lost."""
    svc = make_service()
    cl = svc.cluster
    svc.gather([np.array([1, ROWS + 1], I32)], batching=True)  # warm both servers
    cl.set_batching(True)
    s0, s1 = cl.servers[0], cl.servers[1]
    s0.dataplane = DataPlaneConfig.framed()
    s1.dataplane = DataPlaneConfig.zero_copy(eager_max=0)
    fa = cl.client.submit("server0", "gatherer", svc._pad(np.array([2, 3], I32)),
                          svc.cq, expected=2)
    fb = cl.client.submit("server1", "gatherer", svc._pad(np.array([ROWS + 4], I32)),
                          svc.cq, expected=1)
    cl.client.flush()
    s0.poll()  # the framed RETURN reaches the client
    invokes, pulls = cl.client.stats.invokes, cl.client.stats.region_pulls
    assert cl.client.poll_begin() == 1 and cl.client.in_flight == 1  # its fold in flight
    s1.poll()  # the one-sided RETURN writes B's row and doorbell into the slab
    assert cl.client.stats.zerocopy_returns == 0 and s1.stats.zerocopy_returns == 1
    assert cl.client.stats.region_pulls == pulls + 1  # the fold's result, before the write
    cl.client.poll_complete()
    assert cl.client.stats.invokes == invokes + 1
    assert fa.done() and fb.done()
    np.testing.assert_array_equal(bits(fa.result()[:2]), bits(svc.table[[2, 3]]))
    np.testing.assert_array_equal(bits(fb.result()[:1]), bits(svc.table[[ROWS + 4]]))


def test_a_bad_group_lets_healthy_groups_complete_and_flush_first():
    """One batched poll with two groups: a TSI group whose payload cannot
    decode fails at its dispatch, the gatherer group after it still
    completes and its RETURN is flushed, then the error is raised."""
    svc = make_service()
    cl = svc.cluster
    cl.toolchain.publish(make_tsi())
    server = cl.servers[0]
    server.register_region("counter", np.zeros(1, I32))
    svc.gather([np.array([7], I32)], batching=True)
    cl.client.send_ifunc("server0", "tsi", np.array([1, 2, 3], I32))  # tsi takes one word
    fut = cl.client.submit("server0", "gatherer", svc._pad(np.array([9], I32)),
                           svc.cq, expected=1)
    server.batching = True
    puts = cl.fabric.stats.puts
    with pytest.raises(ValueError):
        server.poll()
    assert server.in_flight == 0
    assert cl.fabric.stats.puts == puts + 1  # the gatherer's RETURN was flushed
    assert server.region("counter")[0] == 0
    cl.run_until(fut.done)
    np.testing.assert_array_equal(bits(fut.result()[0]), bits(svc.table[9]))
