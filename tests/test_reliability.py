"""The reliability layer: exactly-once delivery and attributed failure.

PR 6's contract strengthens PR 1's containment ("a lost frame loses only
itself, detected by idleness") to *recovery*: with
``ReliabilityConfig.on()`` installed, any drop/duplicate/reorder/kill
schedule either completes exactly once — retransmit timers re-drive lost
frames, the receive-side seq gate drops duplicates and re-orders
out-of-order arrivals — or fails loudly with the failure attributed to a
named peer (suspect -> dead escalation, partial results carrying a
validity mask).

The injection points are the same ones tests/test_fault_injection.py
drives (the endpoint inbox, ``Fabric.kill``) plus the new seeded Bernoulli
loss hook ``Fabric.set_loss`` the chaos suite shares.
"""

import numpy as np
import pytest

from repro.core import (
    Cluster,
    DataPlaneConfig,
    Frame,
    FrameKind,
    ReliabilityConfig,
    make_tsi,
    peek_header,
)
from repro.runtime.embed_service import EmbedShardService, ragged_batches

I32 = np.int32


def rel_pair(**kwargs):
    """Two PEs on one fabric with reliability installed (the tsi_pair of
    this suite)."""
    from repro.core.ifunc import PE, Toolchain
    from repro.core.transport import Fabric

    fabric = Fabric("ideal")
    tc = Toolchain()
    names = ["server0", "client"]
    server = PE("server0", fabric, triple="cpu-bf2", toolchain=tc, peers=names)
    client = PE("client", fabric, triple="cpu-host", toolchain=tc, peers=names)
    cfg = ReliabilityConfig.on(**kwargs)
    server.reliability = cfg
    client.reliability = cfg
    server.register_region("counter", np.zeros(1, I32))
    client.register_source(make_tsi())
    return fabric, client, server


def drive(client, server, rounds):
    n = 0
    for _ in range(rounds):
        n += client.poll() + server.poll()
    return n


class TestConfig:
    def test_default_is_disabled(self):
        cfg = ReliabilityConfig()
        assert not cfg.enabled

    def test_on_enables(self):
        assert ReliabilityConfig.on().enabled
        assert ReliabilityConfig.on(rto_ticks=7).rto_ticks == 7

    def test_backoff_schedule(self):
        cfg = ReliabilityConfig.on(rto_ticks=4, backoff=2.0)
        assert [cfg.rto_after(i) for i in range(4)] == [4, 8, 16, 32]

    def test_recovery_horizon_covers_full_budget(self):
        cfg = ReliabilityConfig.on()
        assert cfg.recovery_horizon() >= sum(
            cfg.rto_after(i) for i in range(cfg.retransmit_budget)
        )
        assert cfg.idle_grace() > cfg.recovery_horizon()


class TestWireFormat:
    def test_seq_and_ack_share_the_header_word(self):
        f = Frame(kind=FrameKind.BITCODE, name="x", payload=b"p",
                  seq=0x1234, ack=0xBEEF)
        hdr = peek_header(f.pack())
        assert hdr.seq == 0x1234 and hdr.ack == 0xBEEF

    def test_piggybacked_ack_costs_zero_wire_bytes(self):
        a = Frame(kind=FrameKind.BITCODE, name="x", payload=b"p")
        b = Frame(kind=FrameKind.BITCODE, name="x", payload=b"p",
                  seq=9, ack=1 << 31)
        assert len(a.pack()) == len(b.pack())

    def test_ack_frame_is_header_only(self):
        f = Frame(kind=FrameKind.ACK, name="", payload=b"", ack=17)
        wire = f.wire_bytes(cached=True)
        assert peek_header(wire).ack == 17
        assert len(wire) <= 80  # a bare header, no payload/code sections


class TestRetransmit:
    def test_lost_frame_is_retransmitted_and_completes(self):
        fabric, client, server = rel_pair(rto_ticks=2)
        client.send_ifunc("server0", "tsi", np.array([5], I32))
        server.endpoint.inbox.clear()  # the wire ate it
        assert client.wire.unacked_frames("server0") == 1
        drive(client, server, 40)
        assert server.region("counter")[0] == 5
        assert client.stats.retransmits >= 1
        assert client.wire.unacked_frames("server0") == 0

    def test_retransmit_backoff_is_exponential(self):
        fabric, client, server = rel_pair(rto_ticks=2, backoff=2.0)
        client.send_ifunc("server0", "tsi", np.array([1], I32))
        # eat every delivery: the frame can never be acked
        retx_at = []
        before = 0
        for _ in range(2 + 4 + 8 + 4):
            server.endpoint.inbox.clear()
            client.poll()
            if client.stats.retransmits > before:
                retx_at.append(client.progress.tick)
                before = client.stats.retransmits
        assert len(retx_at) >= 3
        gaps = np.diff(retx_at)
        assert list(gaps[:2]) == [4, 8]  # rto_after(1)=4, rto_after(2)=8

    def test_budget_exhaustion_escalates_suspect_then_dead(self):
        fabric, client, server = rel_pair(rto_ticks=1, retransmit_budget=2)
        client.send_ifunc("server0", "tsi", np.array([1], I32))
        for _ in range(30):
            server.endpoint.inbox.clear()
            client.poll()
            if client.wire.suspects():
                break
        assert "server0" in client.wire.suspects()
        assert "server0" in client.progress.detector.suspects
        assert client.stats.peers_suspected == 1
        retx_at_suspect = client.stats.retransmits
        # no sign of life within max_misses ticks: suspect becomes dead,
        # with no further retransmissions and all sender state dropped
        for _ in range(30):
            server.endpoint.inbox.clear()
            client.poll()
        assert "server0" in client.progress.detector.dead
        assert client.stats.retransmits == retx_at_suspect
        assert client.wire.unacked_frames("server0") == 0

    def test_sign_of_life_clears_suspicion(self):
        # max_misses generous: the redelivery->ack round trip must land
        # inside the suspect window for this schedule to stay deterministic
        fabric, client, server = rel_pair(rto_ticks=1, retransmit_budget=2,
                                          max_misses=8)
        client.send_ifunc("server0", "tsi", np.array([7], I32))
        held = [bytes(b) for b in server.endpoint.inbox]
        for _ in range(30):
            server.endpoint.inbox.clear()
            client.poll()
            if client.wire.suspects():
                break
        assert "server0" in client.wire.suspects()
        # the peer was alive all along: its next frame un-suspects it and
        # re-arms the retransmit timers, so the ifunc still lands
        for raw in held:
            server.endpoint.deliver(raw, src="client")
        drive(client, server, 60)
        assert "server0" not in client.wire.suspects()
        assert "server0" not in client.progress.detector.dead
        assert server.region("counter")[0] == 7


class TestExactlyOnce:
    def test_duplicate_is_dropped_at_the_seq_gate(self):
        fabric, client, server = rel_pair()
        client.send_ifunc("server0", "tsi", np.array([5], I32))
        dup = bytes(server.endpoint.inbox[0])
        server.poll()
        assert server.region("counter")[0] == 5
        # a retransmit that raced the ack: same seq, re-delivered
        server.endpoint.deliver(dup, src="client")
        assert server.poll() >= 1  # drained (a dup IS link progress) ...
        assert server.region("counter")[0] == 5  # ... but never re-runs
        assert server.stats.dup_frames_dropped == 1

    def test_out_of_order_frames_apply_in_seq_order(self):
        fabric, client, server = rel_pair()
        for v in (10, 20, 30):
            client.send_ifunc("server0", "tsi", np.array([v], I32))
        inbox = server.endpoint.inbox
        inbox.rotate(1)  # arrival order 30, 10, 20
        drive(client, server, 10)
        assert server.region("counter")[0] == 60
        assert server.stats.frames_held_ooo >= 1

    def test_invokes_exactly_once_under_heavy_loss(self):
        """The acceptance invariant: at 20% loss the counter ends exactly
        at the sum — no lost add, no double-applied retransmit."""
        fabric, client, server = rel_pair(rto_ticks=2)
        fabric.set_loss(0.2, seed=42)
        vals = list(range(1, 21))
        for v in vals:
            client.send_ifunc("server0", "tsi", np.array([v], I32))
        for _ in range(300):
            if server.region("counter")[0] == sum(vals) and \
                    client.wire.unacked_frames() == 0:
                break
            client.poll()
            server.poll()
        assert server.region("counter")[0] == sum(vals)
        assert fabric.stats.frames_lost > 0


class TestLossInjection:
    def test_loss_rate_validated(self):
        from repro.core.transport import Fabric

        with pytest.raises(ValueError):
            Fabric("ideal").set_loss(1.0)
        with pytest.raises(ValueError):
            Fabric("ideal").set_loss(-0.1)

    def test_loss_is_seeded_and_accounted(self):
        def run(seed):
            fabric, client, server = rel_pair()
            fabric.set_loss(0.3, seed=seed)
            for v in range(10):
                client.send_ifunc("server0", "tsi", np.array([v], I32))
            return fabric.stats.frames_lost

        assert run(7) == run(7)  # deterministic
        assert run(7) > 0

    def test_zero_loss_changes_nothing(self):
        fabric, client, server = rel_pair()
        client.send_ifunc("server0", "tsi", np.array([5], I32))
        server.poll()
        assert fabric.stats.frames_lost == 0
        assert server.region("counter")[0] == 5

    @pytest.mark.parametrize("rate", [0.01, 0.05])
    def test_gather_exactly_once_under_seeded_loss(self, rate):
        """At 1% and 5% seeded loss every gather retires exactly once, not
        degraded (no owner died), its rows bit-identical to the take."""
        cl = Cluster(n_servers=2, wire="ideal")
        svc = EmbedShardService(cl, vocab=128, dim=8, n_keys=6, max_slots=16)
        batches = ragged_batches(svc.vocab, 96, svc.n_keys, seed=5)
        svc.gather(batches[:8])  # code and pad buckets in place, lossless
        cl.set_reliability(ReliabilityConfig.on())
        cl.fabric.set_loss(rate, seed=3)
        n0 = len(svc.finished)
        rids = [svc.submit(b) for b in batches]
        svc.run()
        done = svc.finished[n0:]
        assert sorted(r.rid for r in done) == sorted(rids)
        by_rid = {r.rid: r for r in done}
        for rid, want in zip(rids, svc.oracle(batches)):
            assert not by_rid[rid].degraded
            np.testing.assert_array_equal(by_rid[rid].rows, want)
        assert cl.fabric.stats.frames_lost > 0
        assert sum(pe.stats.retransmits for pe in cl.pes()) > 0


class TestFailureDetector:
    def test_killed_peer_is_declared_dead_and_state_cleared(self):
        cl = Cluster(2)
        cl.set_reliability(ReliabilityConfig.on(rto_ticks=1,
                                                retransmit_budget=2,
                                                max_misses=2))
        svc = EmbedShardService(cl, vocab=64, dim=4, n_keys=4, max_slots=8)
        svc.gather(ragged_batches(64, 4, 4, seed=0))  # warm everything
        cl.kill_server(1)
        cl.client.send_ifunc("server1", "gatherer",
                             np.full(4, -1, I32))  # into the void
        assert cl.client.wire.unacked_frames("server1") == 1
        for _ in range(60):
            cl.client.poll()
        det = cl.client.progress.detector
        assert "server1" in det.dead
        assert cl.client.stats.peers_declared_dead == 1
        # dead-peer state is gone: no retransmit queue, no credits held
        assert cl.client.wire.unacked_frames("server1") == 0
        assert cl.fabric.credit_outstanding("client", "server1") == 0

    def test_quiet_healthy_peer_is_never_declared_dead(self):
        """The suspect gate: a peer with nothing unacked gives no evidence
        of failure, however long it stays silent."""
        cl = Cluster(2)
        cl.set_reliability(ReliabilityConfig.on(max_misses=1))
        svc = EmbedShardService(cl, vocab=64, dim=4, n_keys=4, max_slots=8)
        svc.gather(ragged_batches(64, 2, 4, seed=0))
        for _ in range(50):  # long silence, no traffic either way
            cl.client.poll()
        assert not cl.client.progress.detector.dead


class TestServiceRecovery:
    def test_owner_death_degrades_to_partial_with_valid_mask(self):
        cl = Cluster(3)
        svc = EmbedShardService(cl, vocab=96, dim=4, n_keys=4, max_slots=8)
        cl.set_reliability(ReliabilityConfig.on(rto_ticks=1,
                                                retransmit_budget=2,
                                                max_misses=2,
                                                future_deadline=16))
        keys = np.array([5, 40, 70], I32)  # touches all three shards
        svc.submit(keys)
        cl.kill_server(1)  # owner of key 40
        svc.run()
        (req,) = svc.finished
        assert req.degraded
        assert req.valid.tolist() == [True, False, True]
        np.testing.assert_array_equal(req.rows[req.valid],
                                      svc.table[keys][req.valid])
        assert svc.cq.free_slots == svc.max_slots  # slot recycled

    def test_all_owners_dead_completes_all_invalid(self):
        cl = Cluster(2)
        svc = EmbedShardService(cl, vocab=64, dim=4, n_keys=4, max_slots=8)
        cl.set_reliability(ReliabilityConfig.on(rto_ticks=1,
                                                retransmit_budget=2,
                                                max_misses=2,
                                                future_deadline=8))
        svc.submit(np.array([5, 40], I32))
        cl.kill_server(0)
        cl.kill_server(1)
        svc.run()
        (req,) = svc.finished
        assert req.degraded and not req.valid.any()

    def test_idle_timeout_names_the_stuck_requests(self):
        """Satellite S1: the bare 'service idle' timeout now attributes —
        slots, owners, ages, resubmit counts, queued backlog."""
        cl = Cluster(2)
        svc = EmbedShardService(cl, vocab=64, dim=4, n_keys=4, max_slots=8)
        svc.gather([np.array([1], I32)])  # warm code caches
        svc.submit(np.array([3, 40], I32))
        svc.submit(np.array([7], I32))
        svc._admit()
        cl.servers[1].endpoint.inbox.clear()  # eat server1's partial
        cl.servers[0].endpoint.inbox.clear()  # and both key-frames
        with pytest.raises(TimeoutError) as exc:
            svc.run()
        msg = str(exc.value)
        assert "service idle but requests outstanding" in msg
        assert "owners=" in msg and "arrived=" in msg and "rid=" in msg
        assert "server0" in msg

    def _exhaust_two(self, strict_recovery=False):
        """Drive two concurrent gathers to resubmit-budget exhaustion in
        the same recovery sweep, with one partial row having landed."""
        cl = Cluster(2)
        svc = EmbedShardService(cl, vocab=64, dim=4, n_keys=4, max_slots=8,
                                strict_recovery=strict_recovery)
        cl.set_reliability(ReliabilityConfig.on(rto_ticks=64,
                                                retransmit_budget=1,
                                                max_misses=64,
                                                future_deadline=2))
        # warm both servers' code caches with real (delivered) gathers so
        # later digest-only resubmissions are executable on arrival
        svc.gather([np.array([1], I32), np.array([40], I32)])
        svc.submit(np.array([3, 40], I32))  # spans both shards
        svc.submit(np.array([45], I32))     # server1 only
        svc._admit()

        def eat_and_expire():
            for srv in cl.servers:
                srv.endpoint.inbox.clear()
            svc.cq.advance(2)

        eat_and_expire()
        assert svc._recover() == 2  # round 1: both resubmitted
        assert svc._admit() == 2
        # round 2: request 0's local row lands via the one-sided zero-copy
        # RETURN path (no frame, no seq gate — exactly the data plane whose
        # losses the resubmit loop exists for) before the rest of the round
        # is lost; that row must survive budget exhaustion
        req0 = next(r for r in svc.active.values() if r.keys[0] == 3)
        stride = (2 + svc.cq.width) * 4
        base = req0.future.slot * stride
        cl.fabric.put_region(
            "server0", cl.client.name, svc.cq.region,
            base + 8, svc.table[3].tobytes(), doorbell=(base, 1, "or"),
        )
        eat_and_expire()
        return cl, svc

    def test_budget_exhaustion_degrades_every_expired_request(self):
        """Regression: two in-flight gathers blowing their resubmit budget
        in the same sweep used to raise TimeoutError on the *first* —
        abandoning the second mid-sweep (slot leaked, request stuck) and
        discarding the partial rows that had already arrived (the future
        was cancelled before the budget check).  Exhaustion must instead
        degrade each request to an attributed partial result, finish the
        sweep, and recycle every slot."""
        cl, svc = self._exhaust_two()
        svc._recover()  # must not raise mid-sweep
        assert not svc.active and not svc.queue
        assert svc.cq.free_slots == svc.max_slots
        done = {r.rid: r for r in svc.finished}
        r0, r1 = done[2], done[3]  # rids 0/1 were the warm-up gathers
        assert r0.degraded and r1.degraded
        assert r0.resubmits == 2 and r1.resubmits == 2
        # the row that DID arrive is preserved and attributed valid
        assert r0.valid.tolist() == [True, False]
        np.testing.assert_array_equal(r0.rows[0], svc.table[3])
        assert r1.valid.tolist() == [False]

    def test_strict_recovery_raises_once_after_the_sweep(self):
        """Under ``strict_recovery`` exhaustion still raises — but only
        after every expired future has been degraded and retired, and the
        error names every exhausted request, not just the first."""
        cl, svc = self._exhaust_two(strict_recovery=True)
        with pytest.raises(TimeoutError) as exc:
            svc._recover()
        # the sweep completed before the raise: nothing leaked
        assert not svc.active
        assert svc.cq.free_slots == svc.max_slots
        assert len(svc.finished) == 2
        msg = str(exc.value)
        assert "rid=2" in msg and "rid=3" in msg
        assert "resubmit budget" in msg


class TestKillMidRendezvous:
    def test_source_death_between_descriptor_and_get(self):
        """Satellite S3: the rendezvous descriptor is delivered, then the
        GET source dies before the pull.  The requester must detect the
        death (via the detector, not an unhandled EndpointDead), release
        its CQ slot, and degrade the request — not hang, not crash."""
        cl = Cluster(2)
        svc = EmbedShardService(cl, vocab=64, dim=64, n_keys=4, max_slots=8)
        cl.set_reliability(ReliabilityConfig.on(rto_ticks=1,
                                                retransmit_budget=2,
                                                max_misses=2,
                                                future_deadline=16))
        cl.set_dataplane(DataPlaneConfig.rendezvous(rndv_min=1))
        # warm code caches so the RETURN travels as a descriptor
        svc.gather(ragged_batches(64, 2, 4, seed=0),
                   dataplane=DataPlaneConfig.rendezvous(rndv_min=1))
        cl.set_dataplane(DataPlaneConfig.rendezvous(rndv_min=1))
        svc.submit(np.array([3, 5], I32))  # owned entirely by server0
        svc._admit()
        cl.servers[0].poll()  # server resolves; descriptor now at client
        from repro.core.frame import FrameKind as FK

        kinds = [peek_header(bytes(b)).kind for b in cl.client.endpoint.inbox]
        assert FK.RNDV in kinds  # descriptor really is in flight
        cl.kill_server(0)  # source dies before the requester pulls
        svc.run()
        (req,) = svc.finished
        assert req.degraded and not req.valid.any()
        assert cl.client.stats.rndv_dead_pulls >= 1
        assert "server0" in cl.client.progress.detector.dead
        assert svc.cq.free_slots == svc.max_slots  # CQ slot released


class TestPublishDedupRetirement:
    def test_seen_pubs_retire_once_acked(self):
        """Satellite S2: publish dedup keys are dropped once the publisher
        has seen the cumulative ack for their seq — bounded memory over an
        unbounded publish stream."""
        cl = Cluster(2)
        cl.set_reliability(ReliabilityConfig.on(ack_delay=1))
        cl.client.register_source(make_tsi())
        for pe in cl.servers:
            pe.register_region("counter", np.zeros(1, I32))
        for _ in range(5):
            cl.client.publish_ifunc("tsi", np.array([1], I32))
            cl.drain_rounds()
        for pe in cl.servers:
            assert pe.region("counter")[0] == 5
            # every dedup key retired: the ack high-water mark passed the
            # publishes, so the log and the seen-set are both drained
            assert not pe.progress._pub_log
            assert not pe.progress._seen_pubs

    def test_replayed_publish_after_retirement_is_still_dropped(self):
        """Retirement must not reopen the duplicate window: a stale
        retransmit of a retired PUBLISH dies at the seq gate instead."""
        cl = Cluster(2)
        cl.set_reliability(ReliabilityConfig.on(ack_delay=1))
        cl.client.register_source(make_tsi())
        for pe in cl.servers:
            pe.register_region("counter", np.zeros(1, I32))
        cl.client.publish_ifunc("tsi", np.array([1], I32))
        replay = [bytes(b) for b in cl.servers[0].endpoint.inbox]
        cl.drain_rounds()
        assert not cl.servers[0].progress._seen_pubs  # retired
        for raw in replay:  # the wire re-delivers the original frames
            cl.servers[0].endpoint.deliver(raw, src="client")
        cl.drain_rounds()
        for pe in cl.servers:
            assert pe.region("counter")[0] == 1  # still exactly once


class TestDisabledIsBitCompatible:
    def test_frames_carry_no_seq_when_disabled(self):
        from repro.core.ifunc import PE, Toolchain
        from repro.core.transport import Fabric

        fabric = Fabric("ideal")
        tc = Toolchain()
        names = ["server0", "client"]
        server = PE("server0", fabric, triple="cpu-bf2", toolchain=tc,
                    peers=names)
        client = PE("client", fabric, triple="cpu-host", toolchain=tc,
                    peers=names)
        server.register_region("counter", np.zeros(1, I32))
        client.register_source(make_tsi())
        client.send_ifunc("server0", "tsi", np.array([5], I32))
        hdr = peek_header(bytes(server.endpoint.inbox[0]))
        # the legacy global seq counter still stamps frames; what must be
        # absent is reliability state: no ack, no retransmit tracking
        assert hdr.ack == 0
        assert client.wire.unacked_frames() == 0
        assert not client.progress._recv and not client.progress._ack_owed

    def test_gather_wire_bytes_identical_with_reliability_off(self):
        def run(cfg):
            cl = Cluster(2)
            svc = EmbedShardService(cl, vocab=64, dim=4, n_keys=4,
                                    max_slots=8)
            if cfg is not None:
                cl.set_reliability(cfg)
            rep = svc.gather(ragged_batches(64, 6, 4, seed=3))
            return rep.put_bytes

        assert run(None) == run(ReliabilityConfig())
