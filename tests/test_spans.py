"""Spans and byte counters inside the PE runtime (``repro.core.spans``,
``PEStats.h2d_bytes`` / ``d2h_bytes``) and executables named after the
ifunc they run.

Off, a span site costs a flag read: no annotation is built.  Forced on,
the runtime's spans add up per name, and the bytes its dispatch, region
and sync spans carry are the byte counters' increments.  The counters
themselves are reckoned here by hand for a tiny gather."""

import numpy as np
import pytest
from jax.profiler import TraceAnnotation

from repro.core import Cluster, spans
from repro.core.pe.pe import RESET_BLOCK
from repro.runtime.embed_service import EmbedShardService, ragged_batches

I32 = np.int32
VOCAB, DIM, K, SLOTS, SERVERS = 64, 8, 4, 8, 2


def make_service():
    cl = Cluster(n_servers=SERVERS, wire="ideal")
    return EmbedShardService(cl, vocab=VOCAB, dim=DIM, n_keys=K, max_slots=SLOTS, seed=1)


def io_bytes(svc) -> tuple[int, int]:
    pes = svc.cluster.pes()
    return sum(p.stats.h2d_bytes for p in pes), sum(p.stats.d2h_bytes for p in pes)


@pytest.mark.parametrize("batching", [False, True])
def test_off_builds_no_annotation(monkeypatch, batching):
    class Refused(TraceAnnotation):
        def __init__(self, *args, **kwargs):
            raise AssertionError("a span was built with spans off")

    monkeypatch.setattr(spans, "TraceAnnotation", Refused)
    svc = make_service()
    batches = ragged_batches(VOCAB, 6, K, seed=2)
    rep = svc.gather(batches, batching=batching)
    for got, want in zip(rep.results, svc.oracle(batches)):
        np.testing.assert_array_equal(got, want)
    assert not spans.enabled


@pytest.mark.parametrize("batching", [False, True])
def test_forced_on_totals_carry_the_byte_counters(batching):
    svc = make_service()
    batches = ragged_batches(VOCAB, 6, K, seed=4)
    svc.gather(batches, batching=batching)
    h0, d0 = io_bytes(svc)
    spans.enable(True)  # the totals start over
    try:
        svc.gather(batches, batching=batching)
    finally:
        spans.enable(None)
    h1, d1 = io_bytes(svc)
    tot = spans.totals()
    for name in ("svc/tick", "svc/admit", "svc/retire", "pe/poll", "pe/ingest",
                 "pe/resolve", "pe/exec", "pe/decode", "pe/dispatch", "pe/sync",
                 "pe/actions", "pe/write_region", "pe/h2d") + ("pe/flush",) * batching:
        assert tot[name][0] > 0 and tot[name][1] >= 0, name
    # a dispatch's exec work has two spans: its dispatch, and its completion
    assert tot["pe/exec"][0] == 2 * tot["pe/dispatch"][0]
    assert sum(tot[n][2] for n in ("pe/dispatch", "pe/h2d", "pe/sync")) == (h1 - h0) + (d1 - d0)
    assert tot["pe/h2d"][2] + tot["pe/dispatch"][2] == h1 - h0
    assert tot["pe/sync"][2] == d1 - d0


@pytest.mark.parametrize("batching", [False, True])
def test_byte_counters_reckoned_by_hand(batching):
    """Two requests, all keys on server 0, after a warm-up that put the
    shard on the device.  Server 0 dispatches the gatherer (the payloads
    and its 3-word meta cap go up, one (S+1) x W action matrix a payload
    comes back); the client resets the two recycled CQ slots on the device
    (one block of row numbers and 2-word heads goes up), folds each partial
    RETURN into its CQ slab, which stays on the device, and pulls the slab
    after each fold."""
    svc = make_service()
    keys = [np.array([1, 2, 3], I32), np.array([4, 5, 6, 7], I32)]
    svc.gather(keys, batching=batching)
    h0, d0 = io_bytes(svc)
    svc.gather(keys, batching=batching)
    h1, d1 = io_bytes(svc)
    request = (3 + K) * 4  # [requester, slot, epoch, keys]
    meta = 3 * 4
    ret = (3 + K + K * DIM) * 4  # [slot, epoch, nres, pos(K), rows(K*D)]
    actions = (SERVERS + 1) * (3 + 3 + K + K * DIM) * 4
    slab = SLOTS * (2 + K * DIM) * 4
    reset = RESET_BLOCK * (1 + 2) * 4  # row numbers and [count, epoch] heads
    if batching:  # one dispatch a side: the gatherer's lax.map, the fold
        h2d = 2 * request + meta + reset + 2 * ret + 2  # + the fold's 2-row valid mask
        d2h = 2 * actions + slab
    else:
        h2d = 2 * (request + meta) + reset + 2 * ret
        d2h = 2 * (actions + slab)
    assert (h1 - h0, d1 - d0) == (h2d, d2h)


def test_executables_named_after_their_ifunc():
    svc = make_service()
    batches = ragged_batches(VOCAB, 6, K, seed=5)
    svc.gather(batches[:1])
    svc.gather(batches, batching=True)
    server, client = svc.cluster.servers[0], svc.cluster.client
    gatherer = server.target_cache.lookup("gatherer")
    assert "HloModule jit_call_gatherer" in gatherer.fn.as_text()
    mapped = [server.target_cache.lookup_batched(gatherer.digest, b) for b in (2, 4, 8)]
    assert any(m is not None and "HloModule jit_mapped_gatherer" in m.as_text() for m in mapped)
    fold = client.target_cache.lookup("gather_return")
    folded = [client.target_cache.lookup_batched(fold.digest, b) for b in (2, 4, 8)]
    assert any(m is not None and "HloModule jit_folded_gather_return" in m.as_text()
               for m in folded)


def test_two_phase_tick_spans(monkeypatch):
    """A tick begins every PE's poll before it completes any: each phase is
    a ``pe/poll`` (``phase`` begin or complete) under ``svc/tick``, the
    dispatches sit in the begin phases, the waits (``pe/sync``, with no
    span inside) in the complete phases, and ``svc/tick`` records the
    dispatches in flight at its first wait."""
    class Recorded:
        stack: list = []
        events: list = []

        def __init__(self, name, **args):
            self.name, self.args, self.children = name, dict(args), []

        def set_metadata(self, **args):
            self.args.update(args)

        @staticmethod
        def is_enabled():
            return False

        def __enter__(self):
            self.parent = self.stack[-1] if self.stack else None
            if self.parent is not None:
                self.parent.children.append(self)
            self.stack.append(self)
            self.events.append(self)

        def __exit__(self, *exc):
            self.stack.pop()

    svc = make_service()
    keys = np.array([1, 2, VOCAB // 2 + 1], I32)  # entry on server 0, one FORWARD
    svc.gather([keys], batching=True)
    svc.cluster.set_batching(True)
    svc.batching = True
    svc.submit(keys)
    monkeypatch.setattr(spans, "TraceAnnotation", Recorded)
    spans.enable(True)
    try:
        svc.run()
    finally:
        spans.enable(None)
    np.testing.assert_array_equal(svc.finished[-1].rows, svc.table[keys])
    ticks = [e for e in Recorded.events if e.name == "svc/tick"]
    assert [t.args["inflight"] for t in ticks] == [1, 2, 1]
    polls = [e for e in Recorded.events if e.name == "pe/poll"]
    assert {p.parent.name for p in polls} == {"svc/tick"}
    for tick in ticks:
        phases = [p.args["phase"] for p in tick.children if p.name == "pe/poll"]
        n = SERVERS + 1
        assert phases == ["begin"] * n + ["complete"] * n
    phase_of = {}
    for e in Recorded.events:
        p = e.parent
        while p is not None and p.name != "pe/poll":
            p = p.parent
        phase_of[id(e)] = p.args["phase"] if p is not None else None
    sync = [e for e in Recorded.events if e.name == "pe/sync"]
    dispatch = [e for e in Recorded.events if e.name == "pe/dispatch"]
    assert len(sync) == len(dispatch) == 4  # entry, forward, two folds
    assert all(s.parent.name == "pe/exec" and not s.children for s in sync)
    assert {phase_of[id(s)] for s in sync} == {"complete"}
    assert {phase_of[id(d)] for d in dispatch} == {"begin"}
