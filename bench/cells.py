"""The closed loops the window drives, one class per kind of service.

Each cell builds its data from the seed, builds the system under test
through its public entry points, and then exposes one ``step()``: one
scheduler round that retires what completed and, in a closed loop,
submits a new request for each one retired.  Every retirement is
recorded with the harness's own host clock (submit -> the host seeing the
result) and with what the system answered, for the check after the
window.
"""

from __future__ import annotations

import os
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field

import numpy as np

from .traffic import ChaseTraffic, GatherTraffic, rng_for

SENTINEL = -1  # an empty chase result slot


@dataclass
class Retired:
    """One retired request: which request (its index in the traffic's
    pool), what the system answered, and when (host clock)."""

    index: int
    answer: object
    t_submit: float
    t_done: float


@dataclass
class Counters:
    ticks: int = 0  # scheduler rounds the harness drove
    invokes: int = 0  # XLA dispatches over all PEs (PEStats)
    invoked_payloads: int = 0  # payloads those dispatches retired (PEStats)
    puts: int = 0  # frames on the fabric (TrafficStats)
    jit_ms: float = 0.0  # PEStats.jit_ms_total over all PEs

    def minus(self, other: "Counters") -> "Counters":
        return Counters(**{k: getattr(self, k) - getattr(other, k) for k in self.__dict__})


def make_table(rows: int, dim: int, seed: int, chunks: int = 16) -> np.ndarray:
    """The embedding table from the seed, one numpy stream per chunk of
    rows, the chunks filled by a thread each (read-only: it is the
    reference's copy).  Made on the host because the service takes its
    table from the host: an 8 GiB table made on the TPU took 13.5 s to copy
    back."""
    table = np.empty((rows, dim), np.float32)
    bounds = np.linspace(0, rows, chunks + 1).astype(np.int64)

    def fill(i: int) -> None:
        rng_for(seed, 100 + i).standard_normal(
            out=table[bounds[i] : bounds[i + 1]], dtype=np.float32)

    with ThreadPoolExecutor(max_workers=min(chunks, os.cpu_count() or 1)) as pool:
        list(pool.map(fill, range(chunks)))
    table.flags.writeable = False
    return table


def make_chain(entries: int, seed: int) -> np.ndarray:
    """One random cycle over ``entries``: ``chain[i]`` is the successor of
    ``i``.  Made on the host: a permutation of 2**26 takes seconds there,
    and sorts of that length take tens of seconds on the TPU."""
    perm = rng_for(seed, 3).permutation(entries).astype(np.int32)
    chain = np.empty(entries, np.int32)
    chain[perm] = np.roll(perm, -1)
    chain.flags.writeable = False
    return chain


@dataclass
class ClosedLoop:
    """What both cells share: the records, the counters and the drain."""

    concurrency: int
    spans: object
    done: list = field(default_factory=list)  # Retired, in retirement order
    ticks: int = 0
    next_index: int = 0

    def counters(self) -> Counters:
        pes = self.cluster.pes()
        return Counters(
            ticks=self.ticks,
            invokes=sum(pe.stats.invokes for pe in pes),
            invoked_payloads=sum(pe.stats.invoked_payloads for pe in pes),
            puts=self.cluster.fabric.stats.puts,
            jit_ms=sum(pe.stats.jit_ms_total for pe in pes),
        )

    def fill(self) -> None:
        """Put the closed loop's ``concurrency`` requests in flight."""
        while self.in_flight() < self.concurrency:
            self.submit()

    def drain(self, limit_s: float = 60.0, idle_rounds: int = 50) -> None:
        """Stop submitting and run until every request in flight has
        retired, the system makes no progress for ``idle_rounds`` rounds,
        or ``limit_s`` seconds pass."""
        end, idle = time.perf_counter() + limit_s, 0
        while self.in_flight() and idle <= idle_rounds and time.perf_counter() < end:
            idle = 0 if self.step(resubmit=False) else idle + 1


class GatherCell(ClosedLoop):
    """``EmbedShardService`` under a closed loop of key-batch requests."""

    kind = "gather"

    def __init__(self, config: dict, traffic: dict, seed: int, spans) -> None:
        from repro.core import Cluster
        from repro.runtime.embed_service import EmbedShardService

        super().__init__(int(traffic["concurrency"]), spans)
        rows, dim, n_servers = config["rows"], config["dim"], config["n_servers"]
        if self.concurrency > config["max_slots"]:
            raise ValueError("concurrency exceeds the completion queue's slots")
        if traffic["keys_per_request"] > config["n_keys"]:
            raise ValueError("keys_per_request exceeds the configuration's n_keys")
        t = time.perf_counter()
        self.table = make_table(rows, dim, seed)
        self.traffic = GatherTraffic(traffic, rows, n_servers, seed)
        self.setup_log = {"data_s": time.perf_counter() - t}
        t = time.perf_counter()
        triple = config["triple"]
        self.cluster = Cluster(n_servers=n_servers, server_triple=triple, client_triple=triple)
        self.svc = EmbedShardService(
            self.cluster, vocab=rows, dim=dim, n_keys=config["n_keys"],
            max_slots=config["max_slots"], table=self.table,
        )
        self.cluster.set_batching(bool(traffic["batching"]))
        self.svc.batching = bool(traffic["batching"])
        self._pending: dict[int, tuple[int, float]] = {}  # rid -> (index, t_submit)
        spans.wrap(self.cluster)
        self.setup_log["system_s"] = time.perf_counter() - t

    def in_flight(self) -> int:
        return len(self._pending)

    def submit(self) -> None:
        i = self.next_index
        self.next_index += 1
        keys = self.traffic.request(i)
        with self.spans("bench/submit"):
            t = time.perf_counter()
            rid = self.svc.submit(keys)
        self._pending[rid] = (i, t)

    def step(self, resubmit: bool = True) -> int:
        self.ticks += 1
        with self.spans("bench/tick"):
            progress = self.svc.tick()
        fin = self.svc.finished
        if not fin:
            return progress
        with self.spans("bench/retire"):
            t = time.perf_counter()
            n = len(fin)
            for req in fin:
                i, t_submit = self._pending.pop(req.rid)
                self.done.append(Retired(i, req.rows, t_submit, t))
            fin.clear()
        if resubmit:
            for _ in range(n):
                self.submit()
        return progress + n

    def warm_bursts(self) -> None:
        """Drive every burst shape of :meth:`GatherTraffic.bursts` to
        completion, so each batch size the window can form has compiled."""
        for batch in self.traffic.bursts(self.concurrency):
            for keys in batch:
                self.svc.submit(keys)
            self.svc.run()
            self.svc.finished.clear()

    def release(self) -> None:
        """Drop the system under test (the table stays for the check)."""
        self.svc = self.cluster = None


class ChaseCell(ClosedLoop):
    """X-RDMA Chasers (``PE.send_ifunc``) under a closed loop: each slot of
    the client's ``results`` region holds one chase, relaunched as it
    retires."""

    kind = "chase"

    def __init__(self, config: dict, traffic: dict, seed: int, spans) -> None:
        from repro.core import Cluster, PointerChaseApp

        super().__init__(int(traffic["concurrency"]), spans)
        entries, n_servers = config["entries"], config["n_servers"]
        if self.concurrency > config["max_slots"]:
            raise ValueError("concurrency exceeds the result slots")
        if config["mode"] != "bitcode":
            raise ValueError(f"chase mode {config['mode']!r}: only bitcode is driven")
        t = time.perf_counter()
        self.traffic = ChaseTraffic(traffic, entries, n_servers, seed)
        self.depth = self.traffic.depth
        self.shard = entries // n_servers
        self.chain = make_chain(entries, seed)
        self.setup_log = {"data_s": time.perf_counter() - t}
        t = time.perf_counter()
        triple = config["triple"]
        self.cluster = Cluster(n_servers=n_servers, server_triple=triple, client_triple=triple)
        PointerChaseApp(self.cluster, n_entries=entries, max_slots=config["max_slots"])
        self.setup_log["app_s"] = time.perf_counter() - t
        # the shards hold the harness's chain, so the reference shares
        # nothing the program made
        for i, pe in enumerate(self.cluster.servers):
            pe.register_region("table_shard", self.chain[i * self.shard : (i + 1) * self.shard].copy())
        self.client = self.cluster.client
        self.results = self.client.region("results")
        self.results[: config["max_slots"]] = SENTINEL
        self.results[config["max_slots"]] = 0
        self.client.endpoint.touch_region("results")
        self.cluster.set_batching(bool(traffic["batching"]))
        self._slots: dict[int, tuple[int, float]] = {}  # slot -> (index, t_submit)
        self._free = list(range(self.concurrency - 1, -1, -1))
        spans.wrap(self.cluster)
        self.setup_log["system_s"] = time.perf_counter() - t

    def in_flight(self) -> int:
        return len(self._slots)

    def _launch(self, slot: int, start: int, depth: int) -> float:
        payload = np.array([start, depth, self.cluster.client_index, slot], np.int32)
        with self.spans("bench/submit"):
            t = time.perf_counter()
            self.client.send_ifunc(f"server{start // self.shard}", "chaser", payload)
        return t

    def submit(self) -> None:
        slot = self._free.pop()
        i = self.next_index
        self.next_index += 1
        self._slots[slot] = (i, self._launch(slot, self.traffic.request(i), self.depth))

    def fill(self) -> None:
        super().fill()
        self.client.flush()

    def _poll_round(self) -> int:
        with self.spans("bench/tick"):
            return sum(pe.poll() for pe in self.cluster.alive_pes())

    def _reset(self, slots: np.ndarray) -> None:
        self.results[slots] = SENTINEL
        self.client.endpoint.touch_region("results")

    def step(self, resubmit: bool = True) -> int:
        self.ticks += 1
        progress = self._poll_round()
        res = self.results[: self.concurrency]
        slots = np.flatnonzero(res != SENTINEL)
        if len(slots):
            with self.spans("bench/retire"):
                t = time.perf_counter()
                for slot in slots.tolist():
                    i, t_submit = self._slots.pop(slot)
                    self.done.append(Retired(i, int(res[slot]), t_submit, t))
                    self._free.append(slot)
                self._reset(slots)
            if resubmit:
                for _ in range(len(slots)):
                    self.submit()
                self.client.flush()
        return progress + len(slots)

    def warm_bursts(self) -> None:
        """Drive every burst shape of :meth:`ChaseTraffic.bursts` (one-hop
        chases) to completion, so each batch size the window can form has
        compiled."""
        for starts in self.traffic.bursts(self.concurrency):
            slots = np.arange(len(starts))
            for slot, start in zip(slots.tolist(), starts.tolist()):
                self._launch(slot, start, 1)
            self.client.flush()
            idle = 0
            while np.any(self.results[slots] == SENTINEL):
                idle = 0 if self._poll_round() else idle + 1
                if idle > 50:
                    raise TimeoutError("a warm-up chase never returned")
            self._reset(slots)

    def release(self) -> None:
        """Drop the system under test (the chain stays for the check)."""
        self.cluster = self.client = self.results = None


CELL_KINDS = {"embed_gather": GatherCell, "pointer_chase": ChaseCell}


def build_cell(config: dict, traffic: dict, seed: int, spans):
    """The closed loop for ``config``'s service under ``traffic``."""
    kind = CELL_KINDS[config["service"]]
    if traffic["kind"] != kind.kind or traffic["arrival"] != "closed":
        raise ValueError(f"traffic {traffic} does not fit service {config['service']!r}")
    return kind(config, traffic, seed, spans)
