"""The X-RDMA pointer chase: Chasers (``PE.send_ifunc``) under a closed
loop over a chain sharded by address, checked exactly against the chain's
successor map applied ``depth`` times."""

from __future__ import annotations

import time

import numpy as np

from bench.cells import ClosedLoop, Retired, placed_on, refuse_unless
from bench.traffic import POOL, powers_of_two, rng_for

KIND = "chase"  # the traffic kind this module drives
ARRIVALS = ("closed",)
SENTINEL = -1  # an empty chase result slot


def make_chain(entries: int, seed: int) -> np.ndarray:
    """One random cycle over ``entries``: ``chain[i]`` is the successor of
    ``i``.  Made on the host: a permutation of 2**26 takes seconds there,
    and sorts of that length take tens of seconds on the TPU."""
    perm = rng_for(seed, 3).permutation(entries).astype(np.int32)
    chain = np.empty(entries, np.int32)
    chain[perm] = np.roll(perm, -1)
    chain.flags.writeable = False
    return chain


class ChaseTraffic:
    """Chase start addresses, uniform over the chain, at a fixed depth."""

    def __init__(self, traffic: dict, entries: int, n_servers: int, seed: int) -> None:
        if traffic["starts"]["dist"] != "uniform":
            raise ValueError(f"unknown start distribution {traffic['starts']['dist']!r}")
        self.entries, self.n_servers = entries, n_servers
        self.shard = entries // n_servers
        self.depth = int(traffic["depth"])
        self.pool = rng_for(seed, 2).integers(0, entries, POOL).astype(np.int32)
        self.pool.flags.writeable = False

    def request(self, i: int) -> int:
        return int(self.pool[i % POOL])

    def bursts(self, concurrency: int) -> list[np.ndarray]:
        """Warm-up bursts of one-hop chases: for each power of two ``n`` up
        to the concurrency and each server, ``n`` chases that start in that
        server's shard and RETURN from it, so that server and the client
        each retire ``n`` payloads in one poll."""
        out, j = [], 0
        for n in powers_of_two(concurrency):
            for s in range(self.n_servers):
                out.append((s * self.shard + self.pool[j : j + n] % self.shard).astype(np.int32))
                j += n
        return out


class ChaseCell(ClosedLoop):
    """X-RDMA Chasers (``PE.send_ifunc``) under a closed loop: each slot of
    the client's ``results`` region holds one chase, relaunched as it
    retires."""

    def __init__(self, config: dict, traffic: dict, seed: int, spans, devices) -> None:
        from repro.core import Cluster, PointerChaseApp

        refuse_unless(traffic, KIND, ARRIVALS)
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
        placed_on(self.cluster, devices)
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


Cell = ChaseCell


def chase_reference(chain: np.ndarray, starts: np.ndarray, depth: int) -> np.ndarray:
    """Every chase at once: ``depth`` applications of the successor map."""
    a = np.asarray(starts, np.int64)
    for _ in range(depth):
        a = chain[a]
    return a


def chase_checks(chain: np.ndarray, starts: np.ndarray, answers: np.ndarray, depth: int,
                 missing: int, control: bool = False) -> tuple[dict, int]:
    """Exact: each limit is 0.  The control stops one hop short."""
    want = chase_reference(chain, starts, depth)
    got = chase_reference(chain, starts, depth - 1) if control else np.asarray(answers)
    wrong = int(np.sum(got != want))
    checks = {"chases_differing": (wrong, 0), "chases_missing": (missing, 0)}
    return checks, wrong + missing


def check(cell: ChaseCell, records: list, missing: int,
          control: bool = False) -> tuple[dict, int]:
    """Every retired chase against the chain followed ``depth`` hops."""
    idx = np.array([r.index for r in records], np.int64)
    asked = cell.traffic.pool[idx % len(cell.traffic.pool)]
    answers = np.array([r.answer for r in records], np.int64)
    return chase_checks(cell.chain, asked, answers, cell.depth, missing, control)
