"""The embedding gather: ``EmbedShardService`` under a closed loop of
key-batch requests over a row-sharded table, checked bit for bit against
a numpy take from the harness's own copy of the table."""

from __future__ import annotations

import os
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from bench.cells import ClosedLoop, Retired, placed_on, refuse_unless
from bench.traffic import POOL, powers_of_two, rng_for, zipf_keys

KIND = "gather"  # the traffic kind this module drives
ARRIVALS = ("closed",)
BLOCK = 4096  # requests compared at a time


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


class GatherTraffic:
    """Key batches for the embedding gather: ``keys_per_request`` row ids
    each, Zipf-skewed or uniform over the table's rows."""

    def __init__(self, traffic: dict, rows: int, n_servers: int, seed: int) -> None:
        self.rows, self.n_servers = rows, n_servers
        self.rows_per_shard = rows // n_servers
        self.n_keys = int(traffic["keys_per_request"])
        rng = rng_for(seed, 1)
        dist = traffic["keys"]
        shape = (POOL, self.n_keys)
        if dist["dist"] == "zipf":
            keys = zipf_keys(rows, shape, rng, float(dist["a"]))
        elif dist["dist"] == "uniform":
            keys = rng.integers(0, rows, shape)
        else:
            raise ValueError(f"unknown key distribution {dist['dist']!r}")
        self.pool = np.ascontiguousarray(keys, np.int32)
        self.pool.flags.writeable = False

    def request(self, i: int) -> np.ndarray:
        return self.pool[i % POOL]

    def bursts(self, concurrency: int) -> list[np.ndarray]:
        """Warm-up bursts that reach every batch the window can form: for
        each server and each power of two ``n`` up to the concurrency, ``n``
        requests owned by that server alone (``n`` payloads in one poll
        there, ``n`` RETURNs in one at the client); then ``n`` requests that
        touch every shard, entering at server 0 (up to ``n`` times the
        shard count RETURNs in one client poll).  Last, for each shard count
        ``k`` from 2 to one short of every shard, one request on shards 0 to
        ``k - 1``, entering at server 0: the client folds the entry's RETURN
        in one tick and the other ``k - 1`` in the next, so each fold size a
        single request forms has compiled."""
        rps, out, j = self.rows_per_shard, [], 0
        for n in powers_of_two(concurrency):
            for s in range(self.n_servers):
                out.append(s * rps + self.pool[j : j + n] % rps)
                j += n
            spread = np.arange(self.n_keys) % self.n_servers
            out.append(spread * rps + self.pool[j : j + n] % rps)
            j += n
        for k in range(2, self.n_servers):
            out.append(np.arange(self.n_keys) % k * rps + self.pool[j : j + 1] % rps)
            j += 1
        return [b.astype(np.int32) for b in out]


class GatherCell(ClosedLoop):
    """``EmbedShardService`` under a closed loop of key-batch requests."""

    def __init__(self, config: dict, traffic: dict, seed: int, spans, devices) -> None:
        from repro.core import Cluster
        from repro.runtime.embed_service import EmbedShardService

        refuse_unless(traffic, KIND, ARRIVALS)
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
        placed_on(self.cluster, devices)
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


Cell = GatherCell


def bfloat16_round(x: np.ndarray) -> np.ndarray:
    """f32 -> the nearest bfloat16 (ties to even) -> f32."""
    import ml_dtypes

    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16).astype(np.float32)


def gather_checks(table: np.ndarray, keys: np.ndarray, answers: list, missing: int,
                  control: bool = False) -> tuple[dict, int]:
    """``keys`` is (n, K) row ids, ``answers`` the n (K, D) f32 blocks the
    system returned.  Returns ``({name: (value, limit)}, failed requests)``.
    The configuration states exact rows, so each limit is 0.  The control
    rounds the reference's rows to bfloat16 (what an MXU contraction at
    default precision returns for an f32 table)."""
    differing = wrong = 0
    for lo in range(0, len(answers), BLOCK):
        want = table[keys[lo : lo + BLOCK]]
        got = bfloat16_round(want) if control else np.stack(answers[lo : lo + BLOCK])
        if got.shape != want.shape:
            differ = np.ones(want.shape[:2], bool)
        else:
            differ = np.any(
                got.astype(np.float32).view(np.uint32) != want.view(np.uint32), axis=-1
            )
        differing += int(differ.sum())
        wrong += int(differ.any(axis=1).sum())
    checks = {"rows_differing": (differing, 0), "requests_missing": (missing, 0)}
    return checks, wrong + missing


def check(cell: GatherCell, records: list, missing: int,
          control: bool = False) -> tuple[dict, int]:
    """Every retired gather against a take from the harness's table."""
    idx = np.array([r.index for r in records], np.int64)
    asked = cell.traffic.pool[idx % len(cell.traffic.pool)]
    return gather_checks(cell.table, asked, [r.answer for r in records], missing, control)
