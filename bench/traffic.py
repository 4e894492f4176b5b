"""The one traffic generator: every mix is a data file under ``traffic/``
that names its kind (``gather`` or ``chase``), its closed-loop
concurrency and its request shapes.  The same seed gives the same
requests in the same order; every seed gives the same sizes."""

from __future__ import annotations

import numpy as np

POOL = 1 << 15  # requests drawn per run; the closed loop cycles through them


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent numpy stream per (seed, purpose); any integer seed."""
    return np.random.default_rng(np.random.SeedSequence([seed % (1 << 64), stream]))


def zipf_keys(vocab: int, shape, rng: np.random.Generator, a: float) -> np.ndarray:
    """Row ids drawn Zipf(``a``) over popularity ranks; a random rank -> row
    map spreads the hot rows over every shard."""
    p = np.arange(1, vocab + 1, dtype=np.float64) ** -a
    p /= p.sum()
    row_of_rank = rng.permutation(vocab).astype(np.int32)
    return row_of_rank[rng.choice(vocab, size=shape, p=p)]


def powers_of_two(limit: int) -> list[int]:
    """1, 2, 4, ... up to ``limit``, and ``limit`` itself."""
    out = [1 << i for i in range(max(limit, 1).bit_length()) if 1 << i <= limit]
    return out if out[-1] == limit else out + [limit]


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
        shard count RETURNs in one client poll)."""
        rps, out, j = self.rows_per_shard, [], 0
        for n in powers_of_two(concurrency):
            for s in range(self.n_servers):
                out.append(s * rps + self.pool[j : j + n] % rps)
                j += n
            spread = np.arange(self.n_keys) % self.n_servers
            out.append(spread * rps + self.pool[j : j + n] % rps)
            j += n
        return [b.astype(np.int32) for b in out]


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
