"""What every traffic generator shares.  A mix is a data file under
``traffic/`` that names its kind (the traffic a kind module under
``kinds/`` drives), its arrivals, its closed-loop concurrency and its
request shapes; that kind module's generator reads it.  The same seed
gives the same requests in the same order; every seed gives the same
sizes."""

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
