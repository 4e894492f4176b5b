"""A plain reference for the key-value service, numpy only.

* YCSB's request distribution as published (``ScrambledZipfianGenerator``,
  ``ZipfianGenerator`` and ``Utils.fnvhash64`` of ``site.ycsb``): a
  zipfian item over ``ITEM_COUNT`` items with the precomputed ``ZETAN``,
  scrambled by FNV-64 onto the record count.
* :class:`KVStore`: a sequential store of records of int32 words; a read
  returns the record, an update writes one field and returns the record
  after the write.
* :func:`register_check`: the guarantee under concurrency, per key and
  field.  A request (a read, or an update's acknowledgement, which carries
  the record after its write) sees in each field the initial value or an
  update of that key and field submitted before the request retired, and
  never one that another update of the same key and field superseded
  entirely (submitted after its acknowledgement, acknowledged before the
  request was submitted); all of a field's words come from one write.
  After a drain, each field reads back a write nothing superseded.

Each update's value carries its operation index in word 0 (non-negative);
an initial field's word 0 is negative.  So a field names the write it came
from, and every write is distinct.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable

import numpy as np

ZIPFIAN_CONSTANT = 0.99
ITEM_COUNT = 10_000_000_000  # ScrambledZipfianGenerator.ITEM_COUNT
ZETAN = 26.46902820178302  # its zeta(ITEM_COUNT, 0.99), precomputed as published
FNV_OFFSET_BASIS_64 = 0xCBF29CE484222325
FNV_PRIME_64 = 1099511628211


def fnvhash64(val) -> np.ndarray:
    """``Utils.fnvhash64``: FNV-64 over the 8 octets of each value, low
    first, then ``Math.abs`` of the signed result."""
    val = np.asarray(val, np.int64).astype(np.uint64)
    h = np.full(val.shape, FNV_OFFSET_BASIS_64, np.uint64)
    for _ in range(8):
        h = (h ^ (val & np.uint64(0xFF))) * np.uint64(FNV_PRIME_64)
        val = val >> np.uint64(8)
    return np.abs(h.view(np.int64))


def zipfian_items(u: np.ndarray, items: int = ITEM_COUNT + 1, zetan: float = ZETAN,
                  theta: float = ZIPFIAN_CONSTANT) -> np.ndarray:
    """``ZipfianGenerator.nextLong`` over ``items`` items for uniform draws
    ``u`` in [0, 1)."""
    zeta2 = 1.0 + 0.5**theta
    alpha = 1.0 / (1.0 - theta)
    eta = (1.0 - (2.0 / items) ** (1.0 - theta)) / (1.0 - zeta2 / zetan)
    uz = u * zetan
    far = (items * (eta * u - eta + 1.0) ** alpha).astype(np.int64)
    return np.where(uz < 1.0, 0, np.where(uz < 1.0 + 0.5**theta, 1, far))


def scrambled_zipfian(rng: np.random.Generator, n: int, records: int) -> np.ndarray:
    """``n`` keys as ``CoreWorkload`` draws them for ``records`` records
    (no inserts): ``ScrambledZipfianGenerator`` over [0, records] (the
    zipfian item over [0, ITEM_COUNT], ``fnvhash64`` onto the keys), and a
    key past the last record drawn again (``nextKeynum``)."""
    keys = fnvhash64(zipfian_items(rng.random(n))) % (records + 1)
    while (past := np.flatnonzero(keys == records)).size:
        keys[past] = fnvhash64(zipfian_items(rng.random(past.size))) % (records + 1)
    return keys


class KVStore:
    """The sequential store: records of int32 words, ``fields`` fields of
    equal width each."""

    def __init__(self, table: np.ndarray, fields: int) -> None:
        self.table = np.array(table, np.int32)
        self.field_words = self.table.shape[1] // fields

    def read(self, key: int) -> np.ndarray:
        return self.table[key].copy()

    def update(self, key: int, field: int, value: np.ndarray) -> np.ndarray:
        w = self.field_words
        self.table[key, field * w : (field + 1) * w] = value
        return self.read(key)


@dataclass
class Op:
    """One retired request: a read (``field`` -1) or an update of
    ``field``; ``answer`` is the record the system returned, the times its
    submission and retirement on one clock."""

    index: int
    key: int
    field: int
    t_submit: float
    t_done: float
    answer: np.ndarray


class Writes:
    """The updates of a history: found by operation index, and grouped by
    (key, field) in order of submission, where :meth:`superseded` asks
    whether a later one of the group was acknowledged before a time."""

    def __init__(self, ops: list[Op], fields: int) -> None:
        ops = sorted(ops, key=lambda op: op.index)
        self.index = np.array([op.index for op in ops], np.int64)
        self.group = np.array([op.key * fields + op.field for op in ops], np.int64)
        self.sub = np.array([op.t_submit for op in ops], float)
        self.done = np.array([op.t_done for op in ops], float)
        self.groups, gd = np.unique(self.group, return_inverse=True)
        self.times = np.unique(self.sub)
        self.stride = len(self.times) + 1
        by = np.lexsort((self.sub, gd))
        self.g_by = gd[by]
        self.key_by = self.g_by * self.stride + np.searchsorted(self.times, self.sub[by])
        # from each write on, the earliest acknowledgement of the group's
        # writes submitted no earlier: a suffix minimum, shifted by group so
        # that it starts afresh at each group's last write
        if len(by):
            lo, span = self.done.min(), self.done.max() - self.done.min() + 1.0
            shifted = self.done[by] - lo + self.g_by * span
            self.min_done_from = np.minimum.accumulate(shifted[::-1])[::-1] - self.g_by * span + lo

    def find(self, idx: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """Position of each operation index, and whether it names a write."""
        if not len(self.index):
            return np.zeros(np.shape(idx), np.int64), np.zeros(np.shape(idx), bool)
        pos = np.minimum(np.searchsorted(self.index, idx), len(self.index) - 1)
        return pos, self.index[pos] == idx

    def superseded(self, group: np.ndarray, acked: np.ndarray, before: np.ndarray) -> np.ndarray:
        """Elementwise: was a write of ``group`` submitted after ``acked``
        acknowledged before ``before``?"""
        if not len(self.index):
            return np.zeros(np.shape(group), bool)
        gd = np.minimum(np.searchsorted(self.groups, group), len(self.groups) - 1)
        after = np.searchsorted(self.times, acked, side="right")  # first time > acked
        p = np.searchsorted(self.key_by, gd * self.stride + after)
        q = np.minimum(p, len(self.key_by) - 1)
        return (self.groups[gd] == group) & (p < len(self.key_by)) & (self.g_by[q] == gd) & (
            self.min_done_from[q] < before)


def field_checks(writes: Writes, keys: np.ndarray, answers: np.ndarray,
                 t_submit: np.ndarray, t_done: np.ndarray,
                 initial: Callable[[np.ndarray], np.ndarray],
                 value_of: Callable[[np.ndarray], np.ndarray],
                 fields: int) -> tuple[np.ndarray, np.ndarray]:
    """For each request and field, ``(stale, torn)``: the field came from
    a write that another superseded before the request, from one submitted
    after the request retired or to another key or field, or from no write
    at all; or its words are not all one write's."""
    n = len(keys)
    got = np.asarray(answers, np.int32).reshape(n, fields, -1)
    first = got[:, :, 0].astype(np.int64)
    is_init = first < 0
    pos, is_write = writes.find(np.where(is_init, -1, first))
    want = np.where(is_init[..., None],
                    np.asarray(initial(keys), np.int32).reshape(got.shape),
                    np.asarray(value_of(np.where(is_write, first, 0).reshape(-1)),
                               np.int32).reshape(got.shape))
    known = is_init | is_write
    torn = known & np.any(got != want, axis=-1)
    group = keys[:, None].astype(np.int64) * fields + np.arange(fields)
    foreign, later, acked = ~known, np.zeros_like(known), np.full(known.shape, -np.inf)
    if len(writes.index):
        foreign |= is_write & (writes.group[pos] != group)
        later = is_write & (writes.sub[pos] >= t_done[:, None])
        acked = np.where(is_write, writes.done[pos], -np.inf)
    sup = writes.superseded(group, acked, np.broadcast_to(t_submit[:, None], group.shape))
    return (foreign | later | sup) & ~torn, torn


def register_check(
    history: list[Op],
    checked: list[Op],
    initial: Callable[[np.ndarray], np.ndarray],
    value_of: Callable[[np.ndarray], np.ndarray],
    fields: int,
    readback: dict[int, np.ndarray] | None = None,
) -> dict[str, int]:
    """``history``: every update retired since the table was made (the
    writes a field may come from); ``checked``: the requests compared;
    ``initial(keys)``: those records as made; ``value_of(indices)``: the
    words each update writes; ``readback``: the record of each updated key
    after a drain.  Counts ``stale_reads`` (requests with a stale field),
    ``torn_fields``, ``lost_updates`` (acknowledgements without their own
    value, fields read back from a superseded write) and ``failed``
    (requests that failed)."""
    writes = Writes([op for op in history if op.field >= 0], fields)
    out = {"stale_reads": 0, "torn_fields": 0, "lost_updates": 0, "failed": 0}
    if checked:
        keys = np.array([op.key for op in checked], np.int64)
        answers = np.stack([np.asarray(op.answer).reshape(-1) for op in checked])
        stale, torn = field_checks(
            writes, keys, answers, np.array([op.t_submit for op in checked]),
            np.array([op.t_done for op in checked]), initial, value_of, fields)
        upd = [i for i, op in enumerate(checked) if op.field >= 0]
        own = np.zeros(len(checked), bool)
        if upd:
            mine = answers[upd].reshape(len(upd), fields, -1)[
                np.arange(len(upd)), [checked[i].field for i in upd]]
            own[upd] = np.any(mine != value_of(np.array([checked[i].index for i in upd])), axis=1)
        bad = stale.any(axis=1)
        out.update(stale_reads=int(bad.sum()), torn_fields=int(torn.sum()),
                   lost_updates=int(own.sum()), failed=int((bad | torn.any(axis=1) | own).sum()))
    if readback:
        keys = np.array(sorted(readback), np.int64)
        answers = np.stack([np.asarray(readback[k]).reshape(-1) for k in keys])
        inf = np.full(len(keys), np.inf)
        stale, torn = field_checks(writes, keys, answers, inf, inf, initial, value_of, fields)
        out["lost_updates"] += int((stale | torn).sum())
    return out
