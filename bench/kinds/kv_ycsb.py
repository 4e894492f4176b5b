"""YCSB's core workloads on the key-value shard service:
``KVShardService`` under a closed loop of single-record reads and
one-field updates, drawn as YCSB draws them, over records made on the
device from the seed.  The check compares every answer with a plain
reference of the configuration's guarantee, which imports nothing of the
program: YCSB's generators as published, the records' words, and the
register check per key and field (a copy of the program's
``repro.runtime.kv_reference``, kept apart from it)."""

from __future__ import annotations

import time
from dataclasses import dataclass

import numpy as np

from bench.cells import ClosedLoop, Retired, placed_on, refuse_unless
from bench.traffic import powers_of_two, rng_for

KIND = "ycsb"  # the traffic kind this module drives
ARRIVALS = ("closed",)
OPS = 1 << 18  # operations drawn per run; the closed loop cycles through them
BLOCK = 4096  # requests compared at a time

# ---------------------------------------------- YCSB's generators, as published
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


def scrambled_zipfian(rng: np.random.Generator, n: int, records: int) -> np.ndarray:
    """``n`` keys as ``CoreWorkload`` draws them for ``records`` records
    (no inserts): ``ScrambledZipfianGenerator`` over [0, records] (the
    zipfian item over [0, ITEM_COUNT] as ``ZipfianGenerator.nextLong`` draws
    it with ``ZETAN``, ``fnvhash64`` onto the keys), and a key past the last
    record drawn again (``nextKeynum``)."""
    items, theta = ITEM_COUNT + 1, ZIPFIAN_CONSTANT
    alpha = 1.0 / (1.0 - theta)
    eta = (1.0 - (2.0 / items) ** (1.0 - theta)) / (1.0 - (1.0 + 0.5**theta) / ZETAN)

    def draw(m: int) -> np.ndarray:
        u = rng.random(m)
        far = (items * (eta * u - eta + 1.0) ** alpha).astype(np.int64)
        item = np.where(u * ZETAN < 1.0, 0, np.where(u * ZETAN < 1.0 + 0.5**theta, 1, far))
        return fnvhash64(item) % (records + 1)

    keys = draw(n)
    while (past := np.flatnonzero(keys == records)).size:
        keys[past] = draw(past.size)
    return keys


# ------------------------------------------------- the records' words, from the seed
def mix32(x, salt):
    """murmur3's 32-bit finalizer of ``x ^ salt``, on numpy or jax uint32
    arrays alike."""
    x = (x ^ salt) * np.uint32(0x9E3779B1)
    x = (x ^ (x >> 16)) * np.uint32(0x85EBCA6B)
    x = (x ^ (x >> 13)) * np.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


def initial_records(keys, record_words: int, field_words: int, salt) -> np.ndarray:
    """The records as made: word ``w`` of record ``k`` is ``mix32(k *
    record_words + w)``, with each field's first word negative."""
    w = np.arange(record_words, dtype=np.uint32)
    h = mix32(np.asarray(keys, np.uint32)[:, None] * np.uint32(record_words) + w, np.uint32(salt))
    h = np.where(w % field_words == 0, h | np.uint32(1 << 31), h)
    return h.astype(np.uint32).view(np.int32)


def update_values(indices, field_words: int, salt) -> np.ndarray:
    """The words update ``i`` writes: ``i``, then ``field_words - 1`` words
    from the seed."""
    i = np.asarray(indices, np.int64)
    j = np.arange(1, field_words, dtype=np.uint32)
    rest = mix32(i.astype(np.uint32)[:, None] * np.uint32(field_words) + j, np.uint32(salt))
    return np.concatenate([i[:, None].astype(np.int32), rest.view(np.int32)], axis=1)


def shard_maker(rows: int, record_words: int, field_words: int):
    """The device rendering of :func:`initial_records` for the ``rows``
    records from ``first``: one executable for every shard."""
    import jax
    import jax.numpy as jnp

    def make(first, salt):
        k = first + jnp.arange(rows, dtype=jnp.uint32)[:, None]
        w = jnp.arange(record_words, dtype=jnp.uint32)[None, :]
        h = mix32(k * jnp.uint32(record_words) + w, salt)
        h = jnp.where(w % field_words == 0, h | jnp.uint32(1 << 31), h)
        return jax.lax.bitcast_convert_type(h, jnp.int32)

    return jax.jit(make)


class YcsbTraffic:
    """YCSB's operations for one run: ``operationchooser`` (read or update
    by the mix's proportions), the key from the scrambled zipfian, the
    updated field from ``fieldchooser`` (uniform)."""

    def __init__(self, traffic: dict, records: int, fields: int, seed: int) -> None:
        dist = traffic["keys"]
        if dist != {"dist": "scrambled_zipfian", "constant": ZIPFIAN_CONSTANT}:
            raise ValueError(f"YCSB draws keys by its scrambled zipfian 0.99, not {dist}")
        if traffic["read"] + traffic["update"] != 1.0:
            raise ValueError("a mix of reads and updates only")
        rng = rng_for(seed, 1)
        self.key = scrambled_zipfian(rng, OPS, records)
        self.update = rng.random(OPS) < traffic["update"]
        self.field = rng.integers(0, fields, OPS)
        for a in (self.key, self.update, self.field):
            a.flags.writeable = False

    def op(self, i: int) -> tuple[int, int]:
        """Request ``i``: its key, and the field it updates (-1: a read)."""
        j = i % OPS
        return int(self.key[j]), int(self.field[j]) if self.update[j] else -1


class YcsbCell(ClosedLoop):
    """``KVShardService`` under a closed loop of YCSB operations."""

    def __init__(self, config: dict, traffic: dict, seed: int, spans, devices) -> None:
        from repro.core import Cluster
        from repro.runtime.kv_service import KVShardService

        refuse_unless(traffic, KIND, ARRIVALS)
        super().__init__(int(traffic["concurrency"]), spans)
        records, n_servers = config["records"], config["n_servers"]
        self.fields, self.record_words = config["fields"], config["record_words"]
        self.field_words = self.record_words // self.fields
        if self.field_words * 4 != config["field_bytes"] or config["dtype"] != "int32":
            raise ValueError("a field is field_bytes of int32 words")
        if self.concurrency > config["max_slots"]:
            raise ValueError("concurrency exceeds the completion queue's slots")
        t = time.perf_counter()
        self.traffic = YcsbTraffic(traffic, records, self.fields, seed)
        self.record_salt, self.value_salt = (int(s) for s in rng_for(seed, 2).integers(0, 1 << 32, 2))
        self.setup_log = {"traffic_s": time.perf_counter() - t}
        t = time.perf_counter()
        triple = config["triple"]
        self.cluster = Cluster(n_servers=n_servers, server_triple=triple, client_triple=triple)
        placed_on(self.cluster, devices)
        self.setup_log["cluster_s"] = time.perf_counter() - t
        t = time.perf_counter()
        self.rows_per_shard = records // n_servers
        make = shard_maker(self.rows_per_shard, self.record_words, self.field_words)
        import jax

        shards = []
        for i, pe in enumerate(self.cluster.servers):
            with jax.default_device(pe.device):
                shards.append(make(np.uint32(i * self.rows_per_shard), np.uint32(self.record_salt)))
        jax.block_until_ready(shards)
        self.setup_log["data_s"] = time.perf_counter() - t
        t = time.perf_counter()
        self.svc = KVShardService(self.cluster, shards, self.fields, config["max_slots"])
        self.cluster.set_batching(bool(traffic["batching"]))
        self.svc.batching = bool(traffic["batching"])
        self._pending: dict[int, tuple[int, float]] = {}  # rid -> (index, t_submit)
        self.readback: dict[int, np.ndarray] = {}
        spans.wrap(self.cluster)
        self.setup_log["system_s"] = time.perf_counter() - t

    def value(self, i: int) -> np.ndarray:
        return update_values([i], self.field_words, self.value_salt)[0]

    def in_flight(self) -> int:
        return len(self._pending)

    def submit(self) -> None:
        i = self.next_index
        self.next_index += 1
        key, field = self.traffic.op(i)
        with self.spans("bench/submit"):
            t = time.perf_counter()
            if field < 0:
                rid = self.svc.submit_read(key)
            else:
                rid = self.svc.submit_update(key, field, self.value(i))
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
        """For each server and each power of two ``n`` up to the
        concurrency: ``n`` reads of its records, then ``n`` updates that
        write back a field's initial words (the records do not change), so
        each batch of either operation, and each fold of ``n`` RETURNs at
        the client, has compiled."""
        rps, fw = self.rows_per_shard, self.field_words
        for n in powers_of_two(self.concurrency):
            for s in range(len(self.cluster.servers)):
                keys = s * rps + np.arange(n) % rps
                for key in keys:
                    self.svc.submit_read(int(key))
                self.svc.run()
                init = initial_records(keys, self.record_words, fw, self.record_salt)
                for j, key in enumerate(keys):
                    f = j % self.fields
                    self.svc.submit_update(int(key), f, init[j, f * fw : (f + 1) * fw])
                self.svc.run()
                self.svc.finished.clear()

    def release(self) -> None:
        """Read back every record an update retired on, as the servers hold
        it (a take of those rows on the device), then drop the system."""
        keys = sorted({self.traffic.op(r.index)[0] for r in self.done
                       if self.traffic.op(r.index)[1] >= 0})
        owner = np.array(keys, np.int64) // self.rows_per_shard
        for s, pe in enumerate(self.cluster.servers):
            mine = [k for k, o in zip(keys, owner) if o == s]
            if mine:
                rows = pe.region_rows("embed_shard", np.array(mine) - s * self.rows_per_shard)
                self.readback.update(zip(mine, rows))
        self.svc = self.cluster = None


Cell = YcsbCell


# ------------------------------------------ the register check, per key and field
@dataclass
class Op:
    """One retired request: a read (``field`` -1) or an update of
    ``field``; ``answer`` the record returned; submitted and retired on the
    harness's clock."""

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

    def __init__(self, ops: list, fields: int) -> None:
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
        # the group's writes in order of acknowledgement (for the control)
        ack = np.lexsort((self.done, gd))
        self.ack_g, self.ack_index = gd[ack], self.index[ack]
        self.done_times = np.unique(self.done)
        self.ack_key = self.ack_g * (len(self.done_times) + 1) + np.searchsorted(
            self.done_times, self.done[ack])

    def find(self, idx: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """Position of each operation index, and whether it names a write."""
        if not len(self.index):
            return np.zeros(np.shape(idx), np.int64), np.zeros(np.shape(idx), bool)
        pos = np.minimum(np.searchsorted(self.index, idx), len(self.index) - 1)
        return pos, self.index[pos] == idx

    def dense(self, group: np.ndarray) -> np.ndarray:
        """Each (key, field) group's rank among the written ones, -1 for
        one never written."""
        if not len(self.index):
            return np.full(np.shape(group), -1)
        gd = np.minimum(np.searchsorted(self.groups, group), len(self.groups) - 1)
        return np.where(self.groups[gd] == group, gd, -1)

    def superseded(self, group: np.ndarray, acked: np.ndarray, before: np.ndarray) -> np.ndarray:
        """Elementwise: was a write of ``group`` submitted after ``acked``
        acknowledged before ``before``?"""
        gd = self.dense(group)
        if not len(self.index):
            return np.zeros(np.shape(group), bool)
        after = np.searchsorted(self.times, acked, side="right")  # first time > acked
        p = np.searchsorted(self.key_by, np.maximum(gd, 0) * self.stride + after)
        q = np.minimum(p, len(self.key_by) - 1)
        return (gd >= 0) & (p < len(self.key_by)) & (self.g_by[q] == gd) & (
            self.min_done_from[q] < before)

    def one_stale(self, group: np.ndarray, before: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """For the control: whether a write of ``group`` was acknowledged
        before ``before``, and the index of the one acknowledged just
        before the last of those (-1: the initial value)."""
        gd = self.dense(group)
        if not len(self.index):
            return np.zeros(np.shape(group), bool), np.full(np.shape(group), -1)
        q = np.maximum(gd, 0) * (len(self.done_times) + 1) + np.searchsorted(
            self.done_times, before)  # the first acknowledgement at or after ``before``
        last = np.searchsorted(self.ack_key, q) - 1
        has = (gd >= 0) & (last >= 0) & (self.ack_g[np.maximum(last, 0)] == gd)
        prev = np.maximum(last - 1, 0)
        return has, np.where(has & (last >= 1) & (self.ack_g[prev] == gd), self.ack_index[prev], -1)


def field_checks(writes: Writes, keys, answers, t_submit, t_done, initial, value_of,
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
    want = np.where(is_init[..., None], initial(keys).reshape(got.shape),
                    value_of(np.where(is_write, first, 0).reshape(-1)).reshape(got.shape))
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


def check(cell: YcsbCell, records: list, missing: int, control: bool = False) -> tuple[dict, int]:
    """Every request retired from the window's start against the register
    check, and every record an update retired on as read back after the
    drain.  The configuration states linearizable reads and updates, so
    each limit is 0.  The control answers each read, field by field, with
    the version just before the latest update of the field acknowledged
    before the read was submitted: one version stale."""
    F, fw, W = cell.fields, cell.field_words, cell.record_words

    def initial(keys):
        return initial_records(keys, W, fw, cell.record_salt)

    def value_of(indices):
        return update_values(indices, fw, cell.value_salt)

    def op(r: Retired) -> Op:
        key, field = cell.traffic.op(r.index)
        return Op(r.index, key, field, r.t_submit, r.t_done, np.asarray(r.answer).reshape(-1))

    writes = Writes([o for o in map(op, cell.done) if o.field >= 0], F)
    checked = [op(r) for r in records]
    stale_reads = torn_fields = lost = 0
    failed = missing
    for lo in range(0, len(checked), BLOCK):
        block = checked[lo : lo + BLOCK]
        keys = np.array([o.key for o in block], np.int64)
        answers = np.stack([o.answer for o in block])
        t_submit = np.array([o.t_submit for o in block])
        if control:
            reads = np.array([o.field < 0 for o in block])
            group = keys[:, None] * F + np.arange(F)
            has, prev = writes.one_stale(group, np.broadcast_to(t_submit[:, None], group.shape))
            older = np.where((prev >= 0)[..., None],
                             value_of(np.maximum(prev, 0).reshape(-1)).reshape(*prev.shape, fw),
                             initial(keys).reshape(len(keys), F, fw))
            answers = np.where((has & reads[:, None])[..., None], older,
                               answers.reshape(len(keys), F, fw)).reshape(len(keys), W)
        stale, torn = field_checks(writes, keys, answers, t_submit,
                                   np.array([o.t_done for o in block]), initial, value_of, F)
        own = np.zeros(len(block), bool)
        upd = np.flatnonzero([o.field >= 0 for o in block])
        if len(upd):
            mine = answers[upd].reshape(len(upd), F, fw)[
                np.arange(len(upd)), [block[i].field for i in upd]]
            own[upd] = np.any(mine != value_of([block[i].index for i in upd]), axis=1)
        bad = stale.any(axis=1)
        stale_reads += int(bad.sum())
        torn_fields += int(torn.sum())
        lost += int(own.sum())
        failed += int((bad | torn.any(axis=1) | own).sum())
    back = np.array(sorted(cell.readback), np.int64)
    for lo in range(0, len(back), BLOCK):
        keys = back[lo : lo + BLOCK]
        inf = np.full(len(keys), np.inf)
        stale, torn = field_checks(writes, keys, np.stack([cell.readback[k] for k in keys]),
                                   inf, inf, initial, value_of, F)
        lost += int((stale | torn).sum())
    checks = {"stale_reads": (stale_reads, 0), "torn_fields": (torn_fields, 0),
              "lost_updates": (lost, 0), "requests_missing": (missing, 0)}
    return checks, failed
