"""Key-value shard service: single-record reads and one-field updates
over the X-RDMA substrate, with the records on the device.

A table of records (int32 words, ``fields`` fields of equal width) is
row-sharded across server PEs, one shard a server, host or device arrays.
A read ships :func:`repro.core.xrdma.make_gatherer`'s word rendering with
one key: it takes the record where it lives and RETURNs it.  An update
ships :func:`repro.core.xrdma.make_kv_update`: it writes one field of the
record in place (the shard stays on the device as its region's value) and
RETURNs the record as it stands after the write.  Both enter at the key's
owner, so nothing FORWARDs, and both complete into one int32 completion
queue of ``(1, record_words)`` slots.  Admission, the two-phase tick,
recovery and retirement are :class:`EmbedShardService`'s.

Guarantee: linearizable single-key reads and updates.  A server applies
what arrives in payload order; a dispatch reads the shard every earlier
dispatch of the PE left; an update is acknowledged after its write is the
shard's value.  :mod:`repro.runtime.kv_reference` checks it.
"""

from __future__ import annotations

import time
from dataclasses import dataclass

import numpy as np

from repro.core import Cluster
from repro.core.xrdma import make_gather_return, make_gatherer, make_kv_update
from repro.runtime.embed_service import EmbedShardService, GatherRequest


@dataclass
class KVRequest(GatherRequest):
    field: int = -1  # the field an update writes; -1 for a read
    value: np.ndarray | None = None  # the words it writes


class KVShardService(EmbedShardService):
    """Reads and one-field updates of records sharded over a cluster."""

    op_name = "kv_read"
    return_name = "kv_return"
    update_name = "kv_update"

    def __init__(self, cluster: Cluster, shards: list, fields: int,
                 max_slots: int = 64) -> None:
        if any(np.dtype(s.dtype) != np.int32 for s in shards):
            raise ValueError("records are int32 words")
        self.fields = fields
        self.field_words = shards[0].shape[1] // fields
        self.table = None  # the records stay in the shards
        self._serve(cluster, list(shards), n_keys=1, max_slots=max_slots)

    def _publish_ops(self) -> None:
        rows, words = self.rows_per_shard, self.dim
        publish = self.cluster.toolchain.publish
        publish(make_gatherer(rows, self.cluster.n_servers, 1, words, name=self.op_name,
                              returns=self.return_name, dtype=np.int32))
        publish(make_kv_update(rows, self.fields, self.field_words, words,
                               returns=self.return_name, name=self.update_name))
        publish(make_gather_return(self.max_slots, 1, words, name=self.return_name))

    def submit_read(self, key: int) -> int:
        """Queue a read of record ``key``; returns its request id."""
        return self._queue(KVRequest(self._next_rid, self._key(key)))

    def submit_update(self, key: int, field: int, value: np.ndarray) -> int:
        """Queue a write of ``value`` into field ``field`` of record ``key``;
        returns its request id.  It retires with the record after the
        write."""
        value = np.asarray(value, np.int32)
        if not 0 <= field < self.fields or value.shape != (self.field_words,):
            raise ValueError(f"an update writes one of {self.fields} fields of "
                             f"{self.field_words} words")
        return self._queue(KVRequest(self._next_rid, self._key(key), field=field, value=value))

    def _key(self, key: int) -> np.ndarray:
        if not 0 <= key < self.vocab:
            raise ValueError("key out of table range")
        return np.array([key], np.int32)

    def _queue(self, req: KVRequest) -> int:
        req.t_submit = time.perf_counter()
        self._next_rid += 1
        self.queue.append(req)
        return req.rid

    def _op(self, req: KVRequest) -> str:
        return self.op_name if req.field < 0 else self.update_name

    def _request_body(self, req: KVRequest) -> np.ndarray:
        if req.field < 0:
            return req.keys
        return np.concatenate([req.keys, [req.field], req.value]).astype(np.int32)

    def _span_args(self, reqs: list[KVRequest]) -> dict:
        return dict(super()._span_args(reqs), updates=sum(r.field >= 0 for r in reqs))
