"""X-RDMA operations: Chaser, ReturnResult, TSI (paper Secs. IV-B/IV-C).

An X-RDMA operation is an ifunc whose arrival *executes user code next to
the data*, and whose code may re-inject itself (FORWARD), answer the
requester (RETURN via ReturnResult), or generate new code (SPAWN).  The
decision logic lives in the shipped code; see :mod:`repro.core.pe.exec` for
the fixed action ABI.

All integer state is int32: tables up to 2^31 entries, which keeps the core
independent of the global ``jax_enable_x64`` flag (the LM framework must
stay bf16/f32-default).
"""

from __future__ import annotations

import struct
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .dataplane import SlabLayout
from .frame import FrameKind
from .pe import (
    ACTION_WIDTH,
    A_DONE,
    A_FORWARD,
    A_NOP,
    A_PUBLISH,
    A_RETURN,
    A_SPAWN,
    IFunc,
)
from .transport import RegionWrite

I32 = jnp.int32
CHASER_PAYLOAD = 4  # [addr, depth, requester, slot]
GATHER_HDR = 3  # [requester, slot, epoch] routing header (PE.submit convention)


def _vec(*slots) -> jax.Array:
    """Build a padded i32 action vector from (action, dst, plen, payload...).

    One stack+concatenate instead of a chained ``.at[i].set`` scatter loop:
    same result, ACTION_WIDTH-times fewer ops in every traced action graph.
    """
    vals = jnp.stack([jnp.asarray(s, I32) for s in slots])
    return jnp.concatenate([vals, jnp.zeros((ACTION_WIDTH - len(slots),), I32)])


# ------------------------------------------------------------------ Chaser
def chaser_entry(payload: jax.Array, shard: jax.Array, meta: jax.Array) -> jax.Array:
    """One X-RDMA Chaser hop (paper Sec. IV-C).

    Chase locally (``lax.while_loop`` — the paper's in-process recursive
    call) until the chase completes or the frontier leaves this shard; then
    RETURN the result to the requester or FORWARD *this same code* to the
    owner of the next entry.
    """
    addr0, depth0, requester, slot = payload[0], payload[1], payload[2], payload[3]
    shard_id, shard_size = meta[0], meta[1]
    base = shard_id * shard_size

    def cond(c):
        a, d = c
        return (d > 0) & (a // shard_size == shard_id)

    def body(c):
        a, d = c
        return shard[a - base], d - 1

    addr, depth = lax.while_loop(cond, body, (addr0, depth0))
    done = depth == 0
    ret = _vec(A_RETURN, requester, 2, slot, addr)
    fwd = _vec(A_FORWARD, addr // shard_size, 4, addr, depth, requester, slot)
    return jnp.where(done, ret, fwd)


def make_chaser(
    shard_size: int,
    targets: Sequence[str] = ("cpu-host", "cpu-bf2", "cpu-a64fx", "tpu-v5e"),
    kind: FrameKind = FrameKind.BITCODE,
    name: str = "chaser",
) -> IFunc:
    return IFunc.build(
        name=name,
        fn=chaser_entry,
        payload_aval=jax.ShapeDtypeStruct((CHASER_PAYLOAD,), I32),
        dep_avals=(
            jax.ShapeDtypeStruct((shard_size,), I32),
            jax.ShapeDtypeStruct((3,), I32),
        ),
        deps=("region:table_shard", "cap:shard_meta", "returns:return_result"),
        abi="xrdma",
        targets=targets,
        kind=kind,
    )


# ------------------------------------------------------------ ReturnResult
def return_result_entry(payload: jax.Array, results: jax.Array) -> jax.Array:
    """Write ``value`` into the requester's result slot and bump the
    completion counter (last element)."""
    slot, value = payload[0], payload[1]
    return results.at[slot].set(value).at[results.shape[0] - 1].add(1)


def _chase_slab(max_slots: int, region: str = "results") -> SlabLayout:
    """Zero-copy layout of the chase result buffer: one i32 word per slot
    plus the completion counter at the end.  A RETURN payload ``[slot,
    value]`` becomes one 4-byte WRITE at ``slot*4`` whose doorbell
    FETCH_ADDs the counter word — the paper's 'final PUT' verbatim."""

    def plan(pay: np.ndarray) -> list[RegionWrite]:
        slot, value = int(pay[0]), int(pay[1])
        return [
            RegionWrite(
                region,
                slot * 4,
                struct.pack("<i", value),
                doorbell=(max_slots * 4, 1, "add"),
            )
        ]

    return SlabLayout(region=region, plan=plan)


def make_return_result(
    max_slots: int,
    targets: Sequence[str] = ("cpu-host", "cpu-bf2", "cpu-a64fx", "tpu-v5e"),
    kind: FrameKind = FrameKind.BITCODE,
) -> IFunc:
    return IFunc.build(
        name="return_result",
        fn=return_result_entry,
        payload_aval=jax.ShapeDtypeStruct((2,), I32),
        dep_avals=(jax.ShapeDtypeStruct((max_slots + 1,), I32),),
        deps=("region:results",),
        abi="update",
        targets=targets,
        kind=kind,
        slab=_chase_slab(max_slots),
    )


# ----------------------------------------------------------------- Gather
def _take_rows(shard: jax.Array, keys: jax.Array, lo: jax.Array) -> jax.Array:
    """Masked-take local resolution: rows for keys inside [lo, lo+V_loc),
    zeros elsewhere (the reference semantics of kernels.embed_lookup)."""
    v_loc = shard.shape[0]
    loc = keys - lo
    inside = (loc >= 0) & (loc < v_loc)
    rows = jnp.take(shard, jnp.clip(loc, 0, v_loc - 1), axis=0)
    return jnp.where(inside[:, None], rows, jnp.zeros((), shard.dtype))


def make_gatherer(
    rows_per_shard: int,
    n_servers: int,
    n_keys: int,
    dim: int,
    targets: Sequence[str] = ("cpu-host", "cpu-bf2", "cpu-a64fx", "tpu-v5e"),
    kind: FrameKind = FrameKind.BITCODE,
    name: str = "gatherer",
    returns: str = "gather_return",
    pallas_tpu: bool = True,
    dtype=jnp.float32,
) -> IFunc:
    """The X-RDMA Gather op: one hop of a sharded embedding/KV-row gather.

    Payload (completion-queue convention): ``[requester, slot, epoch,
    key0..key_{K-1}]`` with unused key positions padded to -1.  ``epoch``
    is the slot's generation tag: a late or re-delivered RETURN whose
    epoch no longer matches the slot's is dropped by the RETURN code, so
    slot recycling is safe under at-least-once delivery.  On arrival the
    shipped code

    * resolves the locally-owned subset of the keys against the shard
      region (Pallas ``embed_lookup`` in the TPU slice, masked-take
      reference elsewhere — both produce the identical rows),
    * FORWARDs the unresolved remainder to the owning PE(s), preserving
      each key's *position* so every partial RETURN scatters into the
      right rows of the requester's slot (non-owned positions travel as
      -1), and
    * RETURNs the resolved rows (bit-cast f32->i32, never converted; an
      int32 shard's words as they are) plus their positions and a count to
      the requester's completion queue.

    One action matrix of ``n_servers + 1`` rows covers every case: row
    ``s`` is the potential FORWARD to server ``s``, the last row the
    partial RETURN; unneeded rows are NOPs.  A request whose keys span
    ``m`` shards costs ``m`` RETURNs and at most ``m`` FORWARDs — network
    actions only on locality breaks, exactly the Chaser's contract.
    """
    K, D, S = n_keys, dim, n_servers
    if K > 31:
        raise ValueError("n_keys > 31 would overflow the i32 position bitmask")
    words = jnp.dtype(dtype) == I32  # a word shard: its rows travel as they are
    ret_plen = 3 + K + K * D  # [slot, epoch, nres, pos(K), rows(K*D)]
    width = 3 + ret_plen  # rectangular action matrix; FORWARD rows zero-pad

    def entry_with(resolve):
        def entry(payload: jax.Array, shard: jax.Array, meta: jax.Array) -> jax.Array:
            requester, slot, epoch = payload[0], payload[1], payload[2]
            keys = payload[GATHER_HDR:]
            shard_id, rows_per = meta[0], meta[1]
            lo = shard_id * rows_per
            loc = keys - lo
            real = keys >= 0
            mine = real & (loc >= 0) & (loc < rows_per)
            rows = resolve(shard, keys, lo)  # (K, D), zeros off-shard
            rows = jnp.where(mine[:, None], rows, jnp.zeros((), rows.dtype))
            if not words:
                rows = lax.bitcast_convert_type(rows.astype(jnp.float32), I32)
            irows = rows.reshape(-1)
            pos = jnp.arange(K, dtype=I32)
            nres = jnp.sum(mine.astype(I32))
            ret = jnp.concatenate(
                [
                    jnp.stack(
                        [
                            jnp.where(nres > 0, A_RETURN, A_NOP).astype(I32),
                            requester.astype(I32),
                            jnp.asarray(ret_plen, I32),
                        ]
                    ),
                    jnp.stack([slot, epoch, nres]).astype(I32),
                    jnp.where(mine, pos, -1).astype(I32),
                    irows,
                ]
            )
            # one potential FORWARD row per peer shard (position-preserving)
            owner = jnp.where(real & ~mine, keys // rows_per, -1)
            zpad = jnp.zeros((K * D,), I32)
            fwd_rows = []
            for s in range(S):
                take = owner == s
                cnt = jnp.sum(take.astype(I32))
                fwd_rows.append(
                    jnp.concatenate(
                        [
                            jnp.stack(
                                [
                                    jnp.where(cnt > 0, A_FORWARD, A_NOP).astype(I32),
                                    jnp.asarray(s, I32),
                                    jnp.asarray(GATHER_HDR + K, I32),
                                ]
                            ),
                            jnp.stack([requester, slot, epoch]).astype(I32),
                            jnp.where(take, keys, -1).astype(I32),
                            zpad,
                        ]
                    )
                )
            return jnp.stack([*fwd_rows, ret])  # (S + 1, width)

        return entry

    fn_by_platform = None
    # the TPU slice carries the Pallas one-hot-MXU resolver when the shard
    # shape satisfies its blocking constraints (portable entry otherwise)
    if pallas_tpu and not words and (rows_per_shard <= 512 or rows_per_shard % 512 == 0):
        from repro.kernels.embed_lookup.kernel import embed_lookup

        def pallas_resolve(shard, keys, lo):
            return embed_lookup(shard, keys, lo, bt=min(256, K))

        fn_by_platform = {"tpu": entry_with(pallas_resolve)}

    return IFunc.build(
        name=name,
        fn=entry_with(_take_rows),
        payload_aval=jax.ShapeDtypeStruct((GATHER_HDR + K,), I32),
        dep_avals=(
            jax.ShapeDtypeStruct((rows_per_shard, D), I32 if words else jnp.float32),
            jax.ShapeDtypeStruct((3,), I32),
        ),
        deps=("region:embed_shard", "cap:gather_meta", f"returns:{returns}"),
        abi="xrdma",
        targets=targets,
        kind=kind,
        fn_by_platform=fn_by_platform,
    )


def _gather_slab(n_keys: int, dim: int, region: str = "cq_results") -> SlabLayout:
    """Zero-copy layout of one completion-queue slot: row ``[posmask,
    epoch, data(K*D)]`` of i32 words.  A partial RETURN's resolved rows
    become contiguous-run WRITE segments at their position offsets; the
    doorbell ORs the arrived-position bits into ``posmask`` (idempotent
    under re-delivery, same as the framed fold) and the guard pins the
    slot's generation — a stale write for a retired gather is refused at
    the 'NIC' instead of corrupting the slot's next owner."""
    K, D = n_keys, dim
    stride = (2 + K * D) * 4  # slot row bytes

    def plan(pay: np.ndarray) -> list[RegionWrite]:
        slot, epoch = int(pay[0]), int(pay[1])
        pos = pay[3 : 3 + K]
        rows = pay[3 + K :].reshape(K, D)
        base = slot * stride
        guard = (base + 4, epoch)
        valid = np.flatnonzero(pos >= 0)
        if valid.size == 0:
            return []
        bits = int(np.bitwise_or.reduce(1 << (pos[valid].astype(np.int64))))
        # contiguous (index, position) runs -> one scatter segment each
        breaks = np.where(
            (np.diff(valid) != 1) | (np.diff(pos[valid]) != 1)
        )[0] + 1
        writes = []
        for run in np.split(valid, breaks):
            i0, i1 = int(run[0]), int(run[-1])
            writes.append(
                RegionWrite(
                    region,
                    base + (2 + int(pos[i0]) * D) * 4,
                    rows[i0 : i1 + 1].tobytes(),
                    guard=guard,
                )
            )
        # the doorbell rides the last segment: it fires only after every
        # data word of this partial landed (fenced WQE chain)
        last = writes[-1]
        writes[-1] = RegionWrite(
            last.region, last.offset, last.data,
            doorbell=(base, bits, "or"), guard=guard,
        )
        return writes

    return SlabLayout(region=region, plan=plan)


def make_gather_return(
    max_slots: int,
    n_keys: int,
    dim: int,
    region: str = "cq_results",
    targets: Sequence[str] = ("cpu-host", "cpu-bf2", "cpu-a64fx", "tpu-v5e"),
    kind: FrameKind = FrameKind.BITCODE,
    name: str = "gather_return",
) -> IFunc:
    """Scatter one partial gather result into the requester's completion
    queue: rows land at their request positions (out-of-order safe, any
    interleaving of slots), and the slot's arrived-position *bitmask* ORs
    in the positions this partial carried.  The bitmask (not a counter)
    is what makes at-least-once delivery safe within a generation: a
    re-delivered partial ORs bits already set and scatters rows already
    written — exactly idempotent — so completion (popcount == expected)
    can never fire early off a duplicate.  A RETURN whose epoch does not
    match the slot's current generation is a late result for a *retired*
    gather — dropped whole, so a recycled slot can never be corrupted by
    stale traffic.  Update-ABI, so a burst of partial returns folds into
    the region in one dispatch under the batched runtime.

    Region row layout: ``[posmask, epoch, data(K*D)]``."""
    K, D = n_keys, dim
    if K > 31:
        raise ValueError("n_keys > 31 would overflow the i32 position bitmask")

    def entry(payload: jax.Array, results: jax.Array) -> jax.Array:
        slot, epoch = payload[0], payload[1]  # payload[2] = nres (diagnostic)
        pos = payload[3 : 3 + K]
        rows = payload[3 + K :].reshape(K, D)
        cur = results[slot]
        live = cur[1] == epoch  # stale-generation RETURNs drop whole
        valid = pos >= 0
        bits = jnp.sum(
            jnp.where(valid, jnp.left_shift(jnp.int32(1), jnp.clip(pos, 0, 30)), 0)
        )
        safe = jnp.where(valid, pos, K)  # K = out of bounds -> dropped
        block = cur[2:].reshape(K, D).at[safe].set(rows, mode="drop")
        newrow = jnp.concatenate(
            [(cur[0] | bits)[None], cur[1][None], block.reshape(-1)]
        )
        return results.at[slot].set(jnp.where(live, newrow, cur))

    return IFunc.build(
        name=name,
        fn=entry,
        payload_aval=jax.ShapeDtypeStruct((3 + K + K * D,), I32),
        dep_avals=(jax.ShapeDtypeStruct((max_slots, 2 + K * D), I32),),
        deps=(f"region:{region}",),
        abi="update",
        targets=targets,
        kind=kind,
        slab=_gather_slab(n_keys, dim, region),
    )


# -------------------------------------------------------------- KV update
KV_UPDATE_HDR = GATHER_HDR + 2  # [requester, slot, epoch, key, field]


def make_kv_update(
    rows_per_shard: int,
    fields: int,
    field_words: int,
    record_words: int,
    returns: str = "gather_return",
    targets: Sequence[str] = ("cpu-host", "cpu-bf2", "cpu-a64fx", "tpu-v5e"),
    kind: FrameKind = FrameKind.BITCODE,
    name: str = "kv_update",
) -> IFunc:
    """Write one field of one record where the record lives.

    Payload ``[requester, slot, epoch, key, field, value(field_words)]``,
    entering at the key's owner: the record's words ``field * field_words``
    onwards become ``value`` (a ``dynamic_update_slice`` of the shard,
    which the runtime donates, so the write is made in place).  One RETURN
    in :func:`make_gather_return`'s format (``K = 1``, ``D =
    record_words``) carries the record as it stands after the write, so
    the acknowledgement is also a read of it.  Propagate ABI: the write and
    the RETURN row come from one entry, and a batch of updates folds into
    the shard in payload order in one dispatch."""
    if fields * field_words > record_words:
        raise ValueError("the fields do not fit the record")
    W = record_words
    ret_plen = 3 + 1 + W  # [slot, epoch, nres, pos, record]

    def entry(payload: jax.Array, shard: jax.Array, meta: jax.Array):
        requester, slot, epoch = payload[0], payload[1], payload[2]
        key, field = payload[GATHER_HDR], payload[GATHER_HDR + 1]
        row = key - meta[0] * meta[1]
        shard = lax.dynamic_update_slice(
            shard, payload[KV_UPDATE_HDR:][None, :], (row, field * field_words))
        record = lax.dynamic_slice(shard, (row, jnp.asarray(0, I32)), (1, W)).reshape(-1)
        head = jnp.stack([A_RETURN, requester, ret_plen, slot, epoch, 1, 0])
        return shard, jnp.concatenate([head.astype(I32), record])[None, :]

    return IFunc.build(
        name=name,
        fn=entry,
        payload_aval=jax.ShapeDtypeStruct((KV_UPDATE_HDR + field_words,), I32),
        dep_avals=(
            jax.ShapeDtypeStruct((rows_per_shard, W), I32),
            jax.ShapeDtypeStruct((3,), I32),
        ),
        deps=("region:embed_shard", "cap:gather_meta", f"returns:{returns}"),
        abi="propagate",
        targets=targets,
        kind=kind,
    )


# ----------------------------------------------------------------- Filter
FILTER_HDR = GATHER_HDR + 2  # [requester, slot, epoch, lo, thresh_bits]


def make_filter(
    rows_per_shard: int,
    n_servers: int,
    window: int,
    dim: int,
    targets: Sequence[str] = ("cpu-host", "cpu-bf2", "cpu-a64fx", "tpu-v5e"),
    kind: FrameKind = FrameKind.BITCODE,
    name: str = "filter",
    returns: str = "filter_return",
    pallas_tpu: bool = True,
) -> IFunc:
    """The DPU predicate-pushdown op: filter a contiguous row window *next
    to the shard* and RETURN only the survivors.

    Payload ``[requester, slot, epoch, lo, thresh_bits]``: scan the
    ``window`` rows at global offset ``lo`` (the service aligns windows
    inside one shard), keep rows whose first column exceeds the f32
    threshold (``thresh_bits`` travels bit-cast through the i32 payload),
    and emit ONE ragged RETURN row::

        [slot, epoch, evalmask, spos(W), rows(nsurv*D)]

    with ``plen = 3 + W + nsurv*D`` — the action row's self-describing
    ``plen`` means only the survivor rows cross the wire, which is the
    whole point of pushdown: wire payload bytes scale with selectivity,
    not with the window.  ``spos`` carries the survivors' window
    positions packed to the front (-1 beyond ``nsurv``); ``evalmask`` is
    the full window bitmask, so completion fires after one RETURN even
    when *nothing* survives.  Dropped positions read as zeros at the
    requester (CQ slots are zeroed at alloc), matching the masked oracle
    ``where(pred, rows, 0)``.

    Per-ISA slices via ``fn_by_platform`` (paper Fig. 3): the CPU/TPU
    slices resolve the window with a dynamic slice (Pallas ``embed_lookup``
    on TPU when the shard blocking allows), while the DPU (``cpu-bf2``)
    slice ships a masked-take body — the BF2's Arm cores prefer the
    branch-free gather over a strided slice.  Every slice computes
    identical survivors; only the lowering differs.
    """
    W, D, S = window, dim, n_servers
    if W > 31:
        raise ValueError("window > 31 would overflow the i32 position bitmask")
    evalmask = (1 << W) - 1
    ret_hdr = 3  # [slot, epoch, evalmask]
    width = 3 + ret_hdr + W + W * D  # max plen: every row survives

    def entry_with(resolve):
        def entry(payload: jax.Array, shard: jax.Array, meta: jax.Array) -> jax.Array:
            requester, slot, epoch = payload[0], payload[1], payload[2]
            lo = payload[3]
            thresh = lax.bitcast_convert_type(payload[4], jnp.float32)
            shard_id, rows_per = meta[0], meta[1]
            base = shard_id * rows_per
            rows = resolve(shard, lo, base)  # (W, D) f32 window
            passed = rows[:, 0] > thresh
            nsurv = jnp.sum(passed.astype(I32))
            # survivors packed to the front, original window order kept
            order = jnp.argsort(~passed, stable=True).astype(I32)
            packed = jnp.arange(W, dtype=I32) < nsurv
            spos = jnp.where(packed, order, -1)
            srows = jnp.where(packed[:, None], rows[order], 0.0)
            irows = lax.bitcast_convert_type(
                srows.astype(jnp.float32), I32
            ).reshape(-1)
            plen = ret_hdr + W + nsurv * D  # ragged: survivors only
            return jnp.concatenate(
                [
                    jnp.stack(
                        [jnp.asarray(A_RETURN, I32), requester.astype(I32), plen]
                    ),
                    jnp.stack([slot, epoch, jnp.asarray(evalmask, I32)]),
                    spos,
                    irows,
                ]
            )  # one self-describing action row of `width` i32 words

        return entry

    def sliced_resolve(shard, lo, base):
        return lax.dynamic_slice(shard, (lo - base, jnp.asarray(0, I32)), (W, D))

    def masked_take_resolve(shard, lo, base):
        return _take_rows(shard, lo + jnp.arange(W, dtype=I32), base)

    fn_by_platform: dict = {"cpu-bf2": entry_with(masked_take_resolve)}
    # the TPU slice carries the Pallas resolver under the same blocking
    # constraints as the Gatherer (portable sliced entry otherwise)
    if pallas_tpu and (rows_per_shard <= 512 or rows_per_shard % 512 == 0):
        from repro.kernels.embed_lookup.kernel import embed_lookup

        def pallas_resolve(shard, lo, base):
            keys = lo + jnp.arange(W, dtype=I32)
            return embed_lookup(shard, keys, base, bt=min(256, W))

        fn_by_platform["tpu"] = entry_with(pallas_resolve)

    return IFunc.build(
        name=name,
        fn=entry_with(sliced_resolve),
        payload_aval=jax.ShapeDtypeStruct((FILTER_HDR,), I32),
        dep_avals=(
            jax.ShapeDtypeStruct((rows_per_shard, D), jnp.float32),
            jax.ShapeDtypeStruct((3,), I32),
        ),
        deps=("region:embed_shard", "cap:gather_meta", f"returns:{returns}"),
        abi="xrdma",
        targets=targets,
        kind=kind,
        fn_by_platform=fn_by_platform,
    )


def _filter_slab(window: int, dim: int, region: str = "cq_results") -> SlabLayout:
    """Zero-copy layout of a Filter RETURN over the gather CQ slot row
    ``[posmask, epoch, data(W*D)]``: survivor rows become contiguous-run
    WRITE segments at their window-position offsets and the doorbell ORs
    the *evalmask* (whole window observed) — so the chain stays
    proportional to survivors while completion still fires, even with an
    empty survivor set (doorbell-only write).  Ragged-aware: the payload
    the sender hands over carries ``3 + W + nsurv*D`` words."""
    W, D = window, dim
    stride = (2 + W * D) * 4  # slot row bytes

    def plan(pay: np.ndarray) -> list[RegionWrite]:
        slot, epoch, evalmask = int(pay[0]), int(pay[1]), int(pay[2])
        spos = pay[3 : 3 + W]
        nsurv = int(np.sum(spos >= 0))
        rows = pay[3 + W : 3 + W + nsurv * D].reshape(nsurv, D)
        base = slot * stride
        guard = (base + 4, epoch)
        writes = []
        if nsurv:
            pos = spos[:nsurv].astype(np.int64)
            # survivors are packed; split only on window-position gaps
            breaks = np.where(np.diff(pos) != 1)[0] + 1
            for run in np.split(np.arange(nsurv), breaks):
                i0, i1 = int(run[0]), int(run[-1])
                writes.append(
                    RegionWrite(
                        region,
                        base + (2 + int(pos[i0]) * D) * 4,
                        rows[i0 : i1 + 1].tobytes(),
                        guard=guard,
                    )
                )
        if writes:
            last = writes[-1]
            writes[-1] = RegionWrite(
                last.region, last.offset, last.data,
                doorbell=(base, evalmask, "or"), guard=guard,
            )
        else:
            # nothing survived: the doorbell alone completes the window
            writes.append(
                RegionWrite(
                    region, base, b"", doorbell=(base, evalmask, "or"), guard=guard
                )
            )
        return writes

    return SlabLayout(region=region, plan=plan)


def make_filter_return(
    max_slots: int,
    window: int,
    dim: int,
    region: str = "cq_results",
    targets: Sequence[str] = ("cpu-host", "cpu-bf2", "cpu-a64fx", "tpu-v5e"),
    kind: FrameKind = FrameKind.BITCODE,
    name: str = "filter_return",
) -> IFunc:
    """Fold one Filter RETURN into the requester's completion queue.

    Same idempotent position-scatter discipline as ``gather_return`` —
    OR the arrived bits, scatter rows by position with ``mode="drop"``,
    drop stale-epoch returns whole — with two filter-specific twists.
    The bits come from the payload's ``evalmask`` word: the whole window
    was *observed* even where nothing survived (unobserved is different
    from empty), so one RETURN completes the window regardless of the
    survivor count.  And the payload is **ragged**: only ``nsurv`` rows
    travel behind the always-full ``spos`` vector, and the
    ``ragged:zeros`` dep tag tells the exec layer to zero-extend to the
    declared aval — safe because the ``-1`` sentinels in ``spos`` arrive
    intact and mask off exactly the zero-padded row slots.

    Region row layout: ``[posmask, epoch, data(W*D)]``."""
    W, D = window, dim
    if W > 31:
        raise ValueError("window > 31 would overflow the i32 position bitmask")

    def entry(payload: jax.Array, results: jax.Array) -> jax.Array:
        slot, epoch, evalmask = payload[0], payload[1], payload[2]
        spos = payload[3 : 3 + W]
        rows = payload[3 + W :].reshape(W, D)
        cur = results[slot]
        live = cur[1] == epoch  # stale-generation RETURNs drop whole
        valid = spos >= 0  # packed survivor prefix; -1 beyond nsurv
        bits = evalmask  # the whole window was observed
        safe = jnp.where(valid, spos, W)  # W = out of bounds -> dropped
        block = cur[2:].reshape(W, D).at[safe].set(rows, mode="drop")
        newrow = jnp.concatenate(
            [(cur[0] | bits)[None], cur[1][None], block.reshape(-1)]
        )
        return results.at[slot].set(jnp.where(live, newrow, cur))

    return IFunc.build(
        name=name,
        fn=entry,
        payload_aval=jax.ShapeDtypeStruct((3 + W + W * D,), I32),
        dep_avals=(jax.ShapeDtypeStruct((max_slots, 2 + W * D), I32),),
        deps=(f"region:{region}", "ragged:zeros"),
        abi="update",
        targets=targets,
        kind=kind,
        slab=_filter_slab(window, dim, region),
    )


# --------------------------------------------------------------------- TSI
def tsi_entry(payload: jax.Array, counter: jax.Array) -> jax.Array:
    """Target-Side Increment (paper Sec. IV-B): counter += payload[0]."""
    return counter + payload[0]


def make_tsi(
    targets: Sequence[str] = ("cpu-host", "cpu-bf2", "cpu-a64fx", "tpu-v5e"),
    kind: FrameKind = FrameKind.BITCODE,
    name: str = "tsi",
) -> IFunc:
    return IFunc.build(
        name=name,
        fn=tsi_entry,
        payload_aval=jax.ShapeDtypeStruct((1,), I32),
        dep_avals=(jax.ShapeDtypeStruct((1,), I32),),
        deps=("region:counter",),
        abi="update",
        targets=targets,
        kind=kind,
    )


# ------------------------------------------------------------------ Reduce
def make_reducer(
    width: int,
    targets: Sequence[str] = ("cpu-host", "cpu-bf2", "cpu-a64fx", "tpu-v5e"),
    kind: FrameKind = FrameKind.BITCODE,
    name: str = "reducer",
) -> IFunc:
    """The multi-hop X-RDMA reduction op (one node's step of
    :func:`repro.sharding.collectives.xrdma_reduce`).

    Propagate-ABI: every invocation folds one contribution into this PE's
    ``reduce_acc`` region — ``[count, acc(width)]`` — and emits at most one
    action row.  Payload ``[count, value(width)]``:

    * ``count == 0`` is the broadcast *seed* (delivered by the tree
      publish): fold this PE's own ``reduce_src`` contribution, count 1.
    * ``count > 0`` is a child subtree's partial: fold ``value``, count
      the subtree's nodes.

    When the fold's count reaches the subtree size in ``reduce_meta``
    (``[expected, parent, is_root]``), the completing invocation FORWARDs
    the folded partial — this same ifunc, code and all — to the tree
    parent; at the root it emits DONE with the cluster-wide result.  Under
    the batched runtime several children's partials fold in one masked
    ``lax.scan`` dispatch and only the row that completes the subtree
    carries the upward FORWARD — the scan's sequential carry is exactly
    the fold-before-forward the tree needs.

    At-least-once caveat: seed delivery is deduplicated by the publish
    layer, but a *duplicated child partial* would double-fold and overshoot
    ``expected`` — the count then never equals it and the reduction
    surfaces as an idle timeout (loud containment), matching the paper's
    reliable-connection transport assumption for RETURN traffic.
    """
    W = width

    def entry(
        payload: jax.Array, acc: jax.Array, src: jax.Array, meta: jax.Array
    ) -> tuple[jax.Array, jax.Array]:
        count, val = payload[0], payload[1:]
        seed = count == 0
        new_cnt = acc[0] + jnp.where(seed, jnp.asarray(1, I32), count)
        new_val = acc[1:] + jnp.where(seed, src, val)
        expected, parent, is_root = meta[0], meta[1], meta[2]
        done = new_cnt == expected
        action = jnp.where(
            done, jnp.where(is_root > 0, A_DONE, A_FORWARD), A_NOP
        ).astype(I32)
        dst = jnp.where(done & (is_root == 0), parent, 0).astype(I32)
        plen = jnp.where(done, 1 + W, 0).astype(I32)
        new_acc = jnp.concatenate([new_cnt[None], new_val])
        row = jnp.concatenate([jnp.stack([action, dst, plen]), new_acc])
        return new_acc, row

    return IFunc.build(
        name=name,
        fn=entry,
        payload_aval=jax.ShapeDtypeStruct((1 + W,), I32),
        dep_avals=(
            jax.ShapeDtypeStruct((1 + W,), I32),
            jax.ShapeDtypeStruct((W,), I32),
            jax.ShapeDtypeStruct((3,), I32),
        ),
        deps=("region:reduce_acc", "region:reduce_src", "cap:reduce_meta"),
        abi="propagate",
        targets=targets,
        kind=kind,
    )


# ------------------------------------------------------------------ Gossip
def make_gossiper(
    targets: Sequence[str] = ("cpu-host", "cpu-bf2", "cpu-a64fx", "tpu-v5e"),
    name: str = "gossiper",
) -> IFunc:
    """Injected code that re-publishes *itself* (paper Sec. I, literally).

    Payload ``[hops_left, value]``; deps ``region:gossip_log`` (``[visits,
    sum]``) and ``cap:gossip_meta`` (``[my_index, n_peers]``).  Each
    arrival logs itself locally and, while ``hops_left > 0``, emits
    ``A_PUBLISH`` to the next peer on the ring — the *code* decides where
    its next copy goes; the runtime only carries it.  Hop budget 1 per
    publish, so the tree layer never fans this out: the recursion is
    entirely the ifunc's own.
    """

    def entry(
        payload: jax.Array, log: jax.Array, meta: jax.Array
    ) -> tuple[jax.Array, jax.Array]:
        hops, value = payload[0], payload[1]
        me, n = meta[0], meta[1]
        new_log = jnp.stack([log[0] + 1, log[1] + value])
        nxt = jnp.where(me + 1 >= n, 0, me + 1)
        row = jnp.where(
            hops > 0,
            _vec(A_PUBLISH, nxt, 3, 1, hops - 1, value),
            _vec(A_NOP, 0, 0),
        )
        return new_log, row

    return IFunc.build(
        name=name,
        fn=entry,
        payload_aval=jax.ShapeDtypeStruct((2,), I32),
        dep_avals=(
            jax.ShapeDtypeStruct((2,), I32),
            jax.ShapeDtypeStruct((2,), I32),
        ),
        deps=("region:gossip_log", "cap:gossip_meta"),
        abi="propagate",
        targets=targets,
    )


# ------------------------------------------------------------------- Spawn
def spawner_entry(payload: jax.Array) -> jax.Array:
    """Demo of 'injected code generating new code' (paper Sec. I): arrival
    spawns a TSI ifunc at peer ``payload[0]`` with increment ``payload[1]``."""
    return _vec(A_SPAWN, payload[0], 1, payload[1])


def make_spawner(
    targets: Sequence[str] = ("cpu-host", "cpu-bf2", "cpu-a64fx", "tpu-v5e"),
) -> IFunc:
    return IFunc.build(
        name="spawner",
        fn=spawner_entry,
        payload_aval=jax.ShapeDtypeStruct((2,), I32),
        deps=("spawn:tsi",),
        abi="xrdma",
        targets=targets,
    )
