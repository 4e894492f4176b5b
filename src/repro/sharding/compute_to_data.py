"""The paper's X-RDMA pointer chase as a compiled SPMD tensor program.

``core/pointer_chase.py`` realizes DAPC faithfully: code frames really
travel between PEs, install, and recursively forward.  This module is the
TPU-idiomatic rendering of the *steady state* of the same algorithm (all
code cached everywhere — the regime the paper's own evaluation shows is
what matters): the pointer table is sharded over a mesh axis, B chases
advance as a lock-step frontier, and each round every shard resolves the
frontier entries it owns and the ownership exchange is a psum of
index-sized messages — the Chaser's FORWARD, as a collective.

* :func:`dapc_shard_map` — compute-to-data: per round, each shard looks
  up its owned subset locally (masked take) and the new frontier psums
  back.  Wire bytes per chase-hop: one int32 (times the collective
  factor) — independent of table size.

* :func:`gbpc_reference`  — move-data-to-compute: the client gathers the
  *table shard* entries it needs (all-gather in the worst case / one
  GET per hop in the faithful core version).

The per-shard local resolution uses masked takes on every backend: the
Pallas ``chase`` kernel (kernels/chase) is refused by the v5e compiler
("Only 2D gather is supported") and runs only in interpret mode.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.compat import shard_map as _shard_map


def dapc_shard_map(
    table: jax.Array,  # (N,) int32 successor table, sharded over ``axis``
    starts: jax.Array,  # (B,) int32, replicated
    depth: int,
    mesh: Mesh,
    axis: str = "model",
) -> jax.Array:
    """Lock-step frontier pointer chase, compute-to-data.

    Each round: every shard resolves frontier entries that live in its
    slice (masked local take), contributes zeros elsewhere, and the next
    frontier is the psum.  ``depth`` rounds total.  One chase is still a
    serial dependence chain (intrinsic to the workload); throughput comes
    from B concurrent chases, exactly like the paper's message-rate
    argument.
    """
    n = table.shape[0]
    shards = mesh.shape[axis]
    assert n % shards == 0
    local_n = n // shards

    def local(table_l: jax.Array, frontier: jax.Array) -> jax.Array:
        me = jax.lax.axis_index(axis)
        lo = me * local_n

        def hop(f, _):
            loc = f - lo
            inside = (loc >= 0) & (loc < local_n)
            nxt = jnp.take(table_l, jnp.clip(loc, 0, local_n - 1))
            nxt = jnp.where(inside, nxt, 0)
            # FORWARD: ship the index to whichever shard owns it next
            return jax.lax.psum(nxt, axis), None

        out, _ = jax.lax.scan(hop, frontier, None, length=depth)
        return out

    return _shard_map(
        local, mesh=mesh, in_specs=(P(axis), P()), out_specs=P()
    )(table, starts)


def gather_shard_map(
    table: jax.Array,  # (V, D) embedding rows, sharded over ``axis``
    keys: jax.Array,  # (B,) int32 global row ids, replicated
    mesh: Mesh,
    axis: str = "model",
    use_pallas: bool | None = None,
) -> jax.Array:
    """Steady-state X-RDMA Gather as a collective program (the serving-shape
    sibling of :func:`dapc_shard_map`).

    Each shard resolves the keys it owns — the Pallas ``embed_lookup``
    one-hot-MXU kernel on TPU, the masked-take reference elsewhere — and
    contributes zero rows for the rest; the psum is the Gatherer's partial
    RETURNs meeting in the requester's completion slot.  Wire bytes per
    key: one D-row (times the collective factor) — the table never moves,
    exactly the runtime rendering's byte accounting.
    """
    v = table.shape[0]
    shards = mesh.shape[axis]
    assert v % shards == 0
    local_v = v // shards
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"

    def local(table_l: jax.Array, ks: jax.Array) -> jax.Array:
        me = jax.lax.axis_index(axis)
        lo = (me * local_v).astype(jnp.int32)
        if use_pallas:
            from repro.kernels.embed_lookup.kernel import embed_lookup

            part = embed_lookup(table_l, ks, lo)
        else:
            from repro.kernels.embed_lookup.ref import embed_lookup_ref

            part = embed_lookup_ref(table_l, ks, lo)
        # partial RETURN: rows psum to the requester, zeros elsewhere
        return jax.lax.psum(part, axis)

    return _shard_map(
        local, mesh=mesh, in_specs=(P(axis), P()), out_specs=P()
    )(table, keys)


def gather_ref(table, keys):
    """Pure numpy oracle: a plain row take."""
    import numpy as np

    return np.asarray(table)[np.asarray(keys)]


def gbpc_reference(
    table: jax.Array,
    starts: jax.Array,
    depth: int,
    mesh: Mesh | None = None,
) -> jax.Array:
    """GET-style baseline: chase against the (logically) gathered table.

    Under GSPMD with a sharded table this forces the all-gather — the
    tensor-scale equivalent of the client pulling entries to itself.
    """
    if mesh is not None:
        table = jax.lax.with_sharding_constraint(table, NamedSharding(mesh, P()))

    def hop(f, _):
        return jnp.take(table, f), None

    out, _ = jax.lax.scan(hop, starts, None, length=depth)
    return out


def chase_oracle(table, starts, depth):
    """Pure numpy oracle."""
    import numpy as np

    f = np.asarray(starts).copy()
    t = np.asarray(table)
    for _ in range(depth):
        f = t[f]
    return f
