"""Vocab-sharded embedding lookup as a one-hot MXU matmul (Pallas).

The TPU has no fast arbitrary-gather from HBM, but its MXU eats
(tokens x vocab_tile) @ (vocab_tile x D) for breakfast: the classic TPU
embedding idiom is a *blocked one-hot matmul* — compare a token tile
against a vocab tile (producing a one-hot mask in VREGs, never in HBM)
and accumulate the matmul over vocab tiles.  Out-of-shard ids match no
tile and contribute zeros, which is exactly the partial-lookup semantics
the cross-shard psum needs (models/embedding.embed_c2d).

Grid: (tokens/BT, V_loc/BV) — vocab axis innermost-sequential, f32
accumulator in VMEM scratch.  VMEM per step (BT=256, BV=512, D<=8k bf16):
table tile 512xD + acc 256xD f32 =~ (D=6144) 6.3 + 6.3 MiB — fits; the
ops.py wrapper drops BV for very wide models.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _embed_kernel(lo_ref, ids_ref, tab_ref, o_ref, acc_scr, *, bv: int, nv: int):
    vi = pl.program_id(1)

    @pl.when(vi == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    ids = ids_ref[...]  # (BT,)
    tab = tab_ref[...]  # (BV, D)
    base = lo_ref[0] + vi * bv
    # one-hot in registers: (BT, BV)
    cols = base + jax.lax.broadcasted_iota(jnp.int32, (ids.shape[0], bv), 1)
    onehot = (ids[:, None] == cols).astype(tab.dtype)
    # an f32 table is contracted at f32 precision so each row comes back
    # bit-exact (Mosaic refuses the attribute on bf16 operands)
    precision = jax.lax.Precision.HIGHEST if tab.dtype == jnp.float32 else None
    acc_scr[...] += jax.lax.dot_general(
        onehot, tab, (((1,), (0,)), ((), ())),
        precision=precision, preferred_element_type=jnp.float32,
    )

    @pl.when(vi == nv - 1)
    def _finish():
        o_ref[...] = acc_scr[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bt", "bv", "interpret"))
def embed_lookup(
    table_shard: jax.Array,  # (V_loc, D)
    ids: jax.Array,  # (N,) int32 global ids
    lo: jax.Array,  # scalar int32 shard offset
    bt: int = 256,
    bv: int = 512,
    interpret: bool = False,
) -> jax.Array:
    v_loc, d = table_shard.shape
    n = ids.shape[0]
    bt = min(bt, n)
    bv = min(bv, v_loc)
    assert n % bt == 0 and v_loc % bv == 0, (n, bt, v_loc, bv)
    nv = v_loc // bv
    grid = (n // bt, nv)
    return pl.pallas_call(
        functools.partial(_embed_kernel, bv=bv, nv=nv),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((bt,), lambda ti, vi: (ti,)),
            pl.BlockSpec((bv, d), lambda ti, vi: (vi, 0)),
        ],
        out_specs=pl.BlockSpec((bt, d), lambda ti, vi: (ti, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d), table_shard.dtype),
        scratch_shapes=[pltpu.VMEM((bt, d), jnp.float32)],
        interpret=interpret,
    )(jnp.asarray(lo, jnp.int32).reshape(1), ids, table_shard)
