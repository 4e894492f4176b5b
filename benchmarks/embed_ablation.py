"""Embedding-mode ablation: the paper's GET-vs-compute-to-data argument
measured inside the compiled LM.

Three ways to look up a token in a vocab-sharded table (models/embedding):
  c2d     ship indices, psum D-vectors back (the Chaser)
  gather  replicate the table first (GBPC)
  auto    whatever GSPMD picks for a plain take

Reports collective bytes per mode from the loop-corrected HLO analysis of
a small LM forward on 8 devices — the tensor-scale restatement of paper
Tables IV-VI: steady-state bytes on the wire decide everything.
"""

from __future__ import annotations


def run(vocab: int = 32_768, d_model: int = 256, batch: int = 8, seq: int = 128) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.analysis.hlo import analyze_hlo
    from repro.compat import make_mesh
    from repro.models.embedding import embed_c2d, embed_gather, embed_auto

    n_dev = jax.device_count()
    mesh = make_mesh((1, n_dev), ("data", "model"))
    table_sh = NamedSharding(mesh, P("model", None))
    ids_sh = NamedSharding(mesh, P(None, None))
    sds = jax.ShapeDtypeStruct
    table = sds((vocab, d_model), jnp.bfloat16)
    ids = sds((batch, seq), jnp.int32)

    fns = {
        "c2d": lambda t, i: embed_c2d(t, i, mesh, batch_axes=()),
        "gather": lambda t, i: embed_gather(t, i, mesh),
        "auto": lambda t, i: embed_auto(t, i),
    }
    out: dict = {
        "devices": n_dev, "vocab": vocab, "d_model": d_model,
        "tokens": batch * seq,
        "table_bytes": vocab * d_model * 2,
    }
    for name, fn in fns.items():
        c = jax.jit(fn, in_shardings=(table_sh, ids_sh)).lower(table, ids).compile()
        hc = analyze_hlo(c.as_text())
        out[name] = {
            "collective_bytes_per_dev": hc.collective_bytes,
            "by_kind": {k: round(v) for k, v in hc.collective_by_kind.items()},
            "bytes_per_token": round(hc.collective_bytes / (batch * seq), 1),
        }
    return out


def main() -> None:
    import json

    print(json.dumps(run(), indent=1, default=float))


if __name__ == "__main__":
    main()
