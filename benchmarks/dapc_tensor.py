"""DAPC at tensor scale: the compiled SPMD pointer chase (DESIGN.md §2).

Compares the collective bytes of the compute-to-data chase
(sharding/compute_to_data.dapc_shard_map — indices travel) against the
GET-style baseline (gbpc_reference — the table is gathered), using the
same loop-aware HLO analysis as the dry-run.  This is the paper's Fig 5-8
argument re-run inside the compiler: bytes-on-the-wire per hop is the
whole story, and here the byte counts come from the partitioned HLO.

Also validates both against the numpy oracle on the host device count.
"""

from __future__ import annotations

import numpy as np


def run(n_entries: int = 1 << 22, batch: int = 256, depth: int = 64) -> dict:
    """Defaults reflect the paper's regime: the table (16 MiB of int32 here,
    GBs in production) dwarfs the chase traffic, so moving indices
    (4 B x depth x batch) beats moving the table by orders of magnitude.
    The crossover is exactly depth x batch x 4 = table_bytes — the
    tensor-scale restatement of the paper's Fig 5-8 argument."""
    import jax
    import jax.numpy as jnp

    from repro.analysis.hlo import analyze_hlo
    from repro.compat import make_mesh
    from repro.sharding.compute_to_data import (
        chase_oracle,
        dapc_shard_map,
        gbpc_reference,
    )

    from jax.sharding import NamedSharding, PartitionSpec as P

    n_dev = jax.device_count()
    mesh = make_mesh((1, n_dev), ("data", "model"))
    rng = np.random.default_rng(0)
    perm = rng.permutation(n_entries)
    table = np.empty(n_entries, np.int32)
    table[perm] = np.roll(perm, -1)
    starts = rng.integers(0, n_entries, batch).astype(np.int32)

    t_j, s_j = jnp.asarray(table), jnp.asarray(starts)
    want = chase_oracle(table, starts, depth)
    # the table LIVES sharded over the mesh — both contenders start there
    # (the GET baseline then has to move it; the c2d chase moves indices)
    in_sh = (NamedSharding(mesh, P("model")), NamedSharding(mesh, P()))

    out: dict = {"devices": n_dev, "entries": n_entries, "batch": batch, "depth": depth}
    for name, fn in (
        ("dapc_c2d", lambda t, s: dapc_shard_map(t, s, depth, mesh)),
        ("gbpc_get", lambda t, s: gbpc_reference(t, s, depth, mesh)),
    ):
        c = jax.jit(fn, in_shardings=in_sh).lower(t_j, s_j).compile()
        got = np.asarray(c(t_j, s_j))
        assert np.array_equal(got, want), name
        hc = analyze_hlo(c.as_text())
        out[name] = {
            "collective_bytes_per_dev": hc.collective_bytes,
            "by_kind": {k: round(v) for k, v in hc.collective_by_kind.items()},
            "bytes_per_hop_per_chase": hc.collective_bytes / (depth * batch),
        }
    if out["dapc_c2d"]["collective_bytes_per_dev"] > 0:
        out["gbpc_over_dapc_bytes"] = (
            out["gbpc_get"]["collective_bytes_per_dev"]
            / max(out["dapc_c2d"]["collective_bytes_per_dev"], 1)
        )
    return out


def main() -> None:
    import json

    print(json.dumps(run(), indent=1, default=float))


if __name__ == "__main__":
    main()
