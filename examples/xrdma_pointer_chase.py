"""X-RDMA pointer chase, both renderings of the paper's idea:

1. the faithful runtime (core/): Chaser ifuncs really travel, JIT, cache,
   and recursively forward between processing elements — sweep depth and
   compare DAPC vs GBPC vs Active Messages like Figs 5-8;
2. the compiled SPMD rendering (sharding/compute_to_data): the same
   algorithm as a shard_map collective program, with the Pallas chase
   kernel as the per-shard resolver.

Run:  PYTHONPATH=src python examples/xrdma_pointer_chase.py [--tiny]
"""

import argparse

import numpy as np


def runtime_rendering(tiny: bool) -> None:
    from repro.core import Cluster, PointerChaseApp, chase_ref

    print("== runtime rendering (code really moves) ==")
    n_servers, n_entries = (2, 1 << 8) if tiny else (8, 1 << 14)
    depths = (4, 16) if tiny else (16, 64, 256)
    cl = Cluster(n_servers=n_servers, wire="thor_bf2")
    app = PointerChaseApp(cl, n_entries=n_entries, max_slots=16)
    starts = np.random.default_rng(0).integers(0, n_entries, 16).astype(np.int32)
    print("depth  mode      msgs   wire_KB   modeled_us   rate(chases/s)")
    for depth in depths:
        for mode in ("get", "am", "bitcode"):
            rep = (
                app.gbpc(starts, depth)
                if mode == "get"
                else app.dapc(starts, depth, mode=mode)
            )
            expect = [chase_ref(app.table, s, depth) for s in starts]
            assert rep.results.tolist() == expect
            n_msg = rep.puts + rep.gets
            rate = 16 / (rep.modeled_us / 1e6)
            print(
                f"{depth:5d}  {mode:8s} {n_msg:5d} {(rep.put_bytes+rep.get_bytes)/1024:9.1f}"
                f" {rep.modeled_us:12.1f} {rate:14.0f}"
            )


def compiled_rendering(tiny: bool) -> None:
    import jax
    import jax.numpy as jnp

    from repro.compat import make_mesh
    from repro.kernels.chase.kernel import chase_shard
    from repro.sharding.compute_to_data import chase_oracle, dapc_shard_map

    print("\n== compiled SPMD rendering (steady state: indices move) ==")
    n, b, depth = (1 << 8, 8, 8) if tiny else (1 << 14, 64, 32)
    rng = np.random.default_rng(1)
    perm = rng.permutation(n)
    table = np.empty(n, np.int32)
    table[perm] = np.roll(perm, -1)
    starts = rng.integers(0, n, b).astype(np.int32)
    mesh = make_mesh((1, jax.device_count()), ("data", "model"))
    got = np.asarray(dapc_shard_map(jnp.asarray(table), jnp.asarray(starts), depth, mesh))
    want = chase_oracle(table, starts, depth)
    assert np.array_equal(got, want)
    print(f"dapc_shard_map over {jax.device_count()} device(s): {b} chases x "
          f"depth {depth} verified; wire cost = 4 B/hop/chase (one int32)")

    # per-shard resolver as the Pallas kernel (interpret mode on CPU)
    f, d = chase_shard(
        jnp.asarray(table), jnp.asarray(starts),
        jnp.full(b, depth, jnp.int32), 0,
        block=n, hops_per_visit=depth, rounds=1, interpret=True,
    )
    assert np.array_equal(np.asarray(f), want) and int(np.asarray(d).max()) == 0
    print(f"Pallas chase kernel resolved all {b} chases in-VMEM (interpret mode)")


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--tiny", action="store_true", help="smoke-test sizes")
    args = ap.parse_args()
    runtime_rendering(args.tiny)
    compiled_rendering(args.tiny)
