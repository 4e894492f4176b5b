"""X-RDMA Gather: an embedding-shard service, both renderings.

1. the faithful runtime (core/ + runtime/embed_service): the Gatherer
   ifunc really travels, resolves the locally-owned keys next to each
   shard, FORWARDs the remainder to the owning PEs, and partial results
   RETURN out-of-order into the client's completion queue — many gathers
   overlapped in flight, retired through the batched runtime;
2. the compiled SPMD rendering (sharding/compute_to_data.gather_shard_map):
   the steady state of the same algorithm as a shard_map collective with
   the Pallas embed_lookup kernel as the per-shard resolver on TPU.

Run:  PYTHONPATH=src python examples/xrdma_embed_service.py [--tiny]
"""

import argparse

import numpy as np


def runtime_rendering(tiny: bool) -> None:
    from repro.core import Cluster
    from repro.runtime.embed_service import EmbedShardService, ragged_batches

    print("== runtime rendering (code really moves) ==")
    n_servers, vocab, dim, n_req = (2, 128, 8, 12) if tiny else (8, 4096, 32, 256)
    cl = Cluster(n_servers=n_servers, wire="thor_xeon")
    svc = EmbedShardService(
        cl, vocab=vocab, dim=dim, n_keys=8, max_slots=min(64, n_req), seed=0
    )
    batches = ragged_batches(vocab, n_req, svc.n_keys, seed=1)
    want = svc.oracle(batches)

    print(f"{n_req} gather requests x <= {svc.n_keys} keys over {n_servers} shards")
    print("path        net_ops  wire_KB  modeled_us  XLA_dispatches")
    for label, rep in (
        ("get/row", svc.gather_get(batches)),
        ("xrdma", svc.gather(batches, batching=False)),
        ("xrdma+batch", svc.gather(batches, batching=True)),
    ):
        for got, w in zip(rep.results, want):
            assert np.array_equal(got, w), "diverged from numpy take oracle"
        wire_kb = (rep.put_bytes + rep.get_bytes) / 1024
        print(
            f"{label:11s} {rep.network_ops:7d} {wire_kb:8.1f}"
            f" {rep.modeled_us:11.1f} {rep.invokes:15d}"
        )
    print("all paths bit-identical to the numpy take oracle")


def compiled_rendering(tiny: bool) -> None:
    import jax
    import jax.numpy as jnp

    from repro.compat import make_mesh
    from repro.sharding.compute_to_data import gather_ref, gather_shard_map

    print("\n== compiled SPMD rendering (steady state: keys move, rows psum) ==")
    vocab, dim, b = (128, 8, 16) if tiny else (4096, 64, 256)
    rng = np.random.default_rng(2)
    table = rng.standard_normal((vocab, dim)).astype(np.float32)
    keys = rng.integers(0, vocab, b).astype(np.int32)
    mesh = make_mesh((1, jax.device_count()), ("data", "model"))
    got = np.asarray(
        gather_shard_map(jnp.asarray(table), jnp.asarray(keys), mesh)
    )
    assert np.array_equal(got, gather_ref(table, keys))
    print(
        f"gather_shard_map over {jax.device_count()} device(s): {b} keys x "
        f"dim {dim} verified; wire cost = one {dim}-row per key "
        "(table never moves)"
    )


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--tiny", action="store_true", help="smoke-test sizes")
    args = ap.parse_args()
    runtime_rendering(args.tiny)
    compiled_rendering(args.tiny)
