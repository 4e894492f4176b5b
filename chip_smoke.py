"""Drive the ifunc/X-RDMA main path once on the TPU and check every result.

    python chip_smoke.py            # gather + chase phases on one chip
    python chip_smoke.py --chips 4  # only the row-sharded gather, four chips

Phases (sizes fixed by :func:`main`; the phase functions take them as
arguments so the tests rehearse them on the CPU at tiny sizes):

* gather — ``EmbedShardService`` over 8 ``tpu-v5e`` server PEs, a
  4,194,304 x 128 f32 table (2 GiB, one DLRM/Criteo-style table at dim
  128) in 8 shards of 524,288 rows; 512 requests of 16 Zipf(1.05) keys
  through the batched runtime, every row bit-compared with a numpy take.
* chase — ``PointerChaseApp`` over 2**26 int32 entries (256 MiB) on 8
  ``tpu-v5e`` servers; 256 chases of depth 64 in ``bitcode`` mode with
  batching, each equal to ``chase_ref``.
* sharded_gather (``--chips 4`` only) — ``gather_shard_map`` with the
  Pallas kernel over the same table row-sharded on a 4-device mesh,
  256 replicated keys against the numpy take.

Each phase prints one JSON line, with the persistent compile cache's
hits and writes during the phase; the last line is
``{"ok": true, "device": {"platform", "kind", "count"}}``.  The script
exits non-zero without that line when JAX finds no TPU or a phase fails.
"""

from __future__ import annotations

import argparse
import collections
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.core import Cluster, PointerChaseApp, chase_ref  # noqa: E402
from repro.runtime.embed_service import EmbedShardService  # noqa: E402

ZIPF_A = 1.05


def zipf_keys(vocab: int, shape, rng: np.random.Generator) -> np.ndarray:
    """Row ids drawn Zipf(``ZIPF_A``) over popularity ranks; a random
    rank -> row map spreads the hot rows over every shard."""
    p = np.arange(1, vocab + 1, dtype=np.float64) ** -ZIPF_A
    p /= p.sum()
    row_of_rank = rng.permutation(vocab).astype(np.int32)
    return row_of_rank[rng.choice(vocab, size=shape, p=p)]


def _bits_equal(got: np.ndarray, want: np.ndarray) -> bool:
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return got.shape == want.shape and np.array_equal(
        got.view(np.uint32), want.view(np.uint32)
    )


def _compile_ms(cluster: Cluster) -> float:
    return sum(pe.stats.jit_ms_total for pe in cluster.pes())


def _devices(pes) -> list[str]:
    return sorted({str(pe.device) for pe in pes})


def gather_phase(
    *, n_servers: int, vocab: int, dim: int, n_keys: int, max_slots: int,
    n_requests: int, triple: str, seed: int = 0,
) -> dict:
    """The embedding-shard gather service, cold then warm, bit-checked."""
    rng = np.random.default_rng(seed)
    table = rng.standard_normal((vocab, dim), dtype=np.float32)
    cluster = Cluster(n_servers=n_servers, server_triple=triple, client_triple=triple)
    svc = EmbedShardService(
        cluster, vocab=vocab, dim=dim, n_keys=n_keys, max_slots=max_slots,
        table=table,
    )
    batches = list(zipf_keys(vocab, (n_requests, n_keys), rng))
    want = svc.oracle(batches)
    walls = []
    for _ in range(2):  # cold (compiles), then warm
        t0 = time.perf_counter()
        rep = svc.gather(batches, batching=True)
        walls.append(time.perf_counter() - t0)
        bad = sum(not _bits_equal(g, w) for g, w in zip(rep.results, want))
        if bad:
            raise AssertionError(f"{bad} of {n_requests} gathers differ from the take oracle")
    digest = cluster.toolchain.lookup(svc.op_name).digest.hex()
    execs = []
    for pe in cluster.servers:
        exe = pe.target_cache.lookup_digest(digest)
        if exe is not None:
            execs.append(exe.fn)
            buckets = (1 << i for i in range(max_slots.bit_length() + 1))
            execs += filter(None, (pe.target_cache.lookup_batched(digest, b) for b in buckets))
    if not execs:
        raise AssertionError("no server installed the gatherer")
    return {
        "phase": "gather",
        "devices": _devices(cluster.pes()),
        "platforms": sorted({pe.device.platform for pe in cluster.pes()}),
        "tpu_custom_call": all("tpu_custom_call" in fn.as_text() for fn in execs),
        "executables": len(execs),
        "compile_ms": _compile_ms(cluster),
        "wall_s": walls[0],
        "warm_wall_s": walls[1],
        "requests": n_requests,
        "rows_checked": 2 * n_requests * n_keys,
    }


def chase_phase(
    *, n_servers: int, n_entries: int, n_chases: int, depth: int, triple: str,
    seed: int = 0,
) -> dict:
    """DAPC pointer chases in bitcode mode through the batched runtime."""
    cluster = Cluster(n_servers=n_servers, server_triple=triple, client_triple=triple)
    app = PointerChaseApp(cluster, n_entries=n_entries, max_slots=n_chases, seed=seed)
    starts = np.random.default_rng(seed + 1).integers(0, n_entries, n_chases, dtype=np.int32)
    want = np.array([chase_ref(app.table, s, depth) for s in starts], np.int32)
    t0 = time.perf_counter()
    rep = app.dapc(starts, depth, mode="bitcode", batching=True)
    wall = time.perf_counter() - t0
    bad = int(np.sum(rep.results != want))
    if bad:
        raise AssertionError(f"{bad} of {n_chases} chases differ from chase_ref")
    return {
        "phase": "chase",
        "devices": _devices(cluster.pes()),
        "platforms": sorted({pe.device.platform for pe in cluster.pes()}),
        "compile_ms": _compile_ms(cluster),
        "wall_s": wall,
        "invokes": rep.invokes,
        "chases_checked": n_chases,
        "depth": depth,
    }


def sharded_gather_phase(
    *, devices, vocab: int, dim: int, n_keys: int, use_pallas: bool, seed: int = 0,
) -> dict:
    """``gather_shard_map`` over a table row-sharded across ``devices``:
    each device must hold exactly its own shard, and the psum'd rows must
    equal the numpy take bit for bit."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.compat import make_mesh
    from repro.sharding.compute_to_data import gather_ref, gather_shard_map

    mesh = make_mesh((len(devices),), ("model",), devices=devices)
    rng = np.random.default_rng(seed)
    table = rng.standard_normal((vocab, dim), dtype=np.float32)
    keys = zipf_keys(vocab, (n_keys,), rng)
    t0 = time.perf_counter()
    tab = jax.device_put(table, NamedSharding(mesh, P("model", None)))
    ks = jax.device_put(keys, NamedSharding(mesh, P()))
    rows = vocab // len(devices)
    owners = {}
    for shard in tab.addressable_shards:
        if shard.data.shape != (rows, dim):
            raise AssertionError(f"{shard.device} holds {shard.data.shape}, want {(rows, dim)}")
        owners[shard.device] = shard.index[0].indices(vocab)[0] // rows
    if sorted(owners.values()) != list(range(len(devices))) or set(owners) != set(devices):
        raise AssertionError(f"shards are not one per device: {owners}")
    fn = jax.jit(lambda t, k: gather_shard_map(t, k, mesh, use_pallas=use_pallas))
    t1 = time.perf_counter()
    compiled = fn.lower(tab, ks).compile()
    compile_ms = (time.perf_counter() - t1) * 1e3
    got = np.asarray(compiled(tab, ks))
    wall = time.perf_counter() - t0
    if not _bits_equal(got, gather_ref(table, keys)):
        raise AssertionError("sharded gather differs from the take oracle")
    return {
        "phase": "sharded_gather",
        "devices": sorted(str(d) for d in owners),
        "platforms": sorted({d.platform for d in owners}),
        "tpu_custom_call": "tpu_custom_call" in compiled.as_text(),
        "shard_rows": rows,
        "compile_ms": compile_ms,
        "wall_s": wall,
        "rows_checked": n_keys,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" or len(devices) < args.chips:
        print(
            f"chip_smoke: needs {args.chips} TPU device(s), JAX found "
            f"{len(devices)} {dev.platform!r} device(s)", file=sys.stderr,
        )
        return 1
    cache_dir = enable_compile_cache()
    events = collections.Counter()  # JAX's persistent-cache reads and writes
    jax.monitoring.register_event_listener(
        lambda event, **_: events.update([event.rsplit("/", 1)[-1]])
    )
    vocab, dim = 4_194_304, 128
    if args.chips == 4:
        phases = [lambda: sharded_gather_phase(
            devices=devices[:4], vocab=vocab, dim=dim, n_keys=256, use_pallas=True,
        )]
    else:
        phases = [
            lambda: gather_phase(
                n_servers=8, vocab=vocab, dim=dim, n_keys=16, max_slots=64,
                n_requests=512, triple="tpu-v5e",
            ),
            lambda: chase_phase(
                n_servers=8, n_entries=1 << 26, n_chases=256, depth=64,
                triple="tpu-v5e",
            ),
        ]
    for run in phases:
        before = events.copy()
        out = run()
        out["compile_cache"] = {
            "dir": str(cache_dir),
            "hits": events["cache_hits"] - before["cache_hits"],
            "writes": events["cache_misses"] - before["cache_misses"],
        }
        print(json.dumps(out), flush=True)
        if out["platforms"] != ["tpu"] or out.get("tpu_custom_call") is False:
            print(f"chip_smoke: phase {out['phase']} left the chip or its kernel",
                  file=sys.stderr)
            return 1
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
