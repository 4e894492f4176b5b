"""Benchmark driver: one module per paper table/figure.

  tsi          Tables I-III (overhead breakdown) + IV-VI (latency/rate)
  dapc         Figs 5-8 (depth sweep) + Figs 9-12 (server scaling)
  dapc_tensor  the compiled-SPMD rendering of the same experiment
  roofline     summary of the dry-run artifact table (if present)

Every section runs in this process over ``jax.devices()`` (a chip belongs
to one process); a failed section fails the run.  Writes
artifacts/bench.json and prints a compact CSV per benchmark.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ART = Path(__file__).resolve().parent.parent / "artifacts"


def _section(name: str) -> None:
    print(f"\n===== {name} " + "=" * max(0, 60 - len(name)))


def bench_tsi() -> dict:
    from .tsi import run_tsi

    out = run_tsi()
    _section("TSI (Tables I-VI)")
    print("mode,uncached_B,cached_B,lookup_exec_us,jit_ms")
    for r in out["rows"]:
        print(
            f"{r['mode']},{r['wire_bytes_uncached']},{r['wire_bytes_cached']},"
            f"{r['lookup_exec_us']:.3f},{r['jit_ms'] if r['jit_ms'] else ''}"
        )
    print("profile,metric,ours_pct,paper_pct")
    for p, c in out["claims"].items():
        print(
            f"{p},uncached_vs_cached_latency,{c['uncached_vs_cached_latency_pct']:.1f},"
            f"{c['paper_uncached_vs_cached_latency_pct']:.1f}"
        )
        print(
            f"{p},cached_vs_uncached_rate,{c['cached_vs_uncached_rate_pct']:.1f},"
            f"{c['paper_cached_vs_uncached_rate_pct']:.1f}"
        )
        print(
            f"{p},cached_vs_am_rate,{c['cached_vs_am_rate_pct']:.1f},"
            f"{c['paper_cached_vs_am_rate_pct']:.1f}"
        )
    return out


def bench_dapc(fast: bool = False) -> dict:
    from .dapc import claims, depth_sweep, scaling_sweep

    depths = (1, 4, 16, 64, 256) if fast else (1, 4, 16, 64, 256, 1024)
    servers = (2, 4, 8, 16) if fast else (2, 4, 8, 16, 32)
    d = depth_sweep(depths=depths)
    s = scaling_sweep(servers=servers, depth=depths[-1])
    _section("DAPC depth sweep (Figs 5-8)")
    print("depth,mode,chase_rate_modeled,wire_bytes,puts,gets")
    for r in d:
        print(
            f"{r['depth']},{r['mode']},{r['chase_rate_modeled']:.0f},"
            f"{r['wire_bytes']},{r['puts']},{r['gets']}"
        )
    _section("DAPC scaling (Figs 9-12)")
    print("servers,mode,chase_rate_modeled")
    for r in s:
        print(f"{r['servers']},{r['mode']},{r['chase_rate_modeled']:.0f}")
    cl = claims(d)
    _section("DAPC claims (paper: DAPC beats GBPC by 20-75%)")
    for k, v in cl.items():
        print(f"{k},{v:.1f}%")
    return {"depth_sweep": d, "scaling": s, "claims": cl}


def bench_dapc_batched(fast: bool = False) -> dict:
    from .dapc import batch_sweep, batched_ab

    n_chases = 64 if fast else 256
    ab = batched_ab(n_chases=n_chases)
    rows = batch_sweep(n_chases_list=(16, 64) if fast else (16, 64, 256))
    _section("DAPC batched runtime (per-message vs coalesced/vmapped)")
    print("n_chases,batching,puts,invokes,coalesced_frames,modeled_wire_s")
    for r in rows:
        print(
            f"{r['n_chases']},{int(r['batching'])},{r['puts']},{r['invokes']},"
            f"{r['coalesced_frames']},{r['modeled_wire_s']:.6f}"
        )
    print(
        f"A/B @ {ab['config']['n_chases']} chases, depth {ab['config']['depth']}, "
        f"{ab['config']['n_servers']} servers, {ab['config']['profile']}: "
        f"{ab['dispatch_ratio']}x fewer dispatches, "
        f"{ab['modeled_us_reduction_pct']}% lower modeled wire time"
    )
    out = {"ab": ab, "batch_sweep": rows}
    bench_path = Path(__file__).resolve().parent.parent / "BENCH_dapc.json"
    bench_path.write_text(json.dumps(ab, indent=1, default=float) + "\n")
    print(f"wrote {bench_path}")
    return out


def bench_gather(fast: bool = False) -> dict:
    from .gather import gather_ab

    ab = gather_ab(n_requests=64 if fast else 256)
    _section("X-RDMA Gather (embedding-shard service vs GET-per-row)")
    print("path,network_ops,invokes,coalesced_frames,wire_bytes,modeled_us")
    for label in ("get_per_row", "per_message", "batched", "zerocopy", "rendezvous"):
        r = ab[label]
        print(
            f"{label},{r['network_ops']},{r['invokes']},{r['coalesced_frames']},"
            f"{r['wire_bytes']},{r['modeled_us']}"
        )
    print(
        f"A/B @ {ab['config']['n_requests']} requests, "
        f"{ab['config']['n_servers']} shards, {ab['config']['profile']}: "
        f"{ab['batched_vs_get_ops_ratio']}x fewer network ops, "
        f"{ab['batched_vs_get_modeled_pct']}% lower modeled wire time vs GET, "
        f"zerocopy wire bytes {ab['zerocopy_vs_get_bytes_ratio']}x the GET floor"
    )
    bench_path = Path(__file__).resolve().parent.parent / "BENCH_gather.json"
    bench_path.write_text(json.dumps(ab, indent=1, default=float) + "\n")
    print(f"wrote {bench_path}")
    return ab


def bench_dapc_tensor() -> dict:
    from .dapc_tensor import run

    out = run()
    _section(f"DAPC tensor-scale (compiled SPMD, {out['devices']} devices)")
    print(json.dumps(out, indent=1, default=float))
    return out


def bench_embed_ablation() -> dict:
    from .embed_ablation import run

    out = run()
    _section(f"Embedding ablation: c2d vs gather vs auto ({out['devices']} devices)")
    print(json.dumps(out, indent=1, default=float))
    return out


def bench_roofline() -> dict:
    rows = []
    path = ART / "dryrun.jsonl"
    if not path.exists():
        _section("Roofline (no dry-run artifact yet — run repro.launch.dryrun --all)")
        return {}
    for line in path.read_text().splitlines():
        r = json.loads(line)
        if r.get("status") == "ok":
            rows.append(r)
    _section("Roofline summary (from dry-run artifacts)")
    print("arch,shape,mesh,dominant,t_compute_s,t_memory_s,t_collective_s,mfu_bound,fits_hbm")
    for r in sorted(rows, key=lambda r: (r["arch"], r["shape"], r["mesh"])):
        print(
            f"{r['arch']},{r['shape']},{r['mesh']},{r['dominant']},"
            f"{r['t_compute_s']:.4f},{r['t_memory_s']:.4f},{r['t_collective_s']:.4f},"
            f"{r['mfu_bound']:.3f},{r['fits_hbm']}"
        )
    return {"cells": len(rows)}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--only",
        choices=[
            "tsi", "dapc", "dapc_batched", "gather", "dapc_tensor",
            "embed_ablation", "roofline",
        ],
    )
    ap.add_argument("--fast", action="store_true")
    args = ap.parse_args()
    ART.mkdir(exist_ok=True)
    t0 = time.time()
    out: dict = {}
    todo = [args.only] if args.only else [
        "tsi", "dapc", "dapc_batched", "gather", "dapc_tensor",
        "embed_ablation", "roofline",
    ]
    for name in todo:
        out[name] = {
            "tsi": bench_tsi,
            "dapc": lambda: bench_dapc(args.fast),
            "dapc_batched": lambda: bench_dapc_batched(args.fast),
            "gather": lambda: bench_gather(args.fast),
            "dapc_tensor": bench_dapc_tensor,
            "embed_ablation": bench_embed_ablation,
            "roofline": bench_roofline,
        }[name]()
    (ART / "bench.json").write_text(json.dumps(out, indent=1, default=float))
    print(f"\nall benchmarks done in {time.time()-t0:.1f}s -> {ART/'bench.json'}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
