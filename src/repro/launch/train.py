"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

Runs the fault-tolerant TrainDriver (runtime/driver.py) over the token
pipeline with async checkpointing.  On this container it trains reduced
(``--smoke``) configs for real; full configs train the same code path on
a real TPU slice — the mesh and shardings come from the same
partition-plan module the dry-run proves out.
"""

from __future__ import annotations

import argparse
import json
import time


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction, default=True,
                    help="reduced config (CPU-trainable); --no-smoke = full config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--ckpt-dir", default="artifacts/ckpt")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--data", choices=["synthetic", "memmap"], default="synthetic")
    ap.add_argument("--data-path", default=None)
    ap.add_argument("--mesh", choices=["none", "host"], default="none",
                    help="host = mesh over this process's devices")
    ap.add_argument("--fail-at-step", type=int, default=None,
                    help="inject a simulated host loss (fault-tolerance demo)")
    args = ap.parse_args()

    import jax

    from repro.launch.compile_cache import enable_compile_cache
    from repro.configs import get_config
    from repro.data import DataConfig
    from repro.optim import AdamW, cosine_schedule
    from repro.runtime import TrainDriver

    enable_compile_cache()
    cfg = get_config(args.arch, smoke=args.smoke)
    mesh = None
    if args.mesh == "host":
        n = jax.device_count()
        from repro.launch.mesh import make_smoke_mesh

        mesh = make_smoke_mesh(data=n, model=1)
    opt = AdamW(lr=cosine_schedule(args.lr, args.warmup, args.steps))
    data = DataConfig(
        seq_len=args.seq_len,
        global_batch=args.global_batch,
        vocab=cfg.vocab,
        source=args.data,
        path=args.data_path,
    )
    driver = TrainDriver(
        cfg,
        ckpt_dir=f"{args.ckpt_dir}/{cfg.name}",
        opt=opt,
        mesh=mesh,
        data=data,
        ckpt_every=args.ckpt_every,
    )
    t0 = time.time()
    report = driver.run(args.steps, fail_at_step=args.fail_at_step)
    out = {
        "arch": cfg.name,
        "steps": report.steps_run,
        "restarts": report.restarts,
        "restored_steps": report.restored_steps,
        "first_loss": report.losses[0] if report.losses else None,
        "last_loss": report.losses[-1] if report.losses else None,
        "step_time_s": round(report.step_time_s, 4),
        "wall_s": round(time.time() - t0, 1),
        "tokens_per_s": round(
            args.seq_len * args.global_batch / max(report.step_time_s, 1e-9)
        ),
    }
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
