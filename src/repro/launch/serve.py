"""Serving launcher: batched prefill + decode loop.

``python -m repro.launch.serve --arch gemma2-2b --batch 4 --prompt-len 64
--gen 32`` prefills a batch of prompts and decodes greedily, reporting
prefill/decode throughput.  The full-config serving path (32k/500k caches,
T-sharded over ``model``) is exercised abstractly by the dry-run; this
driver runs the same serve_step end-to-end on reduced configs.
"""

from __future__ import annotations

import argparse
import json
import time


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction, default=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--remote-embed",
        action="store_true",
        help="serving-tier mode: fetch embedding rows from an embedding-shard "
        "service (CQ gathers over the PE fabric) instead of a local lookup "
        "(tests/test_tenancy.py pins the streams bit-identical)",
    )
    ap.add_argument("--embed-servers", type=int, default=2)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch.compile_cache import enable_compile_cache
    from repro.configs import get_config
    from repro.models.zoo import (
        ShapeSpec,
        build_params,
        frontend_len,
        init_kv_cache,
        make_batch,
        make_prefill_step,
        make_serve_step,
    )

    enable_compile_cache()
    cfg = get_config(args.arch, smoke=args.smoke)
    params, _ = build_params(cfg, args.seed)
    t_max = args.prompt_len + args.gen

    # prefill against a cache sized for the whole session
    spec = ShapeSpec("serve", args.prompt_len, args.batch, "prefill")
    batch = make_batch(cfg, spec, seed=args.seed)
    fl = frontend_len(cfg, args.prompt_len)

    def prefill_fn(params, batch):
        from repro.models.zoo import _head, forward

        cache = init_kv_cache(cfg, args.batch, t_max, enc_len=fl, dtype=cfg.dtype)
        h, cache, _ = forward(
            cfg, params, batch, caches=cache, offset=jnp.int32(0),
            return_hidden=True,
        )
        return _head(cfg, params, h[:, -1:, :])[:, -1, :], cache

    prefill = jax.jit(prefill_fn)
    serve = jax.jit(make_serve_step(cfg, remote_embed=args.remote_embed))

    embed_client = None
    if args.remote_embed:
        from repro.runtime.tenancy import RemoteEmbedClient

        embed_client = RemoteEmbedClient(
            np.asarray(params["embed.tok"], np.float32),
            n_servers=args.embed_servers,
        )
        batch = dict(batch)
        batch["token_rows"] = jnp.asarray(embed_client.rows(np.asarray(batch["tokens"])))

    t0 = time.perf_counter()
    logits, cache = jax.block_until_ready(prefill(params, batch))
    t_prefill = time.perf_counter() - t0

    toks = []
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    t0 = time.perf_counter()
    for i in range(args.gen):
        toks.append(np.asarray(tok[:, 0]))
        if embed_client is not None:
            rows = jnp.asarray(embed_client.rows(np.asarray(tok)))
            logits, cache = serve(
                params, cache, tok, jnp.int32(args.prompt_len + i), rows
            )
        else:
            logits, cache = serve(params, cache, tok, jnp.int32(args.prompt_len + i))
        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    jax.block_until_ready(logits)
    t_decode = time.perf_counter() - t0

    gen = np.stack(toks, 1)
    assert np.isfinite(np.asarray(logits, np.float32)).all()
    out = {
        "arch": cfg.name,
        "batch": args.batch,
        "prompt_len": args.prompt_len,
        "generated": int(gen.shape[1]),
        "prefill_s": round(t_prefill, 3),
        "prefill_tok_s": round(args.batch * args.prompt_len / t_prefill),
        "decode_ms_per_tok": round(1e3 * t_decode / args.gen, 2),
        "decode_tok_s": round(args.batch * args.gen / t_decode),
        "sample_ids": gen[0, :8].tolist(),
    }
    if embed_client is not None:
        out["remote_embed"] = True
        out["embed_servers"] = args.embed_servers
        out["embed_gathers"] = embed_client.gathers
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
