"""Distributed-optimization collectives: hierarchical reduction order and
int8 error-feedback compression (numerics + convergence property)."""

import jax
import jax.numpy as jnp
import numpy as np

from _hypothesis_compat import given, settings, st  # hypothesis, or local fallback

from repro.sharding.collectives import (
    compressed_psum_with_feedback,
    dequantize_int8,
    quantize_int8,
)


@settings(max_examples=30, deadline=None)
@given(seed=st.integers(0, 2**16), scale=st.floats(1e-3, 1e3))
def test_quantize_roundtrip_bounded_error(seed, scale):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(0, scale, 64), jnp.float32)
    q, s = quantize_int8(x)
    back = dequantize_int8(q, s)
    # error bounded by one quantization step
    assert float(jnp.max(jnp.abs(back - x))) <= float(s) * 0.5 + 1e-9
    assert q.dtype == jnp.int8


def test_compressed_psum_error_feedback_unbiased():
    """Across steps, error feedback keeps the accumulated compressed sum
    close to the exact sum (the EF-SGD guarantee)."""
    n_ranks, dim, steps = 4, 256, 50
    rng = np.random.default_rng(0)
    grads = rng.normal(0, 1, (steps, n_ranks, dim)).astype(np.float32)

    def one_round(gs, errs):
        # emulate the psum across ranks: quantize each rank's (g + err)
        sent, new_errs, scales = [], [], []
        for r in range(n_ranks):
            g = gs[r] + errs[r]
            q, s = quantize_int8(jnp.asarray(g))
            sent.append(np.asarray(q, np.int32))
            scales.append(float(s))
            new_errs.append(g - np.asarray(dequantize_int8(q, s)))
        smax = max(scales)
        total = np.sum(np.stack(sent), axis=0).astype(np.float32) * smax
        return total, new_errs

    errs = [np.zeros(dim, np.float32) for _ in range(n_ranks)]
    acc_compressed = np.zeros(dim, np.float32)
    acc_exact = np.zeros(dim, np.float32)
    for t in range(steps):
        total, errs = one_round(grads[t], errs)
        acc_compressed += total
        acc_exact += grads[t].sum(0)
    # accumulated drift stays small relative to the signal
    rel = np.abs(acc_compressed - acc_exact).max() / (np.abs(acc_exact).max() + 1e-9)
    assert rel < 0.25  # conservative-scale quantizer; EF bounds the drift


def test_compressed_psum_shard_map():
    """The shard_map form: 8 ranks psum int8 payloads; result approximates
    the f32 psum and wire bytes are 1/4."""
    import subprocess, sys, json, os
    from pathlib import Path

    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.compat import make_mesh, shard_map
from repro.sharding.collectives import compressed_psum_with_feedback
mesh = make_mesh((8,), ("pod",))
rng = np.random.default_rng(1)
g = jnp.asarray(rng.normal(0, 1, (8, 128)), jnp.float32)  # one row per rank
err = jnp.zeros((8, 128), jnp.float32)

def body(g_l, e_l):
    out, new_e = compressed_psum_with_feedback(g_l[0], e_l[0], "pod")
    return out[None], new_e[None]

out, new_err = jax.jit(shard_map(body, mesh=mesh,
    in_specs=(P("pod"), P("pod")), out_specs=(P("pod"), P("pod"))))(g, err)
exact = np.asarray(jnp.sum(g, 0))
got = np.asarray(out[0])
rel = float(np.abs(got - exact).max() / (np.abs(exact).max() + 1e-9))
print("REL::" + json.dumps(rel))
"""
    root = Path(__file__).resolve().parent.parent
    env = dict(os.environ, PYTHONPATH=str(root / "src"))
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, env=env, cwd=root, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    rel = json.loads([l for l in r.stdout.splitlines() if l.startswith("REL::")][-1][5:])
    assert rel < 0.05
