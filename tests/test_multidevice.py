"""Multi-device semantics: the sharded paths must compute the SAME numbers
as the single-device references.  Runs in a subprocess with 8 host-platform
devices (the dry-run owns 512; tests keep their own process clean)."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

results = {}
from repro.compat import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))

# ---- 1. compute-to-data embedding == plain lookup
from repro.models.embedding import embed_c2d, embed_plain
rng = np.random.default_rng(0)
table = jnp.asarray(rng.normal(0, 1, (64, 16)), jnp.float32)
ids = jnp.asarray(rng.integers(0, 64, (4, 8)), jnp.int32)
got = jax.jit(lambda t, i: embed_c2d(t, i, mesh, batch_axes=("data",)))(table, ids)
want = embed_plain(table, ids)
results["embed_c2d"] = float(jnp.max(jnp.abs(got - want)))

# ---- 2. MoE a2a dispatch == scatter reference (same routing decisions)
from repro.models.moe import moe_block_a2a, moe_block_scatter
d, e, f, topk = 16, 8, 32, 2
ks = jax.random.split(jax.random.PRNGKey(1), 5)
x = jax.random.normal(ks[0], (2, 8, d)) * 0.5          # (B=2, S=8): S%4==0
wr = jax.random.normal(ks[1], (d, e)) * 0.3
wi = jax.random.normal(ks[2], (e, d, f)) * 0.3
wg = jax.random.normal(ks[3], (e, d, f)) * 0.3
wo = jax.random.normal(ks[4], (e, f, d)) * 0.3
y1, aux1 = jax.jit(lambda *a: moe_block_a2a(*a, topk=topk, mesh=mesh, capacity_factor=8.0))(x, wr, wi, wg, wo)
y2, aux2 = moe_block_scatter(x, wr, wi, wg, wo, topk, capacity_factor=8.0)
# NOTE: capacity semantics differ at the margin (per-pair vs per-expert
# buckets); with generous capacity both keep every token and must agree.
results["moe_a2a"] = float(jnp.max(jnp.abs(y1 - y2)))
results["moe_aux"] = abs(float(aux1) - float(aux2))

# ---- 3. DAPC shard_map chase == oracle
from repro.sharding.compute_to_data import chase_oracle, dapc_shard_map
n = 4096
perm = rng.permutation(n); table = np.empty(n, np.int32); table[perm] = np.roll(perm, -1)
starts = rng.integers(0, n, 32).astype(np.int32)
got = np.asarray(dapc_shard_map(jnp.asarray(table), jnp.asarray(starts), 17, mesh))
results["dapc"] = int(np.sum(got != chase_oracle(table, starts, 17)))

# ---- 3b. gather shard_map == take oracle (the serving-shape sibling)
from repro.sharding.compute_to_data import gather_ref, gather_shard_map
etab = jnp.asarray(rng.normal(0, 1, (512, 16)), jnp.float32)
gkeys = rng.integers(0, 512, 64).astype(np.int32)
ggot = np.asarray(gather_shard_map(etab, jnp.asarray(gkeys), mesh))
results["gather"] = int(np.sum(ggot != gather_ref(etab, gkeys)))

# ---- 4. sharded train step == single-device train step (loss + params)
from repro.configs import get_config
from repro.models.zoo import ShapeSpec, build_params, make_batch, make_train_step
from repro.optim import AdamW
from repro.optim.adamw import OptState
from repro.sharding.partition import batch_shardings, state_shardings, rules_for_train
cfg = get_config("granite-moe-1b-a400m", smoke=True).replace(n_experts=8, topk=2)
params, axes = build_params(cfg, 0)
opt = AdamW(lr=1e-3)
batch = make_batch(cfg, ShapeSpec("t", 32, 4, "train"), 7)
state0 = {"params": params, "opt": opt.init(params), "step": jnp.int32(0)}
s_plain, m_plain = jax.jit(make_train_step(cfg, opt))(state0, batch)
sh = state_shardings(params, axes, mesh, rules=rules_for_train(cfg, mesh))
b_sh = batch_shardings(batch, mesh)
step = make_train_step(cfg, opt, mesh=mesh)
s_shard, m_shard = jax.jit(step, in_shardings=(sh, b_sh), out_shardings=(sh, None))(state0, batch)
results["train_loss_delta"] = abs(float(m_plain["loss"]) - float(m_shard["loss"]))
pdeltas = [float(jnp.max(jnp.abs(s_plain["params"][k].astype(jnp.float32) -
                                  s_shard["params"][k].astype(jnp.float32)))) for k in params]
results["train_param_delta"] = max(pdeltas)

# ---- 5. attend_sp == attend (odd head count)
from repro.models.attention import attend, attend_sp
q = jax.random.normal(ks[0], (2, 16, 5, 8))
k = jax.random.normal(ks[1], (2, 16, 5, 8))
v = jax.random.normal(ks[2], (2, 16, 5, 8))
pos = jnp.arange(16)
a = attend(q, k, v, q_pos=pos, k_pos=pos, causal=True, window=7)
b = jax.jit(lambda q, k, v: attend_sp(q, k, v, q_pos=pos, k_pos=pos, mesh=mesh,
                                      batch_axes=("data",), chunk=0, causal=True,
                                      window=7))(q, k, v)
results["attend_sp"] = float(jnp.max(jnp.abs(a - b)))

# ---- 6. elastic restore: checkpoint saved once, restored onto a DIFFERENT
# mesh with different shardings (the lost-a-host path)
import tempfile
from repro.checkpoint import restore_state, save_state
from repro.sharding.partition import param_shardings
with tempfile.TemporaryDirectory() as td:
    save_state(td, {"params": params}, step=3)
    like = jax.eval_shape(lambda: {"params": params})
    small_mesh = make_mesh((4, 2), ("data", "model"))  # "lost" devices
    new_sh = {"params": param_shardings(params, axes, small_mesh)}
    restored, step = restore_state(td, like, shardings=new_sh)
    deltas = [float(jnp.max(jnp.abs(restored["params"][k].astype(jnp.float32)
                                    - params[k].astype(jnp.float32))))
              for k in params]
    results["elastic_restore"] = max(deltas)
    results["elastic_step"] = step

print("RESULTS::" + json.dumps(results))
"""


@pytest.fixture(scope="module")
def multidev_results():
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"))
    r = subprocess.run(
        [sys.executable, "-c", _SCRIPT], capture_output=True, text=True,
        env=env, cwd=ROOT, timeout=1200,
    )
    assert r.returncode == 0, r.stderr[-3000:]
    line = [l for l in r.stdout.splitlines() if l.startswith("RESULTS::")][-1]
    return json.loads(line[len("RESULTS::"):])


def test_embed_c2d_matches_plain(multidev_results):
    assert multidev_results["embed_c2d"] < 1e-6


def test_moe_a2a_matches_scatter(multidev_results):
    assert multidev_results["moe_a2a"] < 1e-4
    # the aux load-balance loss is estimated per-EP-rank then averaged in
    # the a2a path (the standard EP formulation); product-of-means !=
    # mean-of-products, so it differs from the global estimator by O(0.1)
    # on tiny token counts — a regularizer-choice difference, not a bug
    assert multidev_results["moe_aux"] < 0.2


def test_dapc_shard_map_matches_oracle(multidev_results):
    assert multidev_results["dapc"] == 0


def test_gather_shard_map_matches_oracle(multidev_results):
    """8-way sharded gather_shard_map is bit-identical to the numpy take."""
    assert multidev_results["gather"] == 0


def test_sharded_train_step_matches_plain(multidev_results):
    # loss differs by the aux-estimator term (weight 0.01) and by which
    # tokens hit capacity drops (per-(src,dst) vs per-expert buckets);
    # parameters after one AdamW step must still agree closely
    assert multidev_results["train_loss_delta"] < 0.05
    assert multidev_results["train_param_delta"] < 5e-3


def test_attend_sp_matches_attend(multidev_results):
    assert multidev_results["attend_sp"] < 1e-5


def test_elastic_restore_with_reshard(multidev_results):
    """Unsharded-on-disk leaves restore bit-exactly onto a different mesh."""
    assert multidev_results["elastic_restore"] == 0.0
    assert multidev_results["elastic_step"] == 3
