"""The main path's programs compile for a TPU v5e chip at deployment size.

The chip is described (``v5e:2x2``), not attached: the TPU compiler
refuses here what it would refuse on the chip (unaligned blocks, VMEM
overuse, an unlowerable kernel) at no chip time.  The topology is
described inside a fixture, never at import: only one process may load
the TPU library, and every test worker imports this file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.cache import TargetCodeCache
from repro.core.pe import PEStats
from repro.core.pe.codecache import CodeCacheLayer
from repro.core.xrdma import make_gather_return, make_gatherer
from repro.kernels.embed_lookup.kernel import embed_lookup

ROWS, DIM, SERVERS, K, SLOTS = 524_288, 128, 8, 16, 64  # chip_smoke's gather shard


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def layer(topo):
    """A TPU PE's code-cache layer, compiling for the described chip."""
    return CodeCacheLayer("server0", "tpu-v5e", TargetCodeCache(), PEStats(), topo.devices[0])


@pytest.fixture(scope="module")
def gatherer_exe(layer):
    return layer.install(make_gatherer(ROWS, SERVERS, K, DIM).make_frame(b""))


def test_embed_lookup_compiles_at_shard_size(one_chip):
    table = jax.ShapeDtypeStruct((ROWS, DIM), jnp.float32, sharding=one_chip)
    ids = jax.ShapeDtypeStruct((K,), jnp.int32, sharding=one_chip)
    lo = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = embed_lookup.lower(table, ids, lo, bt=K).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_gatherer_tpu_slice_installs_with_kernel(layer, gatherer_exe):
    assert gatherer_exe.in_avals[1].shape == (ROWS, DIM)
    assert "tpu_custom_call" in gatherer_exe.fn.as_text()
    assert layer.stats.jit_ms_total > 0


def test_gather_return_tpu_slice_installs(layer):
    exe = layer.install(make_gather_return(SLOTS, K, DIM).make_frame(b""))
    assert exe.extras["abi"] == "update"
    assert exe.fn.as_text()


def test_batched_lax_map_rendering_compiles(layer, gatherer_exe):
    fn = layer.batched_executable(gatherer_exe, 16)
    text = fn.as_text()
    assert "tpu_custom_call" in text and "while" in text  # lax.map -> one loop
    assert layer.cache.lookup_batched(gatherer_exe.digest, 16) is fn
