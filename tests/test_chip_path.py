"""The rules that put each PE on its device, and the chip smoke's phases
rehearsed on the CPU at tiny sizes (``cpu-*`` triples)."""

import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import PE, FatBitcode, local_triple
from repro.core import bitcode
from repro.core.transport import Fabric
from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ------------------------------------------------------------ PE devices
def test_tpu_pe_raises_in_cpu_only_process():
    with pytest.raises(RuntimeError, match="no 'tpu' device"):
        PE("server0", Fabric("ideal"), triple="tpu-v5e")


@pytest.mark.parametrize("triple", ["cpu-host", "cpu-bf2"])
def test_cpu_pe_region_lives_on_a_cpu_device(triple):
    pe = PE("server0", Fabric("ideal"), triple=triple)
    pe.register_region("r", np.arange(8, dtype=np.int32))
    assert pe.device.platform == "cpu"
    assert pe.region_device("r").devices() == {pe.device}


def test_local_triple_maps_device_kind(monkeypatch):
    assert local_triple() == "cpu-host"

    class Unknown:
        device_kind = "TPU v99"

    monkeypatch.setattr(bitcode.jax, "devices", lambda *a: [Unknown()])
    with pytest.raises(ValueError, match="TPU v99"):
        local_triple()


def test_slice_that_fails_to_lower_fails_the_build():
    def unlowerable(x):
        raise NotImplementedError("no lowering for this body")

    with pytest.raises(NotImplementedError):
        FatBitcode.build(
            lambda x: x + 1, (jax.ShapeDtypeStruct((4,), jnp.int32),),
            fn_by_platform={"tpu": unlowerable},
        )


# ---------------------------------------------------------- compile cache
def test_compile_cache_dir_honours_env(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.compile_cache_dir() == tmp_path


def test_compile_cache_dir_defaults_to_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert compile_cache.compile_cache_dir() == ROOT / ".jax_cache"


# ------------------------------------------------- chip_smoke phases, tiny
def test_gather_phase_matches_take_oracle(chip_smoke):
    out = chip_smoke.gather_phase(
        n_servers=4, vocab=2048, dim=16, n_keys=8, max_slots=16, n_requests=48,
        triple="cpu-bf2",
    )
    assert out["platforms"] == ["cpu"] and out["rows_checked"] == 2 * 48 * 8
    assert out["tpu_custom_call"] is False  # cpu slices carry the take body
    assert out["compile_ms"] > 0


def test_chase_phase_matches_chase_ref(chip_smoke):
    out = chip_smoke.chase_phase(
        n_servers=4, n_entries=1 << 12, n_chases=32, depth=16, triple="cpu-host",
    )
    assert out["platforms"] == ["cpu"] and out["chases_checked"] == 32


def test_sharded_gather_phase_matches_take_oracle(chip_smoke):
    out = chip_smoke.sharded_gather_phase(
        devices=jax.devices()[:1], vocab=1024, dim=16, n_keys=64, use_pallas=False,
    )
    assert out["shard_rows"] == 1024 and out["rows_checked"] == 64


def test_zipf_keys_are_skewed_and_in_range(chip_smoke):
    keys = chip_smoke.zipf_keys(1 << 16, (4096,), np.random.default_rng(0))
    assert keys.min() >= 0 and keys.max() < 1 << 16
    _, counts = np.unique(keys, return_counts=True)
    assert counts.max() > 100  # the hottest row dominates a uniform draw's ~1
