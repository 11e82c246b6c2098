"""Device set-up of the serving path: the hardware table, the compile
cache location, parameter dtype and the release of the unsplit weights."""
from __future__ import annotations

import gc
import weakref
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

import repro.configs as C
from repro.core.hardware import DEVICE_KINDS, TPU_V5E, hardware_for
from repro.launch import compile_cache, serve
from repro.models import model as M
from repro.serving.engine import ServingEngine


def test_hardware_table_is_keyed_by_device_kind():
    assert hardware_for(SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")) is TPU_V5E
    assert set(DEVICE_KINDS.values()) == {TPU_V5E}


@pytest.mark.parametrize("device", [
    SimpleNamespace(platform="tpu", device_kind="TPU v9 imaginary"),
    SimpleNamespace(platform="cpu", device_kind="cpu"),
])
def test_unknown_device_is_an_error(device):
    with pytest.raises(ValueError, match="no hardware spec"):
        hardware_for(device)


def test_serve_prices_a_v5e_off_tpu():
    assert jax.default_backend() != "tpu"
    assert serve.resolve_hw() is TPU_V5E


@pytest.fixture
def cache_dir_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_uses_the_environment(monkeypatch, tmp_path, cache_dir_config):
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", "unchanged")
    assert compile_cache.configure() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == "unchanged"   # nothing set


def test_compile_cache_defaults_to_the_checkout(monkeypatch, cache_dir_config):
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    path = compile_cache.configure()
    assert path == str(compile_cache.REPO_CACHE)
    assert jax.config.jax_compilation_cache_dir == path
    assert compile_cache.REPO_CACHE.parent.joinpath("chip_smoke.py").exists()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_params_are_created_in_the_requested_dtype(dtype):
    params = M.init_params(C.get_smoke("starcoder2_3b"), jax.random.PRNGKey(0), dtype)
    assert {leaf.dtype for leaf in jax.tree.leaves(params)} == {jnp.dtype(dtype)}


def test_full_configs_serve_in_bf16_and_smoke_configs_in_f32():
    assert C.get("starcoder2_3b").dtype == "bfloat16"
    assert C.get_smoke("starcoder2_3b").dtype == "float32"


def test_engine_keeps_no_reference_to_the_unsplit_weights():
    cfg = C.get_smoke("starcoder2_3b")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    unsplit = weakref.ref(params["layers"]["wq"])
    engine = ServingEngine(cfg, params, max_batch=2, max_len=16,
                           global_offload_ratio=0.5, page_size=4)
    del params
    gc.collect()
    assert unsplit() is None
    assert engine.params["layers"]["wq"].remote.shape[-1] > 0
