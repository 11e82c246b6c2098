"""The serving kernels compile for a TPU v5e at StarCoder2-3B widths.

No chip is needed: the TPU compiler compiles for a described v5e topology.
Shapes are the ones the engine builds for ``starcoder2_3b`` at offload
ratio 0.5 with 8 slots, 2048-token contexts and 16-token pages, in bf16.
Each test asserts that the compiled program holds the Mosaic kernel
(``tpu_custom_call``).

Only ``pl.ANY`` operands are compiled here: on v5e a ``pltpu.HOST``
operand aborts the compiling process.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro.configs as C
from repro.core.tiering import split_sizes
from repro.kernels.splitk_flashattn import paged_splitk_flashattn
from repro.kernels.splitk_gemm import splitk_gemm

CFG = C.get("starcoder2_3b")
BATCH, MAX_LEN, PAGE = 8, 2048, 16
BLOCK = 128
CUSTOM_CALL = 'custom_call_target="tpu_custom_call"'


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _sds(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


GEMMS = {
    # name: (K, N) of each layer weight the engine splits by columns
    "wq": (CFG.d_model, CFG.padded_heads * CFG.resolved_head_dim),
    "wkv": (CFG.d_model, 2 * CFG.n_kv_heads * CFG.resolved_head_dim),
    "wo": (CFG.padded_heads * CFG.resolved_head_dim, CFG.d_model),
    "wi": (CFG.d_model, CFG.d_ff),
    "wdown": (CFG.d_ff, CFG.d_model),
}
DECODE_ROWS = 32


@pytest.mark.parametrize("name", sorted(GEMMS))
def test_splitk_gemm_compiles_for_v5e(one_chip, name):
    """Every StarCoder2-3B decode GEMM at 32 rows, with the tiles the
    kernel derives from its shapes and a window of 4 (five VMEM slots)."""
    k, n = GEMMS[name]
    n_loc, n_rem = split_sizes(n, 0.5, BLOCK)
    assert n_loc and n_rem
    x = _sds(one_chip, (DECODE_ROWS, k))
    compiled = splitk_gemm.lower(
        x, _sds(one_chip, (k, n_loc)), _sds(one_chip, (k, n_rem)),
        window=4, interpret=False).compile()
    assert CUSTOM_CALL in compiled.as_text()


@pytest.mark.parametrize("heads", [CFG.n_heads, CFG.padded_heads])
def test_paged_flashattn_compiles_for_v5e(one_chip, heads):
    kh, hd = CFG.n_kv_heads, CFG.resolved_head_dim
    max_pages = MAX_LEN // PAGE
    pool = _sds(one_chip, (BATCH * max_pages // 2 + 1, PAGE, kh, hd))
    idx = _sds(one_chip, (BATCH, max_pages), jnp.int32)
    compiled = paged_splitk_flashattn.lower(
        _sds(one_chip, (BATCH, heads, hd)), pool, pool, pool, pool, idx, idx,
        _sds(one_chip, (BATCH,), jnp.int32), window=1, interpret=False).compile()
    assert CUSTOM_CALL in compiled.as_text()
