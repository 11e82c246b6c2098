"""The tiered GEMM's tile choice on published decode shapes.

`splitk_gemm.gemm_blocks` sizes each call's weight tile from the call's
shapes: about one DMA chunk (`core.congestion.DMA_CHUNK_BYTES`) per copy,
``block_n`` dividing both tiers so that every split takes the kernel.
These tests check the choice for StarCoder2-3B and Qwen2.5-14B at 32 decode
rows in bf16, split 0.5 at align 128, without running a kernel; the last
one runs a small case in interpret mode.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.analysis import kernel_lints as KL
from repro.core import engine as E
from repro.core.congestion import DMA_CHUNK_BYTES
from repro.core.ebmodel import WorkloadSpec
from repro.core.hardware import TPU_V5E
from repro.core.tiering import partition, split_sizes
from repro.kernels import ops, ref
from repro.kernels import splitk_gemm as SG
from repro.models import model as M
from repro.serving import tiered_decode as TD
from repro.serving.paged_cache import PagedTieredCache

DECODE_ROWS = 32
BF16 = 2
# The kernel sets no ``vmem_limit_bytes``, so Mosaic gives it v5e's default
# scoped VMEM, not the chip's whole VMEM (`TPU_V5E.vmem_bytes`).
SCOPED_VMEM_V5E = 16 * 1024 * 1024
ARCHS = ("starcoder2_3b", "qwen2p5_14b")


def _gemms(arch: str) -> dict[str, tuple[int, int]]:
    """(K, N) of each layer weight the engine splits by columns."""
    cfg = C.get(arch)
    q = cfg.padded_heads * cfg.resolved_head_dim
    mult = 2 if cfg.mlp == "swiglu" else 1
    return {
        "wq": (cfg.d_model, q),
        "wkv": (cfg.d_model, 2 * cfg.n_kv_heads * cfg.resolved_head_dim),
        "wo": (q, cfg.d_model),
        "wi": (cfg.d_model, mult * cfg.d_ff),
        "wdown": (cfg.d_ff, cfg.d_model),
    }


CASES = [(arch, name) for arch in ARCHS for name in _gemms(arch)]


def _split(arch: str, name: str) -> tuple[int, int, int]:
    k, n = _gemms(arch)[name]
    n_loc, n_rem = split_sizes(n, 0.5, 128)
    assert n_loc and n_rem
    return k, n_loc, n_rem


@pytest.mark.parametrize("arch,name", CASES)
def test_tiles_divide_both_partitions(arch, name):
    k, n_loc, n_rem = _split(arch, name)
    bm, bn, bk = SG.gemm_blocks(DECODE_ROWS, k, n_loc, n_rem, BF16)
    assert bm == DECODE_ROWS                  # decode rows are not padded
    assert bn % 128 == 0 and n_loc % bn == 0 and n_rem % bn == 0
    assert bk % 128 == 0 and k % bk == 0
    tile = bk * bn * BF16
    assert DMA_CHUNK_BYTES / 2 <= tile <= 2 * DMA_CHUNK_BYTES


@pytest.mark.parametrize("window", [1, 2, 4])
@pytest.mark.parametrize("arch,name", CASES)
def test_footprint_fits_scoped_vmem(arch, name, window):
    k, n_loc, n_rem = _split(arch, name)
    bm, bn, bk = SG.gemm_blocks(DECODE_ROWS, k, n_loc, n_rem, BF16)
    fp = SG.vmem_footprint_bytes(DECODE_ROWS, k, n_loc + n_rem, block_m=bm,
                                 block_n=bn, block_k=bk, window=window,
                                 dtype_bytes=BF16)
    assert fp < SCOPED_VMEM_V5E
    # The lint's launch descriptor derives the same blocks and agrees.
    launch = KL.GemmLaunch(name=name, m=DECODE_ROWS, k=k, n_loc=n_loc,
                           n_rem=n_rem, window=window, dtype_bytes=BF16)
    assert (launch.block_m, launch.block_n, launch.block_k) == (bm, bn, bk)
    assert KL.check_gemm_launch(launch, TPU_V5E) == []


@pytest.mark.parametrize("arch,name", CASES)
def test_tile_shape_is_the_same_at_every_window(arch, name, monkeypatch):
    """`tiered_matmul` hands the kernel the same blocks at windows 1, 2, 4."""
    k, n_loc, n_rem = _split(arch, name)
    seen = []

    def spy(x, wl, wr, *, block_m, block_n, block_k, window, interpret):
        seen.append((window, (block_m, block_n, block_k)))
        return jnp.zeros((x.shape[0], wl.shape[1] + wr.shape[1]), x.dtype)

    monkeypatch.setattr(ops, "splitk_gemm", spy)
    bf = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16)  # noqa: E731
    for window in (1, 2, 4):
        jax.eval_shape(lambda x, wl, wr, w=window: ops.tiered_matmul(
            x, (wl, wr), window=w), bf(DECODE_ROWS, k), bf(k, n_loc),
            bf(k, n_rem))
    assert [w for w, _ in seen] == [1, 2, 4]
    assert len({blocks for _, blocks in seen}) == 1


def test_starcoder2_decode_step_takes_the_kernel_for_every_gemm():
    """One StarCoder2-3B decode step, traced on abstract inputs: all 150
    weight GEMMs (5 per layer x 30; the tied head is a plain dot) take the
    kernel, none the jnp fallback."""
    cfg = dataclasses.replace(C.get("starcoder2_3b"), tie_embeddings=True)
    batch, max_len, page = DECODE_ROWS, 1024, 16
    plan = E.plan(cfg, WorkloadSpec(batch=batch, seq_len=max_len,
                                    phase="decode"),
                  TPU_V5E, global_ratio=0.5, kv_page_size=page)
    params = jax.eval_shape(lambda: plan.partition(
        M.init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16), align=128))
    pc = PagedTieredCache(
        cfg.n_layers, cfg.n_kv_heads, cfg.resolved_head_dim, page_size=page,
        local_pages=64, remote_pages=64, max_slots=batch,
        max_pages_per_slot=max_len // page, dtype=jnp.bfloat16)
    lens = np.ones(batch, np.int32)
    wr = pc.write_targets(lens, np.ones(batch, bool))
    table, tier = pc.device_tables()
    args = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), jnp.asarray(a).dtype),
        (pc.pools, np.zeros((batch, 1), np.int32), lens, lens, table, tier,
         *wr))

    def step(p, *a):
        return TD.paged_tiered_decode_step(
            cfg, p, *a, sink_local=pc.sink_local, sink_remote=pc.sink_remote,
            window=plan.window.n_inflight, use_kernel=True)[0]

    with ops.count_dispatch() as dispatch:
        jax.eval_shape(step, params, *args)
    assert dispatch["gemm", "kernel"] == 150
    assert dispatch["gemm", "jnp"] == 0


def test_splitk_gemm_bitwise_equal_across_windows():
    """Two output tiles of two K chunks each: the window changes how many
    copies are in flight (2, 3 and 4 ring slots), never the result."""
    k = 2 * DMA_CHUNK_BYTES // (128 * 4)     # two f32 chunks of 128 columns
    x = jax.random.normal(jax.random.PRNGKey(0), (16, k), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (k, 256), jnp.float32)
    tw = partition(w, 0.5, axis=1, align=128)
    assert SG.gemm_blocks(16, k, 128, 128, 4) == (16, 128, k // 2)
    ys = [np.asarray(ops.tiered_matmul(x, tw, window=w)) for w in (1, 2, 4)]
    for y in ys[1:]:
        np.testing.assert_array_equal(y, ys[0])
    r = np.asarray(ref.splitk_gemm_ref(x, tw.local, tw.remote))
    assert np.max(np.abs(ys[0] - r)) / np.max(np.abs(r)) < 2e-4
