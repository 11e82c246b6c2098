"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret mode)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import tiering
from repro.kernels import ops, ref
from repro.kernels.splitk_gemm import host_first_order

TOL = {jnp.float32: 2e-4, jnp.bfloat16: 5e-2}


def _rel_err(a, b):
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return np.max(np.abs(a - b)) / (np.max(np.abs(b)) + 1e-9)


@pytest.mark.parametrize("m,k,n", [(32, 128, 256), (64, 256, 512),
                                   (130, 384, 640), (256, 512, 128)])
@pytest.mark.parametrize("ratio", [0.0, 0.25, 0.5, 1.0])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_splitk_gemm_sweep(m, k, n, ratio, dtype):
    key = jax.random.PRNGKey(m + k + n)
    x = jax.random.normal(key, (m, k), dtype)
    w = jax.random.normal(jax.random.PRNGKey(1), (k, n), dtype)
    tw = tiering.partition(w, ratio, axis=1, align=128)
    y = ops.tiered_matmul(x, tw, window=2)
    r = ref.splitk_gemm_ref(x, tw.local, tw.remote)
    assert _rel_err(y, r) < TOL[dtype]


@pytest.mark.parametrize("window", [1, 2, 4])
def test_splitk_gemm_window_invariance(window):
    """Congestion window changes scheduling, never results."""
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 256), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (256, 384), jnp.float32)
    tw = tiering.partition(w, 0.33, axis=1, align=128)
    y = ops.tiered_matmul(x, tw, window=window)
    r = ref.splitk_gemm_ref(x, tw.local, tw.remote)
    assert _rel_err(y, r) < TOL[jnp.float32]


def test_splitk_gemm_batched_input():
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 8, 256), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (256, 256), jnp.float32)
    tw = tiering.partition(w, 0.5, axis=1, align=128)
    y = ops.tiered_matmul(x, tw)
    assert y.shape == (4, 8, 256)
    r = ref.splitk_gemm_ref(x.reshape(-1, 256), tw.local, tw.remote).reshape(4, 8, 256)
    assert _rel_err(y, r) < TOL[jnp.float32]


def test_host_first_order():
    order = host_first_order(3, 2)
    assert list(order) == [3, 4, 0, 1, 2]


@pytest.mark.parametrize("b_loc,b_rem", [(4, 2), (0, 6), (6, 0), (1, 1)])
@pytest.mark.parametrize("kv_len", [64, 100, 256])
@pytest.mark.parametrize("heads", [(8, 2), (4, 4), (16, 1)])
def test_splitk_flashattn_sweep(b_loc, b_rem, kv_len, heads):
    h, kh = heads
    hd, s = 32, 256
    b = b_loc + b_rem
    key = jax.random.PRNGKey(b * kv_len + h)
    q = jax.random.normal(key, (b, h, hd), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, s, kh, hd), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, kh, hd), jnp.float32)
    kv = {"k_local": k[:b_loc], "v_local": v[:b_loc],
          "k_remote": k[b_loc:], "v_remote": v[b_loc:]}
    y = ops.tiered_decode_attention(q, kv, kv_len=kv_len, block_s=64, window=2)
    r = ref.splitk_flashattn_ref(q, k[:b_loc], v[:b_loc], k[b_loc:], v[b_loc:], kv_len)
    assert _rel_err(y, r) < 1e-4


def test_splitk_flashattn_bf16():
    b_loc, b_rem, h, kh, hd, s = 2, 2, 8, 2, 64, 128
    q = jax.random.normal(jax.random.PRNGKey(0), (4, h, hd), jnp.bfloat16)
    k = jax.random.normal(jax.random.PRNGKey(1), (4, s, kh, hd), jnp.bfloat16)
    v = jax.random.normal(jax.random.PRNGKey(2), (4, s, kh, hd), jnp.bfloat16)
    kv = {"k_local": k[:b_loc], "v_local": v[:b_loc],
          "k_remote": k[b_loc:], "v_remote": v[b_loc:]}
    y = ops.tiered_decode_attention(q, kv, kv_len=s, block_s=64)
    r = ref.splitk_flashattn_ref(q, k[:b_loc], v[:b_loc], k[b_loc:], v[b_loc:], s)
    assert _rel_err(y, r) < 5e-2


@pytest.mark.parametrize("window", [1, 2, 4])
@pytest.mark.parametrize("lens", [[5, 0, 17, 32], [1, 1, 1, 1], [32, 32, 32, 32]])
def test_paged_flashattn_sweep(window, lens):
    """Paged tiered decode attention vs the gather oracle: ragged lengths,
    random page tables, pages scattered across both tiers."""
    b, h, kh, hd, ps, mp = 4, 8, 2, 32, 8, 4
    pl_, pr_ = 6, 5
    rng = np.random.default_rng(window * 100 + lens[0])
    q = jnp.asarray(rng.normal(size=(b, h, hd)), jnp.float32)
    pools = {n: jnp.asarray(rng.normal(size=(p + 1, ps, kh, hd)), jnp.float32)
             for n, p in (("k_local", pl_), ("v_local", pl_),
                          ("k_remote", pr_), ("v_remote", pr_))}
    table = jnp.asarray(rng.integers(0, 5, size=(b, mp)), jnp.int32)
    tier = jnp.asarray(rng.integers(0, 2, size=(b, mp)), jnp.int32)
    lens_a = jnp.asarray(lens, jnp.int32)
    y = ops.paged_decode_attention(q, pools, table, tier, lens_a, window=window)
    r = ref.paged_flashattn_ref(
        q, pools["k_local"], pools["v_local"], pools["k_remote"],
        pools["v_remote"], table, tier, lens_a)
    assert _rel_err(y, r) < 1e-4
    # empty slots must output exactly zero
    for i, n in enumerate(lens):
        if n == 0:
            assert np.all(np.asarray(y)[i] == 0)


def test_paged_flashattn_bf16():
    b, h, kh, hd, ps, mp = 3, 4, 4, 16, 4, 3
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(b, h, hd)), jnp.bfloat16)
    pools = {n: jnp.asarray(rng.normal(size=(5, ps, kh, hd)), jnp.bfloat16)
             for n in ("k_local", "v_local", "k_remote", "v_remote")}
    table = jnp.asarray(rng.integers(0, 4, size=(b, mp)), jnp.int32)
    tier = jnp.asarray(rng.integers(0, 2, size=(b, mp)), jnp.int32)
    lens = jnp.asarray([7, 12, 3], jnp.int32)
    y = ops.paged_decode_attention(q, pools, table, tier, lens, window=2)
    r = ref.paged_flashattn_ref(
        q, pools["k_local"], pools["v_local"], pools["k_remote"],
        pools["v_remote"], table, tier, lens)
    assert _rel_err(y, r) < 5e-2


def test_broadcast_remote_shard_map():
    """Fetch-once-broadcast: all_gather of the sharded host partition."""
    from jax.sharding import PartitionSpec as P
    mesh = jax.make_mesh((1,), ("model",))
    w = tiering.partition(jnp.arange(32.0).reshape(4, 8), 0.5, axis=0)

    def f(local, remote):
        return ops.broadcast_remote(
            tiering.TieredArray(local, remote, axis=0), "model").materialize()

    out = jax.shard_map(f, mesh=mesh,
                    in_specs=(P(None, None), P("model", None)),
                    out_specs=P(None, None), check_vma=False)(w.local, w.remote)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(w.materialize()))
