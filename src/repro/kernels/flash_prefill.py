"""Causal flash attention for prefill (Pallas TPU).

Tiled online-softmax attention: grid (batch, q-head, q-block, k-block) with
the k-block dimension accumulating into VMEM scratch (m/l/acc survive grid
revisits along the innermost dimension; the final k-block writes the
output).  GQA is group-MAJOR to match `models.layers` (q head h reads kv
head h % K).  Causal blocks above the diagonal are masked; fully-masked
blocks skip the matmuls.

This is the prefill-side perf-critical kernel for TPU deployment; the
pjit/XLA path (`models.layers.attend`) remains the portable fallback and the
oracle for the interpret-mode tests.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BLOCK_Q = 256
DEFAULT_BLOCK_K = 256
NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
            *, block_q: int, block_k: int, n_kblocks: int, causal: bool):
    i = pl.program_id(2)          # q block
    j = pl.program_id(3)          # k block

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = i * block_q
    k_start = j * block_k
    # skip blocks strictly above the causal diagonal
    needed = jnp.logical_or(jnp.logical_not(causal),
                            k_start <= q_start + block_q - 1)

    @pl.when(needed)
    def _():
        q = q_ref[0, 0].astype(jnp.float32)          # [bq, hd]
        k = k_ref[0, 0].astype(jnp.float32)          # [bk, hd]
        v = v_ref[0, 0].astype(jnp.float32)
        hd = q.shape[-1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ()))) * (hd ** -0.5)   # [bq, bk]
        if causal:
            qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        m_new = jnp.maximum(m_ref[...], jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_ref[...] - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())))
        m_ref[...] = m_new

    @pl.when(j == n_kblocks - 1)
    def _():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                       ).astype(o_ref.dtype)


def vmem_footprint_bytes(
    hd: int, *,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    dtype_bytes: int = 4,
) -> int:
    """Per-grid-step VMEM bytes of one `flash_prefill` launch: q/k/v/output
    blocks plus the fp32 online-softmax scratch.  Mirrors the BlockSpec and
    scratch_shapes below (DAK101)."""
    qo_blocks = 2 * block_q * hd * dtype_bytes
    kv_blocks = 2 * block_k * hd * dtype_bytes
    softmax_state = (2 * block_q + block_q * hd) * 4
    return qo_blocks + kv_blocks + softmax_state


@functools.partial(
    jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret"))
def flash_prefill(
    q: jax.Array,          # [B, H, Tq, hd]
    k: jax.Array,          # [B, K, Tk, hd]
    v: jax.Array,          # [B, K, Tk, hd]
    *,
    causal: bool = True,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
) -> jax.Array:
    b, h, tq, hd = q.shape
    kh, tk = k.shape[1], k.shape[2]
    if tq % block_q or tk % block_k:
        raise ValueError(f"T={tq}/{tk} not multiples of {block_q}/{block_k}")
    n_kblocks = tk // block_k

    grid = (b, h, tq // block_q, n_kblocks)
    fn = pl.pallas_call(
        functools.partial(_kernel, block_q=block_q, block_k=block_k,
                          n_kblocks=n_kblocks, causal=causal),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd), lambda b_, h_, i, j: (b_, h_, i, 0)),
            # group-major GQA: q head h -> kv head h % K
            pl.BlockSpec((1, 1, block_k, hd), lambda b_, h_, i, j: (b_, h_ % kh, j, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b_, h_, i, j: (b_, h_ % kh, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd), lambda b_, h_, i, j: (b_, h_, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        out_shape=jax.ShapeDtypeStruct((b, h, tq, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )
    return fn(q, k, v)
