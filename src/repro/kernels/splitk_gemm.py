"""SplitK_GEMM — direct-access tiered GEMM (paper §4.1, Fig. 5) on TPU.

Computes ``y = x @ concat(w_local, w_remote, axis=1)`` where the weight is
column-partitioned between a local and a remote tier.  Every output tile's
producer stream DMAs its weight tiles from its home tier's buffer into VMEM
scratch (the TPU analogue of the paper's TMA remote→SMEM path),
double/multi-buffered so compute on chunk k overlaps the DMA of chunk
k+window.

Both operands are declared ``pl.ANY`` and both tiers live in HBM.  With
JAX 0.9 / libtpu 0.0.34 on v5e, a ``pltpu.HOST`` operand does not compile:
resident in HBM it aborts the compiler ("Unsupported operand memory
space"), and ``pinned_host`` fails as an unimplemented host→VMEM DMA.  A
kernel that copies a ``pinned_host`` array into an HBM buffer does
compile, so host residency has to be a two-hop host→HBM→VMEM stream; until
then the remote partition is a separate HBM buffer with the same tiling.

Paper mechanism ↔ kernel knob:
  * per-op offload ratio      → width of ``w_remote`` (set by the planner,
                                aligned to ``block_n`` — "wave alignment")
  * congestion window N_inflight → ``window`` = in-flight DMA slots
  * host-locality-first scheduling → ``order`` scalar-prefetch array: grid
    steps are remapped so host-sourced tiles are issued first (their
    longer-latency fetches start earliest)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BLOCK_M = 128
DEFAULT_BLOCK_N = 128
DEFAULT_BLOCK_K = 128
DEFAULT_WINDOW = 2


def _kernel(
    order_ref,                 # scalar prefetch: grid step -> n-tile id
    x_ref,                     # [bm, K] VMEM
    wl_hbm,                    # [K, N_loc] local tier (ANY/HBM)
    wr_host,                   # [K, N_rem] remote tier (ANY/HBM)
    o_ref,                     # [bm, bn] VMEM
    w_vmem,                    # scratch [slots, bk, bn]
    acc_ref,                   # scratch [bm, bn] fp32
    sem,                       # DMA semaphores [slots]
    *,
    block_k: int,
    block_n: int,
    n_loc_tiles: int,
    window: int,
):
    j = order_ref[pl.program_id(1)]
    n_k = x_ref.shape[1] // block_k
    is_remote = j >= n_loc_tiles
    n_slots = min(window, n_k)

    def start_copy(kk, slot):
        # Tier-isolated producer streams (paper Fig. 5b): an output tile
        # reads exclusively from its home tier.
        @pl.when(is_remote)
        def _():
            pltpu.make_async_copy(
                wr_host.at[pl.ds(kk * block_k, block_k),
                           pl.ds((j - n_loc_tiles) * block_n, block_n)],
                w_vmem.at[slot], sem.at[slot]).start()

        @pl.when(jnp.logical_not(is_remote))
        def _():
            pltpu.make_async_copy(
                wl_hbm.at[pl.ds(kk * block_k, block_k),
                          pl.ds(j * block_n, block_n)],
                w_vmem.at[slot], sem.at[slot]).start()

    # prologue: fill the congestion window (s bound per iteration: the
    # closure otherwise captures the loop variable by reference and every
    # @pl.when body would issue the *last* slot's copy)
    for s in range(n_slots):
        @pl.when(s < n_k)
        def _(s=s):
            start_copy(s, s)

    acc_ref[...] = jnp.zeros_like(acc_ref)

    def body(kk, _):
        slot = jax.lax.rem(kk, n_slots)
        pltpu.make_async_copy(w_vmem.at[slot], w_vmem.at[slot], sem.at[slot]).wait()
        acc_ref[...] += jnp.dot(
            x_ref[:, pl.ds(kk * block_k, block_k)], w_vmem[slot],
            preferred_element_type=jnp.float32)
        nxt = kk + n_slots           # steady state: never exceed the window
        @pl.when(nxt < n_k)
        def _():
            start_copy(nxt, slot)
        return 0

    jax.lax.fori_loop(0, n_k, body, 0)
    o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def host_first_order(n_loc_tiles: int, n_rem_tiles: int) -> np.ndarray:
    """Host-locality-first schedule: remote tiles before local tiles."""
    return np.concatenate([
        np.arange(n_loc_tiles, n_loc_tiles + n_rem_tiles),
        np.arange(0, n_loc_tiles),
    ]).astype(np.int32)


def vmem_footprint_bytes(
    m: int, k: int, *,
    block_m: int = DEFAULT_BLOCK_M,
    block_n: int = DEFAULT_BLOCK_N,
    block_k: int = DEFAULT_BLOCK_K,
    window: int = DEFAULT_WINDOW,
    dtype_bytes: int = 4,
) -> int:
    """Per-grid-step VMEM bytes one `splitk_gemm` launch holds resident:
    the x and output blocks plus the windowed weight-tile scratch and the
    fp32 accumulator.  Mirrors the BlockSpec/scratch_shapes above — the
    static verifier (DAK101) checks this against the hardware profile, so
    keep it in lockstep with the kernel."""
    del m  # the M extent tiles the grid; one block_m row block is resident
    n_slots = min(window, max(1, k // block_k))
    x_block = block_m * k * dtype_bytes
    out_block = block_m * block_n * dtype_bytes
    w_scratch = n_slots * block_k * block_n * dtype_bytes
    acc = block_m * block_n * 4
    return x_block + out_block + w_scratch + acc


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "window", "interpret"))
def splitk_gemm(
    x: jax.Array,              # [M, K]
    w_local: jax.Array,        # [K, N_loc]
    w_remote: jax.Array,       # [K, N_rem]
    *,
    block_m: int = DEFAULT_BLOCK_M,
    block_n: int = DEFAULT_BLOCK_N,
    block_k: int = DEFAULT_BLOCK_K,
    window: int = DEFAULT_WINDOW,
    interpret: bool = False,
) -> jax.Array:
    """Tiered GEMM. Shapes must be block-aligned (use ops.tiered_matmul for
    the padding/alignment wrapper).  Returns [M, N_loc + N_rem]."""
    m, k = x.shape
    n_loc, n_rem = w_local.shape[1], w_remote.shape[1]
    if m % block_m or k % block_k or n_loc % block_n or n_rem % block_n:
        raise ValueError(
            f"unaligned: M={m}%{block_m}, K={k}%{block_k}, "
            f"N_loc={n_loc}%{block_n}, N_rem={n_rem}%{block_n}")
    n_loc_tiles, n_rem_tiles = n_loc // block_n, n_rem // block_n
    n_tiles = n_loc_tiles + n_rem_tiles
    order = jnp.asarray(host_first_order(n_loc_tiles, n_rem_tiles))
    n_slots = min(window, max(1, k // block_k))
    # Degenerate tiers: both pl.when branches are traced, so an empty
    # partition must still present a sliceable shape. The dummy block is
    # never in `order`, hence never read or written.
    if n_rem == 0:
        w_remote = jnp.zeros((k, block_n), w_local.dtype)
    if n_loc == 0:
        w_local = jnp.zeros((k, block_n), w_remote.dtype)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(m // block_m, n_tiles),
        in_specs=[
            pl.BlockSpec((block_m, k), lambda i, j, order: (i, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((block_m, block_n),
                               lambda i, j, order: (i, order[j])),
        scratch_shapes=[
            pltpu.VMEM((n_slots, block_k, block_n), x.dtype),
            pltpu.VMEM((block_m, block_n), jnp.float32),
            pltpu.SemaphoreType.DMA((n_slots,)),
        ],
    )
    fn = pl.pallas_call(
        functools.partial(
            _kernel, block_k=block_k, block_n=block_n,
            n_loc_tiles=n_loc_tiles, window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n_loc + n_rem), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )
    return fn(order, x, w_local, w_remote)
