"""SplitK_GEMM — direct-access tiered GEMM (paper §4.1, Fig. 5) on TPU.

Computes ``y = x @ concat(w_local, w_remote, axis=1)`` where the weight is
column-partitioned between a local and a remote tier.  Each grid step owns
one ``block_m`` row block and streams the whole weight through a ring of
VMEM slots (the TPU analogue of the paper's TMA remote→SMEM path): the
sequence of (output tile in host-first order) × (K chunk) copies runs as
one stream, with no drain between output tiles.  At ``window`` w the copy
of chunk s+w is issued before the kernel waits on chunk s, so w copies are
outstanding beyond the chunk being consumed (w + 1 slots); at window 1 this
is double buffering.  A finished output tile is written back to HBM by its
own async copy while the stream moves on.

The weight tile is sized from the call's shapes (`gemm_blocks`): about
``DMA_CHUNK_BYTES`` per copy, the chunk the plan sizes its window in.  The
tile never depends on the window, so results are bitwise-independent of it.

Both tiers are ``pltpu.HBM`` operands and live in HBM.  Declared
``pl.ANY``, XLA may place a weight in VMEM and move it there outside the
kernel, which then no longer does the copies it is timed for.  With
JAX 0.9 / libtpu 0.0.34 on v5e, a ``pltpu.HOST`` operand does not compile:
resident in HBM it aborts the compiler ("Unsupported operand memory
space"), and ``pinned_host`` fails as an unimplemented host→VMEM DMA.  A
kernel that copies a ``pinned_host`` array into an HBM buffer does
compile, so host residency has to be a two-hop host→HBM→VMEM stream; until
then the remote partition is a separate HBM buffer with the same tiling.

Paper mechanism ↔ kernel knob:
  * per-op offload ratio      → width of ``w_remote`` (set by the planner,
                                aligned to ``block_n`` — "wave alignment")
  * congestion window N_inflight → ``window`` = weight copies in flight
                                beyond the one being consumed
  * host-locality-first scheduling → ``order`` scalar-prefetch array: output
    tiles are streamed in this order, host-sourced tiles first (their
    longer-latency fetches start earliest)
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.congestion import DMA_CHUNK_BYTES

LANE = 128                     # lane width: block_n and block_k are multiples
SUBLANE_PACK = 16              # bf16 rows per vreg: block_m is a multiple
MAX_BLOCK_M = 128
DEFAULT_WINDOW = 2
# VMEM the weight ring may take: a window beyond it is clamped (the slot
# count only paces copies, so clamping never changes results).
RING_VMEM_BYTES = 8 * 1024 * 1024


def _round_up(v: int, mult: int) -> int:
    return -(-v // mult) * mult


def gemm_blocks(m: int, k: int, n_loc: int, n_rem: int, dtype_bytes: int,
                chunk_bytes: int = DMA_CHUNK_BYTES) -> tuple[int, int, int]:
    """(block_m, block_n, block_k) for one call, from its shapes alone.

    ``block_m`` is M rounded up to the bf16 sublane pack, at most 128.
    ``block_n`` is the widest multiple of 128 that divides both partitions
    and whose 128-row tile fits in ``chunk_bytes`` (128 when none divides
    both: the caller then takes the oracle).  ``block_k`` is the multiple of
    128 dividing K (padded to 128) that brings the weight tile
    ``block_k × block_n`` nearest to ``chunk_bytes``, the smaller on a tie.
    """
    block_m = min(_round_up(max(m, 1), SUBLANE_PACK), MAX_BLOCK_M)
    widest = max(LANE, chunk_bytes // (LANE * dtype_bytes) // LANE * LANE)
    both = math.gcd(n_loc, n_rem)
    block_n = max((c for c in range(LANE, min(both, widest) + 1, LANE)
                   if both % c == 0), default=LANE)
    kp = _round_up(k, LANE)
    block_k = min((c for c in range(LANE, kp + 1, LANE) if kp % c == 0),
                  key=lambda c: (abs(c * block_n * dtype_bytes - chunk_bytes), c))
    return block_m, block_n, block_k


def ring_slots(window: int, n_steps: int, tile_bytes: int) -> int:
    """VMEM slots of the weight ring: the chunk being consumed plus
    ``window`` in flight, no more than the stream's copies, and within
    ``RING_VMEM_BYTES`` (at least double buffering)."""
    cap = max(2, RING_VMEM_BYTES // max(1, tile_bytes))
    return max(1, min(window + 1, n_steps, cap))


def _kernel(
    order_ref,                 # scalar prefetch: tile position -> n-tile id
    x_ref,                     # [bm, K] VMEM
    wl_hbm,                    # [K, N_loc] local tier (ANY/HBM)
    wr_hbm,                    # [K, N_rem] remote tier (ANY/HBM)
    o_hbm,                     # [M, N_loc + N_rem] output (ANY/HBM)
    ring,                      # scratch [slots, bk, bn]: the weight ring
    acc_ref,                   # scratch [bm, bn] fp32
    out_buf,                   # scratch [2, bm, bn]: finished tiles
    sem,                       # DMA semaphores [slots]
    out_sem,                   # DMA semaphores [2]
    *,
    block_m: int,
    block_k: int,
    block_n: int,
    n_k: int,
    n_tiles: int,
    n_loc_tiles: int,
    n_slots: int,
):
    rows = pl.ds(pl.program_id(0) * block_m, block_m)
    n_steps = n_tiles * n_k
    ahead = n_slots - 1        # copies in flight beyond the one consumed

    def start_copy(step, slot):
        # Tier-isolated producer streams (paper Fig. 5b): an output tile
        # reads exclusively from its home tier.
        j = order_ref[step // n_k]
        k_rows = pl.ds(pl.multiple_of((step % n_k) * block_k, block_k), block_k)
        is_remote = j >= n_loc_tiles

        @pl.when(is_remote)
        def _():
            cols = pl.ds(pl.multiple_of((j - n_loc_tiles) * block_n, block_n),
                         block_n)
            pltpu.make_async_copy(wr_hbm.at[k_rows, cols], ring.at[slot],
                                  sem.at[slot]).start()

        @pl.when(jnp.logical_not(is_remote))
        def _():
            cols = pl.ds(pl.multiple_of(j * block_n, block_n), block_n)
            pltpu.make_async_copy(wl_hbm.at[k_rows, cols], ring.at[slot],
                                  sem.at[slot]).start()

    def wait_out(buf):
        pltpu.make_async_copy(out_buf.at[buf], out_buf.at[buf],
                              out_sem.at[buf]).wait()

    def finish_tile(step, total):
        t = step // n_k
        buf = t % 2

        @pl.when(t >= 2)       # the buffer's previous tile has left VMEM
        def _():
            wait_out(buf)

        out_buf[buf] = total.astype(out_buf.dtype)
        cols = pl.ds(pl.multiple_of(order_ref[t] * block_n, block_n), block_n)
        pltpu.make_async_copy(out_buf.at[buf], o_hbm.at[rows, cols],
                              out_sem.at[buf]).start()

    for s in range(ahead):     # fill the window (ahead < n_steps)
        start_copy(s, s)

    def body(step, carry):
        nxt = step + ahead     # issue before waiting: `window` stay in flight

        @pl.when(nxt < n_steps)
        def _():
            start_copy(nxt, nxt % n_slots)

        slot = step % n_slots
        pltpu.make_async_copy(ring.at[slot], ring.at[slot], sem.at[slot]).wait()
        kk = step % n_k
        part = jnp.dot(
            x_ref[:, pl.ds(pl.multiple_of(kk * block_k, block_k), block_k)],
            ring[slot], preferred_element_type=jnp.float32)
        if n_k == 1:
            finish_tile(step, part)
            return carry

        @pl.when(kk == 0)
        def _():
            acc_ref[...] = part

        @pl.when(jnp.logical_and(kk > 0, kk < n_k - 1))
        def _():
            acc_ref[...] += part

        @pl.when(kk == n_k - 1)
        def _():
            finish_tile(step, acc_ref[...] + part)
        return carry

    jax.lax.fori_loop(0, n_steps, body, 0)
    for t in range(max(0, n_tiles - 2), n_tiles):   # drain the write-backs
        wait_out(t % 2)


def host_first_order(n_loc_tiles: int, n_rem_tiles: int) -> np.ndarray:
    """Host-locality-first schedule: remote tiles before local tiles."""
    return np.concatenate([
        np.arange(n_loc_tiles, n_loc_tiles + n_rem_tiles),
        np.arange(0, n_loc_tiles),
    ]).astype(np.int32)


def vmem_footprint_bytes(
    m: int, k: int, n: int, *,
    block_m: int,
    block_n: int,
    block_k: int,
    window: int = DEFAULT_WINDOW,
    dtype_bytes: int = 4,
) -> int:
    """VMEM bytes one `splitk_gemm` launch holds resident: the double-
    buffered x row block, the weight ring (`ring_slots`), the fp32
    accumulator and the two write-back buffers.  Mirrors the BlockSpec and
    scratch shapes above — the static verifier (DAK101) checks this against
    the hardware profile, so keep it in lockstep with the kernel."""
    del m  # the M extent tiles the grid; one block_m row block is resident
    tile = block_k * block_n * dtype_bytes
    n_steps = (n // block_n) * max(1, k // block_k)
    x_blocks = 2 * block_m * k * dtype_bytes
    w_ring = ring_slots(window, n_steps, tile) * tile
    acc = block_m * block_n * 4
    out_bufs = 2 * block_m * block_n * dtype_bytes
    return x_blocks + w_ring + acc + out_bufs


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "window", "interpret"))
def splitk_gemm(
    x: jax.Array,              # [M, K]
    w_local: jax.Array,        # [K, N_loc]
    w_remote: jax.Array,       # [K, N_rem]
    *,
    block_m: int | None = None,
    block_n: int | None = None,
    block_k: int | None = None,
    window: int = DEFAULT_WINDOW,
    interpret: bool = False,
) -> jax.Array:
    """Tiered GEMM. Shapes must be block-aligned (use ops.tiered_matmul for
    the padding/alignment wrapper); a block left None is `gemm_blocks`'
    choice.  Returns [M, N_loc + N_rem]."""
    m, k = x.shape
    n_loc, n_rem = w_local.shape[1], w_remote.shape[1]
    auto = gemm_blocks(m, k, n_loc, n_rem, x.dtype.itemsize)
    block_m = block_m or auto[0]
    block_n = block_n or auto[1]
    block_k = block_k or auto[2]
    if m % block_m or k % block_k or n_loc % block_n or n_rem % block_n:
        raise ValueError(
            f"unaligned: M={m}%{block_m}, K={k}%{block_k}, "
            f"N_loc={n_loc}%{block_n}, N_rem={n_rem}%{block_n}")
    n_loc_tiles, n_rem_tiles = n_loc // block_n, n_rem // block_n
    n_tiles = n_loc_tiles + n_rem_tiles
    n_k = k // block_k
    order = jnp.asarray(host_first_order(n_loc_tiles, n_rem_tiles))
    n_slots = ring_slots(max(1, window), n_tiles * n_k,
                         block_k * block_n * w_local.dtype.itemsize)
    # Degenerate tiers: both pl.when branches are traced, so an empty
    # partition must still present a sliceable shape. The dummy block is
    # never in `order`, hence never read.
    if n_rem == 0:
        w_remote = jnp.zeros((k, block_n), w_local.dtype)
    if n_loc == 0:
        w_local = jnp.zeros((k, block_n), w_remote.dtype)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(m // block_m,),
        in_specs=[
            pl.BlockSpec((block_m, k), lambda i, order: (i, 0)),
            pl.BlockSpec(memory_space=pltpu.HBM),
            pl.BlockSpec(memory_space=pltpu.HBM),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((n_slots, block_k, block_n), w_local.dtype),
            pltpu.VMEM((block_m, block_n), jnp.float32),
            pltpu.VMEM((2, block_m, block_n), x.dtype),
            pltpu.SemaphoreType.DMA((n_slots,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    fn = pl.pallas_call(
        functools.partial(
            _kernel, block_m=block_m, block_k=block_k, block_n=block_n,
            n_k=n_k, n_tiles=n_tiles, n_loc_tiles=n_loc_tiles,
            n_slots=n_slots),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n_loc + n_rem), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
    )
    if not interpret:
        # Left unconstrained, XLA may place a weight in VMEM (its alternate
        # memory) and move it there outside the kernel; both tiers stay in
        # HBM, and the kernel streams them itself.
        w_local = pltpu.with_memory_space_constraint(w_local, pltpu.HBM)
        w_remote = pltpu.with_memory_space_constraint(w_remote, pltpu.HBM)
    return fn(order, x, w_local, w_remote)
