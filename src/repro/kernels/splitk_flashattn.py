"""SplitK_FlashAttn — direct-access tiered flash-decode attention (paper §5).

Decode attention for a batch of requests whose KV caches are partitioned
along the *batch* dimension between a local and a remote tier — exactly the
paper's `SplitK_FlashAttn` partitioning.  Each grid step handles one
request; requests homed on the remote tier stream their K/V chunks from the
remote buffer into VMEM, with the in-flight chunk count bounded by the
congestion ``window``.  Both tiers are ``pl.ANY`` operands resident in HBM:
a ``pltpu.HOST`` operand does not compile on v5e with this toolchain (see
`kernels.splitk_gemm`).
The sequence dimension is processed split-K style with an online-softmax
accumulator, so arbitrarily long caches run in O(block_s) VMEM.

Host-batch-first ordering plays the role of host-locality-first scheduling:
remote requests are issued first so their long-latency DMAs overlap the
local requests' compute.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BLOCK_S = 256
DEFAULT_WINDOW = 2
NEG_INF = -1e30


def _kernel(
    order_ref,                # grid step -> request id (host-first)
    q_ref,                    # [1, H, hd] VMEM (one request's new-token q)
    kl_hbm, vl_hbm,           # [B_loc, S, K, hd] local tier
    kr_host, vr_host,         # [B_rem, S, K, hd] remote tier
    o_ref,                    # [1, H, hd] VMEM
    k_vmem, v_vmem,           # scratch [slots, bs, K, hd]
    m_ref, l_ref, acc_ref,    # online-softmax state [Kh, G, *]
    ksem, vsem,
    *,
    block_s: int,
    n_loc: int,
    kv_len: int,
    window: int,
):
    b = order_ref[pl.program_id(0)]
    s_total = kl_hbm.shape[1]
    n_chunks = pl.cdiv(kv_len, block_s)
    n_slots = min(window, max(1, n_chunks))
    is_remote = b >= n_loc
    kh, hd = kl_hbm.shape[2], kl_hbm.shape[3]
    h = q_ref.shape[1]
    g = h // kh

    def start_copy(cc, slot):
        @pl.when(is_remote)
        def _():
            pltpu.make_async_copy(
                kr_host.at[b - n_loc, pl.ds(cc * block_s, block_s)],
                k_vmem.at[slot], ksem.at[slot]).start()
            pltpu.make_async_copy(
                vr_host.at[b - n_loc, pl.ds(cc * block_s, block_s)],
                v_vmem.at[slot], vsem.at[slot]).start()

        @pl.when(jnp.logical_not(is_remote))
        def _():
            pltpu.make_async_copy(
                kl_hbm.at[b, pl.ds(cc * block_s, block_s)],
                k_vmem.at[slot], ksem.at[slot]).start()
            pltpu.make_async_copy(
                vl_hbm.at[b, pl.ds(cc * block_s, block_s)],
                v_vmem.at[slot], vsem.at[slot]).start()

    # s bound per iteration (a late-bound closure would fill every slot
    # with the last chunk's copy)
    for s in range(n_slots):
        @pl.when(s < n_chunks)
        def _(s=s):
            start_copy(s, s)

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    # group-MAJOR GQA (q head h -> kv head h % kh), matching models.layers
    qg = q_ref[0].reshape(g, kh, hd).swapaxes(0, 1).astype(jnp.float32) * (hd ** -0.5)

    def body(cc, _):
        slot = jax.lax.rem(cc, n_slots)
        pltpu.make_async_copy(k_vmem.at[slot], k_vmem.at[slot], ksem.at[slot]).wait()
        pltpu.make_async_copy(v_vmem.at[slot], v_vmem.at[slot], vsem.at[slot]).wait()
        kc = k_vmem[slot].astype(jnp.float32)            # [bs, Kh, hd]
        vc = v_vmem[slot].astype(jnp.float32)
        # scores [Kh, G, bs] — GQA batched over kv heads
        s_kgb = jax.lax.dot_general(
            qg, kc,
            dimension_numbers=(((2,), (2,)), ((0,), (1,))))
        span = cc * block_s + jax.lax.broadcasted_iota(jnp.int32, (1, 1, block_s), 2)
        s_kgb = jnp.where(span < kv_len, s_kgb, NEG_INF)

        m_new = jnp.maximum(m_ref[...], jnp.max(s_kgb, axis=-1, keepdims=True))
        p = jnp.exp(s_kgb - m_new)
        corr = jnp.exp(m_ref[...] - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        # pv [Kh, G, hd]
        pv = jax.lax.dot_general(
            p, vc, dimension_numbers=(((2,), (0,)), ((0,), (1,))))
        acc_ref[...] = acc_ref[...] * corr + pv
        m_ref[...] = m_new

        nxt = cc + n_slots
        @pl.when(nxt < n_chunks)
        def _():
            start_copy(nxt, slot)
        return 0

    jax.lax.fori_loop(0, n_chunks, body, 0)
    out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)   # [Kh, G, hd]
    o_ref[0] = out.swapaxes(0, 1).reshape(h, hd).astype(o_ref.dtype)


def host_first_batch_order(n_loc: int, n_rem: int) -> np.ndarray:
    return np.concatenate([
        np.arange(n_loc, n_loc + n_rem), np.arange(0, n_loc)
    ]).astype(np.int32)


def vmem_footprint_bytes(
    h: int, kh: int, hd: int, kv_len: int, *,
    block_s: int = DEFAULT_BLOCK_S,
    window: int = DEFAULT_WINDOW,
    dtype_bytes: int = 4,
) -> int:
    """Per-grid-step VMEM bytes of one `splitk_flashattn` launch: the q and
    output blocks, the windowed K/V chunk scratch, and the fp32
    online-softmax state.  Mirrors scratch_shapes above (DAK101)."""
    g = max(1, h // kh)
    n_chunks = max(1, -(-kv_len // block_s))
    n_slots = min(window, n_chunks)
    qo_blocks = 2 * h * hd * dtype_bytes
    kv_scratch = 2 * n_slots * block_s * kh * hd * dtype_bytes
    softmax_state = (2 * kh * g + kh * g * hd) * 4
    return qo_blocks + kv_scratch + softmax_state


def paged_vmem_footprint_bytes(
    h: int, kh: int, hd: int, page_size: int, max_pages: int, *,
    window: int = DEFAULT_WINDOW,
    dtype_bytes: int = 4,
) -> int:
    """Per-grid-step VMEM bytes of one `paged_splitk_flashattn` launch —
    the paged variant streams page-sized K/V chunks (DAK101)."""
    g = max(1, h // kh)
    n_slots = min(window, max_pages)
    qo_blocks = 2 * h * hd * dtype_bytes
    kv_scratch = 2 * n_slots * page_size * kh * hd * dtype_bytes
    softmax_state = (2 * kh * g + kh * g * hd) * 4
    return qo_blocks + kv_scratch + softmax_state


@functools.partial(
    jax.jit,
    static_argnames=("kv_len", "block_s", "window", "interpret"))
def splitk_flashattn(
    q: jax.Array,              # [B, H, hd] (B = B_loc + B_rem, local first)
    k_local: jax.Array,        # [B_loc, S, Kh, hd]
    v_local: jax.Array,
    k_remote: jax.Array,       # [B_rem, S, Kh, hd]
    v_remote: jax.Array,
    *,
    kv_len: int,               # valid cache length (<= S)
    block_s: int = DEFAULT_BLOCK_S,
    window: int = DEFAULT_WINDOW,
    interpret: bool = False,
) -> jax.Array:
    """Tiered flash-decode. Returns o [B, H, hd]."""
    b_loc, s, kh, hd = k_local.shape
    b_rem = k_remote.shape[0]
    b, h, _ = q.shape
    if b != b_loc + b_rem:
        raise ValueError(f"batch mismatch: {b} != {b_loc}+{b_rem}")
    if s % block_s:
        raise ValueError(f"S={s} not a multiple of block_s={block_s}")
    order = jnp.asarray(host_first_batch_order(b_loc, b_rem))
    n_chunks = max(1, -(-kv_len // block_s))
    n_slots = min(window, n_chunks)
    g = h // kh
    # Degenerate tiers: keep both refs sliceable (dummy request is never in
    # `order`, hence never read).
    if b_rem == 0:
        k_remote = jnp.zeros((1, s, kh, hd), k_local.dtype)
        v_remote = jnp.zeros((1, s, kh, hd), v_local.dtype)
    if b_loc == 0:
        k_local = jnp.zeros((1, s, kh, hd), k_remote.dtype)
        v_local = jnp.zeros((1, s, kh, hd), v_remote.dtype)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, h, hd), lambda i, order: (order[i], 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, h, hd), lambda i, order: (order[i], 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((n_slots, block_s, kh, hd), k_local.dtype),
            pltpu.VMEM((n_slots, block_s, kh, hd), v_local.dtype),
            pltpu.VMEM((kh, g, 1), jnp.float32),
            pltpu.VMEM((kh, g, 1), jnp.float32),
            pltpu.VMEM((kh, g, hd), jnp.float32),
            pltpu.SemaphoreType.DMA((n_slots,)),
            pltpu.SemaphoreType.DMA((n_slots,)),
        ],
    )
    fn = pl.pallas_call(
        functools.partial(
            _kernel, block_s=block_s, n_loc=b_loc, kv_len=kv_len, window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )
    return fn(order, q, k_local, v_local, k_remote, v_remote)


# ==========================================================================
# Paged variant — page-table-indexed KV gather per tier (ragged batches)
# ==========================================================================
def _paged_kernel(
    order_ref,                # grid step -> slot id (host-locality-first)
    table_ref,                # [B, MP] page index into the page's tier pool
    tier_ref,                 # [B, MP] 0 = local pool, 1 = remote pool
    lens_ref,                 # [B] valid tokens per slot
    q_ref,                    # [1, H, hd] VMEM
    kl_hbm, vl_hbm,           # [P_loc(+sink), page, Kh, hd] local pool
    kr_host, vr_host,         # [P_rem(+sink), page, Kh, hd] remote pool
    o_ref,                    # [1, H, hd] VMEM
    k_vmem, v_vmem,           # scratch [slots, page, Kh, hd]
    m_ref, l_ref, acc_ref,
    ksem, vsem,
    *,
    window: int,
    scale: float | None = None,
):
    b = order_ref[pl.program_id(0)]
    ps = kl_hbm.shape[1]
    n = lens_ref[b]
    n_chunks = pl.cdiv(n, ps)                    # dynamic: per-slot page count
    max_pages = table_ref.shape[1]
    n_slots = min(window, max_pages)
    kh, hd = kl_hbm.shape[2], kl_hbm.shape[3]
    h = q_ref.shape[1]
    g = h // kh

    def start_copy(cc, slot):
        idx = table_ref[b, cc]
        is_remote = tier_ref[b, cc] > 0

        @pl.when(is_remote)
        def _():
            pltpu.make_async_copy(kr_host.at[idx], k_vmem.at[slot], ksem.at[slot]).start()
            pltpu.make_async_copy(vr_host.at[idx], v_vmem.at[slot], vsem.at[slot]).start()

        @pl.when(jnp.logical_not(is_remote))
        def _():
            pltpu.make_async_copy(kl_hbm.at[idx], k_vmem.at[slot], ksem.at[slot]).start()
            pltpu.make_async_copy(vl_hbm.at[idx], v_vmem.at[slot], vsem.at[slot]).start()

    for s in range(n_slots):
        @pl.when(s < n_chunks)
        def _(s=s):
            start_copy(s, s)

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    sc = (hd ** -0.5) if scale is None else scale
    qg = q_ref[0].reshape(g, kh, hd).swapaxes(0, 1).astype(jnp.float32) * sc

    def body(cc, _):
        slot = jax.lax.rem(cc, n_slots)
        pltpu.make_async_copy(k_vmem.at[slot], k_vmem.at[slot], ksem.at[slot]).wait()
        pltpu.make_async_copy(v_vmem.at[slot], v_vmem.at[slot], vsem.at[slot]).wait()
        kc = k_vmem[slot].astype(jnp.float32)
        vc = v_vmem[slot].astype(jnp.float32)
        s_kgb = jax.lax.dot_general(
            qg, kc, dimension_numbers=(((2,), (2,)), ((0,), (1,))))
        span = cc * ps + jax.lax.broadcasted_iota(jnp.int32, (1, 1, ps), 2)
        s_kgb = jnp.where(span < n, s_kgb, NEG_INF)

        m_new = jnp.maximum(m_ref[...], jnp.max(s_kgb, axis=-1, keepdims=True))
        p = jnp.exp(s_kgb - m_new)
        corr = jnp.exp(m_ref[...] - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p, vc, dimension_numbers=(((2,), (0,)), ((0,), (1,))))
        acc_ref[...] = acc_ref[...] * corr + pv
        m_ref[...] = m_new

        nxt = cc + n_slots
        @pl.when(nxt < n_chunks)
        def _():
            start_copy(nxt, slot)
        return 0

    jax.lax.fori_loop(0, n_chunks, body, 0)
    out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)   # zeros when n == 0
    o_ref[0] = out.swapaxes(0, 1).reshape(h, hd).astype(o_ref.dtype)


def host_first_slot_order(tier: jax.Array, lens: jax.Array, page_size: int) -> jax.Array:
    """Slots holding any in-use remote page are issued first so their
    long-latency host DMAs overlap the local slots' compute
    (host-locality-first scheduling at slot granularity)."""
    mp = tier.shape[1]
    pages_used = -(-lens[:, None] // page_size)            # cdiv, [B,1]
    in_use = jnp.arange(mp)[None, :] < pages_used
    has_remote = jnp.any((tier > 0) & in_use, axis=1)
    return jnp.argsort(jnp.logical_not(has_remote), stable=True).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("window", "scale", "interpret"))
def paged_splitk_flashattn(
    q: jax.Array,              # [B, H, hd]
    k_pages_local: jax.Array,  # [P_loc(+sink), page, Kh, hd]
    v_pages_local: jax.Array,
    k_pages_remote: jax.Array,
    v_pages_remote: jax.Array,
    table: jax.Array,          # [B, MP] int32
    tier: jax.Array,           # [B, MP] int32 (0 local / 1 remote)
    lens: jax.Array,           # [B] int32
    *,
    window: int = DEFAULT_WINDOW,
    scale: float | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Paged tiered flash-decode: each slot's KV is gathered page-by-page
    from whichever pool the page table names, under the congestion window.
    Per-slot ``lens`` makes the batch ragged; lens == 0 slots output zeros.
    ``scale`` overrides the softmax scale (default ``hd**-0.5``) — MLA
    attends latent-width pages with the paper model's ``(nd+rd)**-0.5``."""
    b, h, hd = q.shape
    ps, kh = k_pages_local.shape[1], k_pages_local.shape[2]
    mp = table.shape[1]
    n_slots = min(window, mp)
    g = h // kh
    order = host_first_slot_order(tier, lens, ps)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, h, hd), lambda i, order, table, tier, lens: (order[i], 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, h, hd), lambda i, order, table, tier, lens: (order[i], 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((n_slots, ps, kh, hd), k_pages_local.dtype),
            pltpu.VMEM((n_slots, ps, kh, hd), v_pages_local.dtype),
            pltpu.VMEM((kh, g, 1), jnp.float32),
            pltpu.VMEM((kh, g, 1), jnp.float32),
            pltpu.VMEM((kh, g, hd), jnp.float32),
            pltpu.SemaphoreType.DMA((n_slots,)),
            pltpu.SemaphoreType.DMA((n_slots,)),
        ],
    )
    fn = pl.pallas_call(
        functools.partial(_paged_kernel, window=window, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )
    return fn(order, table.astype(jnp.int32), tier.astype(jnp.int32),
              lens.astype(jnp.int32), q,
              k_pages_local, v_pages_local, k_pages_remote, v_pages_remote)
