"""Public jit'd wrappers around the direct-access kernels.

`tiered_matmul` / `tiered_decode_attention` are the drop-in compute ops the
serving engine uses (the JAX analogue of the paper's SplitK_GEMM /
SplitK_FlashAttn PyTorch modules).  They handle shape alignment ("execution
wave alignment", paper §4.1), run the kernels in interpret mode when the
backend is not a TPU (the CPU test substrate), and fall back to the jnp
oracle for shapes the kernels do not cover.  Inside `count_dispatch()`
every dispatch decision is counted (``kernel`` / ``jnp`` per op) as the
program is traced, so a caller can see how many tiered operands of a
compiled program took the kernel and how many took the jnp path.

Both tiers are HBM-resident operands on this toolchain: a ``pltpu.HOST``
operand does not compile on v5e (see `kernels.splitk_gemm`).

``window`` — the number of weight copies a kernel keeps outstanding beyond
the one it is consuming, each a chunk of about the plan's chunk size
(`core.congestion.DMA_CHUNK_BYTES`) — is a *per-call* value: the serving
engine threads the adaptive runtime's AIMD-controlled window through every
step (`runtime.controller`), so it is normalized here (int, >= 1) rather
than assumed to be the plan-time constant.  The window only schedules DMA
issue; the tiles never depend on it, so results are bitwise-independent
of it.

`broadcast_remote` implements pod-level fetch-once-broadcast (the TMA
multicast analogue, DESIGN.md §2): the host partition is sharded across
chips, each chip pulls a disjoint slice over its own host link, and slices
are exchanged over ICI via all-gather.  It is the fetch stage of mesh
serving — `mesh_fetch_params` applies it to every sharded operand of a
params tree in one ``shard_map``, called each step by
`serving.tiered_decode.fetch_remote_shards`.
"""
from __future__ import annotations

import contextlib
import math
from collections import Counter
from contextvars import ContextVar

import jax
import jax.numpy as jnp

from repro.core.tiering import TieredArray
from repro.kernels import ref
from repro.kernels.splitk_flashattn import (
    DEFAULT_BLOCK_S,
    paged_splitk_flashattn,
    splitk_flashattn,
)
from repro.kernels.splitk_gemm import gemm_blocks, splitk_gemm


_DISPATCH: ContextVar[Counter | None] = ContextVar("dispatch", default=None)


@contextlib.contextmanager
def count_dispatch():
    """Yield a Counter of ``(op, path)`` -> operands traced inside the block,
    where path is ``"kernel"`` or ``"jnp"`` (the oracle fallback)."""
    counts: Counter[tuple[str, str]] = Counter()
    token = _DISPATCH.set(counts)
    try:
        yield counts
    finally:
        _DISPATCH.reset(token)


def _note(op: str, path: str) -> None:
    counts = _DISPATCH.get()
    if counts is not None:
        counts[op, path] += 1


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    r = x.shape[axis] % mult
    if not r:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, mult - r)
    return jnp.pad(x, pads)


def tiered_matmul(
    x: jax.Array,                      # [..., K]
    w: TieredArray | tuple[jax.Array, jax.Array],
    *,
    window: int = 2,
    block_m: int | None = None,
    block_n: int | None = None,
    block_k: int | None = None,
    use_kernel: bool = True,
    interpret: bool | None = None,
    tuner=None,
) -> jax.Array:
    """y = x @ W with W column-partitioned across (HBM, host) tiers.

    A block left None is `splitk_gemm.gemm_blocks`' choice for the call's
    shapes (a weight tile of about the plan's DMA chunk).  ``tuner`` is an
    optional `kernels.autotune.Autotuner`: when it holds (or sweeps) a
    lint-validated winner for this shape, the tuned blocks replace the
    derived ones.  Block resolution happens at trace time (shapes are
    static under jit), so neither costs anything per step."""
    window = max(1, int(window))
    wl, wr = (w.local, w.remote) if isinstance(w, TieredArray) else w
    lead = x.shape[:-1]
    k = x.shape[-1]
    n_loc, n_rem = wl.shape[1], wr.shape[1]
    m_total = math.prod(int(d) for d in lead)
    if tuner is not None and use_kernel and n_loc and n_rem:
        tuned = tuner.best_gemm(m_total, k, n_loc, n_rem, str(x.dtype))
        if tuned is not None:
            block_m = tuned["block_m"]
            block_n = tuned["block_n"]
            block_k = tuned["block_k"]
    auto = gemm_blocks(m_total, k, n_loc, n_rem, wl.dtype.itemsize)
    block_m = block_m or auto[0]
    block_n = block_n or auto[1]
    block_k = block_k or auto[2]
    aligned = (n_loc % block_n == 0) and (n_rem % block_n == 0)
    # Degenerate tiers (fully local / fully remote operand) take the oracle:
    # the kernel grid assumes both partitions are non-empty.
    if not use_kernel or not aligned or n_loc == 0 or n_rem == 0:
        _note("gemm", "jnp")
        return ref.splitk_gemm_ref(x.reshape(-1, k), wl, wr).reshape(*lead, n_loc + n_rem)
    _note("gemm", "kernel")

    x2 = x.reshape(-1, k)
    m = x2.shape[0]
    x2 = _pad_to(_pad_to(x2, 0, block_m), 1, block_k)
    wl_p = _pad_to(wl, 0, block_k)
    wr_p = _pad_to(wr, 0, block_k)
    y = splitk_gemm(
        x2, wl_p, wr_p,
        block_m=block_m, block_n=block_n, block_k=block_k, window=window,
        interpret=_interpret_default() if interpret is None else interpret)
    return y[:m].reshape(*lead, n_loc + n_rem)


def tiered_decode_attention(
    q: jax.Array,                      # [B, H, hd]
    kv: dict[str, jax.Array],          # k_local/v_local [B_loc,S,Kh,hd], k_remote/v_remote
    *,
    kv_len: int,
    window: int = 2,
    block_s: int = DEFAULT_BLOCK_S,
    use_kernel: bool = True,
    interpret: bool | None = None,
    tuner=None,
) -> jax.Array:
    window = max(1, int(window))
    kl, vl = kv["k_local"], kv["v_local"]
    kr, vr = kv["k_remote"], kv["v_remote"]
    s = kl.shape[1]
    if tuner is not None and use_kernel and s:
        b_total = kl.shape[0] + kr.shape[0]
        rem_frac = kr.shape[0] / b_total if b_total else 0.0
        tuned = tuner.best_attn(q.shape[1], kl.shape[2], kl.shape[3], s,
                                rem_frac, str(q.dtype))
        if tuned is not None:
            block_s = tuned["block_s"]
    if not use_kernel or s % block_s or kr.shape[0] == 0 and kl.shape[0] == 0:
        _note("attn", "jnp")
        return ref.splitk_flashattn_ref(q, kl, vl, kr, vr, kv_len)
    _note("attn", "kernel")
    return splitk_flashattn(
        q, kl, vl, kr, vr, kv_len=kv_len, block_s=block_s, window=window,
        interpret=_interpret_default() if interpret is None else interpret)


def paged_decode_attention(
    q: jax.Array,                      # [B, H, hd]
    pools: dict[str, jax.Array],       # k_local/v_local [P_loc+1,page,Kh,hd], k_remote/v_remote
    table: jax.Array,                  # [B, MP] int32 — page index in its tier pool
    tier: jax.Array,                   # [B, MP] int32 — 0 local / 1 remote
    lens: jax.Array,                   # [B] int32 — valid tokens per slot (ragged)
    *,
    window: int = 2,
    scale: float | None = None,
    use_kernel: bool = True,
    interpret: bool | None = None,
    tuner=None,
) -> jax.Array:
    """Ragged paged tiered decode attention (per-slot kv lengths; each page
    fetched from the tier its page-table entry names).  ``scale`` overrides
    the ``hd**-0.5`` softmax scale (MLA latent-width pages).  A ``tuner``
    caps the in-flight DMA slot count at its tuned stage depth (the page
    size fixes the chunk shape; only the pipeline depth is tunable — and
    it never changes results, only DMA pacing)."""
    window = max(1, int(window))
    kl, vl = pools["k_local"], pools["v_local"]
    kr, vr = pools["k_remote"], pools["v_remote"]
    if tuner is not None and use_kernel:
        n_pages = kl.shape[0] + kr.shape[0]
        rem_frac = kr.shape[0] / n_pages if n_pages else 0.0
        tuned = tuner.best_paged(q.shape[1], kl.shape[2], kl.shape[3],
                                 kl.shape[1], table.shape[1], rem_frac,
                                 str(q.dtype))
        if tuned is not None:
            window = max(1, min(window, tuned["slots"]))
    if not use_kernel:
        _note("paged_attn", "jnp")
        return ref.paged_flashattn_ref(q, kl, vl, kr, vr, table, tier, lens,
                                       scale=scale)
    _note("paged_attn", "kernel")
    return paged_splitk_flashattn(
        q, kl, vl, kr, vr, table, tier, lens, window=window, scale=scale,
        interpret=_interpret_default() if interpret is None else interpret)


def broadcast_remote(w: TieredArray, axis_name: str) -> TieredArray:
    """Pod-level fetch-once-broadcast of the host partition (inside shard_map).

    The remote partition arrives sharded along `axis_name` (each chip pulled
    a disjoint slice over its own host link); one ICI all-gather rebuilds the
    full host partition on every chip — each byte crossed the host link
    exactly once (read-amplification 1×, paper §4.3.2).  Returns the operand
    with its remote tier whole (``mesh_axes=None``) so the tier-aware
    compute ops (`tiered_matmul`, the paged attention kernels) consume it
    exactly as on a single chip; ``.materialize()`` the result if a plain
    concatenated array is wanted.

    This is the serving path's fetch stage: `mesh_fetch_params` calls it
    once per sharded operand per engine step (`serving.tiered_decode`).
    """
    gathered = jax.lax.all_gather(w.remote, axis_name, axis=w.axis, tiled=True)
    return TieredArray(w.local, gathered, axis=w.axis)


def mesh_fetch_params(params, mesh, axis_name: str):
    """Fetch-once broadcast of every mesh-sharded remote partition in a
    params tree (one ``shard_map``, one ICI all-gather per operand).

    Leaves whose `TieredArray.mesh_axes` names `axis_name` hold 1/P of
    their host partition per device; this rebuilds each of them via
    `broadcast_remote` and returns a tree of whole-remote operands that
    the single-chip decode/prefill paths consume unchanged.  Trees with no
    sharded leaf (offload 0, or no mesh) are returned as-is.
    """
    from jax.sharding import PartitionSpec as P

    leaves, treedef = jax.tree_util.tree_flatten(
        params, is_leaf=lambda x: isinstance(x, TieredArray))
    idx = [i for i, leaf in enumerate(leaves)
           if isinstance(leaf, TieredArray) and leaf.mesh_axes == axis_name]
    if not idx:
        return params
    remotes = {str(i): leaves[i].remote for i in idx}
    axes = {str(i): leaves[i].axis for i in idx}

    def shard_spec(leaf: TieredArray) -> P:
        spec = [None] * leaf.remote.ndim
        spec[leaf.axis % leaf.remote.ndim] = axis_name
        return P(*spec)

    def fetch(rem):
        # Only the host tier crosses the mesh here — the HBM-resident local
        # partitions stay outside the shard_map (a zero-extent stand-in
        # satisfies the operand signature without shipping their bytes).
        out = {}
        for k, r in rem.items():
            ax = axes[k] % r.ndim
            stub = jax.lax.slice_in_dim(r, 0, 0, axis=ax)
            out[k] = broadcast_remote(
                TieredArray(stub, r, axis=axes[k]), axis_name).remote
        return out

    gathered = jax.shard_map(
        fetch, mesh=mesh,
        in_specs=({str(i): shard_spec(leaves[i]) for i in idx},),
        out_specs={k: P() for k in remotes},
        check_vma=False,
    )(remotes)
    for i in idx:
        leaf = leaves[i]
        leaves[i] = TieredArray(leaf.local, gathered[str(i)], axis=leaf.axis)
    return jax.tree_util.tree_unflatten(treedef, leaves)
