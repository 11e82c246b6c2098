"""Tiered decode path: the paper's system end-to-end, for every family.

This is the serving-side realization of the unified tiering API: params come
from ``TieringPlan.partition`` (stacked leaves, tierable operands wrapped in
`TieredArray` per the operand registry — `models.registry`), and dispatch is
by *operand type*, not by model family: every 2-D tiered weight is computed
by `SplitK_GEMM` (`kernels.ops.tiered_matmul`), tiered MoE expert stacks run
the per-tier expert einsum (`models.layers.moe_block`), and the KV cache is
attended by the page-table-indexed `SplitK_FlashAttn` variant — all under
the congestion ``window`` passed per step.  The window is not a plan-time
constant: the static plan merely seeds it, and the adaptive engine threads
the AIMD controller's current value (`runtime.controller`) into every
decode step.  It only paces DMA issue — outputs are bitwise-independent of
its value.

Family coverage:

* ``paged_tiered_decode_step`` — dense / VLM / MoE / MLA decoders: GQA or
  MLA attention over the paged tiered KV cache
  (`serving.paged_cache.PagedTieredCache`), dense-MLP or MoE FFN.  MLA
  caches the latent ``[ckv | k_rope]`` as single-head pages and attends in
  absorbed form (scores and outputs in latent space) with the model's
  ``(nd+rd)**-0.5`` scale.
* ``tiered_ssm_decode_step`` — pure-SSM decoders (no KV cache): recurrent
  Mamba-2 steps whose projections run through the tiered GEMM.
* ``tiered_hybrid_decode_step`` — Zamba2-style hybrids: shared attention
  blocks over a paged tiered cache (one attention layer per group) plus
  tiered SSM layers.

All steps run real kernels (interpret mode on CPU) and are exercised by
examples/serve_offload.py and the serving tests; the pjit path
(`models.decode_step`) accepts the same tiered params (pure-jnp operand
dispatch) and remains the large-scale route.

Deprecated entry points (one release): ``partition_dense_params`` (use
``TieringPlan.partition``), ``split_cache_batch`` + ``tiered_decode_step``
(the paper's §5 slot-aligned batch-split layout, retained for the kernel
experiments).
"""
from __future__ import annotations

import warnings
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.tiering import TieredArray, partition
from repro.kernels import ops
from repro.models import layers as L
from repro.models import model as M
from repro.models import ssm as S

# Deprecated: the operand registry (models.registry) is the source of truth.
TIERABLE = ("wq", "wkv", "wo", "wi", "wdown", "lm_head")


def partition_dense_params(
    params: dict[str, Any], ratios: dict[str, float], align: int = 128
) -> dict[str, Any]:
    """Deprecated shim — use ``TieringPlan.partition`` (core.engine).

    Partitions the dense-family weight stacks by per-leaf ratios.  Ratio
    keys may be registry paths (``"layers/wq"``) or bare leaf names
    (``"wq"``).  Unlike the pre-registry version, each operand resolves its
    *own* ratio — ``wkv`` no longer silently reuses the ``wq`` entry.
    Returns the unified stacked format (leaves wrapped in `TieredArray`),
    consumable by every decode step in this module and by `models`.
    """
    warnings.warn(
        "partition_dense_params is deprecated; use TieringPlan.partition "
        "(the operand-registry path) instead", DeprecationWarning, stacklevel=2)
    out: dict[str, Any] = dict(params)
    new_layers: dict[str, Any] = dict(params["layers"])
    for key in ("wq", "wkv", "wo", "wi", "wdown"):
        leaf = new_layers.get(key)
        r = ratios.get(f"layers/{key}", ratios.get(key, 0.0))
        if leaf is None or leaf.ndim != 3 or r <= 0.0:
            continue
        new_layers[key] = partition(leaf, r, axis=-1, align=align)
    out["layers"] = new_layers
    r = ratios.get("lm_head", 0.0)
    if "lm_head" in params and r > 0.0:
        out["lm_head"] = partition(params["lm_head"], r, axis=-1, align=align)
    return out


def _mm(x: jax.Array, w: Any, window: int, use_kernel: bool,
        tuner: Any = None) -> jax.Array:
    if isinstance(w, TieredArray):
        return ops.tiered_matmul(x, w, window=window, use_kernel=use_kernel,
                                 tuner=tuner)
    return x @ w


def layer_slice(layers: Any, i) -> Any:
    """Slice layer `i` out of a stacked (possibly tiered) layer tree.

    `TieredArray` is a pytree whose split axis is negative (registry
    convention), so slicing the leading stack axis off both tier buffers
    yields a valid per-layer `TieredArray`."""
    return jax.tree.map(lambda a: a[i], layers)


def split_cache_batch(cache: dict[str, jax.Array], kv_ratio: float,
                      align: int = 1) -> dict[str, Any]:
    """Batch-split a dense KV cache {k,v: [L,B,S,K,hd]} across tiers
    (paper §5: SplitK_FlashAttn partitions the KV cache along batch).

    Deprecated serving-side (the paged cache replaces it); retained for the
    paper's batch-partitioned kernel experiments."""
    b = cache["k"].shape[1]
    b_rem = int(round(b * kv_ratio / align)) * align
    b_loc = b - b_rem
    return {
        "k_local": cache["k"][:, :b_loc], "v_local": cache["v"][:, :b_loc],
        "k_remote": cache["k"][:, b_loc:], "v_remote": cache["v"][:, b_loc:],
    }


def fetch_remote_shards(params: dict[str, Any], mesh: Any,
                        mesh_axis: str | None) -> dict[str, Any]:
    """The decode path's fetch-once stage (paper §4.3.2, pod level).

    Under a serving mesh the params tree arrives with every host-resident
    partition sharded 1/P along its split axis (`launch.sharding.
    shard_tiered_params`); one `kernels.ops.broadcast_remote` pass inside
    ``shard_map`` pulls each chip's disjoint slice over its own host link
    and rebuilds the full partitions over ICI — each offloaded byte
    crosses a host link exactly once per step, then the single-chip
    operand-type dispatch below runs unchanged (bitwise-identical tokens).
    No mesh (or no sharded leaf) is a no-op.
    """
    if mesh is None:
        return params
    return ops.mesh_fetch_params(
        params, mesh, mesh_axis or mesh.axis_names[-1])


def on_every_device(fn: Callable, mesh: Any) -> Callable:
    """Run ``fn`` whole on every device of ``mesh`` (identity off-mesh).

    XLA cannot partition a Mosaic kernel, so under a serving mesh the decode
    body runs inside a ``shard_map`` whose operands and results are all
    replicated: each chip computes the full batch on the full operands the
    fetch-once broadcast rebuilt, as the single-chip path does."""
    if mesh is None:
        return fn
    from jax.sharding import PartitionSpec as P

    return jax.shard_map(fn, mesh=mesh, in_specs=P(), out_specs=P(),
                         check_vma=False)


# --------------------------------------------------------------------------
# Attention bodies.  The cache layouts differ only in how the new K/V row is
# written and how attention gathers the cache, so every decode step injects
# a `write_and_attend(layer, q, k_new, v_new, scale=None)` callback
# (q [B,Hq,w]; k_new/v_new [B,1,Kh,w]; returns attn [B,Hq,w]).
# --------------------------------------------------------------------------
WriteAndAttend = Callable[..., jax.Array]


def _gqa_attend(
    cfg: ModelConfig, lp: dict[str, Any], hn: jax.Array, positions: jax.Array,
    idx: int, window: int, use_kernel: bool, write_and_attend: WriteAndAttend,
    tuner: Any = None,
) -> jax.Array:
    """GQA attention over the injected cache: returns [B,1,Hp*hd] (pre-wo)."""
    hd, hp = cfg.resolved_head_dim, cfg.padded_heads
    b = hn.shape[0]

    def kmm(a, w):
        return _mm(a, w, window, use_kernel, tuner)

    q, k_new, v_new = L.qkv_project(cfg, hn, lp, mm=kmm)
    q, k_new = L._maybe_qk_norm(cfg, q, k_new, lp)
    rot = int(hd * cfg.rope_fraction)
    if rot:
        cos, sin = L.rope_cos_sin(positions[:, None], rot, cfg.rope_theta)
        q = L.apply_rope(q, cos, sin, rot)
        k_new = L.apply_rope(k_new, cos, sin, rot)
    attn = write_and_attend(idx, q[:, 0], k_new, v_new)     # [B,Hp,hd]
    return attn.reshape(b, 1, hp * hd)


def _mla_attend(
    cfg: ModelConfig, lp: dict[str, Any], hn: jax.Array, positions: jax.Array,
    idx: int, window: int, use_kernel: bool, write_and_attend: WriteAndAttend,
    tuner: Any = None,
) -> jax.Array:
    """Absorbed-form MLA over latent-width pages: returns [B,1,H*vd] (pre-wo).

    The page row is the latent ``[ckv | k_rope]`` (one kv head, width
    rank+rd); q is the absorbed ``[q·W_uk | q_rope]`` so the kernel's
    score/accumulate runs entirely in latent space (`layers.mla_decode`
    semantics).  V pages carry ``[ckv | 0]`` — the zero tail contributes
    nothing and the output is sliced back to the latent rank."""
    h, nd, rd, vd = cfg.n_heads, cfg.nope_head_dim, cfg.rope_head_dim, cfg.v_head_dim
    rank = cfg.kv_lora_rank
    b = hn.shape[0]

    def kmm(a, w):
        return _mm(a, w, window, use_kernel, tuner)

    q_nope, q_rope = L.mla_project_q(cfg, hn, lp, mm=kmm)         # [B,1,H,*]
    c_kv, k_rope = L.mla_project_kv_latent(cfg, hn, lp, mm=kmm)   # [B,1,*]
    cos, sin = L.rope_cos_sin(positions[:, None], rd, cfg.rope_theta)
    q_rope = L.apply_rope(q_rope, cos, sin, rd)
    k_rope = L.apply_rope(k_rope[..., None, :], cos, sin, rd)[..., 0, :]
    # wkv_b is HBM-resident by registry design: consumed in absorbed form.
    w_full = lp["wkv_b"].reshape(rank, h, nd + vd)
    w_uk, w_uv = w_full[..., :nd], w_full[..., nd:]
    q_lat = jnp.einsum("bhn,rhn->bhr", q_nope[:, 0], w_uk)        # [B,H,rank]
    q_cat = jnp.concatenate([q_lat, q_rope[:, 0]], axis=-1)       # [B,H,rank+rd]
    k_new = jnp.concatenate([c_kv, k_rope], axis=-1)[:, :, None, :]
    # V aliases the K page (v_new=None): probs @ [ckv | k_rope] sliced to
    # :rank equals probs @ ckv — the rope tail columns are simply dropped —
    # so the latent is stored once, as the planner's KV accounting assumes.
    o = write_and_attend(idx, q_cat, k_new, None, scale=(nd + rd) ** -0.5)
    o_lat = o[..., :rank]                                         # [B,H,rank]
    return jnp.einsum("bhr,rhv->bhv", o_lat, w_uv).reshape(b, 1, h * vd)


def _head(cfg: ModelConfig, params: dict[str, Any], x: jax.Array,
          window: int, use_kernel: bool, tuner: Any = None) -> jax.Array:
    return M.lm_head(cfg, params, x,
                     mm=lambda a, w: _mm(a, w, window, use_kernel, tuner))


def _decode_transformer(
    cfg: ModelConfig,
    params: dict[str, Any],          # stacked tree from TieringPlan.partition
    tokens: jax.Array,               # [B,1] int32
    positions: jax.Array,            # [B] int32 per-slot write positions
    window: int,
    use_kernel: bool,
    write_and_attend: WriteAndAttend,
    tuner: Any = None,
) -> jax.Array:
    """Shared decode body for the attention-decoder families (dense, VLM,
    MoE, MLA): operand-type dispatch picks the attention flavor and FFN per
    layer; tiered weights run the direct-access kernels."""
    x = params["embed"][tokens]                       # [B,1,d]

    def kmm(a, w):
        return _mm(a, w, window, use_kernel, tuner)

    for i in range(cfg.n_layers):
        lp = layer_slice(params["layers"], i)
        hn = L.norm(cfg, x, lp, "ln1")
        attend = _mla_attend if cfg.use_mla else _gqa_attend
        attn = attend(cfg, lp, hn, positions, i, window, use_kernel,
                      write_and_attend, tuner)
        x = x + _mm(attn, lp["wo"], window, use_kernel, tuner)
        hn2 = L.norm(cfg, x, lp, "ln2")
        if cfg.family == "moe":
            x = x + L.moe_block(cfg, hn2, lp, mm=kmm)
        else:
            x = x + L.mlp_block(cfg, hn2, lp, mm=kmm)
    return _head(cfg, params, x, window, use_kernel, tuner)


def tiered_decode_step(
    cfg: ModelConfig,
    params: dict[str, Any],          # stacked tiered params
    cache: dict[str, Any],           # from split_cache_batch
    tokens: jax.Array,               # [B,1] int32
    pos: int,
    *,
    window: int = 2,
    use_kernel: bool = True,
) -> tuple[jax.Array, dict[str, Any]]:
    """One slot-aligned decode step over tiered weights + batch-split KV
    (the paper's §5 layout; dense families only — serving uses the paged
    step below)."""
    b = tokens.shape[0]
    b_loc = cache["k_local"].shape[1]

    def write_and_attend(i, q, k_new, v_new, scale=None):
        assert scale is None, "batch-split legacy path is dense-only"
        if b_loc > 0:
            cache["k_local"] = jax.lax.dynamic_update_slice(
                cache["k_local"], _layer_row(k_new[:b_loc], cache["k_local"]),
                (i, 0, pos, 0, 0))
            cache["v_local"] = jax.lax.dynamic_update_slice(
                cache["v_local"], _layer_row(v_new[:b_loc], cache["v_local"]),
                (i, 0, pos, 0, 0))
        if b_loc < b:
            cache["k_remote"] = jax.lax.dynamic_update_slice(
                cache["k_remote"], _layer_row(k_new[b_loc:], cache["k_remote"]),
                (i, 0, pos, 0, 0))
            cache["v_remote"] = jax.lax.dynamic_update_slice(
                cache["v_remote"], _layer_row(v_new[b_loc:], cache["v_remote"]),
                (i, 0, pos, 0, 0))
        return ops.tiered_decode_attention(
            q,
            {"k_local": cache["k_local"][i], "v_local": cache["v_local"][i],
             "k_remote": cache["k_remote"][i], "v_remote": cache["v_remote"][i]},
            kv_len=pos + 1, window=window, use_kernel=use_kernel)

    positions = jnp.full((b,), pos, jnp.int32)
    logits = _decode_transformer(
        cfg, params, tokens, positions, window, use_kernel, write_and_attend)
    return logits, cache


def _paged_writer(
    pools: dict[str, jax.Array],
    table: jax.Array, tier: jax.Array, attn_lens: jax.Array,
    wr_tier: jax.Array, wr_idx: jax.Array, wr_off: jax.Array,
    sink_local: int, sink_remote: int, window: int, use_kernel: bool,
    tuner: Any = None,
) -> WriteAndAttend:
    """write_and_attend over a paged tiered pool set (mutates `pools`).

    Scatters into both pools: the slot's row goes to its real target in one
    tier and to that tier's sink in the other (never read back); attention
    gathers each slot's pages from the tier its page table names, masked to
    ``attn_lens`` (ragged batch).  ``v_new=None`` means the cache is K-only
    (MLA latent pages): the V read aliases the K pool."""

    def write_and_attend(i, q, k_new, v_new, scale=None):
        idx_l = jnp.where(wr_tier == 0, wr_idx, sink_local)
        idx_r = jnp.where(wr_tier == 1, wr_idx, sink_remote)
        rows = (("k", k_new),) if v_new is None else (("k", k_new), ("v", v_new))
        for name, new in rows:
            row = new[:, 0]
            pl_ = pools[f"{name}_local"]
            pools[f"{name}_local"] = pl_.at[i, idx_l, wr_off].set(row.astype(pl_.dtype))
            pr_ = pools[f"{name}_remote"]
            pools[f"{name}_remote"] = pr_.at[i, idx_r, wr_off].set(row.astype(pr_.dtype))
        v_name = "k" if v_new is None else "v"
        layer_pools = {"k_local": pools["k_local"][i],
                       "k_remote": pools["k_remote"][i],
                       "v_local": pools[f"{v_name}_local"][i],
                       "v_remote": pools[f"{v_name}_remote"][i]}
        return ops.paged_decode_attention(
            q, layer_pools, table, tier, attn_lens,
            window=window, scale=scale, use_kernel=use_kernel, tuner=tuner)

    return write_and_attend


def paged_tiered_decode_step(
    cfg: ModelConfig,
    params: dict[str, Any],          # stacked tree from TieringPlan.partition
    pools: dict[str, jax.Array],     # PagedTieredCache.pools {k,v}_{local,remote}
    tokens: jax.Array,               # [B,1] int32
    positions: jax.Array,            # [B] int32 — per-slot write position
    attn_lens: jax.Array,            # [B] int32 — post-write lengths (0 = idle)
    table: jax.Array,                # [B, MP] int32 page table
    tier: jax.Array,                 # [B, MP] int32 page tiers
    wr_tier: jax.Array,              # [B] int32 write-target tier
    wr_idx: jax.Array,               # [B] int32 write-target page index
    wr_off: jax.Array,               # [B] int32 in-page offset
    *,
    sink_local: int,
    sink_remote: int,
    window: int = 2,
    use_kernel: bool = True,
    mesh: Any = None,
    mesh_axis: str | None = None,
    tuner: Any = None,
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """One ragged decode step over tiered weights + paged tiered KV for the
    attention-decoder families (dense / VLM / MoE / MLA).

    Every slot scatters its new K/V row (GQA heads, or the MLA latent as a
    single-head row) into the page named by (wr_tier, wr_idx, wr_off); idle
    slots must be pointed at a sink page by the caller.  With a ``mesh``
    the weights' sharded host partitions are rebuilt first through the
    fetch-once broadcast (:func:`fetch_remote_shards`)."""
    def body(params, pools, tokens, positions, attn_lens, table, tier,
             wr_tier, wr_idx, wr_off):
        pools = dict(pools)
        write_and_attend = _paged_writer(
            pools, table, tier, attn_lens, wr_tier, wr_idx, wr_off,
            sink_local, sink_remote, window, use_kernel, tuner)
        logits = _decode_transformer(
            cfg, params, tokens, positions, window, use_kernel,
            write_and_attend, tuner)
        return logits, pools

    params = fetch_remote_shards(params, mesh, mesh_axis)
    return on_every_device(body, mesh)(
        params, pools, tokens, positions, attn_lens, table, tier,
        wr_tier, wr_idx, wr_off)


def tiered_ssm_decode_step(
    cfg: ModelConfig,
    params: dict[str, Any],          # stacked tree from TieringPlan.partition
    cache: dict[str, jax.Array],     # {conv: [L,B,W-1,C], state: [L,B,H,P,S]}
    tokens: jax.Array,               # [B,1] int32
    *,
    window: int = 2,
    use_kernel: bool = True,
    mesh: Any = None,
    mesh_axis: str | None = None,
    tuner: Any = None,
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """One recurrent decode step for pure-SSM decoders over tiered weights.

    No KV cache — the conv window and SSD state are per-slot recurrent
    state, always HBM-resident; the offloaded operands are the projection
    stacks (``ssm_in`` / ``ssm_out``), computed by the tiered GEMM."""
    def kmm(a, w):
        return _mm(a, w, window, use_kernel, tuner)

    def body(params, cache, tokens):
        x = params["embed"][tokens]
        convs, states = [], []
        for i in range(cfg.n_layers):
            lp = layer_slice(params["layers"], i)
            hn = L.norm(cfg, x, lp, "ln1")
            y, conv_i, state_i = S.ssm_block_decode(
                cfg, hn, lp, cache["conv"][i], cache["state"][i], mm=kmm)
            x = x + y
            convs.append(conv_i)
            states.append(state_i)
        logits = _head(cfg, params, x, window, use_kernel, tuner)
        return logits, {"conv": jnp.stack(convs), "state": jnp.stack(states)}

    params = fetch_remote_shards(params, mesh, mesh_axis)
    return on_every_device(body, mesh)(params, cache, tokens)


def tiered_hybrid_decode_step(
    cfg: ModelConfig,
    params: dict[str, Any],          # stacked tree from TieringPlan.partition
    cache: dict[str, jax.Array],     # SSM state {conv, state} (all layers)
    pools: dict[str, jax.Array],     # paged KV pools (one layer per group)
    tokens: jax.Array,               # [B,1] int32
    positions: jax.Array,            # [B] int32 — per-slot write position
    attn_lens: jax.Array,            # [B] int32 — post-write lengths (0 = idle)
    table: jax.Array,
    tier: jax.Array,
    wr_tier: jax.Array,
    wr_idx: jax.Array,
    wr_off: jax.Array,
    *,
    sink_local: int,
    sink_remote: int,
    window: int = 2,
    use_kernel: bool = True,
    mesh: Any = None,
    mesh_axis: str | None = None,
    tuner: Any = None,
) -> tuple[jax.Array, dict[str, jax.Array], dict[str, jax.Array]]:
    """One ragged decode step for Zamba2-style hybrids: each group runs its
    shared attention+MLP block (GQA over the group's paged tiered KV layer)
    followed by ``hybrid_attn_every`` tiered SSM layers."""
    def kmm(a, w):
        return _mm(a, w, window, use_kernel, tuner)

    def body(params, cache, pools, tokens, positions, attn_lens, table, tier,
             wr_tier, wr_idx, wr_off):
        pools = dict(pools)
        write_and_attend = _paged_writer(
            pools, table, tier, attn_lens, wr_tier, wr_idx, wr_off,
            sink_local, sink_remote, window, use_kernel, tuner)
        x = params["embed"][tokens]
        h0 = x
        k_every = cfg.hybrid_attn_every
        n_groups = cfg.n_layers // k_every
        n_blocks = max(1, cfg.hybrid_shared_blocks)
        convs, states = [], []
        for g_idx in range(n_groups):
            sp = layer_slice(params["shared"], g_idx % n_blocks)
            z = jnp.concatenate([x, h0], axis=-1) @ sp["concat_proj"]
            zn = L.norm(cfg, z, sp, "ln1")
            attn = _gqa_attend(cfg, sp, zn, positions, g_idx, window,
                               use_kernel, write_and_attend, tuner)
            z = z + _mm(attn, sp["wo"], window, use_kernel, tuner)
            z = z + L.mlp_block(cfg, L.norm(cfg, z, sp, "ln2"), sp, mm=kmm)
            x = x + z
            for j in range(k_every):
                li = g_idx * k_every + j
                lp = layer_slice(params["layers"], li)
                hn = L.norm(cfg, x, lp, "ln1")
                y, conv_i, state_i = S.ssm_block_decode(
                    cfg, hn, lp, cache["conv"][li], cache["state"][li], mm=kmm)
                x = x + y
                convs.append(conv_i)
                states.append(state_i)
        logits = _head(cfg, params, x, window, use_kernel, tuner)
        return (logits, {"conv": jnp.stack(convs), "state": jnp.stack(states)},
                pools)

    params = fetch_remote_shards(params, mesh, mesh_axis)
    return on_every_device(body, mesh)(
        params, cache, pools, tokens, positions, attn_lens, table, tier,
        wr_tier, wr_idx, wr_off)


def _layer_row(new: jax.Array, cache_ref: jax.Array) -> jax.Array:
    """[Bpart,1,K,hd] -> [1,Bpart,1,K,hd] update block."""
    return new.astype(cache_ref.dtype)[None]
