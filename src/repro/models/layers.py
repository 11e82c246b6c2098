"""Shared neural-net layers for the architecture zoo (pure JAX).

Everything is a pure function over explicit param pytrees so layers compose
under ``jax.lax.scan`` (stacked-over-layers params) and shard cleanly under
pjit.  Covers: RMS/LayerNorm, RoPE (full / fractional "2d"), GQA attention
(qk-norm, qkv-bias, softcap), SwiGLU/GELU MLPs, GShard-style capacity-based
MoE with shared experts, and DeepSeek-V2 MLA (latent KV, absorbed decode).
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.tiering import TieredArray, matmul

Params = dict[str, Any]

# Tier-aware matmul (operand-type dispatch): plain weights hit `@`, weights
# partitioned by `TieringPlan.partition` compute each tier from its own
# buffer.  Layer functions take `mm` as a parameter so the serving layer can
# inject the direct-access kernel (`kernels.ops.tiered_matmul`) while the
# jit/scan reference path keeps the pure-jnp dispatch.
Matmul = Any


# --------------------------------------------------------------------------
# Sharding hints.  GSPMD left to its own devices invents pathological
# layouts for attention intermediates (it will happily shard the head_dim
# contraction 8-ways); these constraints pin the conventional layout:
# batch over (pod, data), heads / d_ff / vocab over model.  No-ops when no
# mesh is active (unit tests) or when a dim is not divisible.
# --------------------------------------------------------------------------
def hint(x: jax.Array, *spec: str | None) -> jax.Array:
    """spec entries: 'batch' | 'model' | None per dimension."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or not mesh.axis_names:
        return x
    # Inside a shard_map the mapped axes are manual: nothing to constrain.
    names = set(mesh.axis_names) - set(mesh.manual_axes)
    batch_axes = tuple(a for a in ("pod", "data") if a in names) or None
    resolved: list[Any] = []
    for dim, s in zip(x.shape, spec, strict=True):
        axes = batch_axes if s == "batch" else ("model",) if (s == "model" and "model" in names) else None
        if axes is not None:
            size = 1
            for a in axes:
                size *= mesh.shape[a]
            if size == 1 or dim % size:
                axes = None
        resolved.append(axes)
    if all(a is None for a in resolved):
        return x
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.PartitionSpec(*resolved))


# --------------------------------------------------------------------------
# Norms
# --------------------------------------------------------------------------
def rmsnorm(x: jax.Array, w: jax.Array, eps: float = 1e-5) -> jax.Array:
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps).astype(x.dtype)) * w


def layernorm(x: jax.Array, w: jax.Array, b: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    return (((xf - mu) * jax.lax.rsqrt(var + eps)).astype(x.dtype)) * w + b


def norm(cfg: ModelConfig, x: jax.Array, p: Params, prefix: str) -> jax.Array:
    if cfg.norm == "layernorm":
        return layernorm(x, p[f"{prefix}_w"], p[f"{prefix}_b"], cfg.norm_eps)
    return rmsnorm(x, p[f"{prefix}_w"], cfg.norm_eps)


# --------------------------------------------------------------------------
# RoPE — supports fractional application (chatglm3 "2d RoPE" rotates only the
# first half of each head); positions are explicit for decode.
# --------------------------------------------------------------------------
def rope_cos_sin(positions: jax.Array, rot_dim: int, theta: float) -> tuple[jax.Array, jax.Array]:
    inv = 1.0 / (theta ** (jnp.arange(0, rot_dim, 2, dtype=jnp.float32) / rot_dim))
    ang = positions.astype(jnp.float32)[..., None] * inv          # [..., rot/2]
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array, rot_dim: int) -> jax.Array:
    """x: [..., T, H, hd]; cos/sin: [..., T, rot/2] (broadcast over heads).
    Rotation computed in f32, result cast back to x.dtype (keeps bf16
    K/Q caches bf16 instead of silently promoting the whole attention)."""
    rot, rest = x[..., :rot_dim], x[..., rot_dim:]
    x1, x2 = rot[..., ::2].astype(jnp.float32), rot[..., 1::2].astype(jnp.float32)
    c, s = cos[..., None, :], sin[..., None, :]                   # add head axis
    r1 = x1 * c - x2 * s
    r2 = x2 * c + x1 * s
    rot_out = jnp.stack([r1, r2], axis=-1).reshape(rot.shape).astype(x.dtype)
    return jnp.concatenate([rot_out, rest], axis=-1) if rest.shape[-1] else rot_out


# --------------------------------------------------------------------------
# Attention (GQA) — full-sequence (train/prefill) and single-step (decode)
# --------------------------------------------------------------------------
def _maybe_qk_norm(cfg: ModelConfig, q, k, p: Params):
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm_w"], cfg.norm_eps)
        k = rmsnorm(k, p["k_norm_w"], cfg.norm_eps)
    return q, k


def _softcap(logits: jax.Array, cap: float) -> jax.Array:
    return jnp.tanh(logits / cap) * cap if cap > 0 else logits


def qkv_project(cfg: ModelConfig, x: jax.Array, p: Params, mm: Matmul = matmul):
    """x: [B,T,d] -> q [B,T,Hp,hd], k,v [B,T,K,hd] (rope applied by caller).

    q uses the TP-padded head count (zero weights beyond n_heads — exact);
    the q projection is model-axis sharded while the small GQA k/v
    projection stays replicated across the model axis (standard GQA-TP)."""
    hd = cfg.resolved_head_dim
    hp, kv = cfg.padded_heads, cfg.n_kv_heads
    q = mm(x, p["wq"])
    k_v = mm(x, p["wkv"])
    if cfg.qkv_bias:
        q = q + p["bq"]
        k_v = k_v + p["bkv"]
    k, v = jnp.split(k_v, 2, axis=-1)
    b, t = x.shape[:2]
    q = hint(q.reshape(b, t, hp, hd), "batch", None, "model", None)
    k = hint(k.reshape(b, t, kv, hd), "batch", None, None, None)
    v = hint(v.reshape(b, t, kv, hd), "batch", None, None, None)
    return q, k, v


# Above this many query positions the full [Tq,Tk] score matrix is never
# materialized: queries are processed in checkpointed chunks (flash-style).
ATTN_CHUNK_THRESHOLD = 2048
ATTN_CHUNK_Q = 1024


def _attend_dense(
    cfg: ModelConfig, q, k, v, causal, q_offset=0, kv_len=None,
) -> jax.Array:
    """Group-MAJOR GQA: q head h belongs to group g = h // K, kv head
    k = h % K.  A model-axis shard of the head dim then maps to whole
    groups, so the grouped reshape never forces a reshard."""
    b, tq, h, hd = q.shape
    tk, kv = k.shape[1], k.shape[2]
    g = h // kv
    vd = v.shape[-1]
    qg = q.reshape(b, tq, g, kv, hd)
    logits = jnp.einsum("btgkh,bskh->bgkts", qg, k).astype(jnp.float32)
    logits = _softcap(logits * (hd ** -0.5), cfg.attn_logit_softcap)
    spans = jnp.arange(tk)[None, :]
    if causal:
        qpos = jnp.arange(tq)[:, None] + q_offset
        logits = jnp.where(spans <= qpos, logits, -1e30)
    if kv_len is not None:
        kvl = jnp.asarray(kv_len)
        if kvl.ndim == 1:                       # ragged batch: per-slot length
            kvl = kvl[:, None, None, None, None]
        logits = jnp.where(spans <= kvl - 1, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bgkts,bskh->btgkh", probs, v)
    return out.reshape(b, tq, h, vd)


def attend(
    cfg: ModelConfig,
    q: jax.Array,                 # [B,Tq,H,hd]
    k: jax.Array,                 # [B,Tk,K,hd]
    v: jax.Array,                 # [B,Tk,K,vd]
    causal: bool,
    q_offset: jax.Array | int = 0,
    kv_len: jax.Array | None = None,
) -> jax.Array:
    """Grouped-query attention. `kv_len` masks positions >= kv_len (decode
    with a partially filled cache); `q_offset` is the absolute position of
    q[0] for causal masking.  Long query spans take a q-chunked path whose
    chunk bodies are rematerialized in the backward pass, so peak memory is
    O(Tq_chunk · Tk) instead of O(Tq · Tk)."""
    b, tq, h, hd = q.shape
    if tq <= ATTN_CHUNK_THRESHOLD or tq % ATTN_CHUNK_Q:
        return _attend_dense(cfg, q, k, v, causal, q_offset, kv_len)

    nc = tq // ATTN_CHUNK_Q
    q_chunks = jnp.moveaxis(q.reshape(b, nc, ATTN_CHUNK_Q, h, hd), 1, 0)

    @jax.checkpoint
    def chunk(_, inp):
        ci, qc = inp
        off = q_offset + ci * ATTN_CHUNK_Q
        return None, _attend_dense(cfg, qc, k, v, causal, off, kv_len)

    _, out = jax.lax.scan(chunk, None, (jnp.arange(nc), q_chunks))
    return jnp.moveaxis(out, 0, 1).reshape(b, tq, h, v.shape[-1])


def attention_block(
    cfg: ModelConfig,
    x: jax.Array,                  # [B,T,d]
    p: Params,
    positions: jax.Array,          # [T] absolute positions
    causal: bool,
) -> jax.Array:
    hd = cfg.resolved_head_dim
    q, k, v = qkv_project(cfg, x, p)
    q, k = _maybe_qk_norm(cfg, q, k, p)
    rot = int(hd * cfg.rope_fraction)
    if rot:
        cos, sin = rope_cos_sin(positions, rot, cfg.rope_theta)
        q = apply_rope(q, cos, sin, rot)
        k = apply_rope(k, cos, sin, rot)
    out = attend(cfg, q, k, v, causal=causal)
    return matmul(out.reshape(*x.shape[:2], cfg.padded_heads * hd), p["wo"])


def attention_decode(
    cfg: ModelConfig,
    x: jax.Array,                  # [B,1,d]
    p: Params,
    k_cache: jax.Array,            # [B,S,K,hd]
    v_cache: jax.Array,
    pos: jax.Array,                # scalar (aligned batch) or [B] (ragged):
                                   # index to write / last valid, per slot
) -> tuple[jax.Array, jax.Array, jax.Array]:
    hd = cfg.resolved_head_dim
    pos = jnp.asarray(pos)
    ragged = pos.ndim == 1
    q, k, v = qkv_project(cfg, x, p)
    q, k = _maybe_qk_norm(cfg, q, k, p)
    rot = int(hd * cfg.rope_fraction)
    if rot:
        # [B,1,rot/2] when ragged (per-slot phase), [1,rot/2] when aligned —
        # both broadcast over the head axis inside apply_rope.
        cos, sin = rope_cos_sin(pos[:, None] if ragged else pos[None],
                                rot, cfg.rope_theta)
        q = apply_rope(q, cos, sin, rot)
        k = apply_rope(k, cos, sin, rot)
    if ragged:
        b = x.shape[0]
        k_cache = k_cache.at[jnp.arange(b), pos].set(k[:, 0].astype(k_cache.dtype))
        v_cache = v_cache.at[jnp.arange(b), pos].set(v[:, 0].astype(v_cache.dtype))
    else:
        k_cache = jax.lax.dynamic_update_slice(k_cache, k.astype(k_cache.dtype), (0, pos, 0, 0))
        v_cache = jax.lax.dynamic_update_slice(v_cache, v.astype(v_cache.dtype), (0, pos, 0, 0))
    out = attend(cfg, q, k_cache, v_cache, causal=False, kv_len=pos + 1)
    y = matmul(out.reshape(*x.shape[:2], cfg.padded_heads * hd), p["wo"])
    return y, k_cache, v_cache


def attention_chunk(
    cfg: ModelConfig,
    x: jax.Array,                  # [B,n,d] — prompt chunk [start, start+n)
    p: Params,
    k_cache: jax.Array,            # [B,S,K,hd], filled for [0, start)
    v_cache: jax.Array,
    positions: jax.Array,          # [n] absolute positions (start..start+n)
    start: int,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Multi-token prefill continuation: project the chunk's Q/K/V, write
    K/V into the cache at ``start``, and attend the chunk's queries over
    the whole prefix (cached keys plus this chunk, causal within the
    chunk).  The n==1 case coincides with `attention_decode`; start==0
    against a zero cache is a whole-prefix pass."""
    hd = cfg.resolved_head_dim
    n = x.shape[1]
    q, k, v = qkv_project(cfg, x, p)
    q, k = _maybe_qk_norm(cfg, q, k, p)
    rot = int(hd * cfg.rope_fraction)
    if rot:
        cos, sin = rope_cos_sin(positions, rot, cfg.rope_theta)
        q = apply_rope(q, cos, sin, rot)
        k = apply_rope(k, cos, sin, rot)
    k_cache = jax.lax.dynamic_update_slice(
        k_cache, k.astype(k_cache.dtype), (0, start, 0, 0))
    v_cache = jax.lax.dynamic_update_slice(
        v_cache, v.astype(v_cache.dtype), (0, start, 0, 0))
    out = attend(cfg, q, k_cache, v_cache, causal=True,
                 q_offset=start, kv_len=start + n)
    y = matmul(out.reshape(*x.shape[:2], cfg.padded_heads * hd), p["wo"])
    return y, k_cache, v_cache


def mla_attention_chunk(
    cfg: ModelConfig,
    x: jax.Array,                  # [B,n,d]
    p: Params,
    ckv_cache: jax.Array,          # [B,S,rank], filled for [0, start)
    krope_cache: jax.Array,        # [B,S,rd]
    positions: jax.Array,          # [n]
    start: int,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """MLA prefill continuation: write the chunk's latents into the cache,
    then attend in the *expanded* form (K/V re-expanded from the cached
    latents via ``wkv_b`` — prefill numerics, matching
    `mla_attention_block`; positions past ``start+n`` are masked)."""
    b, n, _ = x.shape
    h, nd, rd, vd = cfg.n_heads, cfg.nope_head_dim, cfg.rope_head_dim, cfg.v_head_dim
    q_nope, q_rope = mla_project_q(cfg, x, p)
    c_kv, k_rope = mla_project_kv_latent(cfg, x, p)
    cos, sin = rope_cos_sin(positions, rd, cfg.rope_theta)
    q_rope = apply_rope(q_rope, cos, sin, rd)
    k_rope_r = apply_rope(k_rope[..., None, :], cos, sin, rd)[..., 0, :]
    ckv_cache = jax.lax.dynamic_update_slice(
        ckv_cache, c_kv.astype(ckv_cache.dtype), (0, start, 0))
    krope_cache = jax.lax.dynamic_update_slice(
        krope_cache, k_rope_r.astype(krope_cache.dtype), (0, start, 0))
    s = ckv_cache.shape[1]
    kv = matmul(ckv_cache, p["wkv_b"]).reshape(b, s, h, nd + vd)
    k_nope, v = kv[..., :nd], kv[..., nd:]
    k_full = jnp.concatenate(
        [k_nope, jnp.broadcast_to(krope_cache[:, :, None, :], (b, s, h, rd))],
        axis=-1)
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
    out = attend(cfg, q_full, k_full, v, causal=True,
                 q_offset=start, kv_len=start + n)
    return matmul(out.reshape(b, n, h * vd), p["wo"]), ckv_cache, krope_cache


# --------------------------------------------------------------------------
# MLPs
# --------------------------------------------------------------------------
def mlp_block(cfg: ModelConfig, x: jax.Array, p: Params, mm: Matmul = matmul) -> jax.Array:
    if cfg.mlp == "swiglu":
        gate_up = hint(mm(x, p["wi"]), "batch", None, "model")
        gate, up = jnp.split(gate_up, 2, axis=-1)
        h = jax.nn.silu(gate) * up
    else:
        h = hint(mm(x, p["wi"]), "batch", None, "model")
        if "bi" in p:
            h = h + p["bi"]
        h = jax.nn.gelu(h)
    out = mm(h, p["wdown"])
    if "bdown" in p:
        out = out + p["bdown"]
    return out


# --------------------------------------------------------------------------
# MoE — sort+scatter capacity dispatch (memory-sane: no [N,E,C] one-hot
# masks; the largest intermediate is the [E, C, d] expert buffer whose total
# size is active_tokens × capacity_factor × d).
# --------------------------------------------------------------------------
def _expert_ffn(buf: jax.Array, wi: jax.Array, wdown: jax.Array) -> jax.Array:
    """Per-expert SwiGLU FFN over a dispatch buffer [G,E,C,d] -> [G,E,C,d].

    Each expert's computation is independent along E, so a tier split of the
    expert stack (whole experts homed per tier — `models.registry`) computes
    each tier's block with this same function and concatenates: numerically
    identical to the unsplit einsum."""
    gu = hint(jnp.einsum("gecd,edf->gecf", buf, wi),
              None, "batch", None, "model")                       # [G,E,C,2ff]
    gate_h, up_h = jnp.split(gu, 2, axis=-1)
    he = jax.nn.silu(gate_h) * up_h
    return hint(jnp.einsum("gecf,efd->gecd", he, wdown),
                None, "batch", None, None)


def moe_block(
    cfg: ModelConfig,
    x: jax.Array,
    p: Params,
    capacity_factor: float | None = None,
    mm: Matmul = matmul,
) -> jax.Array:
    """x: [B,T,d].  Grouped sort+scatter MoE dispatch.

    Tokens are grouped per sequence (train/prefill) so the sort, scatter
    and gather stay local to the batch sharding — only the expert einsum
    crosses the (data→model) boundary, which XLA lowers to all-to-all-class
    collectives (GShard-style EP).  Decode (T==1) uses one global group: the
    token count is tiny and replication is free.  Within a group each
    (token, choice) pair is stably sorted by expert id and scattered into
    per-expert slots of size ``capacity``; a capacity_factor covering n·k
    slots makes the layer exactly dropless (used by parity tests)."""
    b, t, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    cf = cfg.moe_capacity_factor if capacity_factor is None else capacity_factor
    g = b if t > 1 else 1                                         # groups
    n = (b * t) // g                                              # tokens/group
    capacity = min(n * k, max(1, int(round(n * k * cf / e))))

    xg = x.reshape(g, n, d)
    logits = (xg @ p["router"]).astype(jnp.float32)               # [G,N,E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)                 # [G,N,k]
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    flat_e = gate_idx.reshape(g, n * k)
    order = jnp.argsort(flat_e, axis=-1, stable=True)             # per group
    inv_order = jnp.argsort(order, axis=-1)                       # unsort map
    e_sorted = jnp.take_along_axis(flat_e, order, axis=-1)        # [G,N*k]
    tok_sorted = order // k
    first = jax.vmap(lambda a: jnp.searchsorted(a, a, side="left"))(e_sorted)
    slot = jnp.arange(n * k)[None, :] - first
    keep = slot < capacity

    # GATHER-ONLY dispatch (perf iteration B4): GSPMD lowers scatters onto
    # sharded operands via u32-bookkeeping all-reduces of token-sized
    # buffers; expressing dispatch AND combine as take_along_axis gathers
    # keeps all MoE data movement down to the two EP all-to-alls.
    # buf[g,e,c] = token at sorted position first_of(e) + c.
    starts = jax.vmap(
        lambda a: jnp.searchsorted(a, jnp.arange(e), side="left"))(e_sorted)
    src = starts[:, :, None] + jnp.arange(capacity)[None, None, :]   # [G,E,C]
    src_c = jnp.minimum(src, n * k - 1)
    src_e = jnp.take_along_axis(e_sorted, src_c.reshape(g, -1), axis=-1) \
        .reshape(g, e, capacity)
    valid = (src < n * k) & (src_e == jnp.arange(e)[None, :, None])

    xf_sorted = jnp.take_along_axis(xg, tok_sorted[..., None], axis=1)
    buf = jnp.take_along_axis(
        xf_sorted, src_c.reshape(g, -1)[..., None], axis=1
    ).reshape(g, e, capacity, d)
    buf = jnp.where(valid[..., None], buf, jnp.zeros((), x.dtype))
    # EP dispatch: buf is born expert(data)-sharded — each expert owner
    # gathers the token rows it needs — so the expert einsum is co-located
    # with the E-over-data expert weights and no weight ever moves.
    # (Hinting buf group-sharded first and resharding after measured WORSE:
    # GSPMD emitted both the source all-gather and a redundant 4.3 TB
    # all-to-all — perf iterations B3/B5.)
    buf = hint(buf, None, "batch", None, None)

    wi, wdown = p["experts_wi"], p["experts_wdown"]
    if isinstance(wi, TieredArray):
        # Tiered expert stack: whole experts homed per tier (registry axis
        # -3).  Both stacks split by the same op ratio, so the boundaries
        # coincide; each tier's block computes from its own buffer (the
        # host block streams over the host link on a real runtime).
        assert isinstance(wdown, TieredArray), "experts_wi/wdown tier mismatch"
        e_loc = wi.local.shape[-3]
        assert wdown.local.shape[-3] == e_loc, "experts_wi/wdown tier mismatch"
        ye = jnp.concatenate([
            _expert_ffn(buf[:, :e_loc], wi.local, wdown.local),
            _expert_ffn(buf[:, e_loc:], wi.remote, wdown.remote),
        ], axis=1)
    else:
        ye = _expert_ffn(buf, wi, wdown)
    # EP combine: back to group-sharded for the local unsort-gather
    ye = hint(ye, "batch", None, None, None)

    # combine: gather sorted-slot outputs linearly, unsort, sum over k
    lin_idx = e_sorted * capacity + jnp.minimum(slot, capacity - 1)  # [G,N*k]
    y_lin = ye.reshape(g, e * capacity, d)
    w_sorted = (jnp.take_along_axis(gate_vals.reshape(g, n * k), order, axis=-1)
                * keep).astype(x.dtype)
    y_sorted = jnp.take_along_axis(y_lin, lin_idx[..., None], axis=1) \
        * w_sorted[..., None]
    y_tok = jnp.take_along_axis(y_sorted, inv_order[..., None], axis=1)
    y = y_tok.reshape(g, n, k, d).sum(axis=2)

    if cfg.n_shared_experts:
        xf = x.reshape(g, n, d)
        gu_s = mm(xf, p["shared_wi"])
        g_s, u_s = jnp.split(gu_s, 2, axis=-1)
        y = y + mm(jax.nn.silu(g_s) * u_s, p["shared_wdown"])
    return y.reshape(b, t, d)


# --------------------------------------------------------------------------
# DeepSeek-V2 MLA — latent-compressed KV; absorbed matmuls at decode
# --------------------------------------------------------------------------
def mla_project_q(cfg: ModelConfig, x: jax.Array, p: Params, mm: Matmul = matmul):
    """-> q_nope [B,T,H,nd], q_rope [B,T,H,rd]."""
    b, t, _ = x.shape
    h, nd, rd = cfg.n_heads, cfg.nope_head_dim, cfg.rope_head_dim
    if cfg.q_lora_rank:
        q_lat = rmsnorm(mm(x, p["wq_a"]), p["q_a_norm_w"], cfg.norm_eps)
        q = mm(q_lat, p["wq_b"])
    else:
        q = mm(x, p["wq_b"])
    q = hint(q.reshape(b, t, h, nd + rd), "batch", None, "model", None)
    return q[..., :nd], q[..., nd:]


def mla_project_kv_latent(cfg: ModelConfig, x: jax.Array, p: Params,
                          mm: Matmul = matmul):
    """-> c_kv [B,T,rank] (normed latent), k_rope [B,T,rd] (shared per head)."""
    lat = mm(x, p["wkv_a"])
    c_kv, k_rope = jnp.split(lat, [cfg.kv_lora_rank], axis=-1)
    return rmsnorm(c_kv, p["kv_a_norm_w"], cfg.norm_eps), k_rope


def mla_attention_block(
    cfg: ModelConfig, x: jax.Array, p: Params, positions: jax.Array, causal: bool = True
) -> jax.Array:
    """Full-sequence MLA (train/prefill): expand K,V from the latent, then
    run the shared (chunk-capable) `attend` with q/k = [nope | rope]."""
    b, t, _ = x.shape
    h, nd, rd, vd = cfg.n_heads, cfg.nope_head_dim, cfg.rope_head_dim, cfg.v_head_dim
    q_nope, q_rope = mla_project_q(cfg, x, p)
    c_kv, k_rope = mla_project_kv_latent(cfg, x, p)
    kv = (c_kv @ p["wkv_b"]).reshape(b, t, h, nd + vd)
    k_nope, v = kv[..., :nd], kv[..., nd:]
    cos, sin = rope_cos_sin(positions, rd, cfg.rope_theta)
    q_rope = apply_rope(q_rope, cos, sin, rd)
    k_rope = apply_rope(k_rope[..., None, :], cos, sin, rd)       # [B,T,1,rd]
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)           # [B,T,H,nd+rd]
    k_full = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (b, t, h, rd))], axis=-1)
    out = attend(cfg, q_full, k_full, v, causal=causal)           # scale=(nd+rd)^-.5
    return matmul(out.reshape(b, t, h * vd), p["wo"])


def mla_decode(
    cfg: ModelConfig,
    x: jax.Array,                   # [B,1,d]
    p: Params,
    ckv_cache: jax.Array,           # [B,S,rank]
    krope_cache: jax.Array,         # [B,S,rd]
    pos: jax.Array,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Absorbed-form MLA decode: scores/outputs computed in latent space, so
    per-step flops are O(B·S·H·(rank+rd)) instead of re-expanding K,V."""
    b = x.shape[0]
    h, nd, rd, vd = cfg.n_heads, cfg.nope_head_dim, cfg.rope_head_dim, cfg.v_head_dim
    rank = cfg.kv_lora_rank
    pos = jnp.asarray(pos)
    ragged = pos.ndim == 1                                        # [B] per-slot
    q_nope, q_rope = mla_project_q(cfg, x, p)                     # [B,1,H,*]
    c_kv, k_rope = mla_project_kv_latent(cfg, x, p)               # [B,1,*]
    cos, sin = rope_cos_sin(pos[:, None] if ragged else pos[None],
                            rd, cfg.rope_theta)
    q_rope = apply_rope(q_rope, cos, sin, rd)
    k_rope = apply_rope(k_rope[..., None, :], cos, sin, rd)[..., 0, :]
    if ragged:
        ckv_cache = ckv_cache.at[jnp.arange(b), pos].set(c_kv[:, 0].astype(ckv_cache.dtype))
        krope_cache = krope_cache.at[jnp.arange(b), pos].set(k_rope[:, 0].astype(krope_cache.dtype))
    else:
        ckv_cache = jax.lax.dynamic_update_slice(ckv_cache, c_kv.astype(ckv_cache.dtype), (0, pos, 0))
        krope_cache = jax.lax.dynamic_update_slice(krope_cache, k_rope.astype(krope_cache.dtype), (0, pos, 0))
    # absorb W_uk into q: q_lat [B,H,rank].  wkv_b columns are laid out
    # per-head [nd | vd] (matching the reshape in mla_attention_block).
    w_full = p["wkv_b"].reshape(rank, h, nd + vd)
    w_uk, w_uv = w_full[..., :nd], w_full[..., nd:]
    q_lat = jnp.einsum("bhn,rhn->bhr", q_nope[:, 0], w_uk)
    scale = (nd + rd) ** -0.5
    logits = (jnp.einsum("bhr,bsr->bhs", q_lat, ckv_cache)
              + jnp.einsum("bhr,bsr->bhs", q_rope[:, 0], krope_cache)).astype(jnp.float32) * scale
    span = jnp.arange(ckv_cache.shape[1])[None, None, :]
    last = pos[:, None, None] if ragged else pos
    logits = jnp.where(span <= last, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(x.dtype)
    o_lat = jnp.einsum("bhs,bsr->bhr", probs, ckv_cache)          # [B,H,rank]
    out = jnp.einsum("bhr,rhv->bhv", o_lat, w_uv).reshape(b, 1, h * vd)
    return matmul(out, p["wo"]), ckv_cache, krope_cache
