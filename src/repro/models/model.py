"""Config-driven model zoo: init / forward / prefill / decode for every
assigned architecture family (dense, moe, mla-moe, ssm, hybrid, encoder, vlm).

Layer parameters are stacked along a leading ``n_layers`` axis and executed
with ``jax.lax.scan`` so the lowered HLO is O(1) in depth — essential for the
512-device dry-run compiles.  All entry points are pure functions of
(cfg, params, inputs) and pjit-shardable.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import ssm as S

Params = dict[str, Any]
Cache = dict[str, jax.Array]

VISION_EMBED_DIM = 1152      # stub anyres patch-embedding width (frontend stub)
AUDIO_FRAME_DIM = 512        # stub audio frame-embedding width

_INIT_STD = 0.02


# ==========================================================================
# Init
# ==========================================================================
def _norm_params(cfg: ModelConfig, lead: tuple[int, ...], prefix: str, d: int,
                 dtype) -> Params:
    p = {f"{prefix}_w": jnp.ones(lead + (d,), dtype)}
    if cfg.norm == "layernorm":
        p[f"{prefix}_b"] = jnp.zeros(lead + (d,), dtype)
    return p


def _dense(key, lead, shape, dtype, std=_INIT_STD):
    return jax.random.normal(key, lead + shape, dtype) * std


def _attn_params(cfg: ModelConfig, key, lead: tuple[int, ...], dtype) -> Params:
    hd = cfg.resolved_head_dim
    hp = cfg.padded_heads
    keys = jax.random.split(key, 4)
    wq = _dense(keys[0], lead, (cfg.d_model, hp * hd), dtype)
    wo = _dense(keys[1], lead, (hp * hd, cfg.d_model), dtype)
    if hp > cfg.n_heads:
        # TP head padding: zero weights beyond n_heads — numerically exact.
        wq = wq.at[..., cfg.n_heads * hd:].set(0.0)
        wo = wo.at[..., cfg.n_heads * hd:, :].set(0.0)
    p: Params = {
        "wq": wq,
        "wkv": _dense(keys[2], lead, (cfg.d_model, 2 * cfg.n_kv_heads * hd), dtype),
        "wo": wo,
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros(lead + (hp * hd,), dtype)
        p["bkv"] = jnp.zeros(lead + (2 * cfg.n_kv_heads * hd,), dtype)
    if cfg.qk_norm:
        p["q_norm_w"] = jnp.ones(lead + (hd,), dtype)
        p["k_norm_w"] = jnp.ones(lead + (hd,), dtype)
    return p


def _mla_params(cfg: ModelConfig, key, lead: tuple[int, ...], dtype) -> Params:
    keys = jax.random.split(key, 5)
    h, nd, rd, vd = cfg.n_heads, cfg.nope_head_dim, cfg.rope_head_dim, cfg.v_head_dim
    p: Params = {
        "wkv_a": _dense(keys[0], lead, (cfg.d_model, cfg.kv_lora_rank + rd), dtype),
        "kv_a_norm_w": jnp.ones(lead + (cfg.kv_lora_rank,), dtype),
        "wkv_b": _dense(keys[1], lead, (cfg.kv_lora_rank, h * (nd + vd)), dtype),
        "wo": _dense(keys[2], lead, (h * vd, cfg.d_model), dtype),
    }
    if cfg.q_lora_rank:
        p["wq_a"] = _dense(keys[3], lead, (cfg.d_model, cfg.q_lora_rank), dtype)
        p["q_a_norm_w"] = jnp.ones(lead + (cfg.q_lora_rank,), dtype)
        p["wq_b"] = _dense(keys[4], lead, (cfg.q_lora_rank, h * (nd + rd)), dtype)
    else:
        p["wq_b"] = _dense(keys[4], lead, (cfg.d_model, h * (nd + rd)), dtype)
    return p


def _mlp_params(cfg: ModelConfig, key, lead: tuple[int, ...], dtype) -> Params:
    keys = jax.random.split(key, 2)
    mult = 2 if cfg.mlp == "swiglu" else 1
    p: Params = {
        "wi": _dense(keys[0], lead, (cfg.d_model, mult * cfg.d_ff), dtype),
        "wdown": _dense(keys[1], lead, (cfg.d_ff, cfg.d_model), dtype),
    }
    if cfg.norm == "layernorm":       # bias-ful families (OPT/starcoder/hubert)
        p["bi"] = jnp.zeros(lead + (mult * cfg.d_ff,), dtype)
        p["bdown"] = jnp.zeros(lead + (cfg.d_model,), dtype)
    return p


def _moe_params(cfg: ModelConfig, key, lead: tuple[int, ...], dtype) -> Params:
    keys = jax.random.split(key, 5)
    e, ff = cfg.n_experts, cfg.moe_d_ff
    p: Params = {
        "router": _dense(keys[0], lead, (cfg.d_model, e), dtype),
        "experts_wi": _dense(keys[1], lead, (e, cfg.d_model, 2 * ff), dtype),
        "experts_wdown": _dense(keys[2], lead, (e, ff, cfg.d_model), dtype),
    }
    if cfg.n_shared_experts:
        sf = ff * cfg.n_shared_experts
        p["shared_wi"] = _dense(keys[3], lead, (cfg.d_model, 2 * sf), dtype)
        p["shared_wdown"] = _dense(keys[4], lead, (sf, cfg.d_model), dtype)
    return p


def _ssm_params(cfg: ModelConfig, key, lead: tuple[int, ...], dtype) -> Params:
    keys = jax.random.split(key, 3)
    d_inner = cfg.ssm_expand * cfg.d_model
    nh = d_inner // cfg.ssm_head_dim
    conv_dim = d_inner + 2 * cfg.ssm_n_groups * cfg.ssm_state
    kz, kx, kbc, kdt = jax.random.split(keys[0], 4)
    return {
        # split projections (sharding-aligned — perf iteration A2)
        "z_proj": _dense(kz, lead, (cfg.d_model, d_inner), dtype),
        "x_proj": _dense(kx, lead, (cfg.d_model, d_inner), dtype),
        "bc_proj": _dense(kbc, lead, (cfg.d_model, 2 * cfg.ssm_n_groups * cfg.ssm_state), dtype),
        "dt_proj": _dense(kdt, lead, (cfg.d_model, nh), dtype),
        "conv_w": _dense(keys[1], lead, (cfg.ssm_conv_width, conv_dim), dtype, std=0.1),
        "dt_bias": jnp.zeros(lead + (nh,), dtype),
        "A_log": jnp.zeros(lead + (nh,), dtype),         # A = -exp(0) = -1
        "D": jnp.ones(lead + (nh,), dtype),
        "ssm_norm_w": jnp.ones(lead + (d_inner,), dtype),
        "ssm_out": _dense(keys[2], lead, (d_inner, cfg.d_model), dtype),
    }


def _layer_params(cfg: ModelConfig, key, lead: tuple[int, ...], dtype) -> Params:
    keys = jax.random.split(key, 3)
    p: Params = {}
    if cfg.family in ("ssm",) or (cfg.family == "hybrid"):
        p.update(_norm_params(cfg, lead, "ln1", cfg.d_model, dtype))
        p.update(_ssm_params(cfg, keys[0], lead, dtype))
        return p
    p.update(_norm_params(cfg, lead, "ln1", cfg.d_model, dtype))
    p.update(_mla_params(cfg, keys[0], lead, dtype) if cfg.use_mla else _attn_params(cfg, keys[0], lead, dtype))
    p.update(_norm_params(cfg, lead, "ln2", cfg.d_model, dtype))
    p.update(_moe_params(cfg, keys[1], lead, dtype) if cfg.family == "moe" else _mlp_params(cfg, keys[1], lead, dtype))
    return p


def _shared_block_params(cfg: ModelConfig, key, lead: tuple[int, ...], dtype) -> Params:
    """Zamba2 shared attention+MLP block (input: concat(h, h0) -> d)."""
    keys = jax.random.split(key, 3)
    p: Params = {"concat_proj": _dense(keys[0], lead, (2 * cfg.d_model, cfg.d_model), dtype)}
    p.update(_norm_params(cfg, lead, "ln1", cfg.d_model, dtype))
    p.update(_attn_params(cfg, keys[1], lead, dtype))
    p.update(_norm_params(cfg, lead, "ln2", cfg.d_model, dtype))
    p.update(_mlp_params(cfg, keys[2], lead, dtype))
    return p


def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.float32) -> Params:
    keys = jax.random.split(key, 6)
    lead = (cfg.n_layers,)
    p: Params = {"layers": _layer_params(cfg, keys[0], lead, dtype)}
    if cfg.family == "encoder":
        p["in_proj"] = _dense(keys[1], (), (AUDIO_FRAME_DIM, cfg.d_model), dtype)
    else:
        p["embed"] = _dense(keys[1], (), (cfg.vocab, cfg.d_model), dtype)
    if cfg.family == "vlm":
        p["vision_proj"] = _dense(keys[2], (), (VISION_EMBED_DIM, cfg.d_model), dtype)
    if cfg.family == "hybrid" and cfg.hybrid_shared_blocks:
        p["shared"] = _shared_block_params(cfg, keys[3], (cfg.hybrid_shared_blocks,), dtype)
    p.update(_norm_params(cfg, (), "final", cfg.d_model, dtype))
    if not cfg.tie_embeddings:
        p["lm_head"] = _dense(keys[4], (), (cfg.d_model, cfg.vocab), dtype)
    return p


# ==========================================================================
# Blocks (single layer, unstacked params)
# ==========================================================================
def _attn_mlp_layer(cfg: ModelConfig, x, p, positions, causal):
    x = L.hint(x, "batch", None, None)
    attn = (L.mla_attention_block(cfg, L.norm(cfg, x, p, "ln1"), p, positions, causal)
            if cfg.use_mla else
            L.attention_block(cfg, L.norm(cfg, x, p, "ln1"), p, positions, causal))
    x = x + attn
    h = L.norm(cfg, x, p, "ln2")
    ffn = L.moe_block(cfg, h, p) if cfg.family == "moe" else L.mlp_block(cfg, h, p)
    return x + ffn


def _ssm_layer(cfg: ModelConfig, x, p):
    y, _ = S.ssm_block(cfg, L.norm(cfg, x, p, "ln1"), p)
    return x + y


def _shared_block_apply(cfg: ModelConfig, x, h0, sp, positions, causal=True):
    """Zamba2 shared block: concat(h, h0) -> proj -> attn + mlp -> residual."""
    z = jnp.concatenate([x, h0], axis=-1) @ sp["concat_proj"]
    z = z + L.attention_block(cfg, L.norm(cfg, z, sp, "ln1"), sp, positions, causal)
    z = z + L.mlp_block(cfg, L.norm(cfg, z, sp, "ln2"), sp)
    return x + z


def _select_shared(shared: Params, idx: jax.Array) -> Params:
    return jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(a, idx, 0, keepdims=False), shared)


# ==========================================================================
# Embedding / head
# ==========================================================================
def embed_inputs(cfg: ModelConfig, params: Params, batch: dict[str, jax.Array]) -> jax.Array:
    if cfg.family == "encoder":
        return batch["frames"] @ params["in_proj"]
    tok = params["embed"][batch["tokens"]]
    if cfg.family == "vlm" and "patches" in batch:
        vis = batch["patches"] @ params["vision_proj"]
        return jnp.concatenate([vis, tok], axis=1)
    return tok


def lm_head(cfg: ModelConfig, params: Params, x: jax.Array,
            mm: L.Matmul = L.matmul) -> jax.Array:
    x = (L.layernorm(x, params["final_w"], params["final_b"], cfg.norm_eps)
         if cfg.norm == "layernorm" else L.rmsnorm(x, params["final_w"], cfg.norm_eps))
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return L.hint(mm(x, w), "batch", None, "model")


# ==========================================================================
# Forward (train / encoder / prefill-logits)
# ==========================================================================
def forward(cfg: ModelConfig, params: Params, batch: dict[str, jax.Array],
            remat: bool = False, remat_policy=None) -> jax.Array:
    """remat_policy: optional jax.checkpoint policy (e.g.
    ``jax.checkpoint_policies.dots_with_no_batch_dims_saveable`` — §Perf
    iteration D: compute term −15..17% for +5 GB/dev activation memory;
    off by default because train cells are memory-bound)."""
    x = embed_inputs(cfg, params, batch)
    t = x.shape[1]
    positions = jnp.arange(t)
    causal = cfg.is_causal

    if cfg.family == "hybrid":
        return _hybrid_forward(cfg, params, x, positions, remat)

    def layer(h, lp):
        if cfg.family == "ssm":
            return _ssm_layer(cfg, L.hint(h, "batch", None, None), lp), None
        return _attn_mlp_layer(cfg, h, lp, positions, causal), None

    if remat:
        layer = (jax.checkpoint(layer, policy=remat_policy)
                 if remat_policy is not None else jax.checkpoint(layer))
    x, _ = jax.lax.scan(layer, x, params["layers"])
    return lm_head(cfg, params, x)


def _hybrid_forward(cfg: ModelConfig, params: Params, x, positions, remat=False):
    k = cfg.hybrid_attn_every
    n_groups = cfg.n_layers // k
    h0 = x
    stacked = jax.tree.map(
        lambda a: a.reshape((n_groups, k) + a.shape[1:]), params["layers"])
    block_ids = jnp.arange(n_groups) % max(1, cfg.hybrid_shared_blocks)

    def group(h, inp):
        gp, bid = inp
        sp = _select_shared(params["shared"], bid)
        h = _shared_block_apply(cfg, h, h0, sp, positions, causal=cfg.is_causal)

        def inner(hh, lp):
            return _ssm_layer(cfg, hh, lp), None
        h, _ = jax.lax.scan(inner, h, gp)
        return h, None

    if remat:
        group = jax.checkpoint(group)
    x, _ = jax.lax.scan(group, x, (stacked, block_ids))
    return lm_head(cfg, params, x)


# ==========================================================================
# KV / state caches
# ==========================================================================
def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.float32) -> Cache:
    nl, hd = cfg.n_layers, cfg.resolved_head_dim
    if cfg.family == "ssm":
        return _ssm_cache(cfg, nl, batch, dtype)
    if cfg.family == "hybrid":
        c = _ssm_cache(cfg, nl, batch, dtype)
        n_groups = nl // cfg.hybrid_attn_every
        c["k"] = jnp.zeros((n_groups, batch, max_len, cfg.n_kv_heads, hd), dtype)
        c["v"] = jnp.zeros((n_groups, batch, max_len, cfg.n_kv_heads, hd), dtype)
        return c
    if cfg.use_mla:
        return {
            "ckv": jnp.zeros((nl, batch, max_len, cfg.kv_lora_rank), dtype),
            "krope": jnp.zeros((nl, batch, max_len, cfg.rope_head_dim), dtype),
        }
    return {
        "k": jnp.zeros((nl, batch, max_len, cfg.n_kv_heads, hd), dtype),
        "v": jnp.zeros((nl, batch, max_len, cfg.n_kv_heads, hd), dtype),
    }


def _ssm_cache(cfg: ModelConfig, nl: int, batch: int, dtype) -> Cache:
    d_inner = cfg.ssm_expand * cfg.d_model
    nh = d_inner // cfg.ssm_head_dim
    conv_dim = d_inner + 2 * cfg.ssm_n_groups * cfg.ssm_state
    return {
        "conv": jnp.zeros((nl, batch, cfg.ssm_conv_width - 1, conv_dim), dtype),
        "state": jnp.zeros((nl, batch, nh, cfg.ssm_head_dim, cfg.ssm_state), dtype),
    }


# ==========================================================================
# Prefill: forward pass that also fills the cache
# ==========================================================================
def prefill(cfg: ModelConfig, params: Params, batch: dict[str, jax.Array],
            max_len: int | None = None) -> tuple[jax.Array, Cache]:
    x = embed_inputs(cfg, params, batch)
    bsz, t = x.shape[:2]
    max_len = max_len or t
    positions = jnp.arange(t)
    pad = max_len - t

    if cfg.family == "hybrid":
        return _hybrid_prefill(cfg, params, x, positions, pad)

    if cfg.family == "ssm":
        def layer(h, lp):
            hn = L.norm(cfg, h, lp, "ln1")
            y, final = S.ssm_block(cfg, hn, lp)
            # conv cache: last W-1 pre-conv inputs (x | B | C)
            xbc = jnp.concatenate(
                [L.matmul(hn, lp["x_proj"]), L.matmul(hn, lp["bc_proj"])], axis=-1)
            conv = xbc[:, -(cfg.ssm_conv_width - 1):]
            return h + y, {"conv": conv, "state": final}
        x, cache = jax.lax.scan(layer, x, params["layers"])
        logits = lm_head(cfg, params, x[:, -1:])
        return logits, cache

    if cfg.use_mla:
        def layer(h, lp):
            hn = L.norm(cfg, h, lp, "ln1")
            ckv, krope = L.mla_project_kv_latent(cfg, hn, lp)
            cos, sin = L.rope_cos_sin(positions, cfg.rope_head_dim, cfg.rope_theta)
            krope_r = L.apply_rope(krope[..., None, :], cos, sin, cfg.rope_head_dim)[..., 0, :]
            h = _attn_mlp_layer(cfg, h, lp, positions, causal=True)
            entry = {
                "ckv": jnp.pad(ckv, ((0, 0), (0, pad), (0, 0))),
                "krope": jnp.pad(krope_r, ((0, 0), (0, pad), (0, 0))),
            }
            return h, entry
        x, cache = jax.lax.scan(layer, x, params["layers"])
        return lm_head(cfg, params, x[:, -1:]), cache

    def layer(h, lp):
        hn = L.norm(cfg, h, lp, "ln1")
        q, k, v = L.qkv_project(cfg, hn, lp)
        q, k = L._maybe_qk_norm(cfg, q, k, lp)
        rot = int(cfg.resolved_head_dim * cfg.rope_fraction)
        if rot:
            cos, sin = L.rope_cos_sin(positions, rot, cfg.rope_theta)
            q = L.apply_rope(q, cos, sin, rot)
            k = L.apply_rope(k, cos, sin, rot)
        attn = L.attend(cfg, q, k, v, causal=True)
        h = h + L.matmul(attn.reshape(bsz, t, -1), lp["wo"])
        ffn_in = L.norm(cfg, h, lp, "ln2")
        ffn = L.moe_block(cfg, ffn_in, lp) if cfg.family == "moe" else L.mlp_block(cfg, ffn_in, lp)
        h = h + ffn
        entry = {
            "k": jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0))),
            "v": jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0))),
        }
        return h, entry

    x, cache = jax.lax.scan(layer, x, params["layers"])
    return lm_head(cfg, params, x[:, -1:]), cache


def _hybrid_prefill(cfg: ModelConfig, params: Params, x, positions, pad):
    k_every = cfg.hybrid_attn_every
    n_groups = cfg.n_layers // k_every
    h0 = x
    bsz, t = x.shape[:2]
    stacked = jax.tree.map(lambda a: a.reshape((n_groups, k_every) + a.shape[1:]), params["layers"])
    block_ids = jnp.arange(n_groups) % max(1, cfg.hybrid_shared_blocks)

    def group(h, inp):
        gp, bid = inp
        sp = _select_shared(params["shared"], bid)
        z = jnp.concatenate([h, h0], axis=-1) @ sp["concat_proj"]
        zn = L.norm(cfg, z, sp, "ln1")
        q, kk, vv = L.qkv_project(cfg, zn, sp)
        q, kk = L._maybe_qk_norm(cfg, q, kk, sp)
        rot = int(cfg.resolved_head_dim * cfg.rope_fraction)
        if rot:
            cos, sin = L.rope_cos_sin(positions, rot, cfg.rope_theta)
            q = L.apply_rope(q, cos, sin, rot)
            kk = L.apply_rope(kk, cos, sin, rot)
        z = z + L.matmul(L.attend(cfg, q, kk, vv, causal=True).reshape(bsz, t, -1),
                         sp["wo"])
        z = z + L.mlp_block(cfg, L.norm(cfg, z, sp, "ln2"), sp)
        h = h + z

        def inner(hh, lp):
            hn = L.norm(cfg, hh, lp, "ln1")
            y, final = S.ssm_block(cfg, hn, lp)
            xbc = jnp.concatenate(
                [L.matmul(hn, lp["x_proj"]), L.matmul(hn, lp["bc_proj"])], axis=-1)
            return hh + y, {"conv": xbc[:, -(cfg.ssm_conv_width - 1):], "state": final}

        h, inner_cache = jax.lax.scan(inner, h, gp)
        entry = {
            "k": jnp.pad(kk, ((0, 0), (0, pad), (0, 0), (0, 0))),
            "v": jnp.pad(vv, ((0, 0), (0, pad), (0, 0), (0, 0))),
            **inner_cache,
        }
        return h, entry

    x, cache = jax.lax.scan(group, x, (stacked, block_ids))
    out = {
        "k": cache["k"], "v": cache["v"],
        "conv": cache["conv"].reshape((cfg.n_layers,) + cache["conv"].shape[2:]),
        "state": cache["state"].reshape((cfg.n_layers,) + cache["state"].shape[2:]),
    }
    return lm_head(cfg, params, x[:, -1:]), out


# ==========================================================================
# Chunked prefill: continue a partially filled cache by n tokens
# ==========================================================================
def prefill_chunk(cfg: ModelConfig, params: Params, cache: Cache,
                  tokens: jax.Array, start: int) -> tuple[jax.Array, Cache]:
    """Process prompt tokens [start, start+n) against a cache filled for
    [0, start) — the compute primitive behind the serving frontend's
    chunked prefill (prompts split into fixed token budgets interleaved
    with decode steps).

    tokens: [B, n] int32; cache: the full-size cache from ``init_cache``
    (attention families: [L,B,max_len,...] K/V or latent entries; SSM:
    conv/state carries; hybrids: both).  ``start == 0`` against a fresh
    zero cache is a whole-prefix pass: attention masks the empty cache
    away and the SSM conv history of zeros matches `_causal_conv`'s zero
    padding, so feeding a prompt in chunks of any size yields the same
    cache and next-token logits as one `prefill` call (exact-token
    equivalence is pinned by the scheduler parity tests).

    Returns (logits [B,1,vocab] at the chunk's last position, cache).
    """
    x = params["embed"][tokens]
    bsz, t = x.shape[:2]
    positions = jnp.arange(start, start + t)

    if cfg.family == "hybrid":
        return _hybrid_prefill_chunk(cfg, params, cache, x, positions, start)

    if cfg.family == "ssm":
        def layer(h, c):
            lp, conv, state = c
            y, conv, state = S.ssm_block_chunk(
                cfg, L.norm(cfg, h, lp, "ln1"), lp, conv, state)
            return h + y, {"conv": conv, "state": state}
        x, new_cache = jax.lax.scan(
            layer, x, (params["layers"], cache["conv"], cache["state"]))
        return lm_head(cfg, params, x[:, -1:]), new_cache

    if cfg.use_mla:
        def layer(h, c):
            lp, ckv_c, krope_c = c
            hn = L.norm(cfg, h, lp, "ln1")
            attn, ckv_c, krope_c = L.mla_attention_chunk(
                cfg, hn, lp, ckv_c, krope_c, positions, start)
            h = h + attn
            ffn_in = L.norm(cfg, h, lp, "ln2")
            ffn = (L.moe_block(cfg, ffn_in, lp) if cfg.family == "moe"
                   else L.mlp_block(cfg, ffn_in, lp))
            return h + ffn, {"ckv": ckv_c, "krope": krope_c}
        x, new_cache = jax.lax.scan(
            layer, x, (params["layers"], cache["ckv"], cache["krope"]))
        return lm_head(cfg, params, x[:, -1:]), new_cache

    def layer(h, c):
        lp, k_c, v_c = c
        hn = L.norm(cfg, h, lp, "ln1")
        attn, k_c, v_c = L.attention_chunk(cfg, hn, lp, k_c, v_c,
                                           positions, start)
        h = h + attn
        ffn_in = L.norm(cfg, h, lp, "ln2")
        ffn = (L.moe_block(cfg, ffn_in, lp) if cfg.family == "moe"
               else L.mlp_block(cfg, ffn_in, lp))
        return h + ffn, {"k": k_c, "v": v_c}

    x, new_cache = jax.lax.scan(
        layer, x, (params["layers"], cache["k"], cache["v"]))
    return lm_head(cfg, params, x[:, -1:]), new_cache


def _hybrid_prefill_chunk(cfg: ModelConfig, params: Params, cache: Cache,
                          x, positions, start: int):
    k_every = cfg.hybrid_attn_every
    n_groups = cfg.n_layers // k_every
    h0 = x
    stacked = jax.tree.map(
        lambda a: a.reshape((n_groups, k_every) + a.shape[1:]), params["layers"])
    conv = cache["conv"].reshape((n_groups, k_every) + cache["conv"].shape[1:])
    state = cache["state"].reshape((n_groups, k_every) + cache["state"].shape[1:])
    block_ids = jnp.arange(n_groups) % max(1, cfg.hybrid_shared_blocks)

    def group(h, c):
        gp, k_c, v_c, conv_g, state_g, bid = c
        sp = _select_shared(params["shared"], bid)
        z = jnp.concatenate([h, h0], axis=-1) @ sp["concat_proj"]
        zn = L.norm(cfg, z, sp, "ln1")
        attn, k_c, v_c = L.attention_chunk(cfg, zn, sp, k_c, v_c,
                                           positions, start)
        z = z + attn
        z = z + L.mlp_block(cfg, L.norm(cfg, z, sp, "ln2"), sp)
        h = h + z

        def inner(hh, ic):
            lp, cv, st = ic
            y, cv, st = S.ssm_block_chunk(
                cfg, L.norm(cfg, hh, lp, "ln1"), lp, cv, st)
            return hh + y, (cv, st)
        h, (conv_g, state_g) = jax.lax.scan(inner, h, (gp, conv_g, state_g))
        return h, {"k": k_c, "v": v_c, "conv": conv_g, "state": state_g}

    x, new = jax.lax.scan(
        group, x, (stacked, cache["k"], cache["v"], conv, state, block_ids))
    out = {
        "k": new["k"], "v": new["v"],
        "conv": new["conv"].reshape((cfg.n_layers,) + new["conv"].shape[2:]),
        "state": new["state"].reshape((cfg.n_layers,) + new["state"].shape[2:]),
    }
    return lm_head(cfg, params, x[:, -1:]), out


# ==========================================================================
# Decode: one token, cache update
# ==========================================================================
def decode_step(cfg: ModelConfig, params: Params, cache: Cache,
                tokens: jax.Array, pos: jax.Array) -> tuple[jax.Array, Cache]:
    """tokens: [B,1] int32; pos: absolute position(s) to write — scalar
    int32 for a slot-aligned batch, or [B] int32 for a ragged batch (each
    slot writes/attends at its own length; SSM families ignore pos)."""
    x = params["embed"][tokens]

    if cfg.family == "hybrid":
        return _hybrid_decode(cfg, params, cache, x, pos)

    if cfg.family == "ssm":
        def layer(h, c):
            lp, conv, state = c
            y, conv, state = S.ssm_block_decode(cfg, L.norm(cfg, h, lp, "ln1"), lp, conv, state)
            return h + y, {"conv": conv, "state": state}
        x, new_cache = jax.lax.scan(layer, x, (params["layers"], cache["conv"], cache["state"]))
        return lm_head(cfg, params, x), new_cache

    if cfg.use_mla:
        def layer(h, c):
            lp, ckv, krope = c
            hn = L.norm(cfg, h, lp, "ln1")
            attn, ckv, krope = L.mla_decode(cfg, hn, lp, ckv, krope, pos)
            h = h + attn
            ffn_in = L.norm(cfg, h, lp, "ln2")
            ffn = L.moe_block(cfg, ffn_in, lp) if cfg.family == "moe" else L.mlp_block(cfg, ffn_in, lp)
            return h + ffn, {"ckv": ckv, "krope": krope}
        x, new_cache = jax.lax.scan(layer, x, (params["layers"], cache["ckv"], cache["krope"]))
        return lm_head(cfg, params, x), new_cache

    def layer(h, c):
        lp, k_c, v_c = c
        hn = L.norm(cfg, h, lp, "ln1")
        attn, k_c, v_c = L.attention_decode(cfg, hn, lp, k_c, v_c, pos)
        h = h + attn
        ffn_in = L.norm(cfg, h, lp, "ln2")
        ffn = L.moe_block(cfg, ffn_in, lp) if cfg.family == "moe" else L.mlp_block(cfg, ffn_in, lp)
        return h + ffn, {"k": k_c, "v": v_c}

    x, new_cache = jax.lax.scan(layer, x, (params["layers"], cache["k"], cache["v"]))
    return lm_head(cfg, params, x), new_cache


def _hybrid_decode(cfg: ModelConfig, params: Params, cache: Cache, x, pos):
    k_every = cfg.hybrid_attn_every
    n_groups = cfg.n_layers // k_every
    h0 = x
    stacked = jax.tree.map(lambda a: a.reshape((n_groups, k_every) + a.shape[1:]), params["layers"])
    conv = cache["conv"].reshape((n_groups, k_every) + cache["conv"].shape[1:])
    state = cache["state"].reshape((n_groups, k_every) + cache["state"].shape[1:])
    block_ids = jnp.arange(n_groups) % max(1, cfg.hybrid_shared_blocks)

    def group(h, c):
        gp, k_c, v_c, conv_g, state_g, bid = c
        sp = _select_shared(params["shared"], bid)
        z = jnp.concatenate([h, h0], axis=-1) @ sp["concat_proj"]
        zn = L.norm(cfg, z, sp, "ln1")
        attn, k_c, v_c = L.attention_decode(cfg, zn, sp, k_c, v_c, pos)
        z = z + attn
        z = z + L.mlp_block(cfg, L.norm(cfg, z, sp, "ln2"), sp)
        h = h + z

        def inner(hh, ic):
            lp, cv, st = ic
            y, cv, st = S.ssm_block_decode(cfg, L.norm(cfg, hh, lp, "ln1"), lp, cv, st)
            return hh + y, (cv, st)
        h, (conv_g, state_g) = jax.lax.scan(inner, h, (gp, conv_g, state_g))
        return h, {"k": k_c, "v": v_c, "conv": conv_g, "state": state_g}

    x, new = jax.lax.scan(group, x, (stacked, cache["k"], cache["v"], conv, state, block_ids))
    out = {
        "k": new["k"], "v": new["v"],
        "conv": new["conv"].reshape((cfg.n_layers,) + new["conv"].shape[2:]),
        "state": new["state"].reshape((cfg.n_layers,) + new["state"].shape[2:]),
    }
    return lm_head(cfg, params, x), out
