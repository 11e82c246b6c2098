"""Jittable train / prefill / decode step functions + input specs.

These are the functions the dry-run lowers and the drivers execute.
``train_step`` supports microbatched gradient accumulation (scan) so the
live activation set stays within HBM at train_4k scale, and donates
params/opt-state.  ``decode_step`` donates the KV cache.
"""
from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import model as M
from repro.optim import adamw

DTYPE = jnp.bfloat16


def cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)


def constrain_tree(tree, spec_tree):
    """with_sharding_constraint over a tree of PartitionSpecs; no-op when no
    abstract mesh is active (plain-CPU tests/drivers)."""
    mesh = jax.sharding.get_abstract_mesh()
    if spec_tree is None or mesh is None or not mesh.axis_names:
        return tree
    return jax.tree.map(
        lambda x, sp: jax.lax.with_sharding_constraint(x, sp), tree, spec_tree,
        is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))


def make_train_step(
    cfg: ModelConfig,
    opt_cfg: adamw.AdamWConfig = adamw.AdamWConfig(),
    num_microbatches: int = 1,
    remat: bool = True,
    grad_specs=None,
) -> Callable:
    def loss_fn(params, mb):
        # Encoder/VLM logits cover the full (frame/patch+token) sequence;
        # labels are provided at matching length by the pipeline.
        logits = M.forward(cfg, params, mb, remat=remat)
        return cross_entropy(logits, mb["labels"])

    def train_step(params, opt_state, batch):
        if num_microbatches == 1:
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
            # ZeRO-1: reduce-scatter grads so the optimizer runs on shards
            # (params re-gathered once by the output constraint)
            grads = constrain_tree(grads, grad_specs)
        else:
            # Strided microbatching: microbatch i takes rows {i, i+mb, ...}
            # so each data shard contributes equally to every microbatch and
            # the batch sharding survives the reshape (contiguous splitting
            # would force XLA to reshard/replicate every scan step).
            mbs = jax.tree.map(
                lambda a: jnp.swapaxes(
                    a.reshape((a.shape[0] // num_microbatches, num_microbatches)
                              + a.shape[1:]), 0, 1), batch)

            def acc(carry, mb):
                c_loss, c_grads = carry
                l, g = jax.value_and_grad(loss_fn)(params, mb)
                # keep the fp32 accumulator sharded (ZeRO-style): each
                # microbatch contributes via reduce-scatter instead of a
                # full all-reduce (perf-loop iteration A3)
                new = jax.tree.map(lambda a, b: a + b.astype(jnp.float32),
                                   c_grads, g)
                return (c_loss + l, constrain_tree(new, grad_specs)), None

            init = constrain_tree(
                jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params),
                grad_specs)
            init = (jnp.zeros((), jnp.float32), init)
            (loss, grads), _ = jax.lax.scan(acc, init, mbs)
            inv = 1.0 / num_microbatches
            loss = loss * inv
            grads = jax.tree.map(lambda g: g * inv, grads)
        params, opt_state, gnorm = adamw.update(params, grads, opt_state, opt_cfg)
        return loss, params, opt_state, gnorm

    return train_step


def make_prefill_step(cfg: ModelConfig) -> Callable:
    if not cfg.has_decoder:
        # encoder-only: "prefill" is the full forward pass, no KV cache
        def encoder_step(params, batch):
            return M.forward(cfg, params, batch), {}
        return encoder_step

    def prefill_step(params, batch):
        return M.prefill(cfg, params, batch)
    return prefill_step


def make_decode_step(cfg: ModelConfig) -> Callable:
    def decode_fn(params, cache, tokens, pos):
        return M.decode_step(cfg, params, cache, tokens, pos)
    return decode_fn


# --------------------------------------------------------------------------
# Input specs — ShapeDtypeStruct stand-ins for every model input (assignment
# deliverable: weak-type-correct, shardable, no device allocation).
# --------------------------------------------------------------------------
def input_specs(cfg: ModelConfig, shape: ShapeConfig, dtype=DTYPE) -> dict[str, Any]:
    b, t = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    sds = jax.ShapeDtypeStruct

    if shape.step == "train":
        if cfg.family == "encoder":
            return {"frames": sds((b, t, M.AUDIO_FRAME_DIM), dtype),
                    "labels": sds((b, t), i32)}
        if cfg.family == "vlm":
            t_img = t // 2
            return {"tokens": sds((b, t - t_img), i32),
                    "patches": sds((b, t_img, M.VISION_EMBED_DIM), dtype),
                    "labels": sds((b, t), i32)}
        return {"tokens": sds((b, t), i32), "labels": sds((b, t), i32)}

    if shape.step == "prefill":
        if cfg.family == "encoder":
            return {"frames": sds((b, t, M.AUDIO_FRAME_DIM), dtype)}
        if cfg.family == "vlm":
            t_img = t // 2
            return {"tokens": sds((b, t - t_img), i32),
                    "patches": sds((b, t_img, M.VISION_EMBED_DIM), dtype)}
        return {"tokens": sds((b, t), i32)}

    # decode: one new token against a seq_len-deep cache
    return {"tokens": sds((b, 1), i32), "pos": sds((), i32)}


def cache_shapes(cfg: ModelConfig, shape: ShapeConfig, dtype=DTYPE) -> Any:
    return jax.eval_shape(
        functools.partial(M.init_cache, cfg, shape.global_batch, shape.seq_len,
                          dtype=dtype))


def params_shapes(cfg: ModelConfig, dtype=DTYPE) -> Any:
    return jax.eval_shape(
        lambda: M.init_params(cfg, jax.random.PRNGKey(0), dtype=dtype))


def opt_shapes(params_tree: Any) -> Any:
    return jax.eval_shape(adamw.init, params_tree)


def pick_microbatches(cfg: ModelConfig, shape: ShapeConfig, n_data: int) -> int:
    """Size grad-accumulation so per-chip layer-boundary activations stay
    under ~2 GB: bytes ≈ B_local · T · d · 2 · n_layers."""
    if shape.step != "train":
        return 1
    b_local = max(1, shape.global_batch // n_data)
    boundary = b_local * shape.seq_len * cfg.d_model * 2 * cfg.n_layers
    budget = 2e9
    mb = 1
    while boundary / mb > budget and mb < b_local:
        mb *= 2
    return mb


# --------------------------------------------------------------------------
# Distributed-optimization variant: explicit data-parallel train step under
# shard_map with int8-compressed gradient all-reduce + error feedback
# (repro.distributed.collectives).  4x less gradient traffic per step; the
# residual carries the quantization error into the next step.
# --------------------------------------------------------------------------
def make_dp_train_step_compressed(
    cfg: ModelConfig,
    mesh,
    opt_cfg: adamw.AdamWConfig = adamw.AdamWConfig(),
    axis: str = "data",
) -> Callable:
    from jax.sharding import PartitionSpec as P

    from repro.distributed import collectives

    def loss_fn(params, mb):
        logits = M.forward(cfg, params, mb, remat=True)
        return cross_entropy(logits, mb["labels"])

    def local_step(params, opt_state, residual, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        # error-feedback compression, then int8 all-reduce across data
        grads, residual = collectives.ErrorFeedback.apply(grads, residual)
        grads = jax.tree.map(
            lambda g: collectives.compressed_psum(g, axis)
            / jax.lax.psum(1.0, axis), grads)
        loss = jax.lax.pmean(loss, axis)
        params, opt_state, gnorm = adamw.update(params, grads, opt_state, opt_cfg)
        return loss, params, opt_state, residual, gnorm

    def step(params, opt_state, residual, batch):
        in_specs = (
            jax.tree.map(lambda _: P(), params),
            jax.tree.map(lambda _: P(), opt_state),
            jax.tree.map(lambda _: P(), residual),
            {k: P(axis, None) for k in batch},
        )
        out_specs = (P(), jax.tree.map(lambda _: P(), params),
                     jax.tree.map(lambda _: P(), opt_state),
                     jax.tree.map(lambda _: P(), residual), P())
        return jax.shard_map(local_step, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)(
            params, opt_state, residual, batch)

    return step
