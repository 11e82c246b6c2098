"""Weights made from ``--seed``, in the serving program's parameter layout.

The benchmark owns this generator so that its reference can make the same
weights again, a layer at a time, without taking anything the program
made.  A dense GQA decoder's layout (``model`` block of a config file):

    layers/ln1_w, ln1_b*, wq [d, Hp*hd], wkv [d, 2*K*hd] (K then V),
           wo [Hp*hd, d], bq*, bkv*, ln2_w, ln2_b*, wi [d, m*F] (gate then
           up for SwiGLU), wdown [F, d], bi*, bdown*      (* when present)
    embed [V, d], final_w, final_b*, lm_head [d, V] (untied head)

``Hp`` is the stored query-head count (``padded_heads``); heads past
``n_heads`` are zero in ``wq``, ``bq`` and ``wo``, so they add nothing.
Matrices and biases are N(0, 0.02); norm gains are 1 + N(0, 0.05).  Every
leaf has a key of its own, folded from the seed, the layer and the leaf's
name, so one layer made alone equals that layer of the whole tree.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

STD = 0.02
GAIN_STD = 0.05


def base_key(seed: int) -> jax.Array:
    """A key from any non-negative seed, 64-bit ones included (a plain
    ``PRNGKey`` keeps only the low 32 bits)."""
    if seed < 0:
        raise ValueError("seed must be non-negative")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _leaf_id(name: str) -> int:
    return zlib.crc32(name.encode()) & 0x7FFFFFFF


def layer_shapes(m: dict) -> dict[str, tuple[int, ...]]:
    d, hd = m["d_model"], m["head_dim"]
    hp, kv, ff = m["padded_heads"], m["n_kv_heads"], m["d_ff"]
    mult = 2 if m["mlp"] == "swiglu" else 1
    s = {"ln1_w": (d,), "wq": (d, hp * hd), "wkv": (d, 2 * kv * hd),
         "wo": (hp * hd, d), "ln2_w": (d,), "wi": (d, mult * ff),
         "wdown": (ff, d)}
    if m["norm"] == "layernorm":
        s["ln1_b"] = (d,)
        s["ln2_b"] = (d,)
    if m["qkv_bias"]:
        s["bq"] = (hp * hd,)
        s["bkv"] = (2 * kv * hd,)
    if m["mlp_bias"]:
        s["bi"] = (mult * ff,)
        s["bdown"] = (d,)
    return s


def top_shapes(m: dict) -> dict[str, tuple[int, ...]]:
    d, v = m["d_model"], m["vocab"]
    s = {"embed": (v, d), "final_w": (d,)}
    if m["norm"] == "layernorm":
        s["final_b"] = (d,)
    if not m["tie_embeddings"]:
        s["lm_head"] = (d, v)
    return s


def _leaf(key: jax.Array, name: str, shape, dtype) -> jax.Array:
    k = jax.random.fold_in(key, _leaf_id(name))
    z = jax.random.normal(k, shape, jnp.float32)
    if name.endswith("_w"):                       # norm gains
        return (1.0 + GAIN_STD * z).astype(dtype)
    return (STD * z).astype(dtype)


def _zero_padded_heads(m: dict, name: str, x: jax.Array) -> jax.Array:
    real = m["n_heads"] * m["head_dim"]
    if m["padded_heads"] == m["n_heads"]:
        return x
    if name in ("wq", "bq"):
        return x.at[..., real:].set(0)
    if name == "wo":
        return x.at[real:, :].set(0)
    return x


def make_layer(m: dict, seed_key: jax.Array, layer: jax.Array) -> dict:
    """One layer's leaves (traceable; ``layer`` may be a tracer)."""
    dtype = jnp.dtype(m["dtype"])
    key = jax.random.fold_in(jax.random.fold_in(seed_key, 1), layer)
    return {name: _zero_padded_heads(m, name, _leaf(key, name, shape, dtype))
            for name, shape in layer_shapes(m).items()}


def make_top(m: dict, seed_key: jax.Array, name: str) -> jax.Array:
    dtype = jnp.dtype(m["dtype"])
    key = jax.random.fold_in(seed_key, 2)
    return _leaf(key, name, top_shapes(m)[name], dtype)


def make_params(m: dict, seed: int) -> dict:
    """The whole tree on the device in the served dtype, from one jitted
    call; layers are made one at a time (``lax.map``) so the temporaries
    are one layer's."""
    def init(key):
        layers = jax.lax.map(lambda i: make_layer(m, key, i),
                             jnp.arange(m["n_layers"]))
        out = {"layers": layers}
        for name in top_shapes(m):
            out[name] = make_top(m, key, name)
        return out
    return jax.jit(init)(base_key(seed))


def layout(m: dict) -> dict:
    """Shapes of the whole tree, to compare with the program's own."""
    out = {"layers": {k: (m["n_layers"],) + s for k, s in layer_shapes(m).items()}}
    out.update(top_shapes(m))
    return out
