"""Weights made from ``--seed``, in the serving program's parameter layout.

The benchmark owns this generator so that its reference can make the same
weights again, a layer at a time, without taking anything the program
made.  The layout is the architecture module's (``references/<reference>.py``
of the config file): ``stacks(m)`` gives each stack of layers its count and
its leaves' shapes, ``top_shapes(m)`` the leaves outside every stack, and
``finish(m, stack, name, x)`` finishes each leaf made (``stack`` is None
for a top leaf).  A stack's leaves carry the layer index first.

Matrices and biases are N(0, 0.02); norm gains (names ending ``_w``) are
1 + N(0, 0.05).  Every leaf has a key of its own, folded from the seed,
the stack, the layer and the leaf's name, so one layer made alone equals
that layer of the whole tree.  Stack ``layers`` folds 1 and the layer,
the top leaves fold 2; any other stack folds 3, then its name, then the
layer.
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


def _leaf(key: jax.Array, name: str, shape, dtype) -> jax.Array:
    k = jax.random.fold_in(key, _leaf_id(name))
    z = jax.random.normal(k, shape, jnp.float32)
    if name.endswith("_w"):                       # norm gains
        return (1.0 + GAIN_STD * z).astype(dtype)
    return (STD * z).astype(dtype)


def _stack_key(seed_key: jax.Array, stack: str) -> jax.Array:
    if stack == "layers":
        return jax.random.fold_in(seed_key, 1)
    return jax.random.fold_in(jax.random.fold_in(seed_key, 3), _leaf_id(stack))


def make_layer(arch, m: dict, seed_key: jax.Array, stack: str, layer: jax.Array) -> dict:
    """Layer ``layer`` of ``stack`` (traceable; ``layer`` may be a tracer)."""
    dtype = jnp.dtype(m["dtype"])
    key = jax.random.fold_in(_stack_key(seed_key, stack), layer)
    _, shapes = arch.stacks(m)[stack]
    return {name: arch.finish(m, stack, name, _leaf(key, name, shape, dtype))
            for name, shape in shapes.items()}


def make_top(arch, m: dict, seed_key: jax.Array, name: str) -> jax.Array:
    dtype = jnp.dtype(m["dtype"])
    key = jax.random.fold_in(seed_key, 2)
    return arch.finish(m, None, name, _leaf(key, name, arch.top_shapes(m)[name], dtype))


def make_params(arch, m: dict, seed: int) -> dict:
    """The whole tree on the device in the served dtype, from one jitted
    call; layers are made one at a time (``lax.map``) so the temporaries
    are one layer's."""
    def init(key):
        out = {stack: jax.lax.map(lambda i, s=stack: make_layer(arch, m, key, s, i),
                                  jnp.arange(n))
               for stack, (n, _) in arch.stacks(m).items()}
        for name in arch.top_shapes(m):
            out[name] = make_top(arch, m, key, name)
        return out
    return jax.jit(init)(base_key(seed))


def layout(arch, m: dict) -> dict:
    """Shapes of the whole tree, to compare with the program's own."""
    out = {stack: {k: (n,) + s for k, s in shapes.items()}
           for stack, (n, shapes) in arch.stacks(m).items()}
    out.update(arch.top_shapes(m))
    return out
