"""A dense GQA decoder (StarCoder2, Qwen2.5): the architecture module.

Everything the harness knows about this block is here: the weight layout
(``stacks``, ``top_shapes``, ``finish``), the keys that tie the config
file's ``model`` block to the program's ``ModelConfig`` (``PROGRAM_KEYS``),
the work of a decode step (``gemms``, ``kernel_gemms``,
``kv_bytes_per_token``, ``decode_step``) and the plain float32 reference
(``Reference``).  A config file names this module under ``reference``.

The weight layout, one stack ``layers`` of ``n_layers`` identical layers:

    layers/ln1_w, ln1_b*, wq [d, Hp*hd], wkv [d, 2*K*hd] (K then V),
           wo [Hp*hd, d], bq*, bkv*, ln2_w, ln2_b*, wi [d, m*F] (gate then
           up for SwiGLU), wdown [F, d], bi*, bdown*      (* when present)
    embed [V, d], final_w, final_b*, lm_head [d, V] (untied head)

``Hp`` is the stored query-head count (``padded_heads``); heads past
``n_heads`` are zero in ``wq``, ``bq`` and ``wo`` (``finish``), so they
add nothing.

The reference is straight ``jax.numpy`` at ``Precision.HIGHEST``, no
kernels, cache or batching tricks; it imports nothing of the serving
program.  It reads its sizes from the ``model`` block and makes its
weights again from the seed, a layer at a time (``weights.make_layer``),
upcast from the served dtype to float32.

The published block, pre-norm and sequential:

    h = x + Wo . attn(RoPE(norm1(x) Wq + bq), RoPE(norm1(x) Wk + bk), norm1(x) Wv + bv)
    y = h + mlp(norm2(h))
    mlp = GELU-tanh(z Wi + bi) Wdown + bdown      (StarCoder2)
        = (SiLU(z Wg) * z Wu) Wdown                (Qwen2.5)
    logits = norm_f(y) W_head

Layout conventions of the stored weights, which fix how the reference
reads them (each is a fixed permutation of the published layout, so the
function class is the same): query head ``h`` reads KV head ``h % K``
(group-major); RoPE rotates the interleaved pairs ``(2i, 2i+1)`` of a
head; ``wkv`` holds K then V, and SwiGLU's ``wi`` holds gate then up.
Only the first ``n_heads`` stored query heads are read.

The same pass also runs the control: the reference with every weight
matrix rounded to fp8 e4m3 (one scale per output channel), the step
below the served bf16.  Per-channel int8 was tried and read under three
times a sound run's gap, too close to be a control (PERF.md).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from work import Gemm, itemsize

# model-block key -> ModelConfig attribute, or (attribute, {file value:
# program value}) where the two name a choice differently
PROGRAM_KEYS = {
    "n_layers": "n_layers", "d_model": "d_model", "n_heads": "n_heads",
    "n_kv_heads": "n_kv_heads", "head_dim": "resolved_head_dim",
    "padded_heads": "padded_heads", "d_ff": "d_ff", "vocab": "vocab",
    "rope_theta": "rope_theta", "norm": "norm", "norm_eps": "norm_eps",
    "qkv_bias": "qkv_bias", "tie_embeddings": "tie_embeddings", "dtype": "dtype",
    "mlp": ("mlp", {"gelu_tanh": "gelu", "swiglu": "swiglu"}),
}


# -- weight layout -------------------------------------------------------------

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


def stacks(m: dict) -> dict[str, tuple[int, dict[str, tuple[int, ...]]]]:
    return {"layers": (m["n_layers"], layer_shapes(m))}


def top_shapes(m: dict) -> dict[str, tuple[int, ...]]:
    d, v = m["d_model"], m["vocab"]
    s = {"embed": (v, d), "final_w": (d,)}
    if m["norm"] == "layernorm":
        s["final_b"] = (d,)
    if not m["tie_embeddings"]:
        s["lm_head"] = (d, v)
    return s


def finish(m: dict, stack: str | None, name: str, x: jax.Array) -> jax.Array:
    """Zero the stored query heads past ``n_heads``."""
    if stack is None or m["padded_heads"] == m["n_heads"]:
        return x
    real = m["n_heads"] * m["head_dim"]
    if name in ("wq", "bq"):
        return x.at[..., real:].set(0)
    if name == "wo":
        return x.at[real:, :].set(0)
    return x


# -- work of a decode step -----------------------------------------------------

def gemms(m: dict) -> list[Gemm]:
    """The weight GEMMs of one decode step: per layer q, kv, o, up (gate
    and up for SwiGLU) and down, and the LM head."""
    d, hd, h, kv = m["d_model"], m["head_dim"], m["n_heads"], m["n_kv_heads"]
    mult = 2 if m["mlp"] == "swiglu" else 1
    nl = m["n_layers"]
    return [Gemm("wq", d, h * hd, nl), Gemm("wkv", d, 2 * kv * hd, nl),
            Gemm("wo", h * hd, d, nl), Gemm("wi", d, mult * m["d_ff"], nl),
            Gemm("wdown", m["d_ff"], d, nl), Gemm("lm_head", d, m["vocab"], 1)]


def kernel_gemms(m: dict) -> list[Gemm]:
    """The GEMMs that run as tiered ``splitk_gemm`` calls: all of them but
    a tied head, which multiplies by the embedding table the program keeps
    whole."""
    return [g for g in gemms(m) if not (g.name == "lm_head" and m["tie_embeddings"])]


def kv_bytes_per_token(m: dict) -> float:
    return float(m["n_layers"] * 2 * m["n_kv_heads"] * m["head_dim"] * itemsize(m))


def decode_step(m: dict, step) -> tuple[float, float]:
    """(operations, bytes) of a whole decode step (a ``record.Step``): its
    ``decode_tokens`` active requests attending over ``ctx`` cached tokens
    in all (the sum of their lengths, the new token included).  Bytes are
    every weight once, the embedding rows of the batch, the KV read and the
    KV written."""
    batch, ctx = step.decode_tokens, step.ctx
    b = itemsize(m)
    weights = sum(g.count * g.k * g.n for g in gemms(m))
    flops = 2.0 * batch * weights
    flops += 4.0 * m["n_layers"] * m["n_heads"] * m["head_dim"] * ctx
    nbytes = float(b * weights + b * batch * m["d_model"])
    nbytes += kv_bytes_per_token(m) * (ctx + batch)
    return flops, nbytes


# -- reference -----------------------------------------------------------------

HI = jax.lax.Precision.HIGHEST
CONTROLS = ("fp8",)
_MATRICES = ("wq", "wkv", "wo", "wi", "wdown")


def quantize(w: jax.Array, kind: str, axis: int) -> jax.Array:
    """``w`` rounded to ``kind`` with one scale per slice along ``axis``
    (the reduced axis is the input dimension), back in float32."""
    amax = jnp.max(jnp.abs(w), axis=axis, keepdims=True)
    if kind == "fp8":
        scale = jnp.where(amax > 0, amax / 448.0, 1.0)
        return round_e4m3(w / scale) * scale
    raise ValueError(f"unknown control precision {kind!r}")


def round_e4m3(x: jax.Array) -> jax.Array:
    """Round to the nearest float8 e4m3fn value (3 mantissa bits, smallest
    normal 2**-6, subnormal step 2**-9, largest 448) in float32 arithmetic:
    the TPU may hold an fp8 cast in a wider type and not round at all."""
    _, e = jnp.frexp(x)                          # |x| = m * 2**e, m in [0.5, 1)
    step = jnp.ldexp(jnp.ones_like(x), jnp.maximum(e - 1, -6) - 3)
    return jnp.clip(jnp.round(x / step) * step, -448.0, 448.0)


def _norm(m: dict, x, w, b):
    if m["norm"] == "layernorm":
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + m["norm_eps"]) * w + b
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(ms + m["norm_eps"]) * w


def _rope(x, theta: float):
    """x: [T, H, hd]; interleaved pairs rotated by position."""
    t, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv        # [T, hd/2]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1).reshape(x.shape)


def _attention(m: dict, q, k, v):
    """One sequence: q [T, H, hd], k/v [T, K, hd] -> [T, H*hd]."""
    t, h, hd = q.shape
    kv_of = jnp.arange(h) % m["n_kv_heads"]
    kh, vh = k[:, kv_of], v[:, kv_of]                            # [T, H, hd]
    s = jnp.einsum("thd,shd->hts", q, kh, precision=HI) / np.sqrt(hd)
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    s = jnp.where(causal[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("hts,shd->thd", p, vh, precision=HI).reshape(t, h * hd)


def layer(m: dict, x, w: dict):
    """One decoder layer over a batch of sequences x [N, T, d] (float32)."""
    h, hd, kv = m["n_heads"], m["head_dim"], m["n_kv_heads"]
    ln = functools.partial(_norm, m)

    def one(xs):
        z = ln(xs, w["ln1_w"], w.get("ln1_b"))
        q = jnp.dot(z, w["wq"][:, :h * hd], precision=HI)
        kvp = jnp.dot(z, w["wkv"], precision=HI)
        if m["qkv_bias"]:
            q = q + w["bq"][:h * hd]
            kvp = kvp + w["bkv"]
        t = xs.shape[0]
        q = _rope(q.reshape(t, h, hd), m["rope_theta"])
        k = _rope(kvp[:, :kv * hd].reshape(t, kv, hd), m["rope_theta"])
        v = kvp[:, kv * hd:].reshape(t, kv, hd)
        xs = xs + jnp.dot(_attention(m, q, k, v), w["wo"][:h * hd], precision=HI)
        z = ln(xs, w["ln2_w"], w.get("ln2_b"))
        u = jnp.dot(z, w["wi"], precision=HI)
        if m["mlp_bias"]:
            u = u + w["bi"]
        if m["mlp"] == "swiglu":
            g, up = jnp.split(u, 2, axis=-1)
            a = jax.nn.silu(g) * up
        else:
            a = jax.nn.gelu(u, approximate=True)
        y = jnp.dot(a, w["wdown"], precision=HI)
        if m["mlp_bias"]:
            y = y + w["bdown"]
        return xs + y

    return jax.lax.map(one, x)


def head(m: dict, rows, final_w, final_b, w_head):
    """Logits of gathered rows [R, d]; ``w_head`` is [d, V]."""
    return jnp.dot(_norm(m, rows, final_w, final_b), w_head, precision=HI)


class Reference:
    """Reference and control logits at the positions that produced served
    tokens.  ``make_layer(stack, i)`` and ``make_top(name)`` give the
    served-dtype weights again; every matmul runs in float32 at HIGHEST."""

    def __init__(self, m: dict, make_layer, make_top):
        self.m = m
        self._make_layer = make_layer
        self._make_top = make_top
        self._layer = jax.jit(functools.partial(layer, m))
        self._head = jax.jit(functools.partial(head, m))
        self._quant = jax.jit(quantize, static_argnums=(1, 2))

    def _f32(self, tree):
        return jax.tree.map(lambda a: a.astype(jnp.float32), tree)

    def _ctrl_layer(self, w: dict, kind: str) -> dict:
        return {k: (self._quant(a, kind, 0) if k in _MATRICES else a)
                for k, a in w.items()}

    def logits(self, seqs: list[np.ndarray], starts: list[int],
               counts: list[int], controls: tuple[str, ...] = (), pad: int = 256):
        """Float32 logits [R, V] for every sequence ``i`` at positions
        ``starts[i] .. starts[i] + counts[i] - 1``, rows in order, and for
        each of ``controls`` the control's logits at the same rows.
        Sequences are right-padded to a common multiple of ``pad``."""
        m = self.m
        t = -(-max(len(s) for s in seqs) // pad) * pad
        toks = np.zeros((len(seqs), t), np.int32)
        for i, s in enumerate(seqs):
            toks[i, :len(s)] = s
        toks = jnp.asarray(toks)
        emb = self._f32(self._make_top("embed"))
        xs = {None: emb[toks]}
        xs.update({c: self._quant(emb, c, 1)[toks] for c in controls})
        del emb
        for li in range(m["n_layers"]):
            w = self._f32(self._make_layer("layers", li))
            for c in xs:
                xs[c] = self._layer(xs[c], w if c is None else self._ctrl_layer(w, c))
            del w
        rows = jnp.asarray(np.concatenate([i * t + np.arange(a, a + c) for i, (a, c)
                                           in enumerate(zip(starts, counts))]))
        fw = self._f32(self._make_top("final_w"))
        fb = (self._f32(self._make_top("final_b"))
              if m["norm"] == "layernorm" else None)
        w_head = (self._f32(self._make_top("embed")).T if m["tie_embeddings"]
                  else self._f32(self._make_top("lm_head")))
        out = {}
        for c, x in xs.items():
            wh = w_head if c is None else self._quant(w_head, c, 0)
            out[c] = self._head(x.reshape(-1, m["d_model"])[rows], fw, fb, wh)
        return out.pop(None), out
