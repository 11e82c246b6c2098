"""Operations and bytes of one decode step, at the published shapes.

Counted from the config file's ``model`` block, whatever the program
stores: ``n_heads`` query heads (not the zero-padded ``padded_heads``),
the batch as the active requests (not the slots), bf16 bytes of the
served dtype.  Biases, norm gains and activations are left out of the
bytes; at batch 8 to 32 they are under 0.1% of a step's weight bytes.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Gemm:
    name: str
    k: int
    n: int
    count: int          # calls per decode step


def itemsize(m: dict) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[m["dtype"]]


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


def gemm_call(m: dict, g: Gemm, batch: int) -> tuple[float, float]:
    """(operations, bytes) of one call at ``batch`` rows."""
    b = itemsize(m)
    return 2.0 * batch * g.k * g.n, float(b * (g.k * g.n + batch * (g.k + g.n)))


def least_time(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """The chip's least time for the work and which bound sets it."""
    tf, tb = flops / peak["flops"], nbytes / peak["hbm_bytes_per_s"]
    return (tf, "compute") if tf >= tb else (tb, "memory")


def gemm_least_time(m: dict, batch: int, peak: dict) -> float:
    """Least time of one step's ``splitk_gemm`` calls, summed call by call."""
    total = 0.0
    for g in kernel_gemms(m):
        f, nb = gemm_call(m, g, batch)
        total += g.count * least_time(f, nb, peak)[0]
    return total


def kv_bytes_per_token(m: dict) -> float:
    return float(m["n_layers"] * 2 * m["n_kv_heads"] * m["head_dim"] * itemsize(m))


def decode_step(m: dict, batch: int, ctx: int) -> tuple[float, float]:
    """(operations, bytes) of a whole decode step: ``batch`` active
    requests attending over ``ctx`` cached tokens in all (the sum of their
    lengths, the new token included).  Bytes are every weight once, the
    embedding rows of the batch, the KV read and the KV written."""
    b = itemsize(m)
    weights = sum(g.count * g.k * g.n for g in gemms(m))
    flops = 2.0 * batch * weights
    flops += 4.0 * m["n_layers"] * m["n_heads"] * m["head_dim"] * ctx
    nbytes = float(b * weights + b * batch * m["d_model"])
    nbytes += kv_bytes_per_token(m) * (ctx + batch)
    return flops, nbytes
