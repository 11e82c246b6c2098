"""A fixture architecture: one dense layer, then two mixture-of-experts
layers, all with a latent KV (one K-only head of ``kv_rank + rope_dim``
per token and layer).  It has the layout and the work counts of an
architecture module and no reference equations: it shows that the
harness takes an architecture of two stacks, rank-3 expert leaves and a
routing-dependent byte count as new files alone.

    dense/ln1_w, wq [d, H*hd], wkv_a [d, r+p], wo [H*hd, d], ln2_w,
          wi [d, 2F], wdown [F, d]
    moe/  ln1_w, wq, wkv_a, wo, ln2_w, router [d, E],
          experts_wi [E, d, 2Fe], experts_wdown [E, Fe, d]
    embed [V, d], final_w, lm_head [d, V]

A decode step reads each expert's weights once if any token of the batch
was routed to it.  A program that serves such a model would count those
experts in a field of its stats, here ``experts_touched``, summed over
the layers; ``decode_step`` reads the step's change of it from
``Step.counters``.
"""
from __future__ import annotations

from work import Gemm, itemsize

PROGRAM_KEYS = {"d_model": "d_model", "n_heads": "n_heads", "vocab": "vocab",
                "kv_rank": "kv_lora_rank", "rope_dim": "rope_head_dim",
                "n_experts": "n_experts", "experts_per_token": "top_k",
                "expert_ff": "moe_d_ff", "d_ff": "d_ff", "dtype": "dtype"}
COUNTER = "experts_touched"


def _attention(m: dict) -> dict:
    d, hq = m["d_model"], m["n_heads"] * m["head_dim"]
    return {"ln1_w": (d,), "wq": (d, hq), "wkv_a": (d, m["kv_rank"] + m["rope_dim"]),
            "wo": (hq, d), "ln2_w": (d,)}


def stacks(m: dict) -> dict:
    d, e, fe = m["d_model"], m["n_experts"], m["expert_ff"]
    dense = dict(_attention(m), wi=(d, 2 * m["d_ff"]), wdown=(m["d_ff"], d))
    moe = dict(_attention(m), router=(d, e), experts_wi=(e, d, 2 * fe),
               experts_wdown=(e, fe, d))
    return {"dense": (m["n_dense_layers"], dense), "moe": (m["n_moe_layers"], moe)}


def top_shapes(m: dict) -> dict:
    d, v = m["d_model"], m["vocab"]
    return {"embed": (v, d), "final_w": (d,), "lm_head": (d, v)}


def finish(m: dict, stack, name: str, x):
    return x


def gemms(m: dict) -> list[Gemm]:
    """The GEMMs every decode step runs whatever the routing: attention
    projections of every layer, the dense MLP, the router and the head."""
    d, hq = m["d_model"], m["n_heads"] * m["head_dim"]
    nl = m["n_dense_layers"] + m["n_moe_layers"]
    return [Gemm("wq", d, hq, nl), Gemm("wkv_a", d, m["kv_rank"] + m["rope_dim"], nl),
            Gemm("wo", hq, d, nl), Gemm("wi", d, 2 * m["d_ff"], m["n_dense_layers"]),
            Gemm("wdown", m["d_ff"], d, m["n_dense_layers"]),
            Gemm("router", d, m["n_experts"], m["n_moe_layers"]),
            Gemm("lm_head", d, m["vocab"], 1)]


def kernel_gemms(m: dict) -> list[Gemm]:
    """The tiered ``splitk_gemm`` calls: all but the router."""
    return [g for g in gemms(m) if g.name != "router"]


def kv_bytes_per_token(m: dict) -> float:
    nl = m["n_dense_layers"] + m["n_moe_layers"]
    return float(nl * (m["kv_rank"] + m["rope_dim"]) * itemsize(m))


def expert_weights(m: dict) -> int:
    return 3 * m["d_model"] * m["expert_ff"]


def decode_step(m: dict, step) -> tuple[float, float]:
    """(operations, bytes) of a decode step: the fixed GEMMs, each routed
    token's experts, attention over the latent KV (scores and values both
    over ``kv_rank`` plus the RoPE part), and the weights of the experts
    the step touched (``step.counters``)."""
    batch, ctx, b = step.decode_tokens, step.ctx, itemsize(m)
    fixed = sum(g.count * g.k * g.n for g in gemms(m))
    routed = batch * m["experts_per_token"] * m["n_moe_layers"] * expert_weights(m)
    nl = m["n_dense_layers"] + m["n_moe_layers"]
    flops = 2.0 * (batch * fixed + routed)
    flops += 2.0 * nl * m["n_heads"] * (2 * m["kv_rank"] + m["rope_dim"]) * ctx
    touched = step.counters[COUNTER]
    nbytes = float(b * (fixed + touched * expert_weights(m)) + b * batch * m["d_model"])
    nbytes += kv_bytes_per_token(m) * (ctx + batch)
    return flops, nbytes
