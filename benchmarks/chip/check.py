"""The comparison that decides ``correct``.

After the window, a sample of the requests the run finished, drawn from
the seed with the longest always in it, goes through the float32
reference: each prompt followed by its served tokens, in one forward pass.
At the position that produced each served token, the gap is how far the
reference's logit of that token lies below the reference's best logit.
The run's number is the widest gap.  A served token is greedy (argmax of
the program's logits), so a sound run only strays where the reference's
top logits nearly tie; a fault in prefill, the paged cache, the decode
step or the head puts a token far below the best.

The control puts the reference itself, computed in a lower precision
(``references/*.CONTROLS``), in the program's place: at each position,
the gap of the token the lower precision puts first becomes the number
judged, and the program's own gap is kept beside it.
"""
from __future__ import annotations

import numpy as np


def sample(finished: list, seed: int, max_requests: int, min_tokens: int) -> list:
    """The longest finished request (most served tokens, then lowest id),
    then others in the seed's order until ``max_requests`` or
    ``min_tokens`` served tokens."""
    if not finished:
        return []
    order = sorted(finished, key=lambda f: (-len(f[2]), f[0]))
    rest = order[1:]
    rng = np.random.default_rng([seed, 0xC4EC])
    picked, tokens = [order[0]], len(order[0][2])
    for i in rng.permutation(len(rest)):
        if len(picked) >= max_requests or tokens >= min_tokens:
            break
        picked.append(rest[i])
        tokens += len(rest[i][2])
    return picked


def gaps(ref: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """Per row: best reference logit minus the reference logit of ``tokens``."""
    return ref.max(axis=-1) - ref[np.arange(len(tokens)), tokens]


def run(man, config: dict, seed: int, finished: list, mix: dict,
        control: str = "") -> dict:
    import jax

    import weights

    m = config["model"]
    picked = sample(finished, seed, int(mix["sample"]["max_requests"]),
                    int(mix["sample"]["min_tokens"]))
    out = {"requests": len(picked), "tokens": 0, "gap": float("inf"),
           "longest": max((len(p[2]) for p in picked), default=0)}
    if not picked:
        return out
    arch = man.reference(config)
    key = weights.base_key(seed)
    make_layer = jax.jit(lambda stack, i: weights.make_layer(arch, m, key, stack, i),
                         static_argnums=0)
    make_top = jax.jit(lambda name: weights.make_top(arch, m, key, name),
                       static_argnums=0)
    ref = arch.Reference(m, make_layer, make_top)
    seqs = [np.concatenate([p, np.asarray(t[:-1], np.int32)]) for _, p, t in picked]
    starts = [len(p) - 1 for _, p, _ in picked]
    counts = [len(t) for _, _, t in picked]
    served = np.concatenate([np.asarray(t, np.int64) for _, _, t in picked])
    logits, ctrl = ref.logits(seqs, starts, counts,
                              controls=(control,) if control else ())
    ref_np = np.asarray(logits, np.float32)
    out["tokens"] = int(len(served))
    out["gap"] = float(gaps(ref_np, served).max())
    if control:
        out["program_gap"] = out["gap"]
        firsts = np.asarray(ctrl[control], np.float32).argmax(axis=-1)
        out["gap"] = float(gaps(ref_np, firsts).max())
    return out
