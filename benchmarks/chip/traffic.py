"""One general traffic generator, driven by a mix file under ``traffic/``.

A mix is a JSON object:

    {"kind": "offline" | "poisson",
     "slots": 32, "max_len": 1024,            # engine sizes the mix needs
     "requests": 512,                         # requests generated
     "block": 16,                             # requests per block (below)
     "prompt": {"buckets": [128, 256, 512], "median": 200, "sigma": 0.6},
     "output": {"median": 192, "sigma": 0.5, "min": 64, "max": 512},
     "rate_rps": 1.0,                         # poisson only
     "sample": {"max_requests": 8, "min_tokens": 500}}

Lengths are lognormal, ``exp(log(median) + sigma * z)`` rounded and
clipped as ``frontend/workload.py`` draws them, but ``z`` is taken at the
fixed quantiles ``(i + 0.5) / block`` of one block instead of drawn at
random.  Every block holds the same lengths (and, for Poisson arrivals,
the same exponential gaps); the seed only permutes them inside each block
and picks the token ids.  So every seed offers the same work in another
order, and seeds differ by order, not by load.  A prompt length rounds up
to the next bucket (above the largest it takes the largest), so the
engine sees only the bucket lengths.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np

KINDS = ("offline", "poisson")


@dataclasses.dataclass(frozen=True)
class RequestSpec:
    rid: int
    due_s: float            # seconds after the window opens (offline: 0)
    prompt_len: int
    max_new_tokens: int


def _quantiles(n: int) -> np.ndarray:
    nd = NormalDist()
    return np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])


def lognormal_lengths(n: int, median: float, sigma: float,
                      lo: int, hi: int) -> np.ndarray:
    """``n`` lognormal lengths at fixed quantiles, rounded and clipped to
    ``[lo, hi]`` (the ``frontend/workload.py`` arithmetic, without the
    random draw)."""
    raw = np.exp(math.log(median) + sigma * _quantiles(n))
    return np.clip(np.round(raw), lo, hi).astype(int)


def to_bucket(length: int, buckets: list[int]) -> int:
    for b in sorted(buckets):
        if length <= b:
            return b
    return max(buckets)


def block_template(mix: dict) -> list[tuple[float, int, int]]:
    """The (gap, prompt, output) triples of one block, before the seed's
    permutation.  Output lengths are paired with prompt lengths by a fixed
    interleave so long prompts do not always get long outputs."""
    n = int(mix["block"])
    p, o = mix["prompt"], mix["output"]
    prompts = [to_bucket(int(x), p["buckets"]) for x in
               lognormal_lengths(n, p["median"], p["sigma"], 1, max(p["buckets"]))]
    outs = lognormal_lengths(n, o["median"], o["sigma"], o["min"], o["max"])
    outs = np.concatenate([outs[0::2], outs[1::2]])     # fixed pairing
    if mix["kind"] == "poisson":
        rate = float(mix["rate_rps"])
        gaps = [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]
    else:
        gaps = [0.0] * n
    return [(gaps[i], prompts[i], int(outs[i])) for i in range(n)]


def generate(mix: dict, seed: int) -> list[RequestSpec]:
    """The mix's requests for ``seed``: every block is the template in the
    seed's order; Poisson due times are the running sum of the gaps, and
    the first request is due when the window opens."""
    if mix["kind"] not in KINDS:
        raise ValueError(f"unknown traffic kind {mix['kind']!r}")
    template = block_template(mix)
    rng = np.random.default_rng([seed, 0x7AFF1C])
    specs: list[RequestSpec] = []
    t = 0.0
    for b in range(-(-int(mix["requests"]) // len(template))):
        for i in rng.permutation(len(template)):
            rid = len(specs)
            if rid >= int(mix["requests"]):
                break
            gap, plen, olen = template[i]
            if rid:
                t += gap
            specs.append(RequestSpec(rid, t, plen, olen))
    return specs


def prompt_tokens(seed: int, rid: int, length: int, vocab: int) -> np.ndarray:
    """Token ids of one prompt, a function of (seed, rid) alone; ids 0-2
    are left out as special tokens."""
    rng = np.random.default_rng([seed, rid, 0x70C3])
    return rng.integers(3, vocab, length).astype(np.int32)


def engine_max_len(mix: dict) -> int:
    """The engine's ``max_len``: stated in the mix, and at least the
    longest prompt plus the longest output, so no request is cut short."""
    need = max(mix["prompt"]["buckets"]) + int(mix["output"]["max"])
    if int(mix["max_len"]) < need:
        raise ValueError(f"max_len {mix['max_len']} < longest prompt + "
                         f"longest output ({need})")
    return int(mix["max_len"])
