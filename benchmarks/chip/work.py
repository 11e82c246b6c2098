"""Operations and bytes of decode work, at the published shapes.

What a step does is the architecture module's to count
(``references/<reference>.py``: ``gemms``, ``kernel_gemms``,
``kv_bytes_per_token``, ``decode_step``), from the config file's ``model``
block, whatever the program stores: published head counts (not
zero-padded ones), the batch as the active requests (not the slots), the
bytes of the served dtype.  Biases, norm gains and activations are left
out of the bytes; at batch 8 to 32 they are under 0.1% of a step's weight
bytes.  This module holds what every architecture shares: a GEMM call's
work and the chip's least time for it.
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


def gemm_call(m: dict, g: Gemm, batch: int) -> tuple[float, float]:
    """(operations, bytes) of one call at ``batch`` rows."""
    b = itemsize(m)
    return 2.0 * batch * g.k * g.n, float(b * (g.k * g.n + batch * (g.k + g.n)))


def least_time(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """The chip's least time for the work and which bound sets it."""
    tf, tb = flops / peak["flops"], nbytes / peak["hbm_bytes_per_s"]
    return (tf, "compute") if tf >= tb else (tb, "memory")


def gemm_least_time(arch, m: dict, batch: int, peak: dict) -> float:
    """Least time of one step's ``splitk_gemm`` calls (the architecture's
    ``kernel_gemms``), summed call by call."""
    total = 0.0
    for g in arch.kernel_gemms(m):
        f, nb = gemm_call(m, g, batch)
        total += g.count * least_time(f, nb, peak)[0]
    return total
