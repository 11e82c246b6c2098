"""Operations and bytes at the published shapes, counted by the dense GQA
architecture module."""
import json
from pathlib import Path

import pytest

import manifest
import record
import work

BENCH = Path(work.__file__).resolve().parent
PEAK = json.loads((BENCH / "peaks.json").read_text())["TPU v5 lite"]


def model(name):
    config = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    return config["model"], manifest.Manifest({}, BENCH.parents[1]).reference(config)


def step(batch, ctx, **counters):
    return record.Step(0, 0.0, 1.0, 0.0, batch, 0, ctx, counters)


def test_starcoder2_counts_published_heads():
    m, arch = model("starcoder2-3b")
    per_layer = 3072 * 3072 + 3072 * 512 + 3072 * 3072 + 3072 * 12288 + 12288 * 3072
    weights = sum(g.count * g.k * g.n for g in arch.gemms(m))
    assert weights == 30 * per_layer + 3072 * 49152          # 24 heads, not 32
    flops, nbytes = arch.decode_step(m, step(batch=8, ctx=8 * 500))
    assert nbytes == pytest.approx(2 * weights + 2 * 8 * 3072
                                   + 30 * 2 * 2 * 128 * 2 * (4000 + 8))
    assert flops == pytest.approx(2 * 8 * weights + 4 * 30 * 24 * 128 * 4000)
    assert work.least_time(flops, nbytes, PEAK)[1] == "memory"
    # the tied head reads the embedding table, which is not a splitk_gemm call
    assert sum(g.count for g in arch.kernel_gemms(m)) == 150


def test_qwen_stage_counts_swiglu_and_head():
    m, arch = model("qwen2.5-14b.pp8")
    per_layer = 5120 * 5120 + 5120 * 2048 + 5120 * 5120 + 5120 * 2 * 13824 + 13824 * 5120
    assert sum(g.count * g.k * g.n for g in arch.gemms(m)) == 6 * per_layer + 5120 * 152064
    assert arch.kernel_gemms(m) == arch.gemms(m)           # untied head: 31 calls
    assert arch.kv_bytes_per_token(m) == 6 * 2 * 8 * 128 * 2


def test_gemm_calls_are_memory_bound_at_decode_batches():
    m, arch = model("starcoder2-3b")
    for g in arch.gemms(m):
        f, b = work.gemm_call(m, g, 32)
        assert work.least_time(f, b, PEAK)[1] == "memory"
    # one step's splitk_gemm calls read every layer weight once: about 5.76 GB
    # at 819 GB/s (the tied head's 0.30 GB is not among them)
    assert work.gemm_least_time(arch, m, 8, PEAK) == pytest.approx(5.76e9 / 819e9, rel=0.02)
