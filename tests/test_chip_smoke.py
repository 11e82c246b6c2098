"""chip_smoke.py's phases and checks, exercised on the CPU at smoke size.

The script itself refuses to run without a TPU; these tests drive its
serving and logits-check phases through the interpret-mode kernels, and
check that the logits check fails when a kernel writes garbage.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import pytest

import repro.configs as C
from repro.kernels import ops
from repro.launch import serve
from repro.models import model as M
from repro.serving.engine import ServingEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE_ARGS = ["--arch", "starcoder2_3b", "--smoke", "--offload-ratio", "0.5",
              "--max-batch", "3", "--max-len", "32", "--page-size", "4"]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def built():
    return serve.build_engine(serve.parse_args(SMOKE_ARGS))


@pytest.fixture(scope="module")
def wide():
    """StarCoder2's shape at widths where every tiered split is a multiple
    of the kernels' 128-wide tiles, so every operand takes the kernel."""
    cfg = dataclasses.replace(C.get_smoke("starcoder2_3b"), d_model=256,
                              d_ff=512, vocab=512)
    engine = ServingEngine(cfg, M.init_params(cfg, jax.random.PRNGKey(0)),
                           max_batch=3, max_len=32, global_offload_ratio=0.5,
                           page_size=4)
    return cfg, engine


def _prompts(chip_smoke, cfg):
    return chip_smoke.make_prompts(cfg.vocab, n=3, lo=9, hi=20)


def test_refuses_to_run_without_a_tpu(chip_smoke):
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert "no TPU" in str(exc.value.code)


def test_prompts_are_seeded_and_in_range(chip_smoke):
    a = chip_smoke.make_prompts(1000)
    b = chip_smoke.make_prompts(1000)
    assert len(a) == chip_smoke.N_REQUESTS
    assert all((x == y).all() for x, y in zip(a, b, strict=True))
    lo, hi = chip_smoke.PROMPT_LEN
    assert all(lo <= len(p) <= hi for p in a)


def test_serves_every_request_in_full(chip_smoke, built):
    cfg, engine = built
    _, stats, tokens = chip_smoke.serve_requests(engine, _prompts(chip_smoke, cfg), 4)
    chip_smoke.check_served(stats, tokens, 4)
    with pytest.raises(RuntimeError, match="stopped before"):
        chip_smoke.check_served(stats, tokens, 5)


def test_logits_check_passes_on_the_kernel_path(chip_smoke, wide):
    cfg, engine = wide
    res = chip_smoke.decode_logits_check(cfg, engine, _prompts(chip_smoke, cfg))
    chip_smoke.assert_logits_close(res)
    assert res["remote_kv_pages"] > 0          # both KV tiers were read
    assert res["dispatch"][("gemm", "kernel")] > 0
    assert res["dispatch"][("paged_attn", "kernel")] == cfg.n_layers
    assert not any(path == "jnp" for _, path in res["dispatch"])
    assert all(r is None for r in engine.active)   # slots handed back


def _zero_attention(orig):
    return lambda q, *args, **kw: jnp.zeros_like(q)


def _drop_remote_gemm_tier(orig):
    def gemm(x, w_local, w_remote, **kw):
        return orig(x, w_local, w_remote, **kw).at[:, w_local.shape[1]:].set(0)
    return gemm


@pytest.mark.parametrize("name,mutate", [
    ("paged_splitk_flashattn", _zero_attention),
    ("splitk_gemm", _drop_remote_gemm_tier),
])
def test_logits_check_fails_on_a_garbage_kernel(chip_smoke, wide, monkeypatch,
                                                name, mutate):
    cfg, engine = wide
    monkeypatch.setattr(ops, name, mutate(getattr(ops, name)))
    res = chip_smoke.decode_logits_check(cfg, engine, _prompts(chip_smoke, cfg))
    with pytest.raises(RuntimeError, match="differ from the reference"):
        chip_smoke.assert_logits_close(res)
