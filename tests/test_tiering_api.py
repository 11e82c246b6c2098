"""Unified tiering API: operand registry, TieringPlan.partition, operand
dispatch, and the serving-engine behaviours that ride on them (EOS-at-
prefill admission, non-materializing tiered prefill, TTFT accounting)."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.core import engine as offload_engine
from repro.core import tiering
from repro.core.ebmodel import WorkloadSpec
from repro.core.hardware import TPU_V5E
from repro.core.tiering import TieredArray
from repro.models import model as M
from repro.models.registry import operand_registry, registered_ops, resolve
from repro.serving import tiered_decode as TD
from repro.serving.engine import Request, ServingEngine

KEY = jax.random.PRNGKey(0)

# One arch per family exercised by the unified API (deepseek = MLA + MoE).
FAMILY_ARCHS = ["llama2_7b", "qwen3_moe_30b_a3b", "deepseek_v2_236b",
                "mamba2_370m", "zamba2_2p7b"]


def _tiered_leaves(tree):
    return [leaf for leaf in jax.tree.leaves(
        tree, is_leaf=lambda x: isinstance(x, TieredArray))
        if isinstance(leaf, TieredArray)]


# ---------------------------------------------------------------------------
# Registry completeness
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("arch", FAMILY_ARCHS)
def test_registry_resolves_every_planner_op(arch):
    """Every weight-bearing planner op maps to >= 1 real param leaf, and
    every registered path resolves with a usable split axis."""
    cfg = C.get_smoke(arch)
    params = M.init_params(cfg, KEY)
    reg = operand_registry(cfg)
    for od in reg:
        leaf = resolve(params, od.path)
        assert hasattr(leaf, "shape") and leaf.ndim >= 2, od.path_str
        assert -leaf.ndim <= od.axis < 0, f"{od.path_str}: axis {od.axis}"

    wl = WorkloadSpec(batch=2, seq_len=16, phase="decode")
    ops = offload_engine.enumerate_ops(cfg, wl)
    weight_ops = {op.name for op in ops if op.kind == "linear"}
    missing = weight_ops - registered_ops(reg)
    assert not missing, f"planner ops with no registered operand: {missing}"


def test_registry_rejects_bad_path():
    cfg = C.get_smoke("llama2_7b")
    params = M.init_params(cfg, KEY)
    with pytest.raises(KeyError, match="does not resolve"):
        resolve(params, ("layers", "nope"))


# ---------------------------------------------------------------------------
# TieringPlan.partition: one plan -> partition path, per-op ratios
# ---------------------------------------------------------------------------
def test_partition_applies_each_ops_own_ratio():
    """Regression for the wkv<-wq aliasing bug: with distinct per-op ratios,
    every registered leaf realizes the ratio of *its* op."""
    cfg = C.get_smoke("llama2_7b")
    params = M.init_params(cfg, KEY)
    plan = offload_engine.plan(
        cfg, WorkloadSpec(batch=2, seq_len=32, phase="decode"),
        TPU_V5E, global_ratio=0.5)
    ratios = {"attn_qkv": 0.75, "attn_out": 0.25, "mlp_up": 0.5,
              "mlp_down": 0.125, "lm_head": 0.375, "attention": 0.5}
    plan = dataclasses.replace(plan, op_ratios=ratios)
    tiered = plan.partition(params, align=4)
    checked = 0
    for od in plan.registry:
        leaf = resolve(tiered, od.path)
        want = ratios[od.op]
        assert isinstance(leaf, TieredArray), od.path_str
        dim = leaf.shape[od.axis]
        assert abs(leaf.ratio - want) <= 4.0 / dim, (
            f"{od.path_str}: achieved {leaf.ratio} vs op ratio {want}")
        checked += 1
    assert checked >= 6
    # distinct ops actually realized distinct splits
    assert resolve(tiered, ("layers", "wq")).ratio != \
        resolve(tiered, ("layers", "wo")).ratio


def test_partition_dense_params_shim_no_aliasing():
    """The deprecation shim resolves each leaf's own ratio: a bare 'wq'
    entry no longer leaks onto wkv."""
    cfg = C.get_smoke("llama2_7b")
    params = M.init_params(cfg, KEY)
    with pytest.warns(DeprecationWarning):
        out = TD.partition_dense_params(params, {"wq": 0.5}, align=8)
    assert isinstance(out["layers"]["wq"], TieredArray)
    assert not isinstance(out["layers"]["wkv"], TieredArray)
    with pytest.warns(DeprecationWarning):
        out = TD.partition_dense_params(
            params, {"layers/wkv": 0.5, "layers/wq": 0.25}, align=8)
    assert out["layers"]["wkv"].ratio == pytest.approx(0.5, abs=0.2)
    assert out["layers"]["wq"].ratio == pytest.approx(0.25, abs=0.2)


def test_partition_moe_expert_stack_axis():
    """MoE expert stacks split whole experts (registry axis -3), and both
    expert operands split at the same boundary."""
    cfg = C.get_smoke("qwen3_moe_30b_a3b")
    params = M.init_params(cfg, KEY)
    plan = offload_engine.plan(
        cfg, WorkloadSpec(batch=2, seq_len=32, phase="decode"),
        TPU_V5E, global_ratio=0.5)
    plan = dataclasses.replace(
        plan, op_ratios={**plan.op_ratios, "moe_experts": 0.5})
    tiered = plan.partition(params, align=128)   # expert align override: 1
    wi = tiered["layers"]["experts_wi"]
    wdown = tiered["layers"]["experts_wdown"]
    assert isinstance(wi, TieredArray) and wi.axis == -3
    assert wi.local.shape[-3] + wi.remote.shape[-3] == cfg.n_experts
    assert wi.local.shape[-3] == wdown.local.shape[-3] == cfg.n_experts // 2


# ---------------------------------------------------------------------------
# TieredArray pytree round-trip through jit / scan
# ---------------------------------------------------------------------------
def test_tiered_array_roundtrip_jit_scan():
    stacked = jnp.arange(4 * 8 * 6, dtype=jnp.float32).reshape(4, 8, 6)
    t = tiering.partition(stacked, 0.5, axis=-1, align=1)

    # jit: structure (incl. the negative split axis) survives
    doubled = jax.jit(lambda a: jax.tree.map(lambda b: 2 * b, a))(t)
    assert isinstance(doubled, TieredArray) and doubled.axis == t.axis
    np.testing.assert_array_equal(
        np.asarray(doubled.materialize()), 2 * np.asarray(stacked))

    # scan over the stacked leading axis: per-layer slices are valid
    # TieredArrays (negative axis is stable under unstacking)
    def body(carry, lp):
        assert isinstance(lp, TieredArray) and lp.local.ndim == 2
        return carry + tiering.matmul(jnp.ones((1, 8)), lp).sum(), lp.ratio
    total, ratios = jax.lax.scan(body, 0.0, t)
    assert float(total) == pytest.approx(float(stacked.sum()))
    np.testing.assert_allclose(np.asarray(ratios), 0.5)


def test_tiered_matmul_dispatch_exact():
    x = jax.random.normal(KEY, (3, 16))
    w = jax.random.normal(jax.random.PRNGKey(1), (16, 24))
    t = tiering.partition(w, 0.5, axis=-1, align=4)
    y = np.asarray(tiering.matmul(x, t))
    # Each tier's columns are exactly that tier's own matmul.  (A whole-width
    # matmul need not round like two half-width ones, so the concatenation
    # is compared per tier, not with x @ w.)
    n_loc = t.local.shape[-1]
    np.testing.assert_array_equal(y[:, :n_loc], np.asarray(x @ w[:, :n_loc]))
    np.testing.assert_array_equal(y[:, n_loc:], np.asarray(x @ w[:, n_loc:]))
    # plain weights pass straight through
    np.testing.assert_array_equal(np.asarray(tiering.matmul(x, w)),
                                  np.asarray(x @ w))
    with pytest.raises(ValueError, match="column-split"):
        tiering.matmul(x, tiering.partition(w, 0.5, axis=0, align=4))


# ---------------------------------------------------------------------------
# Serving behaviours riding on the unified API
# ---------------------------------------------------------------------------
def test_tiered_prefill_never_materializes(monkeypatch):
    """Acceptance: tiered prefill runs over the tiered params (operand
    dispatch) and never concatenates remote partitions back into HBM."""
    cfg = C.get_smoke("llama2_7b")
    params = M.init_params(cfg, KEY)
    eng = ServingEngine(cfg, params, max_batch=2, max_len=32,
                        global_offload_ratio=0.5, page_size=4)
    assert eng.tiered and len(_tiered_leaves(eng.params)) >= 4

    def boom(self):
        raise AssertionError("TieredArray.materialize called during serving")
    monkeypatch.setattr(TieredArray, "materialize", boom)
    rng = np.random.default_rng(3)
    eng.submit(Request(rid=0, prompt=rng.integers(3, cfg.vocab, 7).astype(np.int32),
                       max_new_tokens=3))
    stats = eng.run()
    assert stats.served == 1


def test_params_for_prefill_shim_returns_tiered_tree():
    cfg = C.get_smoke("llama2_7b")
    params = M.init_params(cfg, KEY)
    eng = ServingEngine(cfg, params, max_batch=2, max_len=32,
                        global_offload_ratio=0.5, page_size=4)
    with pytest.warns(DeprecationWarning):
        p = eng.params_for_prefill()
    assert p is eng.params and len(_tiered_leaves(p)) >= 4


def test_admit_eos_at_prefill_finishes_without_decode():
    """Satellite: a request whose prefill-produced first token is EOS must
    finish at admission — no slot occupancy, no decode steps."""
    cfg = C.get_smoke("llama2_7b")
    params = M.init_params(cfg, KEY)
    rng = np.random.default_rng(5)
    prompt = rng.integers(3, cfg.vocab, 6).astype(np.int32)
    logits, _ = M.prefill(cfg, params, {"tokens": jnp.asarray(prompt)[None, :]},
                          max_len=32)
    first = int(jnp.argmax(logits[0, -1]))

    eng = ServingEngine(cfg, params, max_batch=2, max_len=32,
                        global_offload_ratio=0.5, page_size=4)
    eng.submit(Request(rid=0, prompt=prompt, max_new_tokens=8, eos_id=first))
    stats = eng.run()
    assert stats.served == 1
    assert stats.decode_steps == 0, "EOS-at-prefill burned decode steps"
    assert eng.pcache.local_in_use == 0 and eng.pcache.remote_in_use == 0


def test_ttft_accounting():
    cfg = C.get_smoke("llama2_7b")
    params = M.init_params(cfg, KEY)
    eng = ServingEngine(cfg, params, max_batch=2, max_len=32,
                        global_offload_ratio=0.0, page_size=4)
    rng = np.random.default_rng(6)
    for rid in range(3):
        eng.submit(Request(rid=rid,
                           prompt=rng.integers(3, cfg.vocab, 5).astype(np.int32),
                           max_new_tokens=2))
    stats = eng.run()
    assert stats.served == 3 and len(stats.ttfts) == 3
    assert 0.0 < stats.ttft_p50 <= stats.ttft_p95
