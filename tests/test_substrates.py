"""Substrate tests: checkpointing (async/atomic/elastic/integrity), fault
tolerance, data pipeline determinism, optimizer, compressed collectives."""
from __future__ import annotations

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.checkpoint.manager import CheckpointManager
from repro.configs.base import ShapeConfig
from repro.data.pipeline import SyntheticPipeline
from repro.distributed import collectives, fault
from repro.optim import adamw


# ---------------------------------------------------------------------------
# checkpoint
# ---------------------------------------------------------------------------
def _tree(key=0):
    k = jax.random.PRNGKey(key)
    return {"a": jax.random.normal(k, (8, 4)),
            "nested": {"b": jnp.arange(6, dtype=jnp.int32),
                       "c": jnp.float32(3.5)}}


def test_checkpoint_roundtrip(tmp_path):
    mgr = CheckpointManager(tmp_path)
    t = _tree()
    mgr.save(5, t, extra={"pipeline": {"step": 5}})
    out, extra = mgr.restore(5, like=t)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                            np.asarray(b)), t, out)
    assert extra["pipeline"]["step"] == 5


def test_checkpoint_async_and_gc(tmp_path):
    mgr = CheckpointManager(tmp_path, keep_last=2)
    for s in (1, 2, 3, 4):
        mgr.save_async(s, _tree(s))
    mgr.wait()
    assert mgr.all_steps() == [3, 4]


def test_checkpoint_integrity_detection(tmp_path):
    mgr = CheckpointManager(tmp_path)
    t = _tree()
    res = mgr.save(1, t)
    # corrupt one leaf
    victim = next(res.path.glob("leaf_*.npy"))
    raw = bytearray(victim.read_bytes())
    raw[-1] ^= 0xFF
    victim.write_bytes(bytes(raw))
    with pytest.raises(IOError, match="corruption"):
        mgr.restore(1, like=t)


def test_checkpoint_elastic_resharding(tmp_path):
    """Save from one sharding, restore onto a different mesh/sharding."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    mgr = CheckpointManager(tmp_path)
    t = {"w": jnp.arange(16.0).reshape(4, 4)}
    mgr.save(1, t)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    shard = {"w": NamedSharding(mesh, P("data", None))}
    out, _ = mgr.restore(1, like=t, shardings=shard)
    assert out["w"].sharding == shard["w"]
    np.testing.assert_array_equal(np.asarray(out["w"]), np.asarray(t["w"]))


def test_checkpoint_atomic_publish(tmp_path):
    mgr = CheckpointManager(tmp_path)
    mgr.save(7, _tree())
    assert not list(Path(tmp_path).glob(".tmp_*"))
    manifest = json.loads((Path(tmp_path) / "step_7" / "manifest.json").read_text())
    assert manifest["step"] == 7


# ---------------------------------------------------------------------------
# fault tolerance
# ---------------------------------------------------------------------------
def test_restart_loop_recovers():
    calls = {"n": 0}
    injector = fault.FaultInjector({3})

    def body(start):
        for step in range(start, 6):
            injector.maybe_fail(step)
            calls["n"] += 1
        return 6

    loop = fault.RestartLoop(max_restarts=2)
    final = loop.run(body, 0, on_restart=lambda: 2)
    assert final == 6 and loop.restarts == 1
    assert calls["n"] == 3 + 4          # 0,1,2 then 2,3,4,5


def test_restart_loop_bounded():
    loop = fault.RestartLoop(max_restarts=1)

    def body(start):
        raise RuntimeError("always fails")

    with pytest.raises(RuntimeError, match="exceeded"):
        loop.run(body, 0)


def test_straggler_detector():
    det = fault.StragglerDetector(threshold=2.0)
    for _ in range(10):
        det.observe(0.1)
    assert det.observe(0.5) and det.flagged == 1
    assert not det.observe(0.11)


def test_elastic_plan():
    p = fault.ElasticPlan.for_devices(512 - 32, model_axis=16)   # lost 2 hosts
    assert p.model == 16 and p.data == 16
    p2 = fault.ElasticPlan.for_devices(200, model_axis=16)
    assert p2.data == 8


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------
def test_pipeline_determinism_and_restore():
    cfg = C.get_smoke("llama2_7b")
    shape = ShapeConfig("t", 32, 4, "train")
    p1 = SyntheticPipeline(cfg, shape, seed=7)
    p2 = SyntheticPipeline(cfg, shape, seed=7)
    b1 = p1.batch_at(11)
    b2 = p2.batch_at(11)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    # snapshot/restore keeps the stream position
    it = iter(p1)
    next(it), next(it)
    snap = p1.snapshot()
    p3 = SyntheticPipeline(cfg, shape, seed=0)
    p3.restore(snap)
    np.testing.assert_array_equal(p3.batch_at(p3.state.step)["tokens"],
                                  p1.batch_at(p1.state.step)["tokens"])


def test_pipeline_family_shapes():
    shape = ShapeConfig("t", 16, 2, "train")
    enc = SyntheticPipeline(C.get_smoke("hubert_xlarge"), shape).batch_at(0)
    assert enc["frames"].shape == (2, 16, 512) and enc["labels"].shape == (2, 16)
    vlm = SyntheticPipeline(C.get_smoke("llava_next_34b"), shape).batch_at(0)
    assert vlm["patches"].shape == (2, 8, 1152)
    assert vlm["tokens"].shape == (2, 8) and vlm["labels"].shape == (2, 16)


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------
def test_adamw_decreases_quadratic():
    cfg = adamw.AdamWConfig(lr=0.1, warmup_steps=0, weight_decay=0.0,
                            total_steps=100)
    params = {"w": jnp.asarray([5.0, -3.0])}
    state = adamw.init(params)
    for _ in range(60):
        grads = {"w": 2 * params["w"]}
        params, state, _ = adamw.update(params, grads, state, cfg)
    assert float(jnp.max(jnp.abs(params["w"]))) < 0.5


def test_grad_clip():
    grads = {"g": jnp.full((4,), 100.0)}
    clipped, norm = adamw.clip_by_global_norm(grads, 1.0)
    assert float(norm) == pytest.approx(200.0)
    assert float(jnp.linalg.norm(clipped["g"])) == pytest.approx(1.0, rel=1e-3)


def test_schedule_warmup_cosine():
    cfg = adamw.AdamWConfig(lr=1.0, warmup_steps=10, total_steps=100,
                            min_lr_frac=0.1)
    assert float(adamw.schedule(cfg, jnp.int32(0))) == pytest.approx(0.1)
    assert float(adamw.schedule(cfg, jnp.int32(9))) == pytest.approx(1.0)
    assert float(adamw.schedule(cfg, jnp.int32(99))) == pytest.approx(0.1, rel=0.05)


# ---------------------------------------------------------------------------
# compressed collectives
# ---------------------------------------------------------------------------
def test_quantize_roundtrip_error():
    x = jax.random.normal(jax.random.PRNGKey(0), (128,))
    q, scale = collectives.quantize_int8(x)
    err = np.abs(np.asarray(collectives.dequantize_int8(q, scale) - x))
    assert err.max() <= float(scale) * 0.5 + 1e-6


def test_error_feedback_reduces_bias():
    """With EF, the *accumulated* compressed signal tracks the true sum."""
    key = jax.random.PRNGKey(1)
    g_true = jax.random.normal(key, (64,)) * 0.01
    residual = collectives.ErrorFeedback.init({"g": g_true})
    acc = jnp.zeros((64,))
    for _ in range(50):
        out, residual = collectives.ErrorFeedback.apply({"g": g_true}, residual)
        acc = acc + out["g"]
    rel = float(jnp.linalg.norm(acc - 50 * g_true) / jnp.linalg.norm(50 * g_true))
    assert rel < 0.05


def test_compressed_psum_single_device():
    from jax.sharding import PartitionSpec as P
    mesh = jax.make_mesh((1,), ("data",))
    x = jax.random.normal(jax.random.PRNGKey(2), (32,))
    out = jax.shard_map(lambda v: collectives.compressed_psum(v, "data"),
                    mesh=mesh, in_specs=P(None), out_specs=P(None),
                    check_vma=False)(x)
    assert float(jnp.max(jnp.abs(out - x))) < 0.05 * float(jnp.max(jnp.abs(x)))
