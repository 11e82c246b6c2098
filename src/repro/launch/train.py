"""Fault-tolerant training driver.

Runs a real training loop on whatever devices exist (CPU dev mesh in CI,
the production mesh on a pod): synthetic pipeline → jitted train_step →
async checkpointing → restart-on-failure → straggler monitoring.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch starcoder2_3b --smoke \
      --steps 50 --batch 8 --seq 128
  PYTHONPATH=src python -m repro.launch.train --arch qwen3_32b --smoke \
      --steps 20 --fail-at 7 --restore   # exercises restart path
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

import repro.configs as C
from repro.checkpoint.manager import CheckpointManager
from repro.configs.base import ShapeConfig
from repro.data.pipeline import SyntheticPipeline
from repro.distributed.fault import FaultInjector, RestartLoop, StragglerDetector
from repro.launch import compile_cache, sharding, steps as S
from repro.launch.mesh import make_dev_mesh
from repro.models import model as M
from repro.optim import adamw


def main(argv: list[str] | None = None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="starcoder2_3b")
    ap.add_argument("--smoke", action="store_true", help="use the reduced config")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--restore", action="store_true")
    ap.add_argument("--fail-at", type=int, default=-1,
                    help="inject a failure at this step (tests restart)")
    ap.add_argument("--dtype", default="float32")
    ap.add_argument("--log-every", type=int, default=5)
    args = ap.parse_args(argv)
    compile_cache.configure()

    cfg = C.get_smoke(args.arch) if args.smoke else C.get(args.arch)
    if not args.ckpt_dir:
        args.ckpt_dir = os.path.join(
            tempfile.gettempdir(),
            f"repro_ckpt_{args.arch}{'_smoke' if args.smoke else ''}")
    shape = ShapeConfig("cli", args.seq, args.batch, "train")
    dtype = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    mesh = make_dev_mesh(len(jax.devices()), 1)

    pipe = SyntheticPipeline(cfg, shape)
    params = M.init_params(cfg, jax.random.PRNGKey(0), dtype=dtype)
    opt_cfg = adamw.AdamWConfig(lr=args.lr, warmup_steps=5,
                                total_steps=max(10, args.steps))
    opt_state = adamw.init(params)

    p_spec = sharding.named(mesh, sharding.param_specs(
        cfg, jax.eval_shape(lambda: params), mesh))
    train_step = jax.jit(
        S.make_train_step(cfg, opt_cfg, num_microbatches=args.microbatches),
        donate_argnums=(0, 1))

    ckpt = CheckpointManager(args.ckpt_dir, keep_last=2)
    injector = FaultInjector({args.fail_at} if args.fail_at >= 0 else None)
    straggler = StragglerDetector()
    losses: list[float] = []
    state = {"params": params, "opt": opt_state}

    def restore_latest() -> int:
        nonlocal state
        latest = ckpt.latest_step()
        if latest is None:
            return 0
        tree, extra = ckpt.restore(latest, like={"params": state["params"],
                                                 "opt": state["opt"]})
        state = tree
        pipe.restore(extra.get("pipeline", {"step": latest}))
        print(f"[restore] resumed from step {latest}")
        return latest

    start = restore_latest() if args.restore else 0

    def body(start_step: int) -> int:
        step = start_step
        while step < args.steps:
            injector.maybe_fail(step)
            batch_np = pipe.batch_at(step)
            batch = {k: jnp.asarray(v) for k, v in batch_np.items()}
            t0 = time.time()
            loss, state["params"], state["opt"], gnorm = train_step(
                state["params"], state["opt"], batch)
            loss = float(loss)
            dt = time.time() - t0
            if straggler.observe(dt):
                print(f"[straggler] step {step} took {dt:.3f}s")
            losses.append(loss)
            if step % args.log_every == 0:
                tok_s = args.batch * args.seq / max(dt, 1e-9)
                print(f"step {step:5d} loss {loss:8.4f} gnorm {float(gnorm):7.3f} "
                      f"{dt*1e3:7.1f} ms  {tok_s/1e3:8.1f} ktok/s")
            step += 1
            if step % args.ckpt_every == 0 or step == args.steps:
                ckpt.save_async(step, {"params": state["params"], "opt": state["opt"]},
                                extra={"pipeline": pipe.snapshot()})
        ckpt.wait()
        return step

    loop = RestartLoop(max_restarts=3)
    final = loop.run(body, start, on_restart=restore_latest)
    assert np.isfinite(losses).all(), "non-finite loss"
    print(f"done: {final} steps, restarts={loop.restarts}, "
          f"final loss {losses[-1]:.4f} (first {losses[0]:.4f})")
    return {"losses": losses, "restarts": loop.restarts, "final_step": final}


if __name__ == "__main__":
    main()
