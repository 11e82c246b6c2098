"""Bring-up check of the serving path on a TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the mesh path against one chip

One chip: builds StarCoder2-3B at its published widths in bf16 (random
weights from a seed) through the code ``python -m repro.launch.serve``
uses, at offload ratio 0.5, 8 slots, 2048-token contexts and 16-token
pages; serves 8 seeded requests of 128-512 prompt tokens and 32 new tokens
each on the wall clock; then compares one decode step's logits from the
compiled tiered kernel path with the reference path on the same weights.

``--chips 4`` serves the same requests on one chip and on a 4-chip serving
mesh (remote partitions sharded 1/4 per chip, rebuilt each step by the
fetch-once broadcast) in one process, and checks that the tokens match.

Every phase fails the run on error; nothing falls back to the CPU.  The
last line of standard output is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "starcoder2_3b"
SERVE_ARGS = ["--arch", ARCH, "--offload-ratio", "0.5", "--max-batch", "8",
              "--max-len", "2048", "--page-size", "16"]
N_REQUESTS = 8
PROMPT_LEN = (128, 512)
NEW_TOKENS = 32
MESH_REQUESTS = 4
SEED = 0
# Largest admissible max|logits_kernel - logits_other| as a fraction of the
# reference logits' RMS, where the other logits are `M.decode_step`'s and
# those of the same compiled step with every operand on the jnp path.  All
# use the same bf16 weights, but bf16 keeps 8 significant bits: the
# reference rounds attention scores and probabilities to bf16 where the
# kernel keeps them in f32, and the paths round their sums and residual
# adds in different orders through 30 layers.  On a CPU at 30 layers and
# d_model 512, the bf16 reference is 0.054 of the RMS from an f32 reference
# and the kernel path 0.060; on a v5e at full width the kernel path came to
# 0.187 of the RMS from the reference.  A kernel that zeroes its attention
# output or drops the remote GEMM tier fails the limit by a wide margin
# (tests/test_chip_smoke.py).
LOGIT_TOL = 0.25


def make_prompts(vocab: int, n: int = N_REQUESTS, lo: int = PROMPT_LEN[0],
                 hi: int = PROMPT_LEN[1], seed: int = SEED) -> list[np.ndarray]:
    rng = np.random.default_rng(seed)
    return [rng.integers(3, vocab, int(t)).astype(np.int32)
            for t in rng.integers(lo, hi + 1, n)]


def serve_requests(engine, prompts, new_tokens: int):
    """Serve one request per prompt to completion on a fresh stats record.
    Returns (wall seconds, stats, per-request tokens)."""
    from repro.serving.engine import EngineStats, Request

    engine.stats = EngineStats()
    reqs = [Request(rid=i, prompt=p, max_new_tokens=new_tokens)
            for i, p in enumerate(prompts)]
    t0 = time.perf_counter()
    for r in reqs:
        engine.submit(r)
    stats = engine.run()
    wall = time.perf_counter() - t0
    return wall, stats, [list(r.out_tokens) for r in reqs]


def check_served(stats, tokens, new_tokens: int) -> None:
    if stats.served != len(tokens) or stats.failed_requests:
        raise RuntimeError(f"served {stats.served}/{len(tokens)} requests, "
                           f"{stats.failed_requests} failed")
    short = [i for i, t in enumerate(tokens) if len(t) != new_tokens]
    if short:
        raise RuntimeError(f"requests {short} stopped before {new_tokens} tokens")


def decode_logits_check(cfg, engine, prompts) -> dict:
    """One decode step's logits from the compiled tiered kernel path
    (`paged_tiered_decode_step` under jit) against the reference path
    (`models.decode_step`, pure jnp) after the same prefill, on the engine's
    own weights.  Half of every slot's KV pages are demoted to the remote
    pool first, so the paged kernel reads both tiers.  The engine must be
    idle; its slots are freed again afterwards."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops
    from repro.models import model as M
    from repro.serving import tiered_decode as TD

    pc, params = engine.pcache, engine.params
    b = len(prompts)
    lens = np.array([len(p) for p in prompts], np.int32)
    first, ks, vs = [], [], []
    for slot, p in enumerate(prompts):
        logits, cache = M.prefill(cfg, params, {"tokens": jnp.asarray(p)[None]},
                                  max_len=engine.max_len)
        first.append(int(jnp.argmax(logits[0, -1])))
        pc.ensure_capacity(slot, len(p) + 1)
        pc.write_prompt(slot, cache["k"][:, 0, :len(p)], cache["v"][:, 0, :len(p)])
        pc.demote_slot_pages(slot, max_pages=int(pc.n_pages[slot]) // 2)
        ks.append(cache["k"][:, 0])
        vs.append(cache["v"][:, 0])
    ref_cache = {"k": jnp.stack(ks, axis=1), "v": jnp.stack(vs, axis=1)}
    del ks, vs
    tokens = jnp.asarray(first, jnp.int32)[:, None]
    positions = jnp.asarray(lens)
    active = np.ones(b, bool)
    wr = pc.write_targets(lens, active)
    table, tier = pc.device_tables()
    window = engine.window

    def step(use_kernel):
        def run(params, pools, tokens, positions, attn_lens, table, tier,
                wr_tier, wr_idx, wr_off):
            logits, _ = TD.paged_tiered_decode_step(
                cfg, params, pools, tokens, positions, attn_lens, table, tier,
                wr_tier, wr_idx, wr_off, sink_local=pc.sink_local,
                sink_remote=pc.sink_remote, window=window, use_kernel=use_kernel)
            return logits
        return run

    args = (params, pc.pools, tokens, positions, positions + 1, table, tier, *wr)
    with ops.count_dispatch() as dispatch:
        compiled = jax.jit(step(True)).lower(*args).compile()
    got = np.asarray(compiled(*args)[:, 0], np.float32)
    # The same step with every tiered operand on the jnp path: the kernels
    # are the only difference from `got`.
    oracle = np.asarray(jax.jit(step(False))(*args)[:, 0], np.float32)
    ref_logits, _ = jax.jit(lambda p, c, t, pos: M.decode_step(cfg, p, c, t, pos))(
        params, ref_cache, tokens, positions)
    want = np.asarray(ref_logits[:, 0], np.float32)
    # Counted from the host table before the slots are freed: on the CPU
    # `tier` may share the host table's memory.
    remote_pages = int((pc.tier[:b] > 0).sum())
    for slot in range(b):
        pc.free_slot(slot)
    scale = max(float(np.sqrt(np.mean(want ** 2))), 1e-30)
    err = float(np.max(np.abs(got - want)))
    return {
        "finite": bool(np.isfinite(got).all() and np.isfinite(want).all()
                       and np.isfinite(oracle).all()),
        "max_abs_err": err,
        "ref_rms": scale,
        "rel_err": err / scale,
        "oracle_rel_err": float(np.max(np.abs(got - oracle))) / scale,
        "oracle_ref_rel_err": float(np.max(np.abs(oracle - want))) / scale,
        "remote_kv_pages": remote_pages,
        "tpu_custom_calls": compiled.as_text().count(
            'custom_call_target="tpu_custom_call"'),
        "dispatch": dict(dispatch),
    }


def assert_logits_close(res: dict, tol: float = LOGIT_TOL) -> None:
    if not res["finite"]:
        raise RuntimeError("non-finite logits")
    if not (res["rel_err"] < tol and res["oracle_rel_err"] < tol):
        raise RuntimeError(
            f"kernel logits differ from the reference: max |err| "
            f"{res['max_abs_err']!r} is {res['rel_err']!r} of the reference "
            f"RMS {res['ref_rms']!r}, {res['oracle_rel_err']!r} against the "
            f"jnp path of the same step (limit {tol})")


def _build(argv: list[str]):
    from repro.launch import serve

    t0 = time.perf_counter()
    cfg, engine = serve.build_engine(serve.parse_args(argv))
    return cfg, engine, time.perf_counter() - t0


def _device_line(n: int) -> str:
    import jax

    d = jax.devices()[0]
    return json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind, "count": n}})


def run_one_chip() -> None:
    import jax

    from repro.launch import serve

    cfg, engine, t_build = _build(SERVE_ARGS)
    wq = engine.params["layers"]["wq"]
    dtype = wq.local.dtype
    print(f"model {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"{cfg.n_heads} heads ({cfg.padded_heads} padded) x {cfg.resolved_head_dim}, "
          f"{cfg.n_kv_heads} kv heads, d_ff {cfg.d_ff}, vocab {cfg.vocab}, {dtype}")
    print(f"plan: global ratio {engine.plan.global_ratio} | per-op "
          f"{engine.plan.op_ratios} | kv pages local {engine.pcache.n_local} "
          f"remote {engine.pcache.n_remote} | window {engine.window}")
    print(f"memory kind: layers/wq local {wq.local.sharding.memory_kind}, "
          f"remote {wq.remote.sharding.memory_kind} "
          f"(shapes {wq.local.shape} + {wq.remote.shape})")
    print(f"setup (params + partition + pools): {t_build:.3f} s")

    prompts = make_prompts(cfg.vocab)
    t_warm, _, _ = serve_requests(engine, prompts, 2)
    compiles = engine.compile_count
    print(f"compile (warm-up: every prompt shape once, decode step "
          f"{compiles} bucket(s)): {t_warm:.3f} s")

    wall, stats, tokens = serve_requests(engine, prompts, NEW_TOKENS)
    check_served(stats, tokens, NEW_TOKENS)
    if engine.compile_count != compiles:
        raise RuntimeError("the timed run compiled a new decode step")
    print(f"served {stats.served}/{len(prompts)} requests, prompts "
          f"{[len(p) for p in prompts]}, {NEW_TOKENS} new tokens each")
    print(f"untuned bring-up run (one run, not a benchmark): "
          f"{stats.generated_tokens / wall!r} tokens/s | wall {wall!r} s | "
          f"TTFT p50 {stats.ttft_p50 * 1e3!r} ms p95 {stats.ttft_p95 * 1e3!r} ms | "
          f"TPOT {stats.tpot * 1e3!r} ms over {stats.decode_steps} decode steps | "
          f"prefill {stats.prefill_time!r} s")
    print(f"tokens of request 0: {tokens[0]}")

    res = decode_logits_check(cfg, engine, prompts)
    print(f"decode program: {res['tpu_custom_calls']} tpu_custom_call | tiered "
          f"operands by path {res['dispatch']}")
    print(f"logits check: kernel vs reference max |err| {res['max_abs_err']!r}, "
          f"reference RMS {res['ref_rms']!r}, ratio {res['rel_err']!r}; kernel "
          f"vs the step's jnp path ratio {res['oracle_rel_err']!r} (limit "
          f"{LOGIT_TOL} for both); jnp path vs reference ratio "
          f"{res['oracle_ref_rel_err']!r}; {res['remote_kv_pages']} page-table "
          f"entries in the remote KV pool")
    if res["tpu_custom_calls"] < 2:
        raise RuntimeError("the decode program lacks the GEMM or the paged kernel")
    if any(path == "jnp" for _, path in res["dispatch"]):
        raise RuntimeError(f"tiered operands took the jnp path: {res['dispatch']}")
    assert_logits_close(res)

    hw = serve.resolve_hw()
    peak = serve.peak_device_bytes()
    if peak is None:
        raise RuntimeError("the device reports no memory statistics")
    print(f"peak HBM {peak} bytes ({peak / 1e9:.3f} GB of {hw.hbm.capacity / 1e9:.0f} GB)")
    if peak >= hw.hbm.capacity:
        raise RuntimeError("peak HBM exceeds the chip's capacity")
    print(_device_line(jax.device_count()))


def run_four_chips() -> None:
    import jax

    from repro.launch import serve

    if jax.device_count() < 4:
        raise SystemExit(f"--chips 4 needs 4 devices, have {jax.device_count()}")
    cfg, engine, t_build = _build(SERVE_ARGS)
    # Each prompt length compiles its own eager prefill, once per placement:
    # the first MESH_REQUESTS prompts keep the four-chip call short.
    prompts = make_prompts(cfg.vocab)[:MESH_REQUESTS]
    wall1, stats1, tokens1 = serve_requests(engine, prompts, NEW_TOKENS)
    check_served(stats1, tokens1, NEW_TOKENS)
    print(f"one chip: setup {t_build:.3f} s, served {stats1.served} requests "
          f"in {wall1:.3f} s (compile included)")
    del engine
    gc.collect()

    cfg, engine, t_build = _build(SERVE_ARGS + ["--mesh-devices", "4"])
    wq = engine.params["layers"]["wq"]
    shards = sorted((str(s.device), s.data.shape) for s in wq.remote.addressable_shards)
    print(f"mesh: layers/wq remote {wq.remote.shape} over {wq.remote.sharding.spec}; "
          f"shards {shards}")
    devices = {d for d, _ in shards}
    split = wq.remote.shape[wq.axis] // 4
    if len(devices) != 4 or any(s[wq.axis] != split for _, s in shards):
        raise RuntimeError("remote partition is not spread 1/4 over four devices")
    wall4, stats4, tokens4 = serve_requests(engine, prompts, NEW_TOKENS)
    check_served(stats4, tokens4, NEW_TOKENS)
    print(f"four chips: setup {t_build:.3f} s, served {stats4.served} requests "
          f"in {wall4:.3f} s (compile included)")
    diff = [i for i, (a, b) in enumerate(zip(tokens1, tokens4)) if a != b]
    if diff:
        raise RuntimeError(f"mesh tokens differ from one chip for requests {diff}")
    print(f"tokens match on all {len(prompts)} requests; request 0: {tokens4[0]}")
    print(f"peak HBM on the busiest device {serve.peak_device_bytes()} bytes")
    print(_device_line(jax.device_count()))


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        raise SystemExit(f"chip_smoke: no TPU found (JAX backend is {backend!r})")
    from repro.core.hardware import hardware_for
    from repro.launch import compile_cache

    hardware_for(jax.devices()[0])       # an unknown chip is an error
    print(f"compile cache: {compile_cache.configure()}")
    if args.chips == 4:
        run_four_chips()
    else:
        run_one_chip()


if __name__ == "__main__":
    main()
