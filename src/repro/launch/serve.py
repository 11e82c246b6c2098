"""Serving driver: batched requests through the DAK tiered engine.

  PYTHONPATH=src python -m repro.launch.serve --arch llama2_7b --smoke \
      --requests 8 --offload-ratio 0.4

Two planning modes (paper Fig. 8-10):

* ``--offload-ratio R`` pins the global offload ratio directly (sweep mode);
* ``--hbm-gb G`` derives the ratio from a real HBM budget —
  ``OR = max(0, 1 - budget / footprint)`` — the paper's Fig. 10 mode.

``--adaptive`` attaches the adaptive runtime (`repro.runtime`): AIMD
congestion-window control, phase-aware re-planning and budgeted live page
migration, with per-step telemetry.  ``--bench-json PATH`` writes the
machine-readable benchmark report (tokens/s, TTFT percentiles, achieved
vs predicted bandwidth per tier, modeled static-vs-adaptive throughput);
with ``--adaptive`` it defaults to ``BENCH_serving.json`` so the perf
trajectory is tracked across PRs (the CI smoke job uploads it).

The serving frontend (`repro.frontend`) plugs in through three knobs:
``--scheduler {fcfs,priority,slo}`` selects the admission policy (the SLO
scheduler defaults to chunked prefill + tier-demotion preemption),
``--prefill-chunk N`` caps prompt tokens prefilled per step, and the
workload comes either from ``--trace PATH`` (replay a checked-in trace)
or ``--arrival-rate R`` (synthesize Poisson arrivals with the default
tenant classes).  Both trace modes run on the *modeled clock* — arrival
times are virtual seconds and TTFT/queue-delay/SLO figures are
deterministic functions of the schedule, not of host wall time.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

import repro.configs as C
from repro.configs.base import ModelConfig
from repro.core.hardware import TPU_V5E, HardwareSpec, hardware_for
from repro.frontend.metrics import ModeledClock
from repro.frontend.scheduler import scheduler_names
from repro.frontend.workload import Trace, poisson_trace
from repro.launch import compile_cache
from repro.models import model as M
from repro.obs.flight import FlightRecorder
from repro.obs.metrics import BENCH_SCHEMA_VERSION, provenance, serving_registry
from repro.obs.trace import ChromeTraceRecorder
from repro.serving.engine import Request, ServingEngine


def _write_atomic(path: str, text: str) -> None:
    """Write ``text`` to ``path`` via a same-directory tmp file +
    ``os.replace`` so concurrent readers always see a complete file."""
    import os

    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as fh:
        fh.write(text)
    os.replace(tmp, path)


def _bench_registry(args, engine: ServingEngine, stats, wall: float):
    """The metrics registry behind one serving run's report (the single
    producer of the BENCH stats block and the Prometheus exposition)."""
    return serving_registry(engine, stats, wall, meta={
        "arch": args.arch,
        "smoke": bool(args.smoke),
        "adaptive": bool(args.adaptive),
        "trace": args.trace or ("poisson"
                                if getattr(args, "arrival_rate", None)
                                else None),
        "requests": args.requests,
    })


def bench_report(args, engine: ServingEngine, stats, wall: float,
                 reg=None) -> dict:
    """The BENCH_serving.json schema: one flat dict per serving run.

    Produced by the unified metrics registry (`repro.obs.metrics`): every
    subsystem registers its counters and :meth:`MetricsRegistry.nested`
    emits them in the legacy field order, byte-identical to the hand-built
    dict this function used to assemble.  The only additions sit at the
    *end* of the dict: ``schema_version`` and the ``provenance`` stamp
    (git revision, config, clock type) that lets `benchmarks/compare.py`
    refuse cross-schema / cross-config comparisons."""
    if reg is None:
        reg = _bench_registry(args, engine, stats, wall)
    report = reg.nested()
    report["schema_version"] = BENCH_SCHEMA_VERSION
    report["provenance"] = provenance(engine, arch=args.arch)
    return report


def parse_args(argv: list[str] | None = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama2_7b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--offload-ratio", type=float, default=0.4,
                    help="pinned global offload ratio (ignored with --hbm-gb)")
    ap.add_argument("--hbm-gb", type=float, default=None,
                    help="HBM budget in GB: plan the global ratio from the "
                         "model footprint (paper Fig. 10 mode)")
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--no-kernels", action="store_true")
    ap.add_argument("--adaptive", action="store_true",
                    help="attach the adaptive runtime (AIMD window control, "
                         "phase-aware re-planning, live page migration)")
    ap.add_argument("--mesh-devices", type=int, default=1, metavar="P",
                    help="serve one replica across P chips, each with its own "
                         "host link: the remote tier shards 1/P per link and "
                         "every step rebuilds it fetch-once over ICI (on CPU, "
                         "force devices with XLA_FLAGS="
                         "--xla_force_host_platform_device_count=P)")
    ap.add_argument("--bench-json", default=None, metavar="PATH",
                    help="write the machine-readable benchmark report here "
                         "(default BENCH_serving.json with --adaptive)")
    ap.add_argument("--scheduler", default="fcfs",
                    choices=sorted(scheduler_names()),
                    help="serving frontend policy: fcfs (whole-prompt "
                         "admission order), priority, or slo (earliest "
                         "deadline first + chunked prefill + tier-demotion "
                         "preemption)")
    ap.add_argument("--prefill-chunk", type=int, default=None, metavar="N",
                    help="chunked prefill: at most N prompt tokens per step "
                         "(default: scheduler's own budget; fcfs = whole "
                         "prompts)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="replay a workload trace (frontend.workload JSON) "
                         "on the modeled clock; overrides --requests/"
                         "--prompt-len/--new-tokens")
    ap.add_argument("--arrival-rate", type=float, default=None, metavar="RPS",
                    help="synthesize a Poisson trace at this rate (modeled "
                         "seconds) with the default tenant classes instead "
                         "of submitting everything at t=0")
    ap.add_argument("--slo-ttft-ms", type=float, default=None,
                    help="override the interactive class's TTFT SLO for "
                         "synthesized traces (ms, modeled clock)")
    ap.add_argument("--check-invariants", action="store_true",
                    help="audit the paged cache's page-table invariants "
                         "(repro.analysis, DAK301-305) after every engine "
                         "step; aborts on the first inconsistency.  Read-only "
                         "host bookkeeping — tokens and stats are unchanged")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome trace-event JSON of the run "
                         "(per-step phase spans, per-request lifecycle "
                         "tracks, per-link counter tracks; load in "
                         "Perfetto / chrome://tracing)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write the Prometheus text exposition of the "
                         "run's metrics registry")
    ap.add_argument("--metrics-interval", type=int, default=0, metavar="N",
                    help="with --metrics-out: also rewrite the file every N "
                         "engine steps (atomic tmp-file rename, so a scraper "
                         "never reads a torn file); 0 = end-of-run only")
    ap.add_argument("--attribution", action="store_true",
                    help="attach the bandwidth-attribution profiler "
                         "(repro.obs.attribution): per-step time ledger, "
                         "bottleneck labels, achieved-vs-optimal aggregate "
                         "bandwidth — adds attribution.*/bottleneck.* to the "
                         "bench report and trace")
    ap.add_argument("--flight-dir", default=None, metavar="DIR",
                    help="attach the flight recorder: keep a bounded ring "
                         "of per-step state snapshots and dump a "
                         "post-mortem bundle here on a crash, an "
                         "InvariantViolation, or an SLO breach")
    ap.add_argument("--flight-slo-breach-ms", type=float, default=None,
                    help="with --flight-dir: dump a bundle the first time "
                         "a request's TTFT exceeds this (engine-clock ms)")
    ap.add_argument("--no-jit", action="store_true",
                    help="run the decode step eagerly (per-layer functional "
                         "pool copies, per-step dispatch) instead of the "
                         "compiled, pool-donating step — the baseline side "
                         "of the eager-vs-jitted gate")
    ap.add_argument("--autotune", action="store_true",
                    help="sweep kernel tile shapes per (op, shape, dtype, "
                         "offload ratio, hw) under the EB cost model and "
                         "dispatch with the lint-validated winners "
                         "(kernels.autotune)")
    ap.add_argument("--autotune-cache", default=None, metavar="PATH",
                    help="JSON autotune table: loaded before the run if it "
                         "exists (winners reproduce bit-for-bit; without "
                         "--autotune unseen shapes fall back to defaults), "
                         "rewritten after the run with --autotune")
    ap.add_argument("--tokens-out", default=None, metavar="PATH",
                    help="write every request's emitted tokens as JSON "
                         "{rid: [tokens]} — the parity artifact the CI "
                         "perf-smoke job diffs between eager and jitted runs")
    ap.add_argument("--hbm-shrink", default=None, metavar="STEP:FRAC",
                    help="chaos event: at decode step STEP, shrink the "
                         "modeled HBM page budget to FRAC of the local pool "
                         "(e.g. 6:0.3).  The engine must degrade — demote, "
                         "re-plan to a higher offload ratio, shed admissions "
                         "— and finish with zero failed requests")
    return ap.parse_args(argv)


def resolve_hw() -> HardwareSpec:
    """The spec the planner prices.  On a TPU backend it is the attached
    chip's, looked up by device kind (an unknown kind is an error); on any
    other backend the kernels run interpreted and the planner prices a v5e."""
    if jax.default_backend() == "tpu":
        return hardware_for(jax.devices()[0])
    return TPU_V5E


def build_engine(args: argparse.Namespace, **engine_kw) -> tuple[ModelConfig, ServingEngine]:
    """The config and the serving engine that ``args`` describe.

    Parameters are created in ``cfg.dtype`` by one compiled program (eager
    init compiles every random op per leaf shape, and holds each leaf's
    unscaled draw next to the scaled one) and handed to the engine without
    a name here: once the engine has partitioned them into tiers, nothing
    holds the unsplit tree.  ``engine_kw`` passes the observability and
    clock hooks through to `ServingEngine`."""
    cfg = C.get_smoke(args.arch) if args.smoke else C.get(args.arch)
    mesh = None
    if args.mesh_devices > 1:
        if jax.device_count() < args.mesh_devices:
            raise SystemExit(
                f"--mesh-devices {args.mesh_devices} needs that many devices "
                f"(have {jax.device_count()}); on CPU set XLA_FLAGS="
                f"--xla_force_host_platform_device_count={args.mesh_devices}")
        mesh = jax.sharding.Mesh(
            np.array(jax.devices()[:args.mesh_devices]), ("model",))
    engine = ServingEngine(
        cfg, jax.jit(M.init_params, static_argnums=(0, 2))(
            cfg, jax.random.PRNGKey(0), jnp.dtype(cfg.dtype)),
        hw=resolve_hw(), max_batch=args.max_batch, max_len=args.max_len,
        hbm_budget_bytes=args.hbm_gb * 1e9 if args.hbm_gb is not None else None,
        global_offload_ratio=None if args.hbm_gb is not None else args.offload_ratio,
        use_kernels=not args.no_kernels, page_size=args.page_size,
        adaptive=args.adaptive, mesh=mesh,
        scheduler=args.scheduler, prefill_chunk=args.prefill_chunk,
        check_invariants=args.check_invariants,
        jit_step=not args.no_jit, **engine_kw)
    return cfg, engine


def peak_device_bytes() -> int | None:
    """Largest ``peak_bytes_in_use`` over the devices, where the backend
    reports memory statistics (TPU does; the CPU backend does not)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def main(argv: list[str] | None = None) -> dict:
    args = parse_args(argv)
    compile_cache.configure()
    shrink = None
    if args.hbm_shrink:
        try:
            step_s, frac_s = args.hbm_shrink.split(":")
            shrink = (int(step_s), float(frac_s))
        except ValueError:
            raise SystemExit(
                f"--hbm-shrink expects STEP:FRAC (e.g. 6:0.3), "
                f"got {args.hbm_shrink!r}") from None
    if args.bench_json is None and args.adaptive:
        args.bench_json = "BENCH_serving.json"

    trace = None
    if args.trace:
        trace = Trace.load(args.trace)
    elif args.arrival_rate:
        from repro.frontend.workload import DEFAULT_CLASSES
        classes = DEFAULT_CLASSES
        if args.slo_ttft_ms is not None:
            classes = tuple(
                dataclasses.replace(c, slo_ttft_s=args.slo_ttft_ms / 1e3)
                if c.slo_ttft_s is not None else c
                for c in classes)
        trace = poisson_trace(
            args.requests, rate_rps=args.arrival_rate, classes=classes,
            prompt_max=max(4, args.max_len - args.new_tokens - 2),
            out_max=args.new_tokens, seed=0)
    recorder = None
    if args.trace_out:
        recorder = ChromeTraceRecorder(metadata={
            "arch": args.arch,
            "scheduler": args.scheduler,
            "clock": "modeled" if trace is not None else "wall"})
    flight = None
    if args.flight_dir:
        flight = FlightRecorder(
            args.flight_dir,
            slo_breach_s=(args.flight_slo_breach_ms / 1e3
                          if args.flight_slo_breach_ms is not None else None))
    tuner = None
    if args.autotune or args.autotune_cache:
        import os

        from repro.kernels.autotune import Autotuner
        if args.autotune_cache and os.path.exists(args.autotune_cache):
            tuner = Autotuner.load(args.autotune_cache, sweep=args.autotune)
            print(f"autotune: loaded {len(tuner.table)} entries "
                  f"from {args.autotune_cache} (hw={tuner.hw.name})")
        else:
            tuner = Autotuner(sweep=args.autotune)
    profiler = None
    if args.attribution:
        from repro.obs.attribution import AttributionProfiler
        profiler = AttributionProfiler()
    cfg, engine = build_engine(
        args, clock=ModeledClock() if trace is not None else None,
        recorder=recorder, flight=flight, tuner=tuner, profiler=profiler)
    if shrink is not None:
        engine.schedule_hbm_shrink(*shrink)
        print(f"chaos: HBM shrink to {shrink[1]:.0%} of the local pool "
              f"at decode step {shrink[0]}")

    print(f"plan: global={engine.plan.global_ratio:.2f} "
          f"per-op={ {k: round(v, 2) for k, v in engine.plan.op_ratios.items()} } "
          f"window={engine.plan.window.n_inflight} tiered={engine.tiered} "
          f"jit={engine._jit} adaptive={args.adaptive} "
          f"mesh={engine.mesh_shape}")
    if engine.plan.mesh is not None:
        mp = engine.plan.mesh
        print(f"mesh: {mp.n_devices} host links x "
              f"{mp.host_link_bw / 1e9:.0f} GB/s -> aggregate "
              f"{mp.aggregate_host_bw / 1e9:.0f} GB/s | per-link fetch-once "
              f"{mp.per_link_bytes_multicast / 1e6:.1f} MB vs naive "
              f"{mp.per_link_bytes_naive / 1e6:.1f} MB")
    if args.hbm_gb is not None:
        print(f"budget: {args.hbm_gb:.1f} GB HBM vs "
              f"{engine.plan.footprint_bytes / 1e9:.1f} GB footprint")

    rng = np.random.default_rng(0)
    t0 = time.time()
    submitted: list[Request] = []
    if trace is not None:
        print(f"trace: {trace.description or args.trace} "
              f"({len(trace.entries)} requests) | scheduler {args.scheduler} "
              f"chunk {engine.scheduler.chunk_tokens}")
        for req in trace.to_requests(cfg.vocab):
            submitted.append(req)
            engine.submit(req)
    else:
        for rid in range(args.requests):
            req = Request(
                rid=rid,
                prompt=rng.integers(3, cfg.vocab, args.prompt_len).astype(np.int32),
                max_new_tokens=args.new_tokens)
            submitted.append(req)
            engine.submit(req)
    step_hook = None
    if args.metrics_out and args.metrics_interval > 0:
        # Periodic Prometheus flush for long runs: rebuild the registry
        # from the live engine state every N steps and rename it into
        # place atomically, so a scraper never reads a torn file.
        # Interval 0 leaves the single end-of-run write untouched.
        def step_hook(steps: int) -> None:
            if steps % args.metrics_interval:
                return
            flush_reg = _bench_registry(args, engine, engine.stats,
                                        time.time() - t0)
            _write_atomic(args.metrics_out, flush_reg.to_prometheus())

    stats = engine.run(step_hook=step_hook)
    wall = time.time() - t0
    print(f"served {stats.served} requests in {wall:.2f}s | "
          f"decode steps {stats.decode_steps} | TPOT {stats.tpot*1e3:.1f} ms | "
          f"TTFT p50 {stats.ttft_p50*1e3:.1f} ms p95 {stats.ttft_p95*1e3:.1f} ms | "
          f"queue p95 {stats.queue_delay_p95*1e3:.1f} ms | "
          f"e2e p95 {stats.e2e_p95*1e3:.1f} ms | "
          f"prefill to first tokens {stats.prefill_time:.2f}s")
    peak = peak_device_bytes()
    if peak is not None:
        print(f"peak device memory {peak / 1e9:.3f} GB")
    if stats.prefill_chunks or stats.preemptions:
        print(f"frontend: prefill chunks {stats.prefill_chunks} | "
              f"preemptions {stats.preemptions} "
              f"({stats.preempt_demoted_pages} pages demoted)")
    if engine.health.counters.events:
        print(f"elastic: health {stats.health} | failed requests "
              f"{stats.failed_requests} | CacheFull caught "
              f"{stats.cache_full_caught} | demoted {stats.elastic_demoted_pages} "
              f"pages | remote grown {stats.remote_grown_pages} pages | "
              f"shed steps {stats.shed_steps} | "
              f"elastic replans {stats.elastic_replans}")
    slo = stats.slo_report()
    if trace is not None and slo:
        for cls, rep in slo.items():
            att = ("n/a" if rep["attainment"] is None
                   else f"{rep['attainment']*100:.0f}%")
            print(f"  class {cls}: n={rep['requests']} slo={att} "
                  f"ttft p95 {rep['ttft_p95']*1e3:.1f} ms | "
                  f"queue p95 {rep['queue_delay_p95']*1e3:.1f} ms | "
                  f"preemptions {rep['preemptions']}")
    if engine.tiered and engine.plan.kv_pages is not None:
        pp = engine.plan.kv_pages
        print(f"kv pages: size={pp.page_size} local={pp.local_pages} "
              f"remote={pp.remote_pages} | peak local={stats.local_pages_hwm} "
              f"peak remote={stats.remote_pages_hwm} spills={stats.spills}")
    if engine.runtime is not None:
        rt = engine.runtime.report()
        w, mig, mod = rt["window"], rt["migration"], rt["modeled"]
        print(f"runtime: window {w['static']}->{w['final']} "
              f"(converged={w['converged']}) | replans {rt['replans']} | "
              f"pages promoted {mig['promoted']} demoted {mig['demoted']} | "
              f"modeled tokens/s static {mod['static_tokens_per_s']:.3g} "
              f"adaptive {mod['adaptive_tokens_per_s']:.3g} "
              f"(gain {mod['gain']:.3f})")

    if profiler is not None:
        prep = profiler.report()
        btl = prep["bottleneck"]
        fr = btl["optimal_fraction"]
        labels = ", ".join(f"{k} {v}" for k, v in btl["labels"].items() if v)
        print(f"attribution: {prep['steps']} steps | labels: {labels or 'none'}"
              f" | transitions {btl['transitions']} | bw optimality "
              f"mean {fr['mean']:.3f} max {fr['max']:.3f}")

    reg = _bench_registry(args, engine, stats, wall)
    report = bench_report(args, engine, stats, wall, reg=reg)
    if args.bench_json:
        with open(args.bench_json, "w") as fh:
            json.dump(report, fh, indent=2, default=float)
        print(f"wrote {args.bench_json}")
    if args.trace_out:
        recorder.save(args.trace_out)
        recorder.close()
        print(f"wrote {args.trace_out} "
              f"({len(recorder.events)} trace events)")
    if args.metrics_out:
        with open(args.metrics_out, "w") as fh:
            fh.write(reg.to_prometheus())
        print(f"wrote {args.metrics_out}")
    if args.tokens_out:
        with open(args.tokens_out, "w") as fh:
            json.dump({str(r.rid): list(r.out_tokens) for r in submitted},
                      fh, sort_keys=True)
            fh.write("\n")
        print(f"wrote {args.tokens_out}")
    if tuner is not None:
        print(f"autotune: {tuner.counters()}")
        if args.autotune and args.autotune_cache:
            tuner.save(args.autotune_cache)
            print(f"wrote {args.autotune_cache} ({len(tuner.table)} entries)")
    if flight is not None and flight.dumped:
        print(f"flight bundles: {', '.join(flight.dumped)}")
    return report


if __name__ == "__main__":
    main()
