"""Serving benchmark: static vs adaptive vs mesh-sharded engine, plus
trace-driven scheduler scenarios.

Runs the end-to-end serving driver four ways — the static plan, the
adaptive runtime, a chaos run with a mid-trace HBM shrink (the
never-OOM elastic-degradation acceptance: failed_requests must be 0),
and, when more than one device is present, the mesh-sharded engine — and
emits both the CSV rows the
benchmark harness prints and the machine-readable ``BENCH_serving.json``
payload (``benchmarks.run --json-out``), so the serving perf trajectory
(tokens/s, TTFT percentiles, achieved bandwidth per tier, static vs
adaptive, 1-device vs N-device sharded) is tracked across PRs.

The scenario section replays named workload traces
(`repro.frontend.workload` — steady Poisson, bursty, long-prompt-heavy;
arrival gaps at smoke-model modeled-microsecond scale so the queue
actually builds) through the FCFS baseline and the SLO scheduler
(chunked prefill + tier-demotion preemption) *on identical traces*, and
reports modeled tokens/s and TTFT p95 per scheduler — the frontend's
perf trajectory.  Generated tokens are scheduler-invariant (pinned by
tests); only the latency distribution moves.

Every per-run report carries a ``mesh_shape`` field; the sharded run adds
``mesh_traffic`` (per-link fetch-once bytes vs the multicast oracle).
The sharded row runs in this process on the first
``min(BENCH_MESH_DEVICES, device count)`` devices, and only when that is
more than one; on a CPU host, force devices with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` before starting.
A failure there fails the section like any other row.
"""
from __future__ import annotations

import json
import os
import sys
import tempfile
from typing import Iterable

Row = tuple[str, float, float]

ARGS = [
    "--arch", "llama2_7b", "--smoke", "--requests", "4", "--max-batch", "2",
    "--prompt-len", "8", "--new-tokens", "4", "--max-len", "32",
    "--offload-ratio", "0.5", "--page-size", "4",
]

# Trace scenario runs share the engine shape but take their request mix
# (arrivals, lengths, classes) from the replayed trace.
TRACE_ARGS = [
    "--arch", "llama2_7b", "--smoke", "--max-batch", "2", "--max-len", "64",
    "--offload-ratio", "0.5", "--page-size", "4",
]

SHARDED_DEVICES = int(os.environ.get("BENCH_MESH_DEVICES", "2"))

SCENARIO_SCHEDULERS = ("fcfs", "slo")


def _scenario_traces() -> dict:
    """The named presets from `frontend.workload.SCENARIOS` (the single
    definition — already sized for smoke models on the modeled clock)."""
    from repro.frontend.workload import SCENARIOS, scenario_trace

    return {name: scenario_trace(name) for name in SCENARIOS}


def _scenario_reports() -> dict:
    """{scenario: {scheduler: serve report}} over identical traces."""
    from repro.launch.serve import main as serve_main

    out: dict[str, dict] = {}
    with tempfile.TemporaryDirectory() as tmp:
        for name, trace in _scenario_traces().items():
            path = os.path.join(tmp, f"{name}.json")
            trace.save(path)
            out[name] = {
                sched: serve_main(TRACE_ARGS + [
                    "--scheduler", sched, "--trace", path,
                    "--bench-json", ""])
                for sched in SCENARIO_SCHEDULERS
            }
    return out


def _scenario_rows(scenarios: dict) -> list[Row]:
    rows: list[Row] = []
    for name, reps in scenarios.items():
        for sched, rep in reps.items():
            modeled = rep.get("modeled", {})
            rows.append((f"serving_{name}_{sched}_ttft_p95_us",
                         rep["ttft_p95_ms"] * 1e3,
                         modeled.get("tokens_per_modeled_s", 0.0)))
        # headline: FCFS-vs-SLO interactive-class TTFT p95 ratio (>1 means
        # the SLO scheduler wins for the latency-sensitive class)
        cls = "interactive"
        p95 = {s: reps[s]["scheduling"]["slo"].get(cls, {}).get("ttft_p95", 0.0)
               for s in SCENARIO_SCHEDULERS}
        if p95.get("slo"):
            rows.append((f"serving_{name}_slo_ttft_p95_gain", 0.0,
                         p95["fcfs"] / p95["slo"]))
    return rows


def _sharded_report(n_devices: int) -> dict | None:
    """Serve ``ARGS`` on a mesh of the first ``n_devices`` devices present
    (capped at the device count).  Fewer than two is no sharded row — a
    1-device serve is just the static row and must not be labeled
    sharded."""
    import jax

    from repro.launch.serve import main as serve_main

    n = min(n_devices, jax.device_count())
    if n <= 1:
        return None
    return serve_main(ARGS + ["--mesh-devices", str(n), "--bench-json", ""])


def collect() -> tuple[list[Row], dict]:
    from repro.launch.serve import main as serve_main

    static = serve_main(ARGS + ["--bench-json", ""])
    adaptive = serve_main(ARGS + ["--adaptive", "--bench-json", ""])
    # Chaos row: same workload with a mid-trace HBM shrink to 20% — the
    # never-OOM acceptance; failed_requests must stay 0 while the elastic
    # machinery absorbs the pressure (demotions + host-pool growth).
    chaos = serve_main(ARGS + ["--hbm-shrink", "2:0.2", "--bench-json", ""])
    sharded = _sharded_report(SHARDED_DEVICES)
    runs: list[tuple[str, dict]] = [("static", static), ("adaptive", adaptive),
                                    ("chaos_shrink", chaos)]
    if sharded is not None:
        runs.append((f"sharded_{sharded['mesh_shape'][0]}dev", sharded))
    rows: list[Row] = []
    for name, rep in runs:
        tps = rep["tokens_per_s"]
        us_per_tok = 1e6 / tps if tps > 0 else 0.0
        rows.append((f"serving_{name}_tokens_per_s", us_per_tok, tps))
        rows.append((f"serving_{name}_ttft_p95_ms", rep["ttft_p95_ms"] * 1e3,
                     rep["ttft_p95_ms"]))
    rt = adaptive.get("runtime", {})
    if rt:
        rows.append(("serving_adaptive_modeled_gain", 0.0,
                     rt["modeled"]["gain"]))
        bw = rt["telemetry"]["bandwidth"]
        rows.append(("serving_achieved_local_bw_gbs", 0.0,
                     bw["local"]["achieved"] / 1e9))
        rows.append(("serving_achieved_remote_bw_gbs", 0.0,
                     bw["remote"]["achieved"] / 1e9))
    elastic = chaos.get("elastic", {})
    rows.append(("serving_chaos_failed_requests", 0.0,
                 float(chaos.get("failed_requests", 0))))
    rows.append(("serving_chaos_elastic_events", 0.0, float(
        elastic.get("cache_full_caught", 0) + elastic.get("shrink_events", 0)
        + elastic.get("remote_grown_pages", 0))))
    if sharded is not None and "mesh_traffic" in sharded:
        mt = sharded["mesh_traffic"]
        per_link = max(mt["per_link_bytes"]) if mt["per_link_bytes"] else 0.0
        naive = mt["oracle_per_link_naive"]
        rows.append(("serving_sharded_link_traffic_drop", 0.0,
                     naive / per_link if per_link else 0.0))
    scenarios = _scenario_reports()
    rows.extend(_scenario_rows(scenarios))
    # Eager-vs-jitted decode: the same smoke_50 SLO replay with the decode
    # step eager (per-layer functional pool copies, priced by the modeled
    # clock) vs compiled with pool donation (zero copy traffic).  Tokens
    # are bitwise-identical (CI perf-smoke diffs them); the throughput
    # ratio is the BENCH figure for what donation buys.
    jit_rep = baseline_report()
    eager_rep = eager_report()
    jit_tps = jit_rep["modeled"]["tokens_per_modeled_s"]
    eager_tps = eager_rep["modeled"]["tokens_per_modeled_s"]
    rows.append(("serving_jit_modeled_tokens_per_s", 0.0, jit_tps))
    rows.append(("serving_eager_modeled_tokens_per_s", 0.0, eager_tps))
    rows.append(("serving_jit_vs_eager_gain", 0.0,
                 jit_tps / eager_tps if eager_tps else 0.0))
    report = {"static": static, "adaptive": adaptive, "chaos": chaos,
              "scenarios": scenarios,
              "jit": {"jit": jit_rep, "eager": eager_rep,
                      "gain": jit_tps / eager_tps if eager_tps else 0.0}}
    if sharded is not None:
        report["sharded"] = sharded
    return rows, report


def rows() -> Iterable[Row]:
    return collect()[0]


# ---------------------------------------------------------------------------
# Bench regression baseline (benchmarks/compare.py)
# ---------------------------------------------------------------------------
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BASELINE_TRACE = os.path.join("benchmarks", "traces", "smoke_50.json")
BASELINE_PATH = os.path.join("benchmarks", "baselines",
                             "serving_smoke_slo.json")
EAGER_BASELINE_PATH = os.path.join("benchmarks", "baselines",
                                   "serving_smoke_eager.json")


def baseline_report() -> dict:
    """The deterministic report the bench regression gate diffs: the
    checked-in ``smoke_50`` trace replayed through the SLO scheduler on
    the modeled clock.  Every gated figure (counts, modeled latencies) is
    a deterministic function of the schedule — the trace never emits EOS,
    so generated_tokens cannot drift with sampling either — which is what
    makes a checked-in baseline meaningful across machines.

    Served with ``--attribution`` so the baseline carries the
    ``attribution.*`` / ``bottleneck.*`` blocks and the bandwidth
    optimality fraction is regression-gated (modeled-clock deterministic).
    The eager twin below stays profiler-off — the jit gate references no
    attribution paths, and keeping one baseline unprofiled doubles as a
    standing check that attribution-off output is unchanged."""
    from repro.launch.serve import main as serve_main

    return serve_main(TRACE_ARGS + [
        "--scheduler", "slo", "--trace", os.path.join(ROOT, BASELINE_TRACE),
        "--attribution", "--bench-json", ""])


def eager_report() -> dict:
    """The same smoke_50 SLO replay with ``--no-jit``: the eager decode
    step, whose per-layer functional pool copies the modeled clock prices
    as HBM copy traffic.  This is the checked-in baseline the CI
    perf-smoke job compares the jitted replay against (``compare.py
    --preset jit``: exact tokens, throughput strictly >=)."""
    from repro.launch.serve import main as serve_main

    return serve_main(TRACE_ARGS + [
        "--scheduler", "slo", "--no-jit",
        "--trace", os.path.join(ROOT, BASELINE_TRACE),
        "--bench-json", ""])


def main(argv: list[str] | None = None) -> int:
    """``python -m benchmarks.serving_bench --baseline-out PATH`` writes
    the regression-gate report (refresh the checked-in baseline with
    ``--baseline-out benchmarks/baselines/serving_smoke_slo.json`` after
    an *intended* perf change; CI diffs fresh output against it).
    ``--eager-baseline-out PATH`` writes the eager (``--no-jit``) twin the
    perf-smoke job uses as the jit-gate baseline."""
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--baseline-out", default=None, metavar="PATH",
                    help=f"write the smoke_50 SLO replay report here "
                         f"(checked-in baseline: {BASELINE_PATH})")
    ap.add_argument("--eager-baseline-out", default=None, metavar="PATH",
                    help=f"write the eager (--no-jit) smoke_50 SLO replay "
                         f"here (checked-in baseline: {EAGER_BASELINE_PATH})")
    args = ap.parse_args(argv)
    if args.baseline_out or args.eager_baseline_out:
        for path, make in ((args.baseline_out, baseline_report),
                           (args.eager_baseline_out, eager_report)):
            if not path:
                continue
            rep = make()
            # The trace path is machine-local; pin the repo-relative name
            # so the checked-in baseline is byte-stable across checkouts.
            rep["trace"] = BASELINE_TRACE
            with open(path, "w") as fh:
                json.dump(rep, fh, indent=2, default=float)
                fh.write("\n")
            print(f"wrote {path}")
        return 0
    for name, _, value in rows():
        print(f"{name},{value}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
