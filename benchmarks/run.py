"""Benchmark harness entrypoint: one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  See per-module docstrings for what
`derived` means in each section (EB GB/s, speedup, amplification, roofline
fraction, modeled TPU µs).

  PYTHONPATH=src python -m benchmarks.run [--only fig11]
  PYTHONPATH=src python -m benchmarks.run --only serving \
      --json-out BENCH_serving.json

``--json-out`` additionally writes the serving section's machine-readable
report (static vs adaptive vs mesh-sharded tokens/s, TTFT p50/p95,
achieved bandwidth per tier, per-run ``mesh_shape``, per-link fetch-once
traffic vs the multicast oracle) — the ``BENCH_serving.json`` artifact CI
uploads so the serving perf trajectory is tracked across PRs.  The
sharded run uses ``min(BENCH_MESH_DEVICES, device count)`` devices of this
process (default 2) and is left out on one device; on a CPU host, force
devices with ``XLA_FLAGS=--xla_force_host_platform_device_count=N``.
"""
from __future__ import annotations

import argparse
import json
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="", help="substring filter on section name")
    ap.add_argument("--json-out", default=None, metavar="PATH",
                    help="write BENCH_serving.json (runs the serving section)")
    args = ap.parse_args()

    from benchmarks import fig_benchmarks, kernel_micro, roofline, serving_bench

    sections = {fn.__name__: fn for fn in fig_benchmarks.ALL}
    sections["kernel_micro"] = kernel_micro.rows
    sections["roofline"] = roofline.rows
    sections["serving"] = serving_bench.rows

    print("name,us_per_call,derived")
    failures = 0
    for name, fn in sections.items():
        if args.only and args.only not in name:
            continue
        if name == "serving" and args.json_out:
            continue                      # emitted below with the JSON payload
        try:
            for row_name, us, derived in fn():
                print(f"{row_name},{us:.3f},{derived:.4f}")
        except Exception:  # noqa: BLE001
            failures += 1
            print(f"# section {name} FAILED", file=sys.stderr)
            traceback.print_exc()
    if args.json_out and (not args.only or args.only in "serving"):
        try:
            rows, report = serving_bench.collect()
            for row_name, us, derived in rows:
                print(f"{row_name},{us:.3f},{derived:.4f}")
            with open(args.json_out, "w") as fh:
                json.dump(report, fh, indent=2, default=float)
            print(f"# wrote {args.json_out}", file=sys.stderr)
        except Exception:  # noqa: BLE001
            failures += 1
            print("# section serving FAILED", file=sys.stderr)
            traceback.print_exc()
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
