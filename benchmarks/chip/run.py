"""Chip benchmark of the serving path: one cell of ``BENCHMARK.json``.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

One process, one chip.  The run builds the cell's engine from its config
file, makes the weights on the device from the seed, generates the cell's
traffic from the seed, warms the cell's own shapes, and then drives
``ServingEngine.step()`` for ``--seconds``, timing every step on the host
clock.  With ``--trace 1`` the first seconds of the window are profiled
and the cell's per-layer metrics are reported; with ``--trace 0`` its
end-to-end metrics.  After the window the engine is freed and a float32
reference, run over a sample of the finished requests, decides
``correct``.  The last line of standard output is one JSON object.

Without a TPU, or with fewer chips than the cell needs, it exits non-zero
and prints no result.
"""
from __future__ import annotations

import argparse
import collections
import contextlib
import dataclasses
import gc
import json
import math
import os
import sys
import tempfile
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parents[1]
sys.path.insert(0, str(BENCH))

import faults  # noqa: E402
import manifest  # noqa: E402
import record  # noqa: E402
import traffic  # noqa: E402

# A --trace 1 run profiles its window from the start for at least
# TRACE_SECONDS, and on until the profile holds TRACE_MIN_DECODES decode
# steps and one admitting step (or the window ends), so the breakdown
# shows prefill stalls as well as decode.
TRACE_SECONDS = 4.0
TRACE_MIN_DECODES = 3
DRAIN_SECONDS = 60.0       # wait for first tokens of requests due in the window
CACHE_DIR = BENCH / ".jax_cache"     # the harness's own; nothing else writes it


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", default="", choices=("", "fp8"),
                    help="put the control, the reference in this lower "
                         "precision, in the program's place: the check judges "
                         "the tokens it puts first.  For setting limits; the "
                         "benchmark's own runs do not use it")
    ap.add_argument("--fault", default="", choices=("",) + tuple(faults.FAULTS),
                    help="plant this fault under the timed decode step "
                         "(faults.py).  For setting limits; the benchmark's "
                         "own runs do not use it")
    return ap.parse_args(argv)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def chip_peak(jax, chips: int) -> dict:
    backend = jax.default_backend()
    if backend != "tpu":
        raise SystemExit(f"run.py: no TPU (JAX backend is {backend!r})")
    if jax.device_count() < chips:
        raise SystemExit(f"run.py: the cell needs {chips} chips, "
                     f"JAX sees {jax.device_count()}")
    kind = jax.devices()[0].device_kind
    peaks = json.loads((BENCH / "peaks.json").read_text())
    if kind not in peaks:
        raise SystemExit(f"run.py: no peaks for device kind {kind!r} in peaks.json")
    return peaks[kind]


def configure_cache(jax) -> None:
    """The persistent compilation cache, at a fixed path in the checkout;
    every compile is kept, the small per-op ones of eager prefill too."""
    CACHE_DIR.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Programs JAX built between ``start()`` and ``stop()``, by name (the
    ``Compiling <name>`` record JAX logs at debug level before every
    build), and how many of them it loaded from the persistent cache."""

    LOGGER = "jax._src.interpreters.pxla"

    def __init__(self):
        import logging

        from jax import monitoring

        self.on = False
        self.hits = 0
        self.names: collections.Counter = collections.Counter()
        counter = self

        def counted(event: str, **_):
            if counter.on and event == "/jax/compilation_cache/cache_hits":
                counter.hits += 1

        class Names(logging.Handler):
            def emit(self, record):
                if counter.on and str(record.msg).startswith("Compiling "):
                    counter.names[str(record.args[0])] += 1

        monitoring.register_event_listener(counted)
        self._log = logging.getLogger(self.LOGGER)
        self._log.addHandler(Names())
        self._saved = (self._log.level, self._log.propagate)

    def start(self) -> None:
        import logging

        self.on = True
        self._log.setLevel(logging.DEBUG)
        self._log.propagate = False        # the debug records go nowhere else

    def stop(self) -> None:
        self.on = False
        self._log.setLevel(self._saved[0])
        self._log.propagate = self._saved[1]

    def summary(self) -> str:
        names = ", ".join(f"{n} x{c}" for n, c in self.names.most_common())
        return (f"programs built in window {sum(self.names.values())} ({self.hits} "
                f"loaded from the persistent cache){': ' + names if names else ''}")


class Driver:
    """Submits the traffic, steps the engine and records every step."""

    def __init__(self, engine, system, seed, vocab, annotate):
        self.engine, self.system = engine, system
        self.seed, self.vocab = seed, vocab
        self.annotate = annotate
        self.reqs: dict[int, record.Req] = {}
        self.objs: dict[int, object] = {}
        self.live: dict[int, object] = {}
        self.seen: dict[int, int] = {}
        self.step_no = 0
        self.lateness: list[float] = []

    def submit(self, spec, due: float) -> None:
        prompt = traffic.prompt_tokens(self.seed, spec.rid, spec.prompt_len, self.vocab)
        obj = self.system.request(spec.rid, prompt, spec.max_new_tokens)
        rec = record.Req(spec.rid, spec.prompt_len, spec.max_new_tokens, due=due)
        self.engine.submit(obj)
        rec.submit = obj.t_submit
        self.lateness.append(rec.submit - due)
        self.reqs[spec.rid], self.objs[spec.rid] = rec, obj
        self.live[spec.rid] = obj
        self.seen[spec.rid] = 0

    def step(self) -> record.Step:
        eng = self.engine
        before = counters(eng.stats)
        start = time.time()
        with self.annotate("bench:step", i=self.step_no):
            eng.step()
        end = time.time()
        after = counters(eng.stats)
        delta = {k: v - before[k] for k, v in after.items() if k in before}
        firsts = decoded = ctx = 0
        for rid, obj in list(self.live.items()):
            rec, n = self.reqs[rid], len(obj.out_tokens)
            new = n - self.seen[rid]
            if new <= 0:
                continue
            if self.seen[rid] == 0:
                rec.admit, rec.first = obj.t_admit, obj.t_first
                rec.times.append(obj.t_first)
                firsts += 1
                new -= 1
            if new:
                rec.times.extend([end] * new)
                decoded += new
                # the decode attended over prompt + tokens so far, less the new one
                ctx += rec.prompt_len + n - 1
            self.seen[rid] = n
            if obj.t_done:
                rec.done = obj.t_done
                del self.live[rid]
        gen = delta["generated_tokens"]
        if gen != firsts + decoded:
            raise RuntimeError(f"step {self.step_no}: engine counted {gen} tokens, "
                               f"requests show {firsts + decoded}")
        s = record.Step(self.step_no, start, end, delta["decode_time"],
                        decoded, firsts, ctx, delta)
        self.step_no += 1
        return s


def counters(stats) -> dict:
    """The program's counters: every field of the engine's stats dataclass
    that holds an int or a float (flags, strings and lists are skipped).
    Host reads only; nothing here waits for the device."""
    return {f.name: v for f in dataclasses.fields(stats)
            if type(v := getattr(stats, f.name)) in (int, float)}


def run_cell(man: manifest.Manifest, cell: dict, seed: int, seconds: float,
             trace: bool, peak: dict, t_start: float, control: str = "",
             fault: str = "") -> dict:
    """One run of ``cell``, from build to check.  Returns the result
    object.  The caller has checked the chip."""
    import jax
    import numpy as np

    import system
    import weights
    import xplane

    config, mix = man.config(cell), man.traffic(cell)
    m, arch = config["model"], man.reference(config)
    traffic.engine_max_len(mix)
    counter = CompileCounter()

    # -- set-up: program, weights, engine, traffic, warm-up ------------------
    cfg = system.model_config(man.root, config, arch)
    system.check_layout(cfg, weights.layout(arch, m))
    if fault:
        system.wrap_decode_step(faults.FAULTS[fault])
    engine = system.build_engine(cfg, weights.make_params(arch, m, seed), config, mix,
                                 recorder=trace)
    specs = traffic.generate(mix, seed)

    def annotate(name, **kw):
        return jax.profiler.TraceAnnotation(name, **kw) if trace else contextlib.nullcontext()

    drv = Driver(engine, system, seed, m["vocab"], annotate)
    # Warm-up: every prompt bucket, the decode step, and the KV pool's
    # spill.  Every slot takes the longest bucket and decodes past a page,
    # which overflows the pool's local tier where the slots' longest
    # prompts can fill it: the first page moved to the remote tier builds
    # programs of its own, and a seed that first spills inside the window
    # would build them there.
    buckets = sorted(set(s.prompt_len for s in specs))
    warm = [(p, 2) for p in buckets[:-1]]
    warm += [(buckets[-1], int(config["page_size"]) + 1)] * int(mix["slots"])
    warm_seed = seed ^ 0x5EED
    for j, (plen, new) in enumerate(warm):
        prompt = traffic.prompt_tokens(warm_seed, j, plen, m["vocab"])
        engine.submit(system.request(-1 - j, prompt, new))
    while system.busy(engine):
        engine.step()
    n_compiled, spills = engine.compile_count, system.kv_spills(engine)
    t_warm = time.perf_counter()
    offline = mix["kind"] == "offline"
    if offline:
        t = time.time()
        for s in specs:
            drv.submit(s, due=t)
        while (any(r is None for r in engine.active) and engine.scheduler.ready):
            drv.step()
    setup_s = time.perf_counter() - t_start
    log(f"setup {setup_s!r} s (build and warm-up {t_warm - t_start!r} s, filling "
        f"the slots {setup_s - (t_warm - t_start)!r} s) | decode programs compiled "
        f"{n_compiled} | KV pages spilled in warm-up {spills} | requests {len(specs)} | "
        f"slots {mix['slots']} max_len {mix['max_len']}")

    # -- the measured window -------------------------------------------------
    steps: list[record.Step] = []
    pending = [] if offline else list(specs)
    # Removed when the run ends, on an error too (the object's finalizer).
    trace_dir = tempfile.TemporaryDirectory(prefix="bench_trace_") if trace else None
    profiling = False
    counter.start()
    mark = system.recorder_mark(engine)
    t0 = time.time()
    if trace:
        jax.profiler.start_trace(trace_dir.name, profiler_options=_profile_options(jax))
        profiling = True
    trace_steps: list[record.Step] = []
    while True:
        now = time.time()
        if now >= t0 + seconds:
            break
        if (profiling and now >= t0 + TRACE_SECONDS
                and sum(1 for s in trace_steps if s.decode_tokens) >= TRACE_MIN_DECODES
                and any(s.first_tokens for s in trace_steps)):
            jax.profiler.stop_trace()
            profiling = False
        while pending and t0 + pending[0].due_s <= now:
            spec = pending.pop(0)
            drv.submit(spec, due=t0 + spec.due_s)
        if not system.busy(engine):
            if not pending:
                raise RuntimeError("the traffic ran out inside the window")
            wake = min(t0 + pending[0].due_s, t0 + seconds)
            with annotate("bench:wait"):
                time.sleep(max(0.0, wake - time.time()))
            continue
        s = drv.step()
        steps.append(s)
        if profiling:
            trace_steps.append(s)
    t1 = steps[-1].end if steps else time.time()
    if profiling:
        jax.profiler.stop_trace()
    counter.stop()
    admission_s = system.admission_seconds(engine, mark)
    # Requests due in the window get their first token, with no new arrivals.
    stop = t1 + DRAIN_SECONDS
    while (not offline and time.time() < stop
           and any(r.first == 0 for r in drv.reqs.values()) and system.busy(engine)):
        drv.step()
    mem = _memory_peak(jax)
    failed = engine.stats.failed_requests
    finished = [(rid, np.asarray(drv.objs[rid].prompt), list(drv.objs[rid].out_tokens))
                for rid, r in drv.reqs.items() if r.done]
    adm = [s.end - s.start for s in steps if s.first_tokens]
    dec = [s.end - s.start for s in steps if not s.first_tokens and s.decode_tokens]
    log(f"window {t1 - t0!r} s | steps {len(steps)} ({len(adm)} admitting "
        f"{sum(s.first_tokens for s in steps)} requests, step p50 "
        f"{record.percentile(adm, 50)!r} s max {max(adm, default=0.0)!r} s; "
        f"{len(dec)} decode-only, p50 {record.percentile(dec, 50)!r} s) | "
        f"finished requests {len(finished)} | {counter.summary()}")
    if drv.lateness:
        log(f"generator lateness: median {float(np.median(drv.lateness))!r} s "
            f"max {max(drv.lateness)!r} s over {len(drv.lateness)} submissions")
    del engine, drv.objs, drv.live
    drv.engine = None
    gc.collect()

    run = record.Run(model=m, arch=arch, mix=mix, peak=peak, seconds=seconds,
                     t0=t0, t1=t1, steps=steps, requests=list(drv.reqs.values()),
                     setup_s=setup_s, memory_peak_bytes=mem, admission_s=admission_s,
                     trace_steps=trace_steps)
    breakdown = None
    if trace:
        tr = xplane.load(xplane.find_xplane(trace_dir.name))
        kinds = {s.i: s.kind for s in trace_steps}
        run.trace = tr
        run.reduced = xplane.reduce(
            tr, label=lambda sp: kinds.get(int(sp.args.get("i", -1)), sp.name))
        breakdown = {"device_ops": run.reduced["device_ops"],
                     "idle_gaps": run.reduced["idle_gaps"]}
        named = collections.Counter(sp.name for sp in tr.program_spans)
        log("program spans in the profile: "
            + (", ".join(f"{n} x{c}" for n, c in sorted(named.items())) or "none"))
        trace_dir.cleanup()

    # -- metrics ---------------------------------------------------------------
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for metric in man.metrics(cell, kind):
        value = man.reader(metric)(run)
        if value is None:
            continue
        check_share(metric, value)
        metrics[metric["name"]] = {"value": value, "unit": metric["unit"]}

    due = run.due_in_window() if not offline else []
    missing = sum(1 for r in due if r.first == 0)
    attempted = len(due) if not offline else len(
        [r for r in run.requests if any(run.t0 <= t <= run.t1 for t in r.times)])
    log(f"requests: attempted {attempted}, failed {failed + missing} "
        f"(no first token {missing}), finished in all {len(finished)}")
    ttfts = [r.first - r.due for r in due if r.first]
    if ttfts:
        log(f"ttft p50 {record.percentile(ttfts, 50)!r} s over {len(ttfts)} requests")

    # -- correctness -----------------------------------------------------------
    import check

    limits = man.limits(cell)
    res = check.run(man, config, seed, finished, mix, control=control)
    check_out = {"max_logit_gap": {"value": res["gap"], "limit": limits["max_logit_gap"]}}
    if control:
        log(f"control {control} in the program's place; the program's own gap "
            f"{res.get('program_gap')!r}")
    if fault:
        log(f"fault planted: {fault}")
    correct = (res["gap"] <= limits["max_logit_gap"] and failed + missing == 0
               and res["tokens"] > 0)

    device = {"platform": jax.devices()[0].platform,
              "kind": jax.devices()[0].device_kind,
              "count": jax.device_count(), "memory_peak_bytes": mem}
    if trace:
        device["busy_s"] = run.reduced["busy_s"]
        device["window_s"] = run.reduced["window_s"]
    out = {"correct": bool(correct), "attempted": attempted,
           "failed": failed + missing, "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    log(f"check: sample of {res['requests']} requests, {res['tokens']} served "
        f"tokens, longest {res['longest']}")
    log(f"check max_logit_gap {res['gap']!r} limit {limits['max_logit_gap']!r}")
    out["check"] = check_out
    return out


def check_share(metric: dict, value: float) -> None:
    """A share (unit %) outside [0, 100] means the work or the time is
    miscounted; it is an error, never clipped."""
    if metric["unit"] == "%" and not 0.0 <= value <= 100.0:
        raise AssertionError(f"{metric['name']} = {value!r}% is not a share: "
                             f"the work or the time is miscounted")


def _profile_options(jax):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def _memory_peak(jax) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in jax.devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def main(argv=None) -> int:
    t_start = time.perf_counter()
    args = parse_args(argv)
    if args.seed < 0 or args.seconds <= 0 or not math.isfinite(args.seconds):
        raise SystemExit("run.py: --seed must be >= 0 and --seconds > 0")
    man = manifest.Manifest.load(ROOT)
    cell = man.cell(args.workload)
    import jax

    peak = chip_peak(jax, int(cell["chips"]))
    configure_cache(jax)
    out = run_cell(man, cell, args.seed, args.seconds, bool(args.trace), peak,
                   t_start, control=args.control, fault=args.fault)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
