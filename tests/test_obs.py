"""Observability layer: trace recorder, metrics registry, flight
recorder, CLI, and the bench regression gate.

The load-bearing property is **bitwise neutrality**: with observability
detached (the default NULL_RECORDER / no flight recorder), the serving
engine produces exactly the same tokens and exactly the same stats block
as a fully-instrumented run — tracing observes the schedule, it never
participates in it.  On top of that the registry's JSON view must
reproduce the legacy ``BENCH_serving.json`` stats block byte-for-byte,
traces must round-trip through the ``repro.obs`` CLI, and a forced
``InvariantViolation`` must leave behind a flight bundle whose last
snapshot is the violating step.
"""
from __future__ import annotations

import collections
import copy
import json
import os
import re
import sys

import jax
import numpy as np
import pytest

import repro.configs as C
from repro.analysis import page_table as PT
from repro.frontend.metrics import ModeledClock
from repro.models import model as M
from repro.obs.cli import main as obs_main
from repro.obs.flight import FlightRecorder, load_bundle, summarize_bundle
from repro.obs.metrics import (
    BENCH_SCHEMA_VERSION,
    MetricsRegistry,
    provenance,
    serving_registry,
)
from repro.obs.trace import (
    ENGINE,
    LINKS,
    REQUESTS,
    TRACE_SCHEMA_VERSION,
    ChromeTraceRecorder,
    NULL_RECORDER,
    summarize_trace,
    validate_trace,
)
from repro.serving.engine import Request, ServingEngine

KEY = jax.random.PRNGKey(0)
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CFG = C.get_smoke("llama2_7b")
_PARAMS = M.init_params(_CFG, KEY)


def _compare_mod():
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    import benchmarks.compare as compare

    return compare


def _run(recorder=None, flight=None, **kw):
    """One deterministic modeled-clock serving run (SLO scheduler,
    chunked prefill, adaptive runtime — every emission site live)."""
    eng = ServingEngine(_CFG, _PARAMS, max_batch=2, max_len=32,
                        global_offload_ratio=0.5, page_size=4,
                        scheduler="slo", prefill_chunk=4, adaptive=True,
                        clock=ModeledClock(), recorder=recorder,
                        flight=flight, **kw)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i,
                    prompt=rng.integers(3, _CFG.vocab, 10).astype(np.int32),
                    max_new_tokens=4, slo_ttft_s=0.5)
            for i in range(4)]
    for r in reqs:
        eng.submit(r)
    stats = eng.run()
    return eng, stats, reqs


def _registry(eng, stats):
    # wall pinned to 1.0 so wall-derived fields are comparable across runs
    return serving_registry(eng, stats, 1.0, meta={
        "arch": "llama2_7b", "smoke": True, "adaptive": True,
        "trace": None, "requests": 4})


# ---------------------------------------------------------------------------
# Bitwise neutrality: tracing off == tracing on
# ---------------------------------------------------------------------------
def test_observability_is_bitwise_neutral(tmp_path):
    eng_off, stats_off, reqs_off = _run()
    eng_on, stats_on, reqs_on = _run(
        recorder=ChromeTraceRecorder(),
        flight=FlightRecorder(str(tmp_path / "flight")))
    assert [r.out_tokens for r in reqs_on] == [r.out_tokens for r in reqs_off]
    rep_off = _registry(eng_off, stats_off).nested()
    rep_on = _registry(eng_on, stats_on).nested()
    # tpot is wall-measured compute time — machine noise, the only
    # non-deterministic field on the modeled clock.
    rep_off.pop("tpot_ms")
    rep_on.pop("tpot_ms")
    assert rep_on == rep_off
    assert list(rep_on) == list(rep_off)        # key order too


def test_null_recorder_is_safe_and_disabled():
    assert not NULL_RECORDER.enabled
    NULL_RECORDER.span(ENGINE, 0, "x", 0.0, 1.0)
    NULL_RECORDER.instant(ENGINE, 0, "x", 0.0)
    NULL_RECORDER.counter(LINKS, "x", 0.0, {"v": 1.0})
    NULL_RECORDER.save("/nonexistent/never-written")   # no-op, no error


def test_modeled_clock_step_durations_are_deterministic():
    """Satellite: telemetry step durations come from the *engine clock*,
    so a modeled-clock replay yields identical achieved-bandwidth figures
    run over run (wall-clock durations would differ every time)."""
    eng_a, _, _ = _run()
    eng_b, _, _ = _run()
    dur_a = [s.duration_s for s in eng_a.runtime.telemetry.ring]
    dur_b = [s.duration_s for s in eng_b.runtime.telemetry.ring]
    assert dur_a == dur_b
    assert all(d > 0 for d in dur_a)
    assert (eng_a.runtime.telemetry.achieved_remote_bw
            == eng_b.runtime.telemetry.achieved_remote_bw)


# ---------------------------------------------------------------------------
# Trace content + round-trip
# ---------------------------------------------------------------------------
def test_trace_contents_cover_engine_links_and_requests():
    rec = ChromeTraceRecorder(metadata={"arch": "llama2_7b"})
    _run(recorder=rec)
    doc = rec.to_json()
    assert validate_trace(doc) == []
    evs = doc["traceEvents"]
    spans = {e["name"] for e in evs if e["ph"] == "X"}
    assert "admission" in spans
    assert "decode" in spans
    assert any(s.startswith("prefill[") for s in spans)
    assert {"queued", "active"} <= spans          # request lifecycle
    counters = {e["name"] for e in evs if e["ph"] == "C"}
    assert {"link_bytes", "window", "queue_depth", "health"} <= counters
    instants = {e["name"] for e in evs if e["ph"] == "i"}
    assert {"submit", "first_token"} <= instants
    # lifecycle spans live on the requests process, one track per rid
    req_tracks = {e["tid"] for e in evs
                  if e["ph"] == "X" and e["pid"] == REQUESTS}
    assert req_tracks == {0, 1, 2, 3}
    # every span timestamp is modeled-clock microseconds, non-negative
    assert all(e["ts"] >= 0 for e in evs if e["ph"] != "M")


def test_trace_save_load_summarize_roundtrip(tmp_path):
    rec = ChromeTraceRecorder()
    _run(recorder=rec)
    path = str(tmp_path / "trace.json")
    rec.save(path)
    with open(path) as fh:
        doc = json.load(fh)
    assert validate_trace(doc) == []
    summ = summarize_trace(doc)
    assert summ["schema_version"] == TRACE_SCHEMA_VERSION
    assert summ["processes"] == {ENGINE: "engine", LINKS: "links",
                                 REQUESTS: "requests"}
    assert summ["spans"]["decode"]["count"] > 0
    assert summ["events"] > 0 and summ["span_us"] > 0
    # CLI round-trip on the same file
    assert obs_main(["validate", path]) == 0
    assert obs_main(["summarize", path]) == 0


def test_validate_trace_catches_malformed_events():
    assert validate_trace([]) == ["trace document is not a JSON object"]
    assert validate_trace({}) == ["missing traceEvents list"]
    doc = {"traceEvents": [{"ph": "X"}, {"ph": "?", "name": "x", "pid": 1,
                                         "tid": 0, "ts": 0.0}],
           "otherData": {"schema_version": TRACE_SCHEMA_VERSION}}
    errors = validate_trace(doc)
    assert any("missing keys" in e for e in errors)
    assert any("unknown phase" in e for e in errors)


# ---------------------------------------------------------------------------
# Engine phases on the profiler's clock, the compile meter, the HLO
# ---------------------------------------------------------------------------
# Each phase's enclosing phase (None: the outermost).
_PARENT = {"step": None, "admission": "step", "decode": "step",
           "finish": "step", "prefill": "admission",
           "prefill.sync": "admission", "kv_write": "admission",
           "decode.prep": "decode", "decode.wait": "decode"}


def _wall_engine(recorder=None):
    """A wall-clock engine (FCFS, whole prompts, compiled decode step)
    with 3 requests for 2 slots, so one request is admitted mid-run."""
    eng = ServingEngine(_CFG, _PARAMS, max_batch=2, max_len=32,
                        global_offload_ratio=0.5, page_size=4,
                        recorder=recorder)
    rng = np.random.default_rng(1)
    for i in range(3):
        eng.submit(Request(rid=i, max_new_tokens=3,
                           prompt=rng.integers(3, _CFG.vocab, 6 + 2 * i
                                               ).astype(np.int32)))
    return eng


def _profiled_engine_spans(tmp_path, eng):
    """Run ``eng`` to completion under jax.profiler; return the
    ``engine:`` events of the profile by plane name, and the step count."""
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        steps = 0
        while eng.scheduler.waiting or any(r is not None for r in eng.active):
            eng.step()
            steps += 1
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    by_plane: dict[str, list] = {}
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("engine:"):
                    by_plane.setdefault(plane.name, []).append(
                        (e.name[len("engine:"):], e.start_ns, e.end_ns,
                         line.name, dict(e.stats)))
    return by_plane, steps


def test_engine_phases_nest_on_the_profiler_host_plane(tmp_path):
    rec = ChromeTraceRecorder()
    eng = _wall_engine(rec)
    by_plane, steps = _profiled_engine_spans(tmp_path, eng)
    rec.close()
    assert list(by_plane) == ["/host:CPU"]
    spans = sorted(by_plane["/host:CPU"], key=lambda s: (s[1], -s[2]))
    assert {s[0] for s in spans} == set(_PARENT)
    assert len({s[3] for s in spans}) == 1          # one thread's line
    for name, start, end, _, _ in spans:
        enclosing = [s for s in spans
                     if s[1] <= start and end <= s[2] and s[:3] != (name, start, end)]
        parent = max(enclosing, key=lambda s: s[1])[0] if enclosing else None
        assert parent == _PARENT[name], (name, parent)
    count = collections.Counter(s[0] for s in spans)
    assert count["step"] == steps
    assert count["decode"] == eng.stats.decode_steps
    assert count["prefill.sync"] == 3 == eng.stats.served
    assert {s[4]["rid"] for s in spans if s[0] in ("prefill", "kv_write")} == {0, 1, 2}
    # The Chrome spans hold the same intervals, on the engine's clock.
    chrome = [e for e in rec.events if e["ph"] == "X" and e["pid"] == ENGINE
              and e["name"] != "compile"]
    chrome_by: dict[str, list] = collections.defaultdict(list)
    for e in chrome:
        chrome_by["prefill" if e["name"].startswith("prefill[") else e["name"]
                  ].append((e["ts"] * 1e3, (e["ts"] + e["dur"]) * 1e3))
    prof_by: dict[str, list] = collections.defaultdict(list)
    for name, start, end, _, _ in spans:
        prof_by[name].append((start, end))
    assert {k: len(v) for k, v in chrome_by.items()} == {
        k: len(v) for k, v in prof_by.items()}
    pairs = [(c, p) for k in prof_by
             for c, p in zip(sorted(chrome_by[k]), sorted(prof_by[k]))]
    offset = float(np.median([c[0] - p[0] for c, p in pairs]))
    for c, p in pairs:                      # ns, after aligning the clocks
        assert abs(c[0] - offset - p[0]) < 20e6 and abs(c[1] - offset - p[1]) < 20e6


def _lowered_decode_step(recorder):
    """The StableHLO of the engine's compiled paged decode step, lowered
    inside the call, where the engine's phases are open."""
    eng = _wall_engine(recorder)
    texts = []
    compiled_step = eng._compiled_step

    def spy(kind):
        fn = compiled_step(kind)

        def call(*args):
            texts.append(fn.lower(*args).as_text(debug_info=True))
            return fn(*args)
        return call

    eng._compiled_step = spy
    eng.step()
    recorder.close()
    assert texts
    return texts[0]


def test_phases_leave_the_compiled_decode_step_unchanged():
    # one call site for both, as the source locations are in the text
    traced, untraced = [_lowered_decode_step(rec)
                        for rec in (ChromeTraceRecorder(), NULL_RECORDER)]
    assert traced == untraced


def test_kernel_names_the_benchmark_reads_name_the_jitted_wrappers():
    """The chip benchmark finds the kernels in the device trace by these
    names (``splitk_gemm_roofline`` and its breakdown): a rename of the
    jitted wrappers has to fail here first."""
    text = _lowered_decode_step(NULL_RECORDER)
    for name in ("splitk_gemm", "paged_splitk_flashattn"):
        assert re.search(rf"func\.func private @{name}(_\d+)?\(", text), name


def test_compile_meter_books_builds_to_their_phase():
    from jax._src import monitoring

    listeners = len(monitoring.get_event_duration_listeners())
    rec = ChromeTraceRecorder()
    assert len(monitoring.get_event_duration_listeners()) == listeners + 1
    eng = _wall_engine(rec)
    meter = rec.meter
    built = set(meter.counts)              # the engine's construction
    assert {path for path, _ in built} <= {"outside"}
    eng.step()                        # two eager prefills, a fresh decode bucket
    assert eng.compile_count == 1
    assert meter.counts[("step/decode", "jaxpr_trace")] >= 1   # and nested jits
    assert meter.counts[("step/decode", "lower")] == 1
    assert meter.counts[("step/decode", "backend_compile")] == 1
    assert meter.seconds(under="decode") > 0
    assert meter.seconds(under="admission") > 0
    assert all(path.startswith("step/") for path, _ in set(meter.counts) - built)
    spans = [e for e in rec.events if e["ph"] == "X" and e["name"] == "compile"]
    assert len(spans) == sum(meter.counts.values())
    assert {e["args"]["phase"] for e in spans} == {p for p, _ in meter.counts}
    booked = dict(meter.counts)
    rec.close()
    assert len(monitoring.get_event_duration_listeners()) == listeners
    jax.jit(lambda x: x * 3 + 1)(np.arange(5))      # a build nobody books
    assert dict(meter.counts) == booked


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------
def test_registry_counter_rejects_decrease_and_duplicates():
    reg = MetricsRegistry()
    c = reg.counter("a")
    c.inc(2)
    with pytest.raises(ValueError):
        c.inc(-1)
    with pytest.raises(ValueError):
        reg.counter("a")
    assert reg.value("a") == 2


def test_registry_nested_preserves_registration_order():
    reg = MetricsRegistry()
    reg.const("b", 1)
    reg.gauge("a.x").set(2)
    reg.counter("a.y").inc(3)
    reg.gauge("hidden", in_json=False).set(9)
    out = reg.nested()
    assert list(out) == ["b", "a"]
    assert list(out["a"]) == ["x", "y"]
    assert out == {"b": 1, "a": {"x": 2, "y": 3}}   # in_json=False excluded


def test_registry_nested_detects_collisions():
    reg = MetricsRegistry()
    reg.const("a", 1)
    reg.gauge("a.b")
    with pytest.raises(ValueError, match="nests under"):
        reg.nested()
    reg2 = MetricsRegistry()
    reg2.gauge("x.y")
    reg2.const("x", {"y": 1})
    with pytest.raises(ValueError, match="collides"):
        reg2.nested()


def test_registry_prometheus_exposition_format():
    reg = MetricsRegistry()
    reg.counter("kv.spills", "pressure spills").inc(3)
    reg.gauge("global_ratio").set(0.5)
    reg.const("arch", "llama2_7b")              # string: skipped in prom
    reg.const("window", {"static": 4, "name": "x"})
    h = reg.histogram("ttft_seconds", "ttft")
    h.extend([0.1, 0.2, 0.3, 0.4])
    text = reg.to_prometheus()
    assert "# HELP dak_kv_spills pressure spills" in text
    assert "# TYPE dak_kv_spills counter" in text
    assert "dak_kv_spills 3" in text
    assert "dak_global_ratio 0.5" in text
    assert "dak_window_static 4" in text        # numeric leaf of a const dict
    assert "llama2_7b" not in text              # strings never exported
    assert 'dak_ttft_seconds{quantile="0.5"}' in text
    assert "dak_ttft_seconds_count 4" in text
    hv = h.value()
    assert hv["count"] == 4 and hv["sum"] == pytest.approx(1.0)
    assert hv["p50"] == pytest.approx(0.25, abs=0.06)


def test_serving_registry_carries_provenance_identity():
    eng, stats, _ = _run()
    prov = provenance(eng, arch="llama2_7b")
    assert prov["clock"] == "modeled"
    assert prov["scheduler"] == "slo"
    assert prov["mesh_shape"] == [1]
    assert BENCH_SCHEMA_VERSION == 2


# ---------------------------------------------------------------------------
# Flight recorder: red path
# ---------------------------------------------------------------------------
def test_invariant_violation_dumps_flight_bundle(tmp_path):
    flight = FlightRecorder(str(tmp_path), capacity=8)
    rec = ChromeTraceRecorder()
    eng = ServingEngine(_CFG, _PARAMS, max_batch=2, max_len=32,
                        global_offload_ratio=0.5, page_size=4,
                        check_invariants=True, clock=ModeledClock(),
                        recorder=rec, flight=flight)
    eng.submit(Request(rid=0, prompt=np.arange(3, 9).astype(np.int32),
                       max_new_tokens=8))
    eng.step()                               # healthy step passes the audit
    assert eng.pcache is not None
    eng.pcache.free[PT.LOCAL].append(99)     # corrupt: phantom free page
    with pytest.raises(PT.InvariantViolation):
        eng.run()
    assert len(flight.dumped) == 1
    bundle = load_bundle(flight.dumped[0])
    summ = summarize_bundle(bundle)
    assert summ["reason"] == "InvariantViolation"
    assert "DAK301" in summ["error"]
    # the final snapshot is the violating step's state
    assert summ["last_step"] == eng.stats.decode_steps
    assert summ["last_snapshot"]["pages"]["spills"] == eng.pcache.spills
    assert summ["snapshots"] >= 2            # ring + failure snapshot
    assert summ["trace_tail_events"] > 0     # traced run → tail travels


def test_flight_bundle_cli_summarize_and_convert(tmp_path):
    flight = FlightRecorder(str(tmp_path), capacity=4)
    eng = ServingEngine(_CFG, _PARAMS, max_batch=2, max_len=32,
                        global_offload_ratio=0.5, page_size=4,
                        check_invariants=True, clock=ModeledClock(),
                        recorder=ChromeTraceRecorder(), flight=flight)
    eng.submit(Request(rid=0, prompt=np.arange(3, 9).astype(np.int32),
                       max_new_tokens=8))
    eng.step()
    eng.pcache.free[PT.LOCAL].append(99)
    with pytest.raises(PT.InvariantViolation):
        eng.run()
    path = flight.dumped[0]
    assert obs_main(["summarize", path]) == 0
    out = str(tmp_path / "tail.json")
    assert obs_main(["convert", path, "-o", out]) == 0
    with open(out) as fh:
        assert validate_trace(json.load(fh)) == []
    # validate refuses a bundle (it is not a trace)
    assert obs_main(["validate", path]) == 1


def test_flight_ring_is_bounded_and_breach_threshold_works(tmp_path):
    flight = FlightRecorder(str(tmp_path), capacity=4, slo_breach_s=0.25)
    for i in range(20):
        flight.record({"step": i})
    assert not flight.breached(0.2)
    assert flight.breached(0.3)
    path = flight.dump("slo_breach", final_snapshot={"step": 99})
    bundle = load_bundle(path)
    assert bundle["steps"] == [16, 17, 18, 19, 99]   # ring capped at 4


# ---------------------------------------------------------------------------
# Bench regression gate
# ---------------------------------------------------------------------------
def _fake_report():
    return {
        "schema_version": BENCH_SCHEMA_VERSION,
        "served": 4, "generated_tokens": 16, "decode_steps": 10,
        "ttft_p95_ms": 1.0, "queue_delay_p95_ms": 0.5, "e2e_p95_ms": 3.0,
        "scheduling": {"prefill_chunks": 3, "preemptions": 1},
        "kv": {"spills": 0, "local_pages_hwm": 5, "remote_pages_hwm": 2},
        "failed_requests": 0,
        "modeled": {"makespan_s": 0.16, "tokens_per_modeled_s": 100.0},
        "provenance": {"git_rev": "abc", "arch": "llama2_7b",
                       "config": "ModelConfig", "clock": "modeled",
                       "scheduler": "slo", "mesh_shape": [1], "jax": "x"},
    }


def _gate(tmp_path, baseline, candidate):
    compare = _compare_mod()
    b, c = str(tmp_path / "b.json"), str(tmp_path / "c.json")
    for p, rep in ((b, baseline), (c, candidate)):
        with open(p, "w") as fh:
            json.dump(rep, fh)
    return compare.main([b, c])


def test_compare_passes_identical_reports(tmp_path):
    assert _gate(tmp_path, _fake_report(), _fake_report()) == 0


def test_compare_fails_on_count_and_modeled_regressions(tmp_path):
    cand = _fake_report()
    cand["generated_tokens"] = 12                    # exact gate
    assert _gate(tmp_path, _fake_report(), cand) == 1
    cand = _fake_report()
    cand["modeled"]["tokens_per_modeled_s"] = 80.0   # -20% > 5% tolerance
    assert _gate(tmp_path, _fake_report(), cand) == 1
    cand = _fake_report()
    cand["ttft_p95_ms"] = 1.05                       # +5% within 10%
    assert _gate(tmp_path, _fake_report(), cand) == 0
    cand = _fake_report()
    del cand["modeled"]                              # gated block vanished
    assert _gate(tmp_path, _fake_report(), cand) == 1


def test_compare_improvements_never_fail(tmp_path):
    cand = _fake_report()
    cand["modeled"]["tokens_per_modeled_s"] = 200.0
    cand["ttft_p95_ms"] = 0.1
    assert _gate(tmp_path, _fake_report(), cand) == 0


def test_compare_refuses_incomparable_reports(tmp_path):
    cand = _fake_report()
    cand["provenance"]["arch"] = "qwen3_moe_30b_a3b"
    assert _gate(tmp_path, _fake_report(), cand) == 2
    cand = _fake_report()
    cand["schema_version"] = BENCH_SCHEMA_VERSION + 1
    assert _gate(tmp_path, _fake_report(), cand) == 2
    # git_rev drift is the whole point of the gate — never a refusal
    cand = _fake_report()
    cand["provenance"]["git_rev"] = "def"
    assert _gate(tmp_path, _fake_report(), cand) == 0


def test_checked_in_baseline_matches_current_schema():
    compare = _compare_mod()
    path = os.path.join(ROOT, "benchmarks", "baselines",
                        "serving_smoke_slo.json")
    with open(path) as fh:
        baseline = json.load(fh)
    assert baseline["schema_version"] == BENCH_SCHEMA_VERSION
    prov = baseline["provenance"]
    for field in compare.IDENTITY_FIELDS:
        assert field in prov
    # every gated path that should exist on the modeled clock does
    for g in compare.GATES:
        assert compare._lookup(baseline, g.path) is not None
