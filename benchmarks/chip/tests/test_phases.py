"""Device idle time by engine phase (``phases.py``), on synthetic traces
and on the recorded fixture, which holds no engine spans."""
from pathlib import Path

import pytest

import phases
import xplane

FIXTURE = Path(__file__).parent / "fixtures" / "two_steps.xplane.pb"
S = 1e9     # ns per second


def synthetic(ops, engine):
    """One chip, one harness step over [0, 10] s; ``ops`` and ``engine``
    spans as (start, end) seconds."""
    trace = xplane.Trace([[xplane.Op("fusion", a * S, b * S) for a, b in ops]],
                         [xplane.Span("step", 0.0, 10 * S, {"i": 0})])
    spans = [xplane.Span(name, a * S, b * S, {}) for name, a, b in engine]
    spans.sort(key=lambda s: (s.start, -s.end))
    return trace, spans


ENGINE = [("step", 1, 9), ("admission", 1, 5), ("prefill", 1, 3),
          ("kv_write", 3, 5), ("decode", 5, 8), ("decode.wait", 6, 8)]


def test_a_gap_across_two_spans_splits_at_their_edge():
    # busy [0, 2] and [4, 7]: idle [2, 4] straddles prefill | kv_write,
    # idle [7, 10] straddles decode.wait | step | outside
    trace, spans = synthetic([(0, 2), (4, 7)], ENGINE)
    parts = phases.idle_by_phase(trace, spans)
    assert parts == pytest.approx({
        "step/admission/prefill": 1.0, "step/admission/kv_write": 1.0,
        "step/decode/decode.wait": 1.0, "step": 1.0, "outside": 1.0})
    assert sum(parts.values()) == pytest.approx(10.0 - 5.0)
    assert phases.under(parts, "admission") == pytest.approx(2.0)
    assert phases.under(parts, "decode") == pytest.approx(1.0)
    gaps = phases.longest_gaps(trace, spans, top=1)
    assert gaps[0][0] == pytest.approx(3.0)
    assert gaps[0][1] == pytest.approx(
        {"step/decode/decode.wait": 1.0, "step": 1.0, "outside": 1.0})


def test_a_gap_outside_every_span_goes_to_outside():
    trace, spans = synthetic([(0, 9.5)], [("step", 1, 9)])
    assert phases.idle_by_phase(trace, spans) == pytest.approx({"outside": 0.5})


def test_a_doctored_trace_raises():
    trace, spans = synthetic([(0, 2), (6, 4)], ENGINE)     # an op runs backwards
    with pytest.raises(ValueError, match="end before they start"):
        phases.idle_by_phase(trace, spans)
    trace, spans = synthetic([(0, 2)], [("step", 1, 9), ("admission", 8, 10)])
    with pytest.raises(ValueError, match="without nesting"):
        phases.idle_by_phase(trace, spans)


def test_parts_that_do_not_sum_raise(monkeypatch):
    trace, spans = synthetic([(0, 2), (4, 7)], ENGINE)
    cut = phases._cut
    monkeypatch.setattr(phases, "_cut", lambda a, b, sp: dict(list(cut(a, b, sp).items())[1:]))
    with pytest.raises(ValueError, match="idle parts sum"):
        phases.idle_by_phase(trace, spans)


def test_fixture_without_engine_spans_is_all_outside_and_reduce_is_unchanged():
    trace = xplane.load(str(FIXTURE))
    spans = phases.load(str(FIXTURE))
    assert spans == []
    parts = phases.idle_by_phase(trace, spans)
    r = xplane.reduce(trace, label=lambda s: "decode-only" if s.args["i"] == 1 else "admitting")
    assert list(parts) == ["outside"]
    assert parts["outside"] == pytest.approx(r["window_s"] - r["busy_s"], abs=1e-9)
    # what reduce() returned before the engine spans existed
    assert (r["busy_s"], r["window_s"]) == (0.232247411, 3.332591088)
    assert r["idle_gaps"][:3] == [["admitting", 1.526681156], ["admitting", 1.511335794],
                                  ["admitting", 0.003327473]]
    assert r["device_ops"][0] == ["splitk_gemm", 0.164759431]
    assert [g for g, _ in phases.longest_gaps(trace, spans, top=3)] == [
        1.526681156, 1.511335794, 0.003327473]
