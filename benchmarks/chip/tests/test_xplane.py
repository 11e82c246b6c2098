"""The trace reduction, on two steps recorded on a TPU v5e (StarCoder2-3B,
8 slots: a decode-only step, then a step that admits two requests)."""
from pathlib import Path

import pytest

import phases
import xplane

FIXTURE = Path(__file__).parent / "fixtures" / "two_steps.xplane.pb"


@pytest.fixture(scope="module")
def trace():
    return xplane.load(str(FIXTURE))


def test_planes_and_spans(trace):
    assert len(trace.devices) == 1
    assert [s.args["i"] for s in trace.spans] == [1, 2]
    names = {o.name for o in trace.devices[0]}
    assert {"splitk_gemm", "paged_splitk_flashattn"} <= names


def test_reduction(trace):
    r = xplane.reduce(trace, label=lambda s: "decode-only" if s.args["i"] == 1 else "admitting")
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["window_s"] == pytest.approx(3.3326, abs=1e-3)
    assert r["device_ops"][0][0] == "splitk_gemm"
    assert sum(v for _, v in r["device_ops"]) <= r["busy_s"] + 1e-9
    # the admitting step's two eager prefills leave the chip idle ~1.5 s each
    assert [lab for lab, _ in r["idle_gaps"][:2]] == ["admitting", "admitting"]
    assert r["idle_gaps"][0][1] > 1.0
    assert len(r["device_ops"]) <= 10 and len(r["idle_gaps"]) <= 10


def test_kernel_time_per_step(trace):
    one = xplane.op_time_in(trace, {"splitk_gemm"}, trace.spans[:1])
    assert one == pytest.approx(0.0823, rel=0.01)           # 151 calls
    n = sum(1 for o in trace.devices[0]
            if o.name == "splitk_gemm" and trace.spans[0].start <= o.start < trace.spans[0].end)
    assert n == 151


def test_self_time_does_not_count_nesting_twice():
    ops = [xplane.Op("while", 0, 100), xplane.Op("fusion", 10, 30),
           xplane.Op("fusion", 40, 60), xplane.Op("copy", 120, 130)]
    st = xplane.self_times(ops, 0, 1000)
    assert st == {"while": 60, "fusion": 40, "copy": 10}
    assert xplane.busy(ops, 0, 1000) == [(0, 100), (120, 130)]


def test_instruction_names():
    assert xplane.instruction("%splitk_gemm.274 = bf16[128] custom-call()") == "splitk_gemm"
    assert xplane.instruction("%paged_splitk_flashattn = bf16[8]") == "paged_splitk_flashattn"
    assert xplane.instruction("%slice_bitcast_fusion.2 = (bf16[1])") == "slice_bitcast_fusion"


def test_program_spans_are_kept_apart_from_the_harness_spans(tmp_path):
    """A profile recorded here, on the CPU, with the profiler options a
    traced run uses: the engine's annotations are program spans, and the
    harness spans, and so the window, are the ``bench:`` ones alone."""
    import jax
    import jax.numpy as jnp

    import run as harness

    f = jax.jit(lambda x: x @ x)
    x = jnp.ones((32, 32))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path), profiler_options=harness._profile_options(jax))
    try:
        with jax.profiler.TraceAnnotation("bench:step", i=0):
            with jax.profiler.TraceAnnotation("engine:step"):
                with jax.profiler.TraceAnnotation("engine:decode", n=3):
                    f(x).block_until_ready()
            with jax.profiler.TraceAnnotation("spec:draft"):
                jnp.sum(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    trace = xplane.load(xplane.find_xplane(str(tmp_path)))
    assert [(s.name, s.args) for s in trace.spans] == [("step", {"i": 0})]
    assert [s.name for s in trace.program_spans] == [
        "engine:step", "engine:decode", "spec:draft"]
    assert trace.program_spans[1].args == {"n": 3}
    step = trace.spans[0]
    assert all(step.start <= s.start <= s.end <= step.end for s in trace.program_spans)
    assert xplane.window(trace) == (step.start, step.end)
    assert [s.name for s in phases.engine_spans(trace)] == ["step", "decode"]
