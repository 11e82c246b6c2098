"""Metric arithmetic on synthetic request records."""
import json
from pathlib import Path

import pytest

import manifest
import record
import run as harness
import xplane

BENCH = Path(manifest.__file__).resolve().parent
MAN = manifest.Manifest.load(BENCH.parents[1])
PEAK = json.loads((BENCH / "peaks.json").read_text())["TPU v5 lite"]
CONFIG = json.loads((BENCH / "configs" / "starcoder2-3b.json").read_text())
MODEL, ARCH = CONFIG["model"], MAN.reference(CONFIG)


def read(name, run):
    return MAN.reader({"name": name})(run)


def synthetic(stall: float = 0.0, kind: str = "poisson", stalled=(10,)) -> record.Run:
    """4 slots, 20 steps of 0.1 s from t=0; the ``stalled`` steps last
    0.1 + ``stall``, and step 10 also admits a request.  Requests 0-3
    decode throughout; request 4 is due at 0.5 s and admitted in step 10."""
    steps, t = [], 0.0
    for i in range(20):
        dt = 0.1 + (stall if i in stalled else 0.0)
        steps.append(record.Step(i, t, t + dt, 0.08, 4, 1 if i == 10 else 0, 400))
        t += dt
    reqs = []
    for r in range(4):
        reqs.append(record.Req(r, 100, 50, due=-1.0, first=-0.5, admit=-0.6,
                               times=[-0.5] + [s.end for s in steps], done=0.0))
    adm = steps[10]
    reqs.append(record.Req(4, 100, 50, due=0.5, admit=adm.start, first=adm.end - 0.05,
                           times=[adm.end - 0.05] + [s.end for s in steps[10:]]))
    mix = {"kind": kind, "slots": 4}
    return record.Run(model=MODEL, arch=ARCH, mix=mix, peak=PEAK, seconds=2.0,
                      t0=0.0, t1=steps[-1].end, steps=steps, requests=reqs,
                      setup_s=12.5, memory_peak_bytes=13_000_000_000, admission_s=0.3)


def test_window_metrics():
    run = synthetic()
    assert read("tokens_per_s", run) == pytest.approx((20 * 4 + 1) / 2.0)
    assert read("itl_p90_ms", run) == pytest.approx(100.0)
    assert read("batch_occupancy", run) == pytest.approx(100.0)
    assert read("decode_step_ms", run) == pytest.approx(80.0)
    assert read("prefill_ms_per_request", run) == pytest.approx(300.0)
    assert read("peak_hbm_gb", run) == pytest.approx(13.0)
    assert read("setup_s", run) == 12.5


def test_stalled_steps_move_itl_and_tokens_per_s():
    calm, stalled = synthetic(), synthetic(stall=1.5)
    assert read("itl_p90_ms", stalled) == read("itl_p90_ms", calm)   # 1 gap in 20
    many = synthetic(stall=1.5, stalled=(2, 6, 10, 14, 18))   # 5 of 20 steps
    assert read("itl_p90_ms", many) > 10 * read("itl_p90_ms", calm)
    assert read("tokens_per_s", stalled) < read("tokens_per_s", calm)


def test_decode_mfu_is_a_share_of_the_memory_roofline():
    run = synthetic()
    v = read("decode_mfu", run)
    assert 0 < v <= 100
    # 4 requests of 100 tokens: weights dominate, ~6.1 GB at 819 GB/s ~ 7.4 ms of 80 ms
    assert v == pytest.approx(100 * 6.06e9 / 819e9 / 0.08, rel=0.05)


def test_decode_mfu_and_gemm_roofline_read_what_they_read_before():
    """Five steps of StarCoder2-3B, one admitting, and a trace with 150
    ``splitk_gemm`` calls in each decode step: both readers give the values
    they gave when the dense counts lived in work.py."""
    rows = [(0.07, 0.061, 32, 0, 32 * 400), (0.28, 0.0, 0, 2, 0),
            (0.072, 0.0605, 31, 1, 31 * 377 + 5), (0.066, 0.0598, 8, 0, 8 * 1000),
            (0.07, 0.06, 32, 0, 32 * 910)]
    steps, t = [], 0.0
    for i, (dt, dec_s, dec, first, ctx) in enumerate(rows):
        steps.append(record.Step(i, t, t + dt, dec_s, dec, first, ctx))
        t += dt
    ns = 1e9
    ops = []
    for s in steps:
        for j in range(150 if s.decode_tokens else 0):
            a = s.start * ns + 1e5 + j * 3.3e5 + (s.i * 7919 % 101)
            ops.append(xplane.Op("splitk_gemm", a, a + 5.1e4 + 13 * j))
        ops.append(xplane.Op("fusion", s.start * ns, s.start * ns + 2e4))
    ops.sort(key=lambda o: (o.start, -o.end))
    spans = [xplane.Span("step", s.start * ns, s.end * ns, {"i": s.i}) for s in steps]
    run = record.Run(model=MODEL, arch=ARCH, mix={"kind": "offline", "slots": 32},
                     peak=PEAK, seconds=1.0, t0=0.0, t1=t, steps=steps, requests=[],
                     setup_s=1.0, memory_peak_bytes=None, trace=xplane.Trace([ops], spans),
                     trace_steps=steps)
    assert read("decode_mfu", run) == pytest.approx(13.222641777065318, rel=1e-12)
    assert read("splitk_gemm_roofline", run) == pytest.approx(91.29645044324128, rel=1e-12)


def test_share_guard():
    harness.check_share({"name": "x", "unit": "%"}, 99.0)
    with pytest.raises(AssertionError):
        harness.check_share({"name": "x", "unit": "%"}, 105.0)
    harness.check_share({"name": "y", "unit": "ms"}, 105.0)
