"""The comparison that decides ``correct``, at a size a CPU test holds.

``run_cell`` is driven end to end (the look for a chip skipped) on the
fixture cells: a sound run is correct; a run whose timed decode step is
broken underneath is not, once for each fault a serving cell can have
(``faults.py``); and neither is a run with the control, the reference
computed in a lower precision, in the program's place.  Chip runs repeat
the faults and the control at the cell's own size (``PERF.md``)."""
import json
import time
from pathlib import Path

import numpy as np
import pytest

import check
import faults
import manifest
import run as harness

BENCH = Path(manifest.__file__).resolve().parent
ROOT = BENCH.parents[1]
FIX = Path(__file__).parent / "fixtures"
SEED = 2**33 + 11


@pytest.fixture(scope="module")
def man(tmp_path_factory):
    bench = tmp_path_factory.mktemp("bench")
    for d in ("metrics", "references"):
        (bench / d).symlink_to(BENCH / d)
    for d in ("traffic", "limits"):
        (bench / d).symlink_to(FIX / d)
    doc = json.loads((FIX / "BENCHMARK.json").read_text())
    real = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = [w["name"] for w in doc["workloads"]]
    for k in ("end_to_end", "per_layer"):
        doc[k] = [dict(m, workloads=cells) if "workloads" in m else m for m in real[k]]
    return manifest.Manifest(doc, ROOT, bench)


PEAK = json.loads((BENCH / "peaks.json").read_text())["TPU v5 lite"]


def go(man, cell, seconds=3.0, control="", fault=""):
    return harness.run_cell(man, man.cell(cell), SEED, seconds, False, PEAK,
                            time.perf_counter(), control=control, fault=fault)


def test_sample_keeps_the_longest():
    fin = [(i, np.zeros(4, np.int32), [1] * n) for i, n in enumerate([3, 9, 5, 9, 2])]
    s = check.sample(fin, 5, max_requests=3, min_tokens=100)
    assert s[0][0] == 1 and len(s) == 3
    assert check.sample(fin, 5, 3, 100) == s
    assert len(check.sample(fin, 5, max_requests=10, min_tokens=12)) == 2


def test_gaps():
    ref = np.array([[0.0, 2.0, 1.0], [3.0, 0.0, -1.0]])
    assert check.gaps(ref, np.array([1, 2])).tolist() == [0.0, 4.0]


@pytest.mark.parametrize("cell", ["tiny-coder.tiny-offline", "tiny-chat.tiny-poisson"])
def test_sound_run_is_correct(man, cell):
    out = go(man, cell)
    assert out["correct"], out
    assert list(out)[-1] == "check"
    assert out["check"]["max_logit_gap"]["value"] <= out["check"]["max_logit_gap"]["limit"]
    assert out["attempted"] > 0 and out["failed"] == 0
    for name in ("tokens_per_s", "itl_p90_ms", "setup_s"):
        assert out["metrics"][name]["value"] > 0


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_broken_decode_step_is_not_correct(man, monkeypatch, fault):
    from repro.serving import tiered_decode as TD

    # undone after the test: run_cell plants the fault for the process
    monkeypatch.setattr(TD, "paged_tiered_decode_step", TD.paged_tiered_decode_step)
    out = go(man, "tiny-coder.tiny-offline", fault=fault)
    assert not out["correct"], out["check"]


def test_control_in_the_programs_place_is_not_correct(man):
    """The control is fp8: per-channel int8 weights stay within a few times
    a sound run's gap, at this size and at the cell's own (PERF.md)."""
    out = go(man, "tiny-coder.tiny-offline", control="fp8")
    gap = out["check"]["max_logit_gap"]
    assert not out["correct"] and gap["value"] > gap["limit"], gap
