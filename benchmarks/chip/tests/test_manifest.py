"""BENCHMARK.json against the contract, and cells found by name."""
import json
import shutil
from pathlib import Path

import pytest

import manifest
import record

BENCH = Path(manifest.__file__).resolve().parent
ROOT = BENCH.parents[1]
DOC = json.loads((ROOT / "BENCHMARK.json").read_text())


def _metrics():
    return DOC["end_to_end"] + DOC["per_layer"]


def _reported(cell: str, kind: str) -> set:
    return {m["name"] for m in DOC[kind] if "workloads" not in m or cell in m["workloads"]}


def test_names_units_and_keys():
    for group in ("configs", "workloads"):
        for e in DOC[group]:
            assert manifest.NAME.match(e["name"]), e["name"]
    for w in DOC["workloads"]:
        assert manifest.NAME.match(w["traffic"]) and manifest.NAME.match(w["config"])
        assert w["chips"] in (1, 4) and 0 < len(w["why"]) <= 200
    for c in DOC["configs"]:
        assert all(manifest.NAME.match(k) for k in c["reduced"])
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    names = [m["name"] for m in _metrics()]
    assert len(names) == len(set(names))
    for m in _metrics():
        assert manifest.NAME.match(m["name"]) and manifest.UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in DOC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert "setup_s" in {m["name"] for m in DOC["end_to_end"]}
    layers = {m["layer"] for m in DOC["per_layer"]}
    assert all("\n" not in lay and len(lay) <= 200 for lay in layers)


def test_every_cell_reports_what_its_layer_metrics_move():
    for w in DOC["workloads"]:
        e2e, per = _reported(w["name"], "end_to_end"), _reported(w["name"], "per_layer")
        assert "setup_s" in e2e and len(e2e) >= 2 and per
        for m in DOC["per_layer"]:
            if m["name"] in per:
                assert m["moves"] in e2e, (w["name"], m["name"])


def test_every_name_resolves_to_a_file():
    man = manifest.Manifest.load(ROOT)
    for w in DOC["workloads"]:
        config, mix = man.config(w), man.traffic(w)
        assert "max_logit_gap" in man.limits(w)
        assert man.reference(config).Reference
        assert mix["slots"] > 0 and config["model"]["n_layers"] > 0
    for m in _metrics():
        assert callable(man.reader(m))
    for c in DOC["configs"]:
        assert (ROOT / c["file"]).is_file() and c["file"].startswith(DOC["paths"][0] + "/")


def test_a_new_config_mix_and_metric_load_with_no_edit(tmp_path):
    """A later PR adds files and entries only: copy the benchmark, add one
    of each, and the harness finds them by name."""
    bench = tmp_path / "bench"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns("tests", "__pycache__"))
    cfg = json.loads((bench / "configs" / "starcoder2-3b.json").read_text())
    cfg["offload_ratio"] = 0.0
    (bench / "configs" / "starcoder2-3b.or0.json").write_text(json.dumps(cfg))
    mix = json.loads((bench / "traffic" / "sharegpt-offline.json").read_text())
    mix["slots"] = 16
    (bench / "traffic" / "sharegpt-offline-16.json").write_text(json.dumps(mix))
    (bench / "limits" / "starcoder2-3b.or0.sharegpt-offline-16.json").write_text(
        '{"max_logit_gap": 1.0}')
    (bench / "metrics" / "steps_in_window.py").write_text(
        "def read(run):\n    return float(len(run.steps))\n")
    doc = json.loads(json.dumps(DOC))
    doc["configs"].append({"name": "starcoder2-3b.or0", "source": "x",
                           "file": "bench/configs/starcoder2-3b.or0.json",
                           "reduced": [], "why": "offload ratio 0"})
    doc["workloads"].append({"name": "starcoder2-3b.or0.sharegpt-offline-16",
                             "config": "starcoder2-3b.or0",
                             "traffic": "sharegpt-offline-16", "chips": 1, "why": "x"})
    doc["per_layer"].append({"name": "steps_in_window", "unit": "steps",
                             "better": "higher", "source": "host_clock",
                             "layer": "decode step", "moves": "tokens_per_s"})
    doc["per_layer"].append({"name": "elsewhere", "unit": "steps", "better": "higher",
                             "source": "host_clock", "layer": "decode step",
                             "moves": "tokens_per_s", "workloads": ["another-cell"]})
    man = manifest.Manifest(doc, tmp_path, bench)
    cell = man.cell("starcoder2-3b.or0.sharegpt-offline-16")
    assert man.config(cell)["offload_ratio"] == 0.0
    assert man.traffic(cell)["slots"] == 16
    assert man.limits(cell)["max_logit_gap"] == 1.0
    names = [m["name"] for m in man.metrics(cell, "per_layer")]
    assert "steps_in_window" in names
    assert "elsewhere" not in names               # listed for another cell only
    run = record.Run(model={}, arch=None, mix={"slots": 16}, peak={}, seconds=1.0,
                     t0=0.0, t1=1.0, steps=[object()] * 3, requests=[], setup_s=1.0,
                     memory_peak_bytes=None)
    metric = next(m for m in doc["per_layer"] if m["name"] == "steps_in_window")
    assert man.reader(metric)(run) == 3.0


def test_unknown_cell_is_an_error():
    with pytest.raises(KeyError):
        manifest.Manifest.load(ROOT).cell("no-such-cell")
