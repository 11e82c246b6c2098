"""``BENCHMARK.json`` and the files it names, found by name.

A cell names a configuration and a traffic mix.  The configuration's
``file`` is a JSON file under ``configs/``; the mix is
``traffic/<traffic>.json``; each metric is read by ``metrics/<name>.py``;
a cell's output limits are ``limits/<cell>.json``.  A configuration's
architecture is ``references/<reference>.py``: its weight layout
(``stacks``, ``top_shapes``, ``finish``), the keys checked against the
program (``PROGRAM_KEYS``), the work of a decode step (``gemms``,
``kernel_gemms``, ``kv_bytes_per_token``, ``decode_step``) and its plain
reference (``Reference``, ``CONTROLS``).  A reader sees it as
``Run.arch``, each step's program counters as ``Step.counters`` and the
program's spans as ``Trace.program_spans``.  Adding a cell, a
configuration, a mix, a metric or an architecture adds files and entries
and edits none.
"""
from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path

BENCH = Path(__file__).resolve().parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


class Manifest:
    def __init__(self, doc: dict, root: Path, bench: Path = BENCH):
        self.doc = doc
        self.root = root
        self.bench = bench

    @classmethod
    def load(cls, root: Path, bench: Path = BENCH) -> "Manifest":
        with open(root / "BENCHMARK.json") as fh:
            return cls(json.load(fh), root, bench)

    def cell(self, name: str) -> dict:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload named {name!r} in BENCHMARK.json")

    def config(self, cell: dict) -> dict:
        for c in self.doc["configs"]:
            if c["name"] == cell["config"]:
                return _json(self.root / c["file"])
        raise KeyError(f"no config named {cell['config']!r}")

    def traffic(self, cell: dict) -> dict:
        return _json(self.bench / "traffic" / f"{cell['traffic']}.json")

    def limits(self, cell: dict) -> dict:
        return _json(self.bench / "limits" / f"{cell['name']}.json")

    def metrics(self, cell: dict, kind: str) -> list[dict]:
        """The cell's ``end_to_end`` or ``per_layer`` metrics."""
        return [m for m in self.doc[kind]
                if "workloads" not in m or cell["name"] in m["workloads"]]

    def reader(self, metric: dict):
        return load_module(self.bench / "metrics" / f"{metric['name']}.py").read

    def reference(self, config: dict):
        return load_module(self.bench / "references" / f"{config['reference']}.py")


def _json(path: Path) -> dict:
    with open(path) as fh:
        return json.load(fh)


def load_module(path: Path):
    if not path.exists():
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
