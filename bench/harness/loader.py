"""Find a cell's pieces by the names in BENCHMARK.json.

A configuration is ``configs/<config>.json`` with its plain reference
``configs/<config>.py`` and ``configs/<config>.program.py``, which
builds the program's own configuration from the file; a traffic mix is
``traffic/<traffic>.json``, whose ``driver`` names ``drivers/<driver>.py``;
a cell's correctness limits are ``cells/<workload>.json``; a per-layer
metric is ``metrics/<metric>.py``. Adding a cell adds files and one
BENCHMARK.json entry; nothing here names a cell, a family or a mix."""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def load_module(path: Path) -> ModuleType:
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(path: Path) -> dict:
    return json.loads(Path(path).read_text())


class Cell:
    """One entry of BENCHMARK.json's ``workloads`` with its files."""

    def __init__(self, name: str, bench_file: Path = ROOT / "BENCHMARK.json"):
        self.bench = read_json(bench_file)
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in {bench_file.name} "
                           f"(have {sorted(cells)})")
        self.entry = cells[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        configs = {c["name"]: c for c in self.bench["configs"]}
        self.config_entry = configs[self.entry["config"]]
        self.config_path = ROOT / self.config_entry["file"]
        self.config = read_json(self.config_path)
        self.traffic = read_json(BENCH / "traffic"
                                 / f"{self.entry['traffic']}.json")
        self.limits = read_json(BENCH / "cells" / f"{name}.json")

    def reference(self) -> ModuleType:
        """The plain reference; it imports nothing of the program."""
        return load_module(self.config_path.with_suffix(".py"))

    def program_config(self):
        """The program's configuration object for this configuration."""
        mod = load_module(self.config_path.with_suffix(".program.py"))
        return mod.program_config(self.config)

    def driver(self) -> ModuleType:
        """The driver module the traffic mix names."""
        return load_module(BENCH / "drivers" / f"{self.traffic['driver']}.py")

    def end_to_end(self) -> list:
        return [m for m in self.bench["end_to_end"]
                if self.name in m.get("workloads", [self.name])]

    def per_layer(self) -> list:
        """Per-layer metrics this cell reports: those that list it, and
        those without a list whose ``moves`` metric the cell reports."""
        mine = {m["name"] for m in self.end_to_end()}
        out = []
        for m in self.bench["per_layer"]:
            if "workloads" in m:
                if self.name in m["workloads"]:
                    out.append(m)
            elif m["moves"] in mine:
                out.append(m)
        return out


def metric_reader(name: str) -> ModuleType:
    return load_module(BENCH / "metrics" / f"{name}.py")
