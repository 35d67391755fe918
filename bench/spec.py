"""Resolve a cell of ``BENCHMARK.json`` to the files that define it.

A cell names a configuration and a traffic mix; each metric names its
reader.  Every piece is a file found by that name, so a later change adds a
configuration, a mix or a metric by adding files and entries, and edits
none that are there.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
from dataclasses import dataclass, field
from types import ModuleType

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def check_name(name: str) -> str:
    if not isinstance(name, str) or not NAME.match(name):
        raise ValueError(f"not a valid name: {name!r}")
    return name


def load_module(path: str) -> ModuleType:
    """Import a file by its path; a name with a dot in it is not importable
    by dotted name."""
    if not os.path.exists(path):
        raise FileNotFoundError(path)
    mod_name = "bench_file_" + re.sub(r"\W", "_", os.path.relpath(path, BENCH_DIR))
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Metric:
    name: str
    unit: str
    better: str
    source: str
    kind: str  # "end_to_end" | "per_layer"
    workloads: list[str] | None
    reader: ModuleType = field(repr=False)

    def applies_to(self, cell: str) -> bool:
        return self.workloads is None or cell in self.workloads


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    reference: ModuleType = field(repr=False)
    system: ModuleType = field(repr=False)
    end_to_end: list[Metric]
    per_layer: list[Metric]


class Benchmark:
    """``BENCHMARK.json`` with every name resolved to its file."""

    def __init__(self, root: str = ROOT, doc: dict | None = None):
        self.root = root
        self.bench_dir = os.path.join(root, "bench")
        if doc is None:
            with open(os.path.join(root, "BENCHMARK.json")) as f:
                doc = json.load(f)
        self.configs = {check_name(c["name"]): c for c in doc["configs"]}
        self.workloads = {check_name(w["name"]): w for w in doc["workloads"]}
        self.metrics = [self._metric(m, "end_to_end") for m in doc["end_to_end"]] + [
            self._metric(m, "per_layer") for m in doc["per_layer"]
        ]

    def _metric(self, m: dict, kind: str) -> Metric:
        name = check_name(m["name"])
        if not UNIT.match(m["unit"]):
            raise ValueError(f"{name}: not a valid unit {m['unit']!r}")
        if m["better"] not in ("lower", "higher"):
            raise ValueError(f"{name}: better is {m['better']!r}")
        return Metric(name, m["unit"], m["better"], m["source"], kind, m.get("workloads"), self._reader(kind, name))

    def _reader(self, kind: str, name: str) -> ModuleType:
        """``<name>.py``; a metric split by the end-to-end metric it moves,
        ``<base>.<split>``, may share the reader ``<base>.py``."""
        d = os.path.join(self.bench_dir, "end_to_end" if kind == "end_to_end" else "layer_metrics")
        own = os.path.join(d, name + ".py")
        base = os.path.join(d, name.split(".")[0] + ".py")
        return load_module(base if "." in name and not os.path.exists(own) and os.path.exists(base) else own)

    def config(self, name: str) -> dict:
        entry = self.configs[check_name(name)]
        with open(os.path.join(self.root, entry["file"])) as f:
            cfg = json.load(f)
        if cfg.get("name") != name:
            raise ValueError(f"{entry['file']} names {cfg.get('name')!r}, not {name!r}")
        return cfg

    def traffic(self, name: str) -> dict:
        with open(os.path.join(self.bench_dir, "traffic", check_name(name) + ".json")) as f:
            mix = json.load(f)
        mix.setdefault("name", name)
        return mix

    def cell(self, name: str) -> Cell:
        if name not in self.workloads:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json")
        w = self.workloads[name]
        cfg = self.config(w["config"])
        return Cell(
            name=name,
            chips=int(w["chips"]),
            config=cfg,
            traffic=self.traffic(w["traffic"]),
            reference=load_module(os.path.join(self.bench_dir, "configs", check_name(cfg["reference"]) + ".py")),
            system=load_module(os.path.join(self.bench_dir, "systems", check_name(cfg["system"]) + ".py")),
            end_to_end=[m for m in self.metrics if m.kind == "end_to_end" and m.applies_to(name)],
            per_layer=[m for m in self.metrics if m.kind == "per_layer" and m.applies_to(name)],
        )
