"""Find the benchmark's pieces by name.

``BENCHMARK.json`` names the cells; each cell names a configuration and a
traffic mix, and each configuration names its generator and reference.
Every piece is a file of its own under the benchmark directory, so a new
cell, mix, generator, reference or per-layer metric is added by adding a
file and an entry, never by editing an existing file:

    configs/<config>.json         (the path BENCHMARK.json gives)
    traffic/<traffic>.json
    generators/<generator>.py     edges(params, seed, start, count)
    references/<reference>.py     states / control_states / canon / mismatches
    metrics/<metric>.py           read(ctx) -> number or None
    peaks.json                    device peaks keyed by device_kind
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
from dataclasses import dataclass
from typing import Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def load_module(path: str):
    """Import one piece from its file (names may hold ``.`` and ``-``)."""
    if not os.path.isfile(path):
        raise FileNotFoundError(f"benchmark piece missing: {path}")
    mod_name = "benchmark_piece_" + "".join(
        c if c.isalnum() else "_" for c in os.path.relpath(path, ROOT)
    )
    mod = sys.modules.get(mod_name)
    if mod is not None and getattr(mod, "__file__", None) == path:
        return mod
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _for_cell(metrics: list, cell: str) -> list:
    return [m for m in metrics if cell in m.get("workloads", [cell])]


@dataclass
class Cell:
    """One workload of BENCHMARK.json with everything it names, loaded."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list
    bench_dir: str = BENCH_DIR

    def to_json(self) -> dict:
        """What the client side of a run needs (it runs in another
        process)."""
        return {
            "name": self.name,
            "config": self.config,
            "traffic": self.traffic,
            "bench_dir": self.bench_dir,
        }


class Benchmark:
    """``BENCHMARK.json`` at ``root`` and the files it names."""

    def __init__(self, root: str = ROOT):
        self.root = root
        self.bench_dir = os.path.join(root, "benchmark")
        self.doc = _read_json(os.path.join(root, "BENCHMARK.json"))

    def cell(self, name: str, traffic_override: Optional[dict] = None) -> Cell:
        by_name = {w["name"]: w for w in self.doc["workloads"]}
        if name not in by_name:
            raise KeyError(
                f"no workload {name!r} in BENCHMARK.json "
                f"(have: {', '.join(sorted(by_name))})"
            )
        w = by_name[name]
        cfg_entry = {c["name"]: c for c in self.doc["configs"]}[w["config"]]
        config = _read_json(os.path.join(self.root, cfg_entry["file"]))
        traffic = _read_json(
            os.path.join(self.bench_dir, "traffic", w["traffic"] + ".json")
        )
        if traffic_override:
            traffic = {**traffic, **traffic_override}
        return Cell(
            name=name,
            chips=int(w["chips"]),
            config=config,
            traffic=traffic,
            end_to_end=_for_cell(self.doc["end_to_end"], name),
            per_layer=_for_cell(self.doc["per_layer"], name),
            bench_dir=self.bench_dir,
        )

    def metric_reader(self, name: str):
        return load_module(os.path.join(self.bench_dir, "metrics", name + ".py"))

    def peaks(self, device_kind: str) -> dict:
        table = _read_json(os.path.join(self.bench_dir, "peaks.json"))
        if device_kind not in table["devices"]:
            raise KeyError(
                f"no published peaks for device kind {device_kind!r} in "
                "peaks.json: add them with their source"
            )
        return table["devices"][device_kind]


def generator(bench_dir: str, name: str):
    return load_module(os.path.join(bench_dir, "generators", name + ".py"))


def reference(bench_dir: str, name: str):
    return load_module(os.path.join(bench_dir, "references", name + ".py"))
