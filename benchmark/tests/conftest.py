"""Shared fixtures: a copy of the benchmark at a size a CPU test can hold.

The copy keeps every file and entry of the real benchmark and changes
only the configurations' scale (SCALE 10, 2^10-edge windows, 2^9-edge
pushes) and the open-loop rate, so the tests drive the harness, the client
process, the references and the metric readers exactly as a chip run does.
"""

import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TINY = {"SCALE": 10, "capacity": 1 << 10, "window_edges": 1 << 10, "batch": 1 << 9}
TINY_RATE = 2e5


def make_tiny_root(dst: str) -> str:
    shutil.copytree(
        os.path.join(ROOT, "benchmark"),
        os.path.join(dst, "benchmark"),
        ignore=shutil.ignore_patterns("out", "__pycache__", "tests"),
    )
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    for c in doc["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        cfg.update(TINY)
        with open(os.path.join(dst, c["file"]), "w") as f:
            json.dump(cfg, f)
    for w in doc["workloads"]:
        path = os.path.join(dst, "benchmark", "traffic", w["traffic"] + ".json")
        with open(path) as f:
            traffic = json.load(f)
        if traffic["loop"] == "open":
            traffic["rate_edges_per_s"] = TINY_RATE
        with open(path, "w") as f:
            json.dump(traffic, f)
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f)
    return dst


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    return make_tiny_root(str(tmp_path_factory.mktemp("tiny")))


@pytest.fixture(scope="session")
def real_doc():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)
