"""A configuration, a traffic mix and a per-layer metric are added by
adding files and entries: nothing that exists is edited."""

import json
import os
import shutil

import pytest

from benchmark import spec

from .conftest import ROOT


@pytest.fixture
def extended(tmp_path):
    """A copy of the benchmark with a new config, mix, metric and cell
    dropped in, and only new entries appended to BENCHMARK.json."""
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    bench = os.path.join(root, "benchmark")
    with open(os.path.join(bench, "configs", "cc-throwaway.json"), "w") as f:
        json.dump({"generator": "kronecker", "SCALE": 8, "edgefactor": 4, "A": 0.57,
                   "B": 0.19, "C": 0.19, "graph_seed": 1, "query": "cc", "reference": "cc",
                   "capacity": 256, "window_edges": 64, "batch": 32,
                   "record_bytes_per_vertex": 5, "check_records": 1}, f)
    with open(os.path.join(bench, "traffic", "trickle.json"), "w") as f:
        json.dump({"loop": "open", "rate_edges_per_s": 1000}, f)
    with open(os.path.join(bench, "metrics", "throwaway_ms.py"), "w") as f:
        f.write("def read(ctx):\n    return 1.5 * ctx['seconds_per_window']\n")
    doc["configs"].append({"name": "cc-throwaway", "source": "test",
                           "file": "benchmark/configs/cc-throwaway.json",
                           "reduced": [], "why": "test"})
    doc["workloads"].append({"name": "cc-throwaway.trickle", "config": "cc-throwaway",
                             "traffic": "trickle", "chips": 1, "why": "test"})
    doc["per_layer"].append({"name": "throwaway_ms", "unit": "ms", "better": "lower",
                             "source": "host_clock", "layer": "test", "moves": "setup_s",
                             "workloads": ["cc-throwaway.trickle"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f)
    return root


def test_new_files_are_found_by_name(extended):
    bench = spec.Benchmark(extended)
    cell = bench.cell("cc-throwaway.trickle")
    assert cell.config["capacity"] == 256
    assert cell.traffic["rate_edges_per_s"] == 1000
    assert [m["name"] for m in cell.per_layer] == ["throwaway_ms"]
    assert [m["name"] for m in cell.end_to_end] == ["setup_s"]
    assert bench.metric_reader("throwaway_ms").read({"seconds_per_window": 2.0}) == 3.0
    gen = spec.generator(cell.bench_dir, cell.config["generator"])
    assert gen.num_edges(cell.config) == 4 << 8


def test_existing_cells_keep_their_metrics(extended, real_doc):
    bench = spec.Benchmark(extended)
    for w in real_doc["workloads"]:
        cell = bench.cell(w["name"])
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert cell.per_layer, w["name"]
        for m in cell.per_layer:
            assert callable(bench.metric_reader(m["name"]).read)


def test_unknown_cell_and_unknown_chip_are_errors():
    bench = spec.Benchmark(ROOT)
    with pytest.raises(KeyError):
        bench.cell("no-such-cell")
    with pytest.raises(KeyError):
        bench.peaks("cpu")
    assert bench.peaks("TPU v5 lite")["hbm_gbps"] == 819.0
