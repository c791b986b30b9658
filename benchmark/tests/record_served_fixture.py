#!/usr/bin/env python3
"""Record the small traced served runs that ``test_span_readers.py`` reads.

    python benchmark/tests/record_served_fixture.py [--seconds 0.6]

Runs on the chip: each cell of BENCHMARK.json, at a small size (SCALE 14,
2^13-edge windows, 2^12-edge pushes, the open loop at 2^18 edges/s),
through the harness with ``--trace 1`` as the benchmark runs it, and
copies each cell's ``.xplane.pb`` to ``fixtures/served/<cell>.pb`` (not
``.xplane.pb``: ``test_tracereduce.py`` takes the newest ``.xplane.pb`` under
``fixtures/``).  Prints each run's result line.
"""

import argparse
import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

SMALL = {"SCALE": 14, "capacity": 1 << 14, "window_edges": 1 << 13, "batch": 1 << 12}
SMALL_RATE = float(1 << 18)
SEED = 2**31 + 23


def small_root(dst: str) -> str:
    """A copy of the benchmark with every configuration cut to ``SMALL``
    and the open loop slowed to ``SMALL_RATE``."""
    from benchmark.tests.conftest import make_tiny_root

    make_tiny_root(dst)
    with open(os.path.join(dst, "BENCHMARK.json")) as f:
        doc = json.load(f)
    for c in doc["configs"]:
        path = os.path.join(dst, c["file"])
        with open(path) as f:
            cfg = json.load(f)
        cfg.update(SMALL)
        with open(path, "w") as f:
            json.dump(cfg, f)
    for w in doc["workloads"]:
        path = os.path.join(dst, "benchmark", "traffic", w["traffic"] + ".json")
        with open(path) as f:
            traffic = json.load(f)
        if traffic["loop"] == "open":
            traffic["rate_edges_per_s"] = SMALL_RATE
        with open(path, "w") as f:
            json.dump(traffic, f)
    return dst


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seconds", type=float, default=0.6)
    args = ap.parse_args()
    import jax

    from benchmark import harness, spec, tracereduce

    if jax.devices()[0].platform != "tpu":
        print("record_served_fixture: no TPU", file=sys.stderr)
        return 3
    out_dir = os.path.join(ROOT, "benchmark", "out")
    os.makedirs(out_dir, exist_ok=True)
    root = small_root(tempfile.mkdtemp(dir=out_dir))
    bench = spec.Benchmark(root)
    for w in bench.doc["workloads"]:
        name = w["name"]
        out = harness.run_cell(bench, bench.cell(name), SEED, args.seconds, True)
        print(json.dumps({"cell": name, **out.line}), flush=True)
        src = tracereduce.find_xplane(os.path.join(bench.bench_dir, "out", "trace", name))
        dst = os.path.join(HERE, "fixtures", "served", name + ".pb")
        os.makedirs(os.path.dirname(dst), exist_ok=True)
        shutil.copy(src, dst)
        print(dst, os.path.getsize(dst), flush=True)
    shutil.rmtree(root)
    return 0


if __name__ == "__main__":
    sys.exit(main())
