"""The triangles cell: whole runs at a tiny size on the CPU (SCALE 10,
through ``conftest.tiny_root``), its plain reference against brute force,
and its three per-layer readers on a small traced run recorded on a TPU v5e
(``record_served_fixture.py``).  A sound run comes out correct; the control
and two planted faults — the count kept across windows, half of each batch
left out — do not."""

import itertools
import os
import shutil

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness, spec
from gelly_streaming_tpu.core import compile_cache
from gelly_streaming_tpu.library.triangles import WindowTriangleCount

from .conftest import ROOT

CELL = "triangles-g500-s23.open"
SEED = 2**31 + 29
SECONDS = 2.0
FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures", "served", CELL + ".pb")

tri = spec.reference(spec.BENCH_DIR, "triangles")


@pytest.fixture(autouse=True)
def fresh_executables():
    # planted faults change what a kernel computes under the same cache key
    compile_cache.clear()
    yield
    compile_cache.clear()


def _run(tiny_root, control=False):
    bench = spec.Benchmark(tiny_root)
    return harness.run_cell(
        bench, bench.cell(CELL), SEED, SECONDS, False,
        require_tpu=False, control=control,
    )


def test_sound_run_is_correct(tiny_root):
    out = _run(tiny_root)
    line = out.line
    assert line["correct"], line
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["checks"]["mismatched_entries"] == {"value": 0, "limit": 0}
    assert set(line["metrics"]) == {"emit_latency_p50_ms", "setup_s"}
    assert out.window["compiles"] == 0 and out.window["recompiles"] == 0
    assert len(out.window["records_compared"]) >= 2


def test_control_is_not_correct(tiny_root):
    out = _run(tiny_root, control=True)
    assert not out.line["correct"], out.line
    assert out.line["checks"]["mismatched_entries"]["value"] > 0
    assert out.window["served_mismatched_entries"] == 0


_UPDATE = WindowTriangleCount.update


def _half_batch(self, state, src, dst, val, mask):
    keep = mask & (jnp.arange(mask.shape[0]) < mask.shape[0] // 2)
    return _UPDATE(self, state, src, dst, val, keep)


FAULTS = {
    # the running count carried from window to window
    "count_not_reset": {"transient_state": False, "combine": lambda self, a, b: a + b},
    "half_batch_left_out": {"update": _half_batch},
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_is_not_correct(tiny_root, fault, monkeypatch):
    for attr, value in FAULTS[fault].items():
        monkeypatch.setattr(WindowTriangleCount, attr, value)
    out = _run(tiny_root)
    assert not out.line["correct"], out.line
    assert out.line["checks"]["mismatched_entries"]["value"] > 0


# -- the plain reference -------------------------------------------------------


def _brute(src, dst) -> int:
    adj = {}
    for a, b in zip(src.tolist(), dst.tolist()):
        if a != b:
            adj.setdefault(a, set()).add(b)
            adj.setdefault(b, set()).add(a)
    return sum(
        1
        for x, y, z in itertools.combinations(sorted(adj), 3)
        if y in adj[x] and z in adj[x] and z in adj[y]
    )


def test_reference_matches_brute_force_per_window():
    rng = np.random.default_rng(7)
    w = 120
    src = rng.integers(0, 30, 4 * w).astype(np.int32)
    dst = rng.integers(0, 30, 4 * w).astype(np.int32)
    got = dict(tri.states(lambda lo, hi: (src[lo:hi], dst[lo:hi]), [0, 2, 3], w, 30))
    for k, count in got.items():
        assert count == _brute(src[k * w : (k + 1) * w], dst[k * w : (k + 1) * w]) > 0


def test_reference_canon_reads_the_two_limbs():
    assert tri.canon([np.array([5, 2], np.int32)], 8) == 5 + (2 << 30)
    assert tri.mismatches(3, 3) == 0 and tri.mismatches(3, 4) == 1


def test_control_misses_a_hubs_triangles():
    # a hub joined to a 70-vertex path: 69 triangles, and the hub's row
    # keeps only 64 of its neighbours
    path = np.arange(1, 71, dtype=np.int32)
    src = np.concatenate([np.zeros(70, np.int32), path[:-1]])
    dst = np.concatenate([path, path[1:]])
    assert tri.count(src, dst) == 69
    assert tri.capped_count(src, dst) < 69


# -- the per-layer readers on a recorded traced run --------------------------


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    bench_dir = tmp_path_factory.mktemp("served")
    dst = bench_dir / "out" / "trace" / CELL
    dst.mkdir(parents=True)
    shutil.copy(FIXTURE, dst / "small.xplane.pb")
    cell = spec.Benchmark(ROOT).cell(CELL)
    cell.bench_dir = str(bench_dir)
    return {"cell": cell, "peaks": {"hbm_gbps": 819.0}}


def _read(ctx, name):
    return spec.Benchmark(ROOT).metric_reader(name).read(ctx)


def test_readers_are_listed_for_the_cell():
    names = {m["name"] for m in spec.Benchmark(ROOT).cell(CELL).per_layer}
    assert names == {
        "triangle_device_ms_p50.open",
        "triangle_roofline_share.open",
        "tri_slots_per_wedge_p50.open",
    }


def test_device_ms_reads_the_fold_modules(traced):
    spans = spec.load_module(os.path.join(ROOT, "benchmark", "metrics", "_spans.py"))
    folds = spans.load(traced)["modules"]["jit_gelly_fold"]
    assert folds
    assert _read(traced, "triangle_device_ms_p50.open") == spans.median_ms(folds)


def test_roofline_share_is_the_window_bytes_over_the_device_time(traced):
    ms = _read(traced, "triangle_device_ms_p50.open")
    share = _read(traced, "triangle_roofline_share.open")
    least_s = 8 * traced["cell"].config["window_edges"] / 819e9
    assert share == pytest.approx(least_s / (ms / 1e3) * 100.0)
    assert 0 < share <= 100


def test_slots_per_wedge_joins_the_copy_spans_counters(traced):
    spans = spec.load_module(os.path.join(ROOT, "benchmark", "metrics", "_spans.py"))
    copies = spans.load(traced)["spans"]["gelly.sink.copy"]
    tagged = [st for _s, _t, st in copies if st.get("tri_wedges")]
    assert tagged and all("window" in st and "tri_bucket" in st for st in tagged)
    value = _read(traced, "tri_slots_per_wedge_p50.open")
    assert value >= 1
    assert value == pytest.approx(
        float(np.median([st["tri_slots"] / st["tri_wedges"] for st in tagged]))
    )


def test_readers_read_nothing_without_a_trace(tmp_path):
    cell = spec.Benchmark(ROOT).cell(CELL)
    cell.bench_dir = str(tmp_path)
    ctx = {"cell": cell, "peaks": {"hbm_gbps": 819.0}}
    for name in ("triangle_device_ms_p50.open", "triangle_roofline_share.open",
                 "tri_slots_per_wedge_p50.open"):
        assert _read(ctx, name) is None
