"""The span readers (``metrics/_spans.py`` and the eight per-layer metrics
that read it), on small traced served runs recorded on a TPU v5e
(``record_served_fixture.py``: each cell at SCALE 14 for 0.6 s)."""

import os
import shutil
import statistics

import pytest

from benchmark import spec

from .conftest import ROOT

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")

READERS = {
    "cc-g500-s23.backlog": [
        "fold_device_ms_p50.backlog",
        "combine_device_ms_p50.backlog",
        "idle_in_sink_copy_share.backlog",
    ],
    "degree-g500-s23.open": [
        "close_to_host_ms_p50.open",
        "sink_copy_ms_p50.open",
        "results_frame_ms_p50.open",
        "pane_put_ms_p50.open",
        "idle_in_source_wait_share.open",
    ],
}


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """A benchmark directory holding each cell's recorded trace where the
    harness writes a cell's trace: ``<bench_dir>/out/trace/<cell>/``.  The
    fixtures end in ``.pb`` alone, so that ``tracereduce.find_xplane`` over
    ``fixtures/`` keeps finding ``tpu_small.xplane.pb``."""
    bench_dir = tmp_path_factory.mktemp("served")
    for cell in READERS:
        dst = bench_dir / "out" / "trace" / cell
        dst.mkdir(parents=True)
        shutil.copy(
            os.path.join(FIXTURES, "served", cell + ".pb"), dst / "small.xplane.pb"
        )
    return str(bench_dir)


def _ctx(bench_dir, cell):
    return {"cell": spec.Cell(cell, 1, {}, {}, [], [], bench_dir=bench_dir)}


def _reader(name):
    return spec.Benchmark(ROOT).metric_reader(name)


def _spans():
    return spec.load_module(os.path.join(ROOT, "benchmark", "metrics", "_spans.py"))


def test_every_new_metric_is_declared_for_its_cell(real_doc):
    declared = {m["name"]: m for m in real_doc["per_layer"]}
    for cell, names in READERS.items():
        for name in names:
            assert declared[name]["workloads"] == [cell]
            assert declared[name]["source"] == "device_trace"


@pytest.mark.parametrize(
    "cell,name", [(c, n) for c, names in READERS.items() for n in names]
)
def test_each_reader_reads_a_number_from_a_served_trace(served, cell, name):
    value = _reader(name).read(_ctx(served, cell))
    assert isinstance(value, float), (name, value)
    assert value > 0
    if name.startswith("idle_in_"):
        assert value <= 100.0


def test_fold_and_combine_are_read_by_their_stable_names(served):
    trace = _spans().load(_ctx(served, "cc-g500-s23.backlog"))
    assert trace["modules"]["jit_gelly_fold"] and trace["modules"]["jit_gelly_combine"]
    want = statistics.median(t - s for s, t in trace["modules"]["jit_gelly_fold"]) / 1e6
    got = _reader("fold_device_ms_p50.backlog").read(_ctx(served, "cc-g500-s23.backlog"))
    assert got == want


def test_spans_are_inside_the_traced_window_and_share_window_ids(served):
    trace = _spans().load(_ctx(served, "degree-g500-s23.open"))
    spans = trace["spans"]
    busy_lo = min(s for ev in trace["modules"].values() for s, _ in ev)
    for name in ("gelly.pane.cut", "gelly.pane.put", "gelly.sink.wait", "gelly.sink.copy"):
        assert spans[name], name
    cut = {st["window"] for _s, _t, st in spans["gelly.pane.cut"]}
    copy = {st["window"] for _s, _t, st in spans["gelly.sink.copy"]}
    assert len(cut & copy) >= 0.9 * len(copy)
    assert all(s >= busy_lo for ev in spans.values() for s, _t, _st in ev)


def test_close_to_host_joins_by_window_id_not_by_time(served):
    reader = _reader("close_to_host_ms_p50.open")
    # window 1's record reaches the host before window 0's (an out-of-order
    # sink): a join by time would pair each copy with the nearest cut
    trace = {
        "spans": {
            "gelly.pane.cut": [(100, 110, {"window": 0}), (200, 210, {"window": 1})],
            "gelly.sink.copy": [(240, 250, {"window": 1}), (900, 1000, {"window": 0})],
        }
    }
    assert reader.close_to_host_ns(trace) == {0: 900, 1: 50}
    # the fixture: each window's value is its own copy end less its own cut
    trace = _spans().load(_ctx(served, "degree-g500-s23.open"))
    per_window = reader.close_to_host_ns(trace)
    assert per_window
    cuts = {st["window"]: s for s, _t, st in trace["spans"]["gelly.pane.cut"]}
    ends = {st["window"]: t for _s, t, st in trace["spans"]["gelly.sink.copy"]}
    for w, ns in per_window.items():
        assert ns == ends[w] - cuts[w]
    got = reader.read(_ctx(served, "degree-g500-s23.open"))
    assert got == statistics.median(per_window.values()) / 1e6


def test_idle_share_counts_overlap_once():
    trace = {
        "gaps": [(0, 100), (200, 300)],
        "spans": {"x": [(50, 150, {}), (60, 120, {}), (250, 400, {})]},
    }
    assert _spans().idle_share(trace, "x") == pytest.approx(50.0)
    assert _spans().idle_share(trace, "absent") is None


@pytest.mark.parametrize("cell", sorted(READERS))
def test_a_trace_without_the_spans_reads_none(tmp_path, cell):
    """A program without the spans (the earlier tree) gives no reading,
    and no error; so does a run with no trace at all."""
    for name in READERS[cell]:
        assert _reader(name).read(_ctx(str(tmp_path), cell)) is None
    dst = tmp_path / "out" / "trace" / cell
    dst.mkdir(parents=True)
    shutil.copy(os.path.join(FIXTURES, "tpu_small.xplane.pb"), dst / "x.xplane.pb")
    for name in READERS[cell]:
        assert _reader(name).read(_ctx(str(tmp_path), cell)) is None
