"""The trace reduction, on a small trace recorded on a TPU v5e
(``record_trace_fixture.py``: five 10 ms scatter-adds, 3 ms sleeps)."""

import os

import numpy as np
import pytest

from benchmark import tracereduce

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures")


@pytest.fixture(scope="module")
def profile():
    from jax.profiler import ProfileData

    return ProfileData.from_file(tracereduce.find_xplane(FIXTURE))


def _device_events(profile):
    (plane,) = [p for p in profile.planes if p.name == "/device:TPU:0"]
    return {line.name: list(line.events) for line in plane.lines}


def test_union_merges_overlaps_and_keeps_gaps():
    assert tracereduce.union([(5, 7), (0, 2), (1, 3), (3, 4)]) == [[0, 4], [5, 7]]
    assert tracereduce.union([(0, 10), (2, 3), (20, 25)]) == [[0, 10], [20, 25]]


def test_busy_equals_a_brute_force_sweep(profile):
    lines = _device_events(profile)
    spans = [
        (e.start_ns, e.start_ns + e.duration_ns)
        for name in ("XLA Modules", "XLA Ops")
        for e in lines[name]
    ]
    lo = min(s for s, _ in spans)
    hi = max(e for _, e in spans)
    covered = np.zeros(int(hi - lo), bool)
    for s, e in spans:
        covered[int(s - lo) : int(e - lo)] = True
    got = tracereduce.reduce_profile(profile)
    assert got["busy_s"] == pytest.approx(covered.sum() / 1e9, abs=1e-9)
    # five modules of ~10 ms each
    assert 0.045 < got["busy_s"] < 0.055


def test_gaps_are_the_holes_between_modules_and_name_the_host(profile):
    got = tracereduce.reduce_profile(profile)
    assert len(got["idle_gaps"]) == 4
    for label, seconds in got["idle_gaps"]:
        assert 0.003 < seconds < 0.010
        assert "sleep" in label
    mods = sorted((e.start_ns, e.start_ns + e.duration_ns) for e in _device_events(profile)["XLA Modules"])
    span_s = (mods[-1][1] - mods[0][0]) / 1e9
    gap_s = sum(s for _, s in got["idle_gaps"])
    assert got["busy_s"] + gap_s == pytest.approx(span_s, rel=1e-6)


def test_ops_are_named_by_module(profile):
    got = tracereduce.reduce_profile(profile)
    names = [n for n, _ in got["device_ops"]]
    assert names[0] == "jit__lambda/fusion"
    assert sum(s for _, s in got["device_ops"]) <= got["busy_s"] + 1e-9


def test_no_device_plane_reads_as_no_busy_time():
    class Empty:
        planes = []

    assert tracereduce.reduce_profile(Empty())["busy_s"] == 0.0
