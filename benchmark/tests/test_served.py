"""Whole runs of each cell at a tiny size on the CPU: the harness, the
client process, the served path and the check.  A sound run comes out
correct; each fault a cell can have, planted in the program underneath
the timed path, makes ``correct`` false; and the control does not pass
the comparison.  The chip check is skipped (``require_tpu=False``); the
rest is the run as the benchmark makes it."""

import jax.numpy as jnp
import pytest

from benchmark import harness, spec
from gelly_streaming_tpu.core import compile_cache
from gelly_streaming_tpu.library.connected_components import CCState, _CCMixin
from gelly_streaming_tpu.library.degree_distribution import (
    DegreeDistributionSummary,
    DegreeSummaryState,
)

CELLS = ["cc-g500-s23.backlog", "degree-g500-s23.open"]
SEED = 2**31 + 11
SECONDS = 2.0


@pytest.fixture(autouse=True)
def fresh_executables():
    # planted faults change what a kernel computes under the same cache key
    compile_cache.clear()
    yield
    compile_cache.clear()


def _run(tiny_root, name, control=False):
    bench = spec.Benchmark(tiny_root)
    return harness.run_cell(
        bench, bench.cell(name), SEED, SECONDS, False,
        require_tpu=False, control=control,
    )


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(tiny_root, name):
    out = _run(tiny_root, name)
    line = out.line
    assert line["correct"], line
    assert line["failed"] == 0 and line["attempted"] > 0
    assert list(line)[-1] == "checks"
    assert line["checks"]["mismatched_entries"] == {"value": 0, "limit": 0}
    assert {m for m in line["metrics"]} == {
        m["name"] for m in spec.Benchmark(tiny_root).cell(name).end_to_end
    }
    assert out.window["compiles"] == 0 and out.window["recompiles"] == 0
    assert len(out.window["records_compared"]) >= 2


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(tiny_root, name, monkeypatch):
    if name.startswith("degree"):
        # at this size no degree reaches int16's range: let the control
        # keep int8, its analogue one step down from this size's counts
        ref = spec.reference(spec.Benchmark(tiny_root).bench_dir, "degree")
        monkeypatch.setattr(
            ref, "control_states",
            lambda e, ks, w, c: ref._degrees(e, ks, w, c, jnp.int8.dtype),
        )
    out = _run(tiny_root, name, control=True)
    assert not out.line["correct"], out.line
    assert out.line["checks"]["mismatched_entries"]["value"] > 0
    assert out.window["served_mismatched_entries"] == 0


def _unchanged(self, state, src, dst, val, mask):
    return state


def _half_cc(self, state, src, dst, val, mask):
    keep = mask & (jnp.arange(mask.shape[0]) < mask.shape[0] // 2)
    return _CC_UPDATE(self, state, src, dst, val, keep)


def _half_degree(self, state, src, dst, val, mask):
    keep = mask & (jnp.arange(mask.shape[0]) < mask.shape[0] // 2)
    return _DEG_UPDATE(self, state, src, dst, val, keep)


def _altered_cc(self, state):
    return _CC_TRANSFORM(self, CCState(state.parent, state.seen.at[0].set(~state.seen[0])))


def _altered_degree(self, state):
    return _DEG_TRANSFORM(self, DegreeSummaryState(state.deg.at[0].add(1)))


_CC_UPDATE = _CCMixin.update
_DEG_UPDATE = DegreeDistributionSummary.update
_CC_TRANSFORM = _CCMixin.transform
_DEG_TRANSFORM = DegreeDistributionSummary.transform

FAULTS = {
    "state_unchanged": {"cc": ("update", _unchanged), "degree": ("update", _unchanged)},
    "half_batch_left_out": {"cc": ("update", _half_cc), "degree": ("update", _half_degree)},
    "answer_altered": {"cc": ("transform", _altered_cc), "degree": ("transform", _altered_degree)},
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("name", CELLS)
def test_planted_fault_is_not_correct(tiny_root, name, fault, monkeypatch):
    kind = name.split("-")[0]
    attr, fn = FAULTS[fault][kind]
    cls = _CCMixin if kind == "cc" else DegreeDistributionSummary
    monkeypatch.setattr(cls, attr, fn)
    out = _run(tiny_root, name)
    assert not out.line["correct"], out.line
    assert out.line["checks"]["mismatched_entries"]["value"] > 0
