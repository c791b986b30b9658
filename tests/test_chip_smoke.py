"""chip_smoke.py on the CPU at tiny sizes (ISSUE 21).

The smoke's phases run for real only on the chip; here they run at a few
thousand vertices so their checks (oracle, recompiles, shard placement)
cannot rot between chip runs.  The script itself must refuse the CPU.
"""

import os
import subprocess
import sys
from types import SimpleNamespace

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TINY = dict(capacity=1 << 12, window_edges=1 << 10, batch=1 << 9, edges=4 << 10)


@pytest.mark.timeout_cap(180)
def test_script_refuses_the_cpu():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode == 2
    assert '"ok"' not in proc.stdout
    assert "no TPU" in proc.stderr


@pytest.mark.timeout_cap(180)
def test_phase_served_cc_matches_oracle():
    report = chip_smoke.phase_served_cc(0, **TINY)
    assert report["windows"] == 4
    assert report["components_equal_oracle"] and report["seen_equal_oracle"]
    assert report["recompiles_after_warmup"] == 0


@pytest.mark.timeout_cap(180)
def test_phase_sharded_cc_on_four_devices():
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    report = chip_smoke.phase_sharded_cc(0, **TINY)
    assert report["num_shards_4"]["equal_oracle"]
    assert report["num_shards_1"]["equal_oracle"]
    assert report["sharded_state_device_sets"] == [[0, 1, 2, 3]]


@pytest.mark.parametrize(
    "spans, busy_ns",
    [
        ([(0, 10)], 10),
        ([(0, 10), (5, 10)], 15),  # overlapping: counted once
        ([(0, 10), (20, 5)], 15),  # gap: not busy
        ([(20, 5), (0, 30)], 30),  # nested, out of order
    ],
)
def test_busy_seconds_is_the_union_of_spans(spans, busy_ns):
    events = [SimpleNamespace(start_ns=s, duration_ns=d) for s, d in spans]
    assert chip_smoke._busy_seconds(events) == pytest.approx(busy_ns / 1e9)
