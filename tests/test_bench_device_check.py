"""bench.py refuses to measure anything but a TPU (ISSUE 21).

Without a chip JAX comes up on the CPU; the bench must then print its
partial JSON line with an error and exit 3 before any timed device work,
never a CPU-measured headline.  An unknown device kind has no published
peak and is an error too.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import bench  # noqa: E402  (repo-root module; no jax at import time)


@pytest.mark.timeout_cap(180)
def test_bench_on_cpu_exits_nonzero_without_headline():
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        GELLY_BENCH_EDGES=str(1 << 16),
        GELLY_BENCH_BATCH=str(1 << 14),
        GELLY_BENCH_CPU_TRIALS="1",
        GELLY_BENCH_INGEST="0",
    )
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        cwd=REPO,
        env=env,
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode == 3, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert "no TPU" in line["error"]
    assert line["value"] is None
    assert "device_eps" not in line


def test_unknown_device_kind_has_no_default_peak():
    assert bench.device_peaks("TPU v5 lite")["hbm_gbps"] == 819.0
    with pytest.raises(KeyError, match="no published peaks"):
        bench.device_peaks("cpu")
