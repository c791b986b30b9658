"""The command as the driver runs it: no result without a chip, and none
without the program."""

import os
import shutil
import subprocess
import sys

from .conftest import ROOT

ARGS = ["--workload", "degree-g500-s23.open", "--seed", str(2**31 + 5),
        "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "benchmark/run.py", *ARGS],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def test_no_tpu_exits_nonzero_with_no_result():
    proc = _run(ROOT)
    assert proc.returncode == 3, proc.stderr
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_benchmark_alone_exits_nonzero_with_no_result(tmp_path):
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    proc = _run(tmp_path)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
