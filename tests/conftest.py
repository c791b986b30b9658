"""Test configuration: force a virtual 8-device CPU mesh before jax imports.

This is the MiniCluster analog (SURVEY.md §4): the reference tests "distributed"
execution on an in-JVM Flink MiniCluster with multiple task slots; here we test
multi-shard SPMD on one host by splitting the CPU backend into 8 XLA devices.
Must run before jax initializes, hence module-level in conftest.
"""

import os
import sys

# XLA's CPU client sizes its worker pools from the detected core count (1
# here); with 8 virtual devices the partitions' blocking collective waits
# can then hold every pool worker — a schedule-dependent in-process
# DEADLOCK (observed: rare multi-minute stalls / 40 s-timeout aborts on
# ppermute-heavy tests).  NPROC is the pool-size override the client
# honors: 16 workers mean 8 waiting partitions can never exhaust the pool.
os.environ.setdefault("NPROC", "16")

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    _flags = (_flags + " --xla_force_host_platform_device_count=8").strip()
# 8 virtual devices on few (here: one) physical cores: a starved
# partition thread can miss XLA's default 40 s collective rendezvous,
# which abort()s the whole pytest process (observed intermittently on
# the ppermute-heavy mesh tests under host load).  Starvation must be a
# slow test, never suite death.  (Per-flag guards: never shadow a
# user-set value with an appended duplicate.)
#
# NOT every XLA build knows these flags — and XLA FATALLY aborts the whole
# process on an unknown XLA_FLAGS entry (parse_flags_from_env.cc), killing
# the suite before pytest prints a byte.  Probe support in a throwaway
# subprocess first and only append the flags a real jax init accepts.


def _xla_accepts(flag: str) -> bool:
    """Probe once per jax version, caching the verdict on disk: the probe
    costs a full cold jax init (~seconds), too much to pay per pytest run."""
    import subprocess
    import tempfile

    try:
        from importlib.metadata import version

        ver = version("jax")
    except Exception:
        ver = "unknown"
    marker = os.path.join(
        tempfile.gettempdir(), f"gelly_xla_flag_probe_{ver}.txt"
    )
    try:
        with open(marker) as f:
            return f.read().strip() == "ok"
    except OSError:
        pass
    env = dict(os.environ, XLA_FLAGS=flag, JAX_PLATFORMS="cpu")
    ok = False
    flag_rejected = False
    try:
        probe = subprocess.run(
            [sys.executable, "-c", "import jax; jax.devices()"],
            env=env,
            capture_output=True,
            timeout=120,
        )
        ok = probe.returncode == 0
        # XLA's unknown-flag abort is the ONE durable negative; anything
        # else (timeout, OOM, load spike) is transient and must be
        # re-probed next run, not cached as a permanent "bad"
        flag_rejected = b"Unknown flags in XLA_FLAGS" in (probe.stderr or b"")
    except Exception:
        pass
    if ok or flag_rejected:
        try:
            with open(marker, "w") as f:
                f.write("ok" if ok else "bad")
        except OSError:
            pass
    return ok


_timeout_flags = [
    "--xla_cpu_collective_call_warn_stuck_timeout_seconds=120",
    "--xla_cpu_collective_call_terminate_timeout_seconds=900",
]
_missing = [
    f
    for f in _timeout_flags
    # per-flag guard: never shadow a user-set value with a duplicate
    if f[2:].split("=")[0] not in _flags
]
if _missing and _xla_accepts(" ".join(_timeout_flags)):
    _flags += " " + " ".join(_missing)
os.environ["XLA_FLAGS"] = _flags

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Tests run on the CPU (the driver sets JAX_PLATFORMS=cpu; this makes a
# bare ``pytest`` do the same) and never touch the persistent compile cache
# the entry points place (core/compile_cache.use_persistent_cache).
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_compilation_cache", False)
assert len(jax.devices()) == 8, "expected the virtual 8-device CPU mesh"


# ---------------------------------------------------------------------------
# Per-test wall-clock cap for the threaded async-pipeline tests
# (@pytest.mark.timeout_cap(seconds)): a hung completion queue must FAIL the
# test, not wedge the whole tier-1 run.  Same philosophy as the XLA flag
# probe above — capability is PROBED and unsupported configurations degrade
# to running uncapped rather than aborting: the cap needs SIGALRM delivered
# on the main thread (POSIX); when the pytest-timeout plugin is installed it
# owns per-test timeouts and this fixture stands down.

import threading as _threading  # noqa: E402

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _timeout_cap(request):
    marker = request.node.get_closest_marker("timeout_cap")
    if marker is None:
        yield
        return
    seconds = float(marker.args[0]) if marker.args else 120.0
    if request.config.pluginmanager.hasplugin("timeout"):
        # pytest-timeout owns per-test timeouts ONLY where one is actually
        # configured for this test (its marker or a global --timeout) —
        # its mere presence must not turn the cap into a silent no-op
        configured = request.node.get_closest_marker("timeout") is not None
        if not configured:
            try:
                configured = float(
                    request.config.getoption("--timeout") or 0
                ) > 0
            except Exception:
                configured = False
        if configured:
            yield
            return
    import signal

    if (
        not hasattr(signal, "SIGALRM")
        or _threading.current_thread() is not _threading.main_thread()
    ):
        yield  # unsupported platform/thread: run uncapped, don't abort
        return

    def _expired(signum, frame):
        raise TimeoutError(
            f"test exceeded its {seconds:.0f}s timeout_cap — a pipeline "
            "thread or completion queue is likely hung"
        )

    old_handler = signal.signal(signal.SIGALRM, _expired)
    signal.setitimer(signal.ITIMER_REAL, seconds)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, old_handler)
