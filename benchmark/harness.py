"""The server side of one run, and the assembly of its result line.

This process holds the chip.  It runs the system under test in-process,
as a user's ``gelly-serve --listen`` would: a ``JobManager`` under a
``StreamServer`` on loopback, both with their default configuration.  The
client side (``loadgen.py``) runs in a child process that never loads the
chip's library.

Set-up is everything from the start of the process until the measured
job's second record reached the client: JAX start-up, edge generation (in
the child, meanwhile), the server, and the job's first two windows, which
compile (or load from the persistent cache) the fold and the combine.
The window then opens for ``--seconds`` seconds.  With ``--trace 1`` the
profiler records exactly that window.
"""

from __future__ import annotations

import json
import os
import queue
import shutil
import subprocess
import sys
import threading
import time
from dataclasses import dataclass
from typing import Optional

from benchmark import spec, tracereduce


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


class RunFailed(RuntimeError):
    """The run could not produce a result line."""


# JAX compile activity by monitoring event, counted for the whole process
_JAX_EVENTS: dict = {}
_JAX_LISTENING = False
_WATCHED = (
    "/jax/core/compile/backend_compile_duration",
    "/jax/compilation_cache/cache_retrieval_time_sec",
)


def _listen_for_compiles() -> None:
    global _JAX_LISTENING
    if _JAX_LISTENING:
        return
    import jax

    def on_event(name, _secs, **_kw):
        if name in _WATCHED:
            _JAX_EVENTS[name] = _JAX_EVENTS.get(name, 0) + 1

    jax.monitoring.register_event_duration_secs_listener(on_event)
    _JAX_LISTENING = True


def devices(chips: int, require_tpu: bool = True) -> list:
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


class Child:
    """The client process and its JSON-line pipes."""

    def __init__(self, setup: dict):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "benchmark.loadgen"],
            cwd=spec.ROOT,
            env=env,
            stdin=subprocess.PIPE,
            stdout=subprocess.PIPE,
            text=True,
            bufsize=1,
        )
        self.events: queue.Queue = queue.Queue()
        threading.Thread(target=self._pump, daemon=True).start()
        self.send(setup=setup)

    def _pump(self) -> None:
        for line in self.proc.stdout:
            try:
                self.events.put(json.loads(line))
            except json.JSONDecodeError:
                print(line, end="", file=sys.stderr)
        self.events.put(None)

    def send(self, **msg) -> None:
        self.proc.stdin.write(json.dumps(msg) + "\n")
        self.proc.stdin.flush()

    def expect(self, event: str, timeout: float) -> dict:
        deadline = time.monotonic() + timeout
        while True:
            try:
                msg = self.events.get(timeout=max(0.01, deadline - time.monotonic()))
            except queue.Empty:
                raise RunFailed(f"client sent no {event!r} within {timeout:.0f} s")
            if msg is None:
                raise RunFailed(f"client exited before {event!r} (rc {self.proc.wait()})")
            if msg.get("event") == event:
                return msg

    def close(self) -> None:
        if self.proc.poll() is None:
            try:
                self.proc.stdin.close()
            except OSError:
                pass
            try:
                self.proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()


def _hist_buckets(name: str) -> dict:
    from gelly_streaming_tpu.utils import metrics

    snap = metrics.hist_snapshot()["global"].get(name)
    return {lo: n for lo, n in snap["buckets"]} if snap else {}


def _hist_diff(before: dict, after: dict) -> dict:
    return {lo: n - before.get(lo, 0) for lo, n in after.items() if n - before.get(lo, 0)}


def _tenant_bytes() -> tuple:
    from gelly_streaming_tpu.utils import metrics

    row = metrics.tenant_stats("default")
    return row.get("tenant_ingest_wire_bytes", 0), row.get("tenant_ingest_edges", 0)


@dataclass
class Outcome:
    """What one run found: its result line, and the earlier lines."""

    line: dict
    setup_parts: dict
    window: dict


def run_cell(
    bench: spec.Benchmark,
    cell: spec.Cell,
    seed: int,
    seconds: float,
    trace: bool,
    *,
    require_tpu: bool = True,
    control: bool = False,
    t_start_ns: Optional[int] = None,
) -> Outcome:
    """One run of one cell."""
    t_start = t_start_ns or time.monotonic_ns()
    child = Child({"cell": cell.to_json(), "seed": seed, "control": control})
    try:
        return _serve(bench, cell, seconds, trace, require_tpu, child, t_start)
    finally:
        child.close()


def _serve(bench, cell, seconds, trace, require_tpu, child, t_start) -> Outcome:
    t = time.monotonic_ns()
    devs = devices(cell.chips, require_tpu)
    jax_init_s = (time.monotonic_ns() - t) / 1e9
    # an unknown chip is an error before any timed work, not a default
    peaks = bench.peaks(devs[0].device_kind) if require_tpu else None
    _listen_for_compiles()
    import jax

    from gelly_streaming_tpu.core import compile_cache
    from gelly_streaming_tpu.core.config import ServerConfig
    from gelly_streaming_tpu.runtime.manager import JobManager
    from gelly_streaming_tpu.runtime.server import StreamServer
    from gelly_streaming_tpu.utils import metrics

    trace_dir = os.path.join(bench.bench_dir, "out", "trace", cell.name)
    jm = JobManager()
    server = StreamServer(jm, ServerConfig()).start()
    stopped = False
    try:
        t_server = time.monotonic_ns()
        child.send(port=server.port)
        warm = child.expect("warm", 1200)
        cc0 = compile_cache.stats()
        jax0 = dict(_JAX_EVENTS)
        hist0 = _hist_buckets("push_to_fold_ms")
        bytes0 = _tenant_bytes()
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            # host events from JAX's own TraceMe scopes; Python function
            # tracing would slow the serving threads it watches
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        t0 = time.monotonic_ns()
        t1 = t0 + int(seconds * 1e9)
        child.send(go={"t0": t0, "t1": t1})
        queue_depth = []
        while True:
            left = (t1 - time.monotonic_ns()) / 1e9
            if left <= 0:
                break
            time.sleep(min(0.5, left))
            for row in metrics.all_job_health().values():
                queue_depth.append((time.monotonic_ns(), row.get("backlog_batches", 0)))
        t_close = time.monotonic_ns()
        if trace:
            jax.profiler.stop_trace()
        cc1 = compile_cache.stats()
        jax1 = dict(_JAX_EVENTS)
        hist1 = _hist_buckets("push_to_fold_ms")
        bytes1 = _tenant_bytes()
        peak = max(
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs
        )
        child.send(stop=True)
        child.expect("done", 120 + seconds)
        server.stop()
        jm.shutdown(cancel=True, timeout=120)
        stopped = True
        result = child.expect("result", 600)
    finally:
        if not stopped:
            server.stop()
            jm.shutdown(cancel=True, timeout=120)

    setup_s = (t0 - t_start) / 1e9
    window_s = (t_close - t0) / 1e9
    counts = result["counts"]
    check = result["check"]
    wire_b, wire_e = bytes1[0] - bytes0[0], bytes1[1] - bytes0[1]
    setup_parts = {
        "setup_s": setup_s,
        "jax_init": jax_init_s,
        "server_up": (t_server - t_start) / 1e9,
        "client_started": (warm["t_child"] - t_start) / 1e9,
        "client_imported": (warm["t_imported"] - t_start) / 1e9,
        "port_received": (warm["t_port"] - t_start) / 1e9,
        "submit_sent": (warm["t_submit_sent"] - t_start) / 1e9,
        "job_submitted": (warm["t_submit"] - t_start) / 1e9,
        "first_record": (warm["t_records"][0] - t_start) / 1e9,
        "second_record": (warm["t_records"][1] - t_start) / 1e9,
        "submit_call": (warm["t_submit"] - warm["t_submit_sent"]) / 1e9,
        "edges_made_s": warm["edges_made_s"],
        "compile_s": cc0["compile_time_s"],
        "compiles": cc0["compiles"],
    }
    window = {
        "seconds": window_s,
        "compiles": cc1["compiles"] - cc0["compiles"],
        "recompiles": cc1["recompiles"] - cc0["recompiles"],
        "jax_backend_compiles": jax1.get(_WATCHED[0], 0) - jax0.get(_WATCHED[0], 0),
        "jax_cache_loads": jax1.get(_WATCHED[1], 0) - jax0.get(_WATCHED[1], 0),
        "peak_bytes_in_use": peak,
        "socket_bytes_per_edge": wire_b / wire_e if wire_e else None,
        "source_queue_batches": [q for _t, q in queue_depth[:1] + queue_depth[-1:]],
        "source_queue_batches_slope_per_s": _slope(queue_depth),
        **counts,
        "latency_ms": result.get("latency_ms"),
        "records_compared": check["compared"],
        "reference_s": check["reference_s"],
    }
    if "served_mismatched_entries" in check:
        window["served_mismatched_entries"] = check["served_mismatched_entries"]

    checks = {
        "mismatched_entries": {"value": check["mismatched_entries"], "limit": 0},
        "records_missing": {"value": result["missing"], "limit": 0},
    }
    correct = (
        check["mismatched_entries"] == 0
        and result["missing"] == 0
        and len(check["compared"]) >= 1
        and counts["read_error"] is None
        and counts["push_error"] is None
    )
    device = {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
        "memory_peak_bytes": peak,
    }
    line: dict = {
        "correct": correct,
        "attempted": result["attempted"],
        "failed": result["missing"] + check["records_wrong"],
    }
    metrics_out = {}
    if trace:
        reduced = tracereduce.reduce_dir(trace_dir)
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = window_s
        ctx = {
            "cell": cell,
            "trace": reduced,
            "window_s": window_s,
            "seconds_per_window": result.get("seconds_per_window"),
            "hist_window": {"push_to_fold_ms": _hist_diff(hist0, hist1)},
            "push_call_ms": result.get("push_call_ms", []),
            "peaks": peaks,
        }
        for m in cell.per_layer:
            value = bench.metric_reader(m["name"]).read(ctx)
            if value is not None:
                metrics_out[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            value = setup_s if m["name"] == "setup_s" else result["metrics"].get(m["name"])
            if value is not None:
                metrics_out[m["name"]] = {"value": value, "unit": m["unit"]}
    line["metrics"] = metrics_out
    line["device"] = device
    if trace:
        line["breakdown"] = {
            "device_ops": reduced["device_ops"],
            "idle_gaps": reduced["idle_gaps"],
        }
    line["checks"] = checks
    return Outcome(line, setup_parts, window)


def _slope(samples) -> Optional[float]:
    if len(samples) < 3:
        return None
    import numpy as np

    t = (np.array([s[0] for s in samples], np.float64) - samples[0][0]) / 1e9
    y = np.array([s[1] for s in samples], np.float64)
    if np.ptp(t) == 0:
        return None
    return float(np.polyfit(t, y, 1)[0])
