"""Observability: throughput and window-latency counters, profiler hooks.

The reference has none in-repo (log4j root logger is OFF,
src/main/resources/log4j.properties:22; the only measurement is an ad-hoc
getNetRuntime print, CentralizedWeightedMatching.java:62-64 — SURVEY.md §5.1/5.5).
The TPU build makes edges/sec and per-window latency first-class, plus an
optional jax.profiler trace context for device-level inspection.
"""

from __future__ import annotations

import collections
import contextlib
import threading
import time
from typing import List, Optional

from gelly_streaming_tpu.utils.tracing import LatencyHistogram, nearest_rank


class ThroughputMeter:
    """Edges/sec over a processing run (count what the device actually saw).

    ``record_batch`` may be driven from any pipeline stage thread (pack /
    transfer / drain), so the counters are lock-guarded — the unguarded
    ``+=`` read-modify-write loses updates under contention (the lock-
    discipline analyzer pass enforces the annotation, and
    tests/test_metrics_threads.py hammers the no-lost-update behavior).
    """

    def __init__(self):
        self._lock = threading.Lock()
        self.edges = 0  # guarded-by: _lock
        self.batches = 0  # guarded-by: _lock
        self._start: Optional[float] = None
        self._stop: Optional[float] = None

    def start(self) -> None:
        self._start = time.perf_counter()

    def record_batch(self, num_edges: int) -> None:
        if self._start is None:
            self.start()
        with self._lock:
            self.edges += int(num_edges)
            self.batches += 1

    def stop(self) -> None:
        self._stop = time.perf_counter()

    @property
    def elapsed(self) -> float:
        if self._start is None:
            return 0.0
        end = self._stop if self._stop is not None else time.perf_counter()
        return end - self._start

    @property
    def edges_per_sec(self) -> float:
        with self._lock:
            edges = self.edges
        return edges / self.elapsed if self.elapsed > 0 else 0.0


class _RecordingDeque(collections.deque):
    """Bounded sample window that mirrors every append into a histogram —
    keeps the old ``recorder.latencies_ms.append(...)`` call sites feeding
    the bounded histogram without an API break."""

    def __init__(self, histogram: LatencyHistogram, maxlen: int):
        super().__init__(maxlen=maxlen)
        self._histogram = histogram

    def append(self, ms) -> None:
        self._histogram.record(ms)
        super().append(float(ms))


class WindowLatencyRecorder:
    """Wall-clock latency from a window's close to its emitted result.

    Now a thin shim over the bounded machinery (utils/tracing.py): every
    sample lands in a :class:`LatencyHistogram` (O(1) memory forever — the
    fix for the unbounded list a long-lived ``gelly-serve --listen``
    process grew without limit), and ``latencies_ms`` keeps the list-like
    API as a bounded deque of the most recent ``max_samples`` raw values.
    ``percentile`` uses proper nearest-rank math over those raw samples
    (exact while nothing has been evicted); ``histogram`` holds the
    all-time log-bucketed distribution.
    """

    def __init__(self, max_samples: int = 4096):
        self.histogram = LatencyHistogram()
        self.latencies_ms = _RecordingDeque(self.histogram, max_samples)
        self._open: Optional[float] = None

    def window_closed(self) -> None:
        self._open = time.perf_counter()

    def result_emitted(self) -> None:
        if self._open is not None:
            self.record((time.perf_counter() - self._open) * 1e3)
            self._open = None

    def record(self, ms: float) -> None:
        """Record one latency sample (histogram + bounded raw window)."""
        self.latencies_ms.append(ms)

    def percentile(self, p: float) -> float:
        """Nearest-rank percentile of the retained raw samples (p50 of
        [1, 2] is 1; p100 is the maximum, no clamp games — see
        tracing.nearest_rank for the exact definition and the old bug)."""
        return nearest_rank(sorted(self.latencies_ms), p)

    @property
    def p50_ms(self) -> float:
        return self.percentile(50)


# ---------------------------------------------------------------------------
# Async-window pipeline occupancy (core/async_exec.py + io/wire.Prefetcher).
# Process-global like the compile-cache counters: the pipeline spans several
# threads (pack, transfer, dispatch/drain), so per-object counters would be
# invisible to the bench's single JSON report.


_PIPE_LOCK = threading.Lock()


def _pipeline_zero() -> dict:
    return {
        # windows dispatched-but-undrained at once (completion-queue length)
        "pipeline_inflight_high_water": 0,
        # seconds the pack stage sat blocked (arena backpressure)
        "pipeline_pack_stall_s": 0.0,
        # seconds the transfer stage waited on the pack stage for input
        "pipeline_transfer_stall_s": 0.0,
        # seconds the dispatch loop waited on the prefetcher for input
        "pipeline_dispatch_stall_s": 0.0,
        # seconds the completion-queue drain spent materializing results
        "pipeline_drain_stall_s": 0.0,
        # deepest configured prefetch queue seen (transfers in flight bound)
        "pipeline_prefetch_depth": 0,
        "pipeline_windows_dispatched": 0,
        "pipeline_windows_drained": 0,
        # the Merger's route per pane: folded straight into the running
        # summary, or folded from the initial state and merged by combine
        "merge_running_folds": 0,
        "merge_combines": 0,
        # transient descriptors: a pane folded from the initial state and
        # emitted alone, with no combine
        "merge_transient_folds": 0,
        # the served triangles query's kernel counters, summed per window
        # (ops/oriented_triangles.counters), and the largest out-degree
        # bucket a window needed
        "tri_windows": 0,
        "tri_vertices": 0,
        "tri_edges": 0,
        "tri_wedges": 0,
        "tri_slots": 0,
        "tri_bucket_high_water": 0,
    }


# Bumped from the pack, transfer, dispatch, and drain threads at once; the
# annotation is enforced by the lock-discipline analyzer pass, and
# tests/test_metrics_threads.py pins the no-lost-update behavior.
_PIPELINE = _pipeline_zero()  # guarded-by: _PIPE_LOCK


def pipeline_add(key: str, amount: float) -> None:
    """Accumulate a pipeline counter (thread-safe; hot-path cheap)."""
    with _PIPE_LOCK:
        _PIPELINE[key] += amount


def pipeline_high_water(key: str, value: float) -> None:
    """Raise a pipeline high-water mark to ``value`` if it is higher."""
    with _PIPE_LOCK:
        if value > _PIPELINE[key]:
            _PIPELINE[key] = value


def pipeline_stats() -> dict:
    """Process-wide async-window pipeline occupancy counters: in-flight
    window high-water mark, per-stage stall seconds (pack / transfer /
    dispatch / drain), prefetcher queue depth, dispatched/drained window
    counts, the Merger's panes by route (``merge_running_folds`` /
    ``merge_combines`` / ``merge_transient_folds``) and the served triangle
    count's per-window kernel counters (``tri_*``).  Reported by bench.py
    next to ``compile_cache_stats``."""
    with _PIPE_LOCK:
        out = dict(_PIPELINE)
    out["pipeline_pack_stall_s"] = round(out["pipeline_pack_stall_s"], 4)
    out["pipeline_transfer_stall_s"] = round(
        out["pipeline_transfer_stall_s"], 4
    )
    out["pipeline_dispatch_stall_s"] = round(
        out["pipeline_dispatch_stall_s"], 4
    )
    out["pipeline_drain_stall_s"] = round(out["pipeline_drain_stall_s"], 4)
    return out


def reset_pipeline_stats() -> None:
    """Zero the pipeline occupancy counters (call before a measurement
    window, read ``pipeline_stats`` after)."""
    global _PIPELINE
    with _PIPE_LOCK:
        _PIPELINE = _pipeline_zero()


# ---------------------------------------------------------------------------
# Mesh collective comms accounting (the owner-sharded summary plane, ISSUE 4).
# Process-global like the pipeline counters: dispatches happen on the merge
# loop / async dispatch threads while stats drain elsewhere.  Byte figures
# combine static per-call buffer sizes (collective shapes are compile-time
# constants) with the DYNAMIC round counts the exchange kernels report, so
# they measure what actually crossed the mesh, not a one-shot estimate.


_COMMS_LOCK = threading.Lock()


def _comms_zero() -> dict:
    return {
        # device dispatches that fed the mesh data plane
        "comms_dispatches": 0,
        # bytes shipped by delta/slab exchange passes (all_to_all)
        "comms_bytes_exchange": 0.0,
        # bytes shipped reassembling the replicated view at emit/snapshot
        # boundaries (gather_blocks).  Only the OWNER-SHARDED plane meters
        # itself; replicated-fallback runs (sharded_state=0) leave every
        # counter at zero — their per-dispatch all_gather volume is the
        # S*C*itemsize/dispatch the sharded plane exists to remove.
        "comms_bytes_gather": 0.0,
        # exchange passes executed (dynamic: chains/spills retry)
        "comms_exchange_rounds": 0,
        # max per-owner changed-row demand seen before capping (sizes the
        # pow2-bucketed delta buffers; > capacity means spill-retry rounds)
        "comms_delta_occupancy_hwm": 0,
        # delta rows deferred past a full buffer (retried, never dropped)
        "comms_delta_spilled": 0,
    }


_COMMS = _comms_zero()  # guarded-by: _COMMS_LOCK


def comms_add(key: str, amount: float) -> None:
    """Accumulate a mesh-comms counter (thread-safe; hot-path cheap)."""
    with _COMMS_LOCK:
        _COMMS[key] += amount


def comms_high_water(key: str, value: float) -> None:
    """Raise a mesh-comms high-water mark to ``value`` if it is higher."""
    with _COMMS_LOCK:
        if value > _COMMS[key]:
            _COMMS[key] = value


def comms_stats() -> dict:
    """Process-wide mesh collective counters: per-dispatch collective byte
    volume (exchange vs gather), exchange round counts, and the
    delta-occupancy high-water mark.  Reported by bench.py next to
    ``pipeline_stats`` and by the multichip scaling sweep (quadrant D) as
    bytes/edge — the measured evidence that sharded-path comms scale
    O(C/S + delta) per dispatch rather than O(C * S)."""
    with _COMMS_LOCK:
        out = dict(_COMMS)
    out["comms_bytes_total"] = round(
        out["comms_bytes_exchange"] + out["comms_bytes_gather"], 1
    )
    out["comms_bytes_exchange"] = round(out["comms_bytes_exchange"], 1)
    out["comms_bytes_gather"] = round(out["comms_bytes_gather"], 1)
    n = max(out["comms_dispatches"], 1)
    out["comms_bytes_per_dispatch"] = round(out["comms_bytes_total"] / n, 1)
    return out


def reset_comms_stats() -> None:
    """Zero the mesh-comms counters (call before a measurement window,
    read ``comms_stats`` after)."""
    global _COMMS
    with _COMMS_LOCK:
        _COMMS = _comms_zero()


# ---------------------------------------------------------------------------
# Wire-path transfer accounting (the binned + compressed ingest, ISSUE 6).
# Process-global like the pipeline counters: packing runs on the prefetcher's
# pack thread and the ingest pool workers while stats drain elsewhere.  Byte
# figures count the buffers actually shipped (bucket padding included — those
# pad bytes cross the link too), next to the raw 8 B/edge the host arrays
# would cost, so the compression ratio measures the real transfer saving.


_WIRE_LOCK = threading.Lock()


def _wire_zero() -> dict:
    return {
        # wire buffers / arenas shipped to the device (padding included)
        "wire_bytes_total": 0,
        # what the same edges would cost as raw int32 pairs (8 B/edge)
        "wire_raw_bytes_total": 0,
        # edges those buffers carried
        "wire_edges_total": 0,
        # micro-batches shipped (superbatch groups count their members)
        "wire_batches": 0,
        # longest single destination bin (equal-dst run) seen by the binning
        # pass — the propagation-blocking skew indicator
        "wire_bin_occupancy_hwm": 0,
    }


# Bumped from the pack thread and the ingest pool workers at once; the
# annotation is enforced by the lock-discipline analyzer pass.
_WIRE = _wire_zero()  # guarded-by: _WIRE_LOCK


def wire_high_water(key: str, value: float) -> None:
    """Raise a wire-path high-water mark to ``value`` if it is higher."""
    with _WIRE_LOCK:
        if value > _WIRE[key]:
            _WIRE[key] = value


def wire_record_batch(batches: int, edges: int, nbytes: int) -> None:
    """Account one shipped wire buffer/arena under ONE lock acquisition."""
    with _WIRE_LOCK:
        _WIRE["wire_batches"] += int(batches)
        _WIRE["wire_edges_total"] += int(edges)
        _WIRE["wire_raw_bytes_total"] += 8 * int(edges)
        _WIRE["wire_bytes_total"] += int(nbytes)


def wire_stats() -> dict:
    """Process-wide wire-path counters plus the derived per-edge figures:
    ``wire_bytes_per_edge`` (shipped bytes / edges) and
    ``wire_compress_ratio`` (raw int32-pair bytes / shipped bytes — > 1
    means the binned/compressed formats beat raw columns).  Reported by
    bench.py next to ``comms_stats``; ``_PARTIAL``-safe (pure host state,
    readable even when the device never came up)."""
    with _WIRE_LOCK:
        out = dict(_WIRE)
    edges = max(out["wire_edges_total"], 1)
    out["wire_bytes_per_edge"] = round(out["wire_bytes_total"] / edges, 3)
    out["wire_compress_ratio"] = round(
        out["wire_raw_bytes_total"] / max(out["wire_bytes_total"], 1), 3
    )
    return out


def reset_wire_stats() -> None:
    """Zero the wire-path counters (call before a measurement window,
    read ``wire_stats`` after)."""
    global _WIRE
    with _WIRE_LOCK:
        _WIRE = _wire_zero()


# ---------------------------------------------------------------------------
# Per-job counter scoping (the multi-tenant job runtime, runtime/manager.py).
# The scheduler thread, per-job sink threads, and status() readers all touch
# these registries at once, so every access goes through _JOB_LOCK — the
# lock-discipline analyzer pass enforces the annotations, and
# tests/test_metrics_threads.py hammers concurrent-job isolation (no lost
# updates within a job, no cross-job bleed between jobs).
#
# Module aggregates are preserved as SUMS: additive counters accumulate into
# ``_JOB_TOTALS`` alongside the per-job dict, so ``job_totals()`` equals the
# field-wise sum of ``all_job_stats()`` at any quiescent point.  High-water
# marks aggregate as MAX (a sum of peak queue depths is not a meaningful
# module figure).


# The per-registry locks are SIBLING leaves (drop_job_stats takes the
# job then the histogram registry in sequence, never nested); if a
# future edit needs both at once, this is the sanctioned direction.
# lock-order: metrics._JOB_LOCK < metrics._HIST_LOCK
_JOB_LOCK = threading.Lock()


def _job_zero() -> dict:
    return {
        # emissions delivered into the job's bounded output queue
        "job_records": 0,
        # iterator pulls the scheduler executed for this job (each pull
        # dispatches that job's next window through the shared pipeline)
        "job_dispatches": 0,
        # edges attributed to this job (edges_per_record hint x records;
        # 0 when the query's per-record edge count is unknown)
        "job_edges": 0,
        # wall seconds the scheduler spent inside this job's pulls
        "job_dispatch_s": 0.0,
        # wall seconds this job's sink spent consuming its records (sink
        # pump thread only; sink-less jobs stay 0)
        "job_sink_stall_s": 0.0,
        # weighted-fair rounds in which this job made progress
        "job_sched_rounds": 0,
        # rounds the job was skipped because its output queue was full
        # (the slow-sink isolation boundary doing its job)
        "job_queue_full_skips": 0,
        # rounds the job was skipped because its source had no complete
        # window queued (the network-ingest isolation boundary: a slow or
        # dead client idles ITS job, never the scheduler round)
        "job_source_wait_skips": 0,
        # deepest output-queue occupancy seen (sink lag indicator)
        "job_queue_depth_hwm": 0,
    }


# job id -> counter dict; entries appear at first bump, not at submit
_JOB_COUNTERS: dict = {}  # guarded-by: _JOB_LOCK
_JOB_TOTALS = _job_zero()  # guarded-by: _JOB_LOCK


def job_add(job_id: str, key: str, amount: float) -> None:
    """Accumulate a per-job counter AND its module aggregate (thread-safe)."""
    with _JOB_LOCK:
        counters = _JOB_COUNTERS.get(job_id)
        if counters is None:
            counters = _JOB_COUNTERS[job_id] = _job_zero()
        counters[key] += amount
        _JOB_TOTALS[key] += amount


def job_high_water(job_id: str, key: str, value: float) -> None:
    """Raise a per-job high-water mark (module aggregate keeps the max)."""
    with _JOB_LOCK:
        counters = _JOB_COUNTERS.get(job_id)
        if counters is None:
            counters = _JOB_COUNTERS[job_id] = _job_zero()
        if value > counters[key]:
            counters[key] = value
        if value > _JOB_TOTALS[key]:
            _JOB_TOTALS[key] = value


def job_stats(job_id: str) -> dict:
    """One job's counters (zeros for a job that never bumped anything)."""
    with _JOB_LOCK:
        return dict(_JOB_COUNTERS.get(job_id) or _job_zero())


def all_job_stats() -> dict:
    """{job id -> counter dict} snapshot across every job seen."""
    with _JOB_LOCK:
        return {jid: dict(c) for jid, c in _JOB_COUNTERS.items()}


def job_totals() -> dict:
    """Module aggregates over all jobs: sums for counters, max for
    high-water marks — reported by bench.py's multi_tenant sweep and
    ``JobManager.status()`` next to the per-job breakdown."""
    with _JOB_LOCK:
        out = dict(_JOB_TOTALS)
    out["job_dispatch_s"] = round(out["job_dispatch_s"], 4)
    return out


def drop_job_stats(job_id: str) -> None:
    """Forget one job's per-job registry row (the JobManager calls this
    when it evicts an old terminal job).  The module TOTALS keep the job's
    contribution — aggregates stay sums over every job ever run, only the
    per-job breakdown is bounded.  The job's latency-histogram rows go
    with it (the global-scope histograms keep its samples), so a
    long-lived serving process's histogram registry is bounded by the
    LIVE job set, not the job history."""
    with _JOB_LOCK:
        _JOB_COUNTERS.pop(job_id, None)
    with _HIST_LOCK:
        for key in [k for k in _HISTS if k[0] == "job" and k[1] == job_id]:
            del _HISTS[key]
    # the health plane's rows leave with the job too: gauges (a stale
    # backlog row would keep an SLO alert burning on a dead job), the
    # job's alert rows, and its elastic-control-plane scale row
    drop_job_health(job_id)
    drop_alerts("job", job_id)
    drop_job_scale(job_id)


def reset_job_stats() -> None:
    """Drop every per-job registry entry and zero the aggregates (call
    before a measurement window, read ``all_job_stats`` after)."""
    global _JOB_TOTALS
    with _JOB_LOCK:
        _JOB_COUNTERS.clear()
        _JOB_TOTALS = _job_zero()


# ---------------------------------------------------------------------------
# Per-tenant counter scoping (the streaming RPC serving plane, ISSUE 8,
# runtime/server.py).  Connection handler threads, the drain path, and
# status() readers all touch these registries at once, so every access goes
# through _TENANT_LOCK — same discipline (and the same analyzer pin) as the
# per-job registries above.  Aggregates are SUMS for counters and MAX for
# high-water marks, mirroring job_totals().


_TENANT_LOCK = threading.Lock()


def _tenant_zero() -> dict:
    return {
        # request frames this tenant authenticated (every verb)
        "tenant_requests": 0,
        # jobs this tenant submitted through the serving plane
        "tenant_jobs_submitted": 0,
        # submits refused by tenant or global admission control
        "tenant_admission_rejections": 0,
        # edges this tenant pushed over the network ingest path
        "tenant_ingest_edges": 0,
        # wire bytes those pushes carried (the socket cost)
        "tenant_ingest_wire_bytes": 0,
        # what the same edges would cost as raw int32 pairs (8 B/edge)
        "tenant_ingest_raw_bytes": 0,
        # push frames refused by the wire-format guards (size/id bounds)
        "tenant_ingest_rejects": 0,
        # seconds this tenant's connections slept in the ingest rate limiter
        "tenant_throttle_s": 0.0,
        # emission records delivered to this tenant's results fetches
        "tenant_records_fetched": 0,
        # deepest per-source decoded-batch queue occupancy seen
        "tenant_ingest_queue_hwm": 0,
    }


# tenant id -> counter dict; entries appear at first bump, like jobs
_TENANT_COUNTERS: dict = {}  # guarded-by: _TENANT_LOCK
_TENANT_TOTALS = _tenant_zero()  # guarded-by: _TENANT_LOCK


def tenant_add(tenant: str, key: str, amount: float) -> None:
    """Accumulate a per-tenant counter AND its module aggregate."""
    with _TENANT_LOCK:
        counters = _TENANT_COUNTERS.get(tenant)
        if counters is None:
            counters = _TENANT_COUNTERS[tenant] = _tenant_zero()
        counters[key] += amount
        _TENANT_TOTALS[key] += amount


def tenant_high_water(tenant: str, key: str, value: float) -> None:
    """Raise a per-tenant high-water mark (module aggregate keeps the max)."""
    with _TENANT_LOCK:
        counters = _TENANT_COUNTERS.get(tenant)
        if counters is None:
            counters = _TENANT_COUNTERS[tenant] = _tenant_zero()
        if value > counters[key]:
            counters[key] = value
        if value > _TENANT_TOTALS[key]:
            _TENANT_TOTALS[key] = value


def tenant_stats(tenant: str) -> dict:
    """One tenant's counters (zeros for a tenant that never bumped any)."""
    with _TENANT_LOCK:
        return dict(_TENANT_COUNTERS.get(tenant) or _tenant_zero())


def all_tenant_stats() -> dict:
    """{tenant id -> counter dict} snapshot across every tenant seen —
    surfaced by the server's ``status`` verb next to the per-job rows and
    by bench.py's serving sweep beside ``job_stats``/``wire_stats``."""
    with _TENANT_LOCK:
        return {t: dict(c) for t, c in _TENANT_COUNTERS.items()}


def tenant_totals() -> dict:
    """Module aggregates over all tenants (sums; max for high-water)."""
    with _TENANT_LOCK:
        out = dict(_TENANT_TOTALS)
    out["tenant_throttle_s"] = round(out["tenant_throttle_s"], 4)
    return out


def reset_tenant_stats() -> None:
    """Drop every per-tenant row and zero the aggregates (call before a
    measurement window, read ``all_tenant_stats`` after)."""
    global _TENANT_TOTALS
    with _TENANT_LOCK:
        _TENANT_COUNTERS.clear()
        _TENANT_TOTALS = _tenant_zero()


# ---------------------------------------------------------------------------
# Per-job health gauges (the streaming health plane, ISSUE 10).  Where the
# counter registries above record what HAPPENED, these record whether each
# job is KEEPING UP with its stream right now: watermark lag, backlog depth
# and age, EWMA arrival vs drain rates, and the derived keep-up ratio /
# time-to-queue-full estimate.  Written by the scheduler loop's 1 Hz-ish
# sampler (runtime/manager.py _sample_health — plain Python counter reads,
# never a device sync), read by status()/the health verb/gelly-top/the SLO
# monitors, so the registry is lock-guarded like its siblings.
#
# Gauge vocabulary (all per job):
#   watermark_lag_windows   closable-but-undelivered ingest windows (the
#                           positional accounting NetworkEdgeSource.ready
#                           already does, surfaced as a gauge)
#   backlog_batches/edges   decoded batches queued ahead of the fold
#   backlog_age_s           age of the OLDEST queued batch (how long the
#                           job has not been keeping up, not just whether)
#   arrival_eps/drain_eps   EWMA edge rates in and out of the source queue
#   keepup_ratio            drain/arrival (>= 1.0 = keeping up)
#   time_to_queue_full_s    backlog headroom / net inflow (-1 = not
#                           filling; the operator's "minutes to stall")
#   out_queue_depth         emission-queue occupancy (sink-side backlog)


_HEALTH_LOCK = threading.Lock()
# job id -> gauge dict; rows appear at first sample, leave with the job
# (terminal transition / eviction), so a DONE job cannot hold a stale
# backlog gauge that wedges an SLO alert open
_JOB_HEALTH: dict = {}  # guarded-by: _HEALTH_LOCK


class KeepUpTracker:
    """EWMA arrival/drain rate estimator for ONE job's cumulative edge
    counters.  Owned by the scheduler loop (single producer — no lock):
    ``sample`` takes (now, edges_in, edges_out) and maintains half-life
    smoothed rates, so a bursty client doesn't flap the keep-up verdict
    while a sustained imbalance converges within a few half-lives."""

    __slots__ = ("halflife_s", "arrival_eps", "drain_eps", "_t", "_in", "_out", "_seeded")

    def __init__(self, halflife_s: float = 5.0):
        self.halflife_s = float(halflife_s)
        self.arrival_eps = 0.0
        self.drain_eps = 0.0
        self._t: Optional[float] = None
        self._in = 0
        self._out = 0
        self._seeded = False

    def sample(self, now: float, edges_in: int, edges_out: int):
        """Fold one sample; returns (arrival_eps, drain_eps)."""
        if self._t is None:
            self._t, self._in, self._out = now, int(edges_in), int(edges_out)
            return self.arrival_eps, self.drain_eps
        dt = now - self._t
        if dt <= 0:
            return self.arrival_eps, self.drain_eps
        inst_in = max(0.0, (int(edges_in) - self._in) / dt)
        inst_out = max(0.0, (int(edges_out) - self._out) / dt)
        self._t, self._in, self._out = now, int(edges_in), int(edges_out)
        if not self._seeded:
            self._seeded = True
            self.arrival_eps, self.drain_eps = inst_in, inst_out
        else:
            alpha = 1.0 - 0.5 ** (dt / max(self.halflife_s, 1e-6))
            self.arrival_eps += alpha * (inst_in - self.arrival_eps)
            self.drain_eps += alpha * (inst_out - self.drain_eps)
        return self.arrival_eps, self.drain_eps


def job_health_update(job_id: str, gauges: dict) -> None:
    """Merge gauges into a job's health row (partial writers: tests,
    external instrumentation)."""
    with _HEALTH_LOCK:
        row = _JOB_HEALTH.get(job_id)
        if row is None:
            row = _JOB_HEALTH[job_id] = {}
        row.update(gauges)


def job_health_set(job_id: str, gauges: dict) -> None:
    """REPLACE a job's health row with one sweep's complete gauge set —
    what the scheduler's sampler uses, so a probe that stops producing
    (source torn down mid-drain) cannot leave last sweep's backlog/lag
    values frozen in the row driving SLO verdicts forever."""
    with _HEALTH_LOCK:
        _JOB_HEALTH[job_id] = dict(gauges)


def job_health(job_id: str) -> dict:
    """One job's gauge row ({} until the sampler has seen it)."""
    with _HEALTH_LOCK:
        return dict(_JOB_HEALTH.get(job_id) or {})


def all_job_health() -> dict:
    """{job id -> gauge dict} snapshot of every sampled live job."""
    with _HEALTH_LOCK:
        return {jid: dict(row) for jid, row in _JOB_HEALTH.items()}


def drop_job_health(job_id: str) -> None:
    """Forget a job's gauge row (terminal transition / eviction) so SLO
    monitors stop evaluating it and its alerts can be retired."""
    with _HEALTH_LOCK:
        _JOB_HEALTH.pop(job_id, None)


def reset_job_health() -> None:
    with _HEALTH_LOCK:
        _JOB_HEALTH.clear()


# ---------------------------------------------------------------------------
# Per-job scale gauges (the elastic control plane, ISSUE 11).  One row per
# autoscale-managed job: the geometry the policy WANTS (desired_shards) next
# to the geometry the job RUNS AT (actual_shards), the last decision's
# reason, and the rescale count/downtime — what gelly-top's SCALE column and
# the Prometheus exposition read.  Written by the autoscaler's policy thread
# and its register/unregister callers (server connection threads), read by
# status()/metrics consumers, so the registry is lock-guarded like its
# siblings.  A desired != actual row IS the alert: the policy decided and
# the actuation hasn't landed (or failed and is cooling down).


_SCALE_LOCK = threading.Lock()
# job id -> gauge dict; rows appear at autoscaler registration, leave when
# the job is unregistered (terminal) or evicted
_JOB_SCALE: dict = {}  # guarded-by: _SCALE_LOCK


def job_scale_update(job_id: str, gauges: dict) -> None:
    """Merge scale gauges into a job's row (policy sweep + actuation both
    write partial updates; merge keeps the rescale history fields)."""
    with _SCALE_LOCK:
        row = _JOB_SCALE.get(job_id)
        if row is None:
            row = _JOB_SCALE[job_id] = {}
        row.update(gauges)


def job_scale(job_id: str) -> dict:
    """One job's scale row ({} until the autoscaler manages it)."""
    with _SCALE_LOCK:
        return dict(_JOB_SCALE.get(job_id) or {})


def all_job_scale() -> dict:
    """{job id -> scale gauge dict} snapshot of every managed job."""
    with _SCALE_LOCK:
        return {jid: dict(row) for jid, row in _JOB_SCALE.items()}


def drop_job_scale(job_id: str) -> None:
    """Forget a job's scale row (autoscaler unregister / job eviction)."""
    with _SCALE_LOCK:
        _JOB_SCALE.pop(job_id, None)


def reset_job_scale() -> None:
    with _SCALE_LOCK:
        _JOB_SCALE.clear()


# ---------------------------------------------------------------------------
# SLO alert registry (runtime/slo.py writes, everything else reads).  One
# row per (scope kind, scope id, slo name): current OK/WARN/PAGE state, the
# burn rates that justify it, and the transition timestamp — surfaced in
# job/tenant status rows, the health/alerts verbs, gelly-top badges, and
# the Prometheus exposition (gelly_slo_state 0/1/2).


ALERT_LEVELS = {"OK": 0, "WARN": 1, "PAGE": 2}

_ALERT_LOCK = threading.Lock()
_ALERTS: dict = {}  # guarded-by: _ALERT_LOCK  (scope, id, slo) -> row


def alert_set(scope: str, scope_id: str, slo: str, row: dict) -> None:
    """Install/refresh one alert row (the monitor calls this every
    evaluation, transition or not, so burn rates stay current)."""
    with _ALERT_LOCK:
        _ALERTS[(scope, scope_id, slo)] = dict(
            row, scope=scope, id=scope_id, slo=slo
        )


def alert_state(scope: str, scope_id: str, slo: str) -> Optional[dict]:
    with _ALERT_LOCK:
        row = _ALERTS.get((scope, scope_id, slo))
        return dict(row) if row is not None else None


def all_alerts() -> List[dict]:
    """Every alert row, sorted by (scope, id, slo) for stable exposition."""
    with _ALERT_LOCK:
        items = sorted(_ALERTS.items())
    return [dict(row) for _key, row in items]


def alerts_for(scope: str, scope_id: str) -> List[dict]:
    """The alert rows attached to one scope instance (a job's status row)."""
    with _ALERT_LOCK:
        items = sorted(
            (key, row)
            for key, row in _ALERTS.items()
            if key[0] == scope and key[1] == scope_id
        )
    return [dict(row) for _key, row in items]


def drop_alert(scope: str, scope_id: str, slo: str) -> None:
    """Retire ONE alert row (the monitor pruning a dead instance of one
    spec — other specs' alerts on the same id stay)."""
    with _ALERT_LOCK:
        _ALERTS.pop((scope, scope_id, slo), None)


def drop_alerts(scope: str, scope_id: str) -> None:
    """Retire every alert row of one scope instance (job eviction, or the
    monitor pruning an instance whose gauges disappeared)."""
    with _ALERT_LOCK:
        for key in [k for k in _ALERTS if k[0] == scope and k[1] == scope_id]:
            del _ALERTS[key]


def reset_alerts() -> None:
    with _ALERT_LOCK:
        _ALERTS.clear()


# ---------------------------------------------------------------------------
# Bounded latency histograms (the observability plane, ISSUE 9).  Named
# log-bucketed histograms registered per scope — process-global, per-job,
# per-tenant — beside the counter registries above, replacing unbounded
# sample lists.  The canonical names:
#
#   submit_to_first_emission_ms   job admission -> first record delivered
#   window_close_to_emission_ms   window close -> record on the host (the
#                                 sink pump, explicitly job-scoped)
#   push_to_fold_ms               network ingest queue residency
#   sched_queue_wait_ms           gap between a job's scheduler quanta
#
# Scoping rides a THREAD-LOCAL job tag: the scheduler wraps each job's
# pulls in set_hist_job(), so histograms recorded deep inside the merge
# loops land in that job's rows without the loops knowing about jobs.


_HIST_LOCK = threading.Lock()
# (kind, scope id, histogram name) -> LatencyHistogram; kind in
# {"global", "job", "tenant"} with scope id "" for global
_HISTS: dict = {}  # guarded-by: _HIST_LOCK

_HIST_TL = threading.local()  # per-thread current-job tag (no lock needed)


def set_hist_job(job_id: "str | None") -> "str | None":
    """Tag this thread's subsequent ``hist_record`` calls with a job scope
    (None clears it); returns the previous tag so callers can restore."""
    old = getattr(_HIST_TL, "job", None)
    _HIST_TL.job = job_id
    return old


def _hist(kind: str, scope: str, name: str) -> LatencyHistogram:
    key = (kind, scope, name)
    with _HIST_LOCK:
        h = _HISTS.get(key)
        if h is None:
            h = _HISTS[key] = LatencyHistogram()
        return h


def hist_record(
    name: str,
    ms: float,
    job: "str | None" = None,
    tenant: "str | None" = None,
    record_global: bool = True,
) -> None:
    """Record one latency sample into the global histogram plus the job
    scope (explicit, or this thread's ``set_hist_job`` tag) and the tenant
    scope when given.  Bounded state per scope; one lock per registry hit.

    ``record_global=False`` records the scoped rows only — for a second
    measurement point of a sample the global scope already counted (the
    server sink's per-tenant submit-to-first stamp next to the
    scheduler's per-job one), so global quantiles never double-count.
    """
    if record_global:
        _hist("global", "", name).record(ms)
    job = job if job is not None else getattr(_HIST_TL, "job", None)
    if job:
        _hist("job", job, name).record(ms)
    if tenant:
        _hist("tenant", tenant, name).record(ms)


def hist_snapshot() -> dict:
    """JSON-ready view of every registered histogram, grouped by scope:
    ``{"global": {name: snap}, "jobs": {id: {name: snap}},
    "tenants": {id: {name: snap}}}`` where each snap carries count, sum,
    min/max, p50/p90/p99, and the non-empty buckets."""
    with _HIST_LOCK:
        items = list(_HISTS.items())
    out: dict = {"global": {}, "jobs": {}, "tenants": {}}
    for (kind, scope, name), h in items:
        if kind == "global":
            out["global"][name] = h.snapshot()
        elif kind == "job":
            out["jobs"].setdefault(scope, {})[name] = h.snapshot()
        else:
            out["tenants"].setdefault(scope, {})[name] = h.snapshot()
    return out


def job_latency_snapshot(job_id: str) -> dict:
    """One job's histogram rows, compacted for status(): name ->
    {count, p50_ms, p99_ms, max_ms}."""
    with _HIST_LOCK:
        items = [
            (name, h)
            for (kind, scope, name), h in _HISTS.items()
            if kind == "job" and scope == job_id
        ]
    out = {}
    for name, h in items:
        snap = h.snapshot()
        out[name] = {
            "count": snap["count"],
            "p50_ms": snap["p50_ms"],
            "p99_ms": snap["p99_ms"],
            "max_ms": snap["max_ms"],
        }
    return out


def hist_totals_over(
    kind: str, scope: str, name: str, over_ms: float
) -> "tuple[int, int]":
    """(total samples, samples above ``over_ms``) for one registered
    histogram — (0, 0) when the scope never recorded that metric.  The
    SLO monitors' probe: cumulative pairs diffed across burn windows,
    WITHOUT creating registry rows for scopes that carry no traffic."""
    with _HIST_LOCK:
        h = _HISTS.get((kind, scope, name))
    if h is None:
        return 0, 0
    return h.totals_over(over_ms)


def hist_scopes(kind: str) -> set:
    """The scope ids that hold at least one histogram of ``kind`` — how
    the SLO monitors discover live job/tenant instances to evaluate."""
    with _HIST_LOCK:
        return {scope for (k, scope, _name) in _HISTS if k == kind}


def reset_histograms() -> None:
    """Drop every registered histogram (call before a measurement
    window, read ``hist_snapshot`` after)."""
    with _HIST_LOCK:
        _HISTS.clear()


# ---------------------------------------------------------------------------
# Cross-tenant fused dispatch accounting (runtime/manager.py cohorts over
# core/aggregation.py's FoldRequest plane).  Process-global like the pipeline
# counters: cohorts form on the one scheduler thread but stats drain from
# bench/server/metrics threads, so the lock is load-bearing, not ceremony.


_FUSED_LOCK = threading.Lock()


def _fused_zero() -> dict:
    return {
        # vmapped mega-folds dispatched (>= 2 tenant rows each)
        "fused_dispatches": 0,
        # tenant-job rows folded across all fused dispatches (mean
        # jobs-per-dispatch = fused_jobs_total / fused_dispatches)
        "fused_jobs_total": 0,
        # most tenant rows ever folded by one dispatch
        "fused_jobs_per_dispatch_hwm": 0,
        # windows that found no same-key peer and solo-dispatched (the
        # oracle path) despite fused mode being on
        "fused_solo_fallbacks": 0,
        # pow2 bucket padding: all-masked rows dispatched (bucket size
        # minus live cohort rows, summed) — the cost of 0-recompile
        # tenancy variation
        "fused_pad_rows_total": 0,
    }


_FUSED = _fused_zero()  # guarded-by: _FUSED_LOCK


def fused_add(key: str, amount: int) -> None:
    """Accumulate a fused-dispatch counter (thread-safe; hot-path cheap)."""
    with _FUSED_LOCK:
        _FUSED[key] += amount


def fused_high_water(key: str, value: int) -> None:
    """Raise a fused-dispatch high-water mark to ``value`` if higher."""
    with _FUSED_LOCK:
        if value > _FUSED[key]:
            _FUSED[key] = value


def fused_dispatch_stats() -> dict:
    """Process-wide cross-tenant fused-dispatch counters: mega-fold count,
    jobs-per-dispatch HWM and mean, solo fallbacks, and pow2 bucket pad
    waste.  Reported by bench.py next to ``compile_cache_stats``."""
    with _FUSED_LOCK:
        out = dict(_FUSED)
    n = out["fused_dispatches"]
    out["fused_jobs_per_dispatch_mean"] = (
        round(out["fused_jobs_total"] / n, 4) if n else 0.0
    )
    return out


def reset_fused_dispatch_stats() -> None:
    """Zero the fused-dispatch counters (call before a measurement window,
    read ``fused_dispatch_stats`` after)."""
    global _FUSED
    with _FUSED_LOCK:
        _FUSED = _fused_zero()


# ---------------------------------------------------------------------------
# Masked-SpMV kernel core accounting (ops/spmv.py direction optimization).
# Fixpoints run on whatever thread drives the window loop while stats drain
# from bench/server/metrics threads, so the lock is load-bearing here too.


_SPMV_LOCK = threading.Lock()

# frontier-density histogram bins: bin b counts iterations whose density
# landed in [b/8, (b+1)/8) — 8 SCALAR keys, not a nested dict, so the
# Prometheus renderer (which skips non-scalar values) exports them
SPMV_DENSITY_BINS = 8


def _spmv_zero() -> dict:
    d = {
        # direction-optimized fixpoints driven to completion
        "spmv_fixpoints": 0,
        # iterations lowered as sparse push (SpMSpV) / dense pull (SpMV)
        "spmv_push_iters": 0,
        "spmv_pull_iters": 0,
        # push<->pull flips WITHIN a fixpoint (the regime switches the
        # density threshold actually bought)
        "spmv_direction_switches": 0,
    }
    for b in range(SPMV_DENSITY_BINS):
        d[f"spmv_density_hist_{b}"] = 0
    return d


_SPMV = _spmv_zero()  # guarded-by: _SPMV_LOCK


def spmv_add(key: str, amount: int = 1) -> None:
    """Accumulate a kernel-core counter (thread-safe; hot-path cheap)."""
    with _SPMV_LOCK:
        _SPMV[key] += amount


def spmv_stats() -> dict:
    """Process-wide masked-SpMV direction-optimization counters: push vs
    pull iterations, direction switches per fixpoint, and the frontier-
    density histogram.  Reported by bench.py beside
    ``fused_dispatch_stats``."""
    with _SPMV_LOCK:
        out = dict(_SPMV)
    total = out["spmv_push_iters"] + out["spmv_pull_iters"]
    out["spmv_iters_total"] = total
    out["spmv_push_fraction"] = (
        round(out["spmv_push_iters"] / total, 4) if total else 0.0
    )
    return out


def reset_spmv_stats() -> None:
    """Zero the kernel-core counters (call before a measurement window,
    read ``spmv_stats`` after)."""
    global _SPMV
    with _SPMV_LOCK:
        _SPMV = _spmv_zero()


# ---------------------------------------------------------------------------
# Sketch-summary accounting (library/sketches.py).  Every sketch job carries
# a declared (eps, delta) error contract and a fixed-tiny-state footprint —
# both belong in the observability plane so an operator can see WHICH jobs
# are approximate, at what accuracy, and how many exact-job state budgets
# one chip's sketch tenancy replaced.  Registrations come from the server's
# submit thread while scrapes come from metrics/bench threads, so the lock
# carries the same discipline as every registry above.


_SKETCH_LOCK = threading.Lock()


def _sketch_zero() -> dict:
    return {
        # sketch jobs admitted since the last reset
        "sketch_jobs_registered": 0,
        # persistent summary bytes across registered sketch jobs
        "sketch_state_bytes": 0,
        # admission-priced bytes (state + declared emission scratch) —
        # the figure the admission caps actually charged
        "sketch_admission_bytes": 0,
    }


_SKETCH = _sketch_zero()  # guarded-by: _SKETCH_LOCK
# job key -> {"kind", "eps", "delta", "state_bytes", "admission_bytes"}
_SKETCH_JOBS: dict = {}  # guarded-by: _SKETCH_LOCK


def sketch_register(
    job: str,
    kind: str,
    eps: float,
    delta: float,
    state_bytes: int,
    admission_bytes: int,
) -> None:
    """Record one admitted sketch job and its (eps, delta) contract.

    Re-registering a job key (resubmit after cancel) replaces its row
    without double-counting the byte totals."""
    with _SKETCH_LOCK:
        old = _SKETCH_JOBS.get(job)
        if old is not None:
            _SKETCH["sketch_state_bytes"] -= old["sketch_state_bytes"]
            _SKETCH["sketch_admission_bytes"] -= old["sketch_admission_bytes"]
        else:
            _SKETCH["sketch_jobs_registered"] += 1
        _SKETCH_JOBS[job] = {
            "kind": kind,
            "sketch_eps": float(eps),
            "sketch_delta": float(delta),
            "sketch_state_bytes": int(state_bytes),
            "sketch_admission_bytes": int(admission_bytes),
        }
        _SKETCH["sketch_state_bytes"] += int(state_bytes)
        _SKETCH["sketch_admission_bytes"] += int(admission_bytes)


def sketch_stats() -> dict:
    """Process-wide sketch-tenancy figures: registered job count and the
    summed persistent/admission byte footprints of every live contract."""
    with _SKETCH_LOCK:
        return dict(_SKETCH)


def all_sketch_stats() -> dict:
    """Per-job contract rows: kind, declared (eps, delta), and the
    state/admission byte prices the job was admitted at."""
    with _SKETCH_LOCK:
        return {j: dict(row) for j, row in _SKETCH_JOBS.items()}


def reset_sketch_stats() -> None:
    """Forget every sketch contract (tests and bench measurement windows)."""
    global _SKETCH
    with _SKETCH_LOCK:
        _SKETCH = _sketch_zero()
        _SKETCH_JOBS.clear()


# ---------------------------------------------------------------------------
# exposition: one snapshot of every registry, plus a Prometheus renderer


def metrics_snapshot() -> dict:
    """The full observability registry as one JSON-ready dict: pipeline /
    comms / wire counters, compile-cache stats, per-job and per-tenant
    rows with their module totals, every latency histogram, and the
    flight recorder's per-plane per-stage span aggregates.  This is what
    the server's ``metrics`` verb returns and ``gelly-top`` polls."""
    from gelly_streaming_tpu.utils import tracing

    from gelly_streaming_tpu.utils import events

    return {
        "pipeline": pipeline_stats(),
        "comms": comms_stats(),
        "wire": wire_stats(),
        "compile_cache": compile_cache_stats(),
        "fused": fused_dispatch_stats(),
        "spmv": spmv_stats(),
        "sketch": sketch_stats(),
        "sketch_jobs": all_sketch_stats(),
        "jobs": all_job_stats(),
        "job_totals": job_totals(),
        "tenants": all_tenant_stats(),
        "tenant_totals": tenant_totals(),
        "histograms": hist_snapshot(),
        "spans": tracing.span_stats(),
        "health": all_job_health(),
        "scale": all_job_scale(),
        "alerts": all_alerts(),
        "events": events.journal().stats(),
    }


def _prom_escape(value: str) -> str:
    return (
        str(value)
        .replace("\\", "\\\\")
        .replace('"', '\\"')
        .replace("\n", "\\n")
    )


def _prom_sanitize(name: str) -> str:
    return "".join(c if (c.isalnum() or c == "_") else "_" for c in name)


def render_prometheus(snap: Optional[dict] = None) -> str:
    """Render a metrics snapshot in the Prometheus text exposition format
    (``gelly_``-prefixed): flat counters as gauges, per-job/per-tenant
    rows and health gauges as labeled gauges, SLO alerts as numeric
    ``slo_state`` (0=OK 1=WARN 2=PAGE) plus burn-rate gauges, histograms
    as real Prometheus histograms (cumulative ``_bucket{le=...}`` +
    ``_sum`` + ``_count``), and the span stage aggregates as labeled
    totals.

    Samples are grouped by METRIC FAMILY with one ``# HELP``/``# TYPE``
    header each — the grammar the exposition spec requires (all series of
    a family contiguous, metadata before samples) and the one the
    strict-format lint in tests/test_prometheus_lint.py enforces.  The
    pre-health-plane renderer interleaved a family's job-labeled series
    between other families' rows, which strict scrapers reject.
    """
    if snap is None:
        snap = metrics_snapshot()
    # family name -> {"type", "samples": [(label-str-no-braces, value)]}
    # or {"type": "histogram", "hists": [(label, snapshot dict)]}; dict
    # insertion order IS the exposition order
    fams: dict = {}

    def add(name, value, label="", mtype="gauge"):
        if isinstance(value, bool) or not isinstance(value, (int, float)):
            return
        fam = fams.setdefault(
            f"gelly_{_prom_sanitize(name)}", {"type": mtype, "samples": []}
        )
        fam["samples"].append((label, value))

    for section in (
        "pipeline",
        "comms",
        "wire",
        "compile_cache",
        "fused",
        "spmv",
        "sketch",
        "events",
    ):
        for key, val in sorted(snap.get(section, {}).items()):
            add(key, val)
    # labeled rows grouped PER KEY (one family's series stay contiguous)
    for scope_key, label_name in (
        ("jobs", "job"),
        ("tenants", "tenant"),
        ("health", "job"),
        ("scale", "job"),
        ("sketch_jobs", "job"),
    ):
        rows = snap.get(scope_key, {})
        keys = sorted(
            {
                key
                for row in rows.values()
                for key, val in row.items()
                if isinstance(val, (int, float)) and not isinstance(val, bool)
            }
        )
        for key in keys:
            for sid in sorted(rows):
                val = rows[sid].get(key)
                if isinstance(val, bool) or not isinstance(val, (int, float)):
                    continue
                add(key, val, f'{label_name}="{_prom_escape(sid)}"')
    for row in snap.get("alerts", []):
        label = (
            f'scope="{_prom_escape(row.get("scope", ""))}",'
            f'id="{_prom_escape(row.get("id", ""))}",'
            f'slo="{_prom_escape(row.get("slo", ""))}"'
        )
        add("slo_state", ALERT_LEVELS.get(row.get("state"), 0), label)
        add("slo_burn_fast", row.get("burn_fast", 0.0), label)
        add("slo_burn_slow", row.get("burn_slow", 0.0), label)
    hists = snap.get("histograms", {})
    for name, h in hists.get("global", {}).items():
        fam = fams.setdefault(
            f"gelly_{_prom_sanitize(name)}", {"type": "histogram", "hists": []}
        )
        fam.setdefault("hists", []).append(("", h))
    for scope_key, label_name in (("jobs", "job"), ("tenants", "tenant")):
        for sid, row in sorted(hists.get(scope_key, {}).items()):
            for name, h in row.items():
                fam = fams.setdefault(
                    f"gelly_{_prom_sanitize(name)}",
                    {"type": "histogram", "hists": []},
                )
                fam.setdefault("hists", []).append(
                    (f'{label_name}="{_prom_escape(sid)}"', h)
                )
    for plane, stages in snap.get("spans", {}).get("stages", {}).items():
        for stage, cell in sorted(stages.items()):
            label = (
                f'plane="{_prom_escape(plane)}",'
                f'stage="{_prom_escape(stage)}"'
            )
            add("span_stage_ms_total", cell["total_ms"], label)
            add("span_stage_count", cell["count"], label)

    ratio = 2.0 ** (1.0 / LatencyHistogram.PER_OCTAVE)
    lines: List[str] = []
    for fam_name, fam in fams.items():
        help_text = fam_name[len("gelly_"):].replace("_", " ")
        lines.append(f"# HELP {fam_name} {help_text}")
        lines.append(f"# TYPE {fam_name} {fam['type']}")
        if fam["type"] == "histogram":
            for label, h in fam.get("hists", []):
                sep = "," if label else ""
                cum = 0
                for lower, count in h.get("buckets", []):
                    cum += count
                    # le is the bucket's UPPER bound (snapshot stores lowers)
                    lines.append(
                        f'{fam_name}_bucket{{{label}{sep}'
                        f'le="{round(lower * ratio, 6)}"}} {cum}'
                    )
                lines.append(
                    f'{fam_name}_bucket{{{label}{sep}le="+Inf"}} {h["count"]}'
                )
                braces = f"{{{label}}}" if label else ""
                lines.append(f'{fam_name}_sum{braces} {h["sum_ms"]}')
                lines.append(f'{fam_name}_count{braces} {h["count"]}')
        else:
            for label, value in fam["samples"]:
                braces = f"{{{label}}}" if label else ""
                lines.append(f"{fam_name}{braces} {value}")
    return "\n".join(lines) + "\n"


def compile_cache_stats() -> dict:
    """Process-wide executable-cache counters (core/compile_cache.py):
    entry hits/misses, XLA compiles + compile wall time, steady-state
    dispatch hits, and the retrace count (``recompiles`` — compile events
    beyond the first for the same kernel label + shape, which a healthy
    streaming run keeps at zero)."""
    from gelly_streaming_tpu.core import compile_cache

    return compile_cache.stats()


def reset_compile_cache_stats() -> None:
    """Zero the executable-cache counters (executables stay cached) —
    call before a measurement window, read ``compile_cache_stats`` after."""
    from gelly_streaming_tpu.core import compile_cache

    compile_cache.reset_stats()


@contextlib.contextmanager
def profiled(trace_dir: Optional[str] = None):
    """jax.profiler trace context; no-op when trace_dir is None."""
    if trace_dir is None:
        yield
        return
    import jax

    with jax.profiler.trace(trace_dir):
        yield
