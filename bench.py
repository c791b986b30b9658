#!/usr/bin/env python
"""Benchmark: streaming Connected Components throughput on the TPU data plane.

The BASELINE.json north-star metric: edges/sec on streaming CC (the reference's
hot path, SummaryBulkAggregation fold of DisjointSet.union per edge —
SURVEY.md §3.1) at >= 100M edges.  The reference repo publishes no numbers,
so the baseline is *measured here*: the same edge stream through
an optimized native single-core CPU union-find (native/edge_parser.cpp
cc_baseline — a strictly stronger stand-in for the reference's JVM per-edge
fold).  The denominator is PINNED (VERDICT r3 weak #1): fixed-seed trials run
FIRST in the process — before the device backend exists, so no JAX service
threads compete for the single host core — and the JSON reports every trial
plus the spread alongside the median.

Pipeline under test — the PRODUCT API, not a bespoke harness:

  EdgeStream.from_wire(bufs, ...).aggregate(ConnectedComponents())

i.e. the wire-REPLAY ingest: records arrive already in the framework's wire
format (io/wire.py pack_stream, EF40 sorted-multiset encoding, ~2.7 B/edge)
and the timed loop is transfer -> device unpack -> fused union-find fold with
donated state.  That is the ingest contract the reference's hot operator
actually lives under: Flink's SummaryBulkAggregation consumes tuples the
upstream network stack already serialized (SummaryBulkAggregation.java:76-83);
serialization is the producer's cost, and it is measured and reported here
separately (``pack_eps``), as is the everything-on-one-host path that packs
inside the timed loop (``e2e_eps``, EdgeStream.from_arrays).

The stream folds once, chunk by chunk (GELLY_BENCH_CHUNK_BUFS buffers per
chunk), each chunk timed individually; chunk summaries merge through the
descriptor's own combine (the product combine path — CC is order-free), and
the merged labels are cross-checked against the native CPU union-find over
the full stream.

Headline accounting: value = total_edges / sum(chunk times); value_wall =
total_edges / phase wall; chunks[] / chunk_gbps[] are per-chunk edges/s and
wire rate.

Prints ONE JSON line:
  {"metric": "streaming_cc_edges_per_sec", "value": ..., "unit": "edges/s",
   "vs_baseline": ..., "value_wall": ..., "vs_baseline_wall": ...,
   "edges": ..., "chunks": [...], "chunk_gbps": [...],
   "active_s": ..., "wall_s": ..., "wire_bytes_per_edge": ...,
   "cpu_baseline_eps": ..., "cpu_trials": [...], "cpu_spread": ...,
   "flink_proxy_eps": ..., "vs_flink_proxy": ...,
   "pack_eps": ..., "ckpt_eps": ..., "e2e_eps": ...,
   "e2e_pack_s": ..., "e2e_transfer_s": ..., "e2e_fold_s": ...,
   "e2e_overlap_ratio": ...,
   "device_eps": ..., "device_wire_gbps": ..., "hbm_peak_gbps": ...,
   "hbm_util_lower_bound": ...,
   "triangle_p50_ms": ..., "triangle_p95_ms": ...,
   "triangle_device_p50_ms": ..., "triangle_panes_per_sec": ...,
   "sage_device_p50_ms": ..., "sage_feature_gather_gbps": ...,
   "failed_phases": [...]}

device_eps is the device-only fold rate (unpack + union-find on a resident
buffer): device_wire_gbps = device_eps x wire bytes/edge is a LOWER bound on
achieved HBM bandwidth (state scatters add more traffic), reported against
the chip's published peak (``DEVICE_PEAKS``, keyed by device kind) as
hbm_util_lower_bound.

Failure is loud: a device backend that does not come up within
GELLY_BENCH_INIT_TIMEOUT, comes up on anything but a TPU, or reports a
device kind without a ``DEVICE_PEAKS`` entry exits 3 with the partial JSON
before any timed device work (there is no CPU fallback); a phase that
raises is listed in ``failed_phases`` of the JSON line, and the run then
exits 1.  A phase skipped on purpose (its knob = 0) is not a failure.

Scale knobs via env: GELLY_BENCH_EDGES (default 104857600 = 50 x 2^21 —
the >=100M north-star volume), GELLY_BENCH_VERTICES (default 2^20),
GELLY_BENCH_BATCH (default 2^21 edges -> ~5.6 MB EF40 buffers),
GELLY_BENCH_CHUNK_BUFS (buffers per timed chunk, default 5 -> ~28 MB),
GELLY_BENCH_CPU_TRIALS (5), GELLY_BENCH_E2E_EDGES (default 4M),
GELLY_BENCH_SUPERBATCH
(coalesce K wire batches per device dispatch on the drive; 0 = off),
GELLY_BENCH_INGEST (=0 skips the pre-device ingest-scaling sub-benchmark),
GELLY_INGEST_WORKERS (host ingest worker pool size; default = usable cores).

Host-ingest keys (ISSUE 1): ``ingest_pack_eps_by_workers`` /
``ingest_parse_eps_by_workers`` map worker count -> pre-device edges/s with
``ingest_*_speedup_at_4plus`` the multi-worker multiple over one thread;
``cache_recompiles`` counts XLA recompiles across 100 same-shape windows
after warmup (target 0 — the executable cache, core/compile_cache.py).

Async-window keys (ISSUE 2): ``sync_window_eps`` / ``async_window_eps`` /
``async_window_speedup`` compare the windowed plane's lockstep loop against
the asynchronous pipeline (core/async_exec.py; GELLY_BENCH_ASYNC=0 skips,
GELLY_ASYNC_WINDOWS sets the depth, default 4) over 100 same-shape windows
with a materializing consumer; ``async_emissions_equal`` attests the record
sequences matched bit-for-bit and ``async_cache_recompiles`` that the async
plane stayed at zero recompiles.  The ``pipeline_*`` keys are the
occupancy counters (utils/metrics.pipeline_stats): in-flight window
high-water mark, per-stage stall seconds, prefetch depth, window counts.

Mesh-comms keys (ISSUE 4): the ``comms_*`` counters
(utils/metrics.comms_stats) meter the owner-sharded summary plane —
per-dispatch collective byte volume split into delta-exchange vs
emit/snapshot-gather traffic, exchange round counts, and the
delta-occupancy high-water mark.  The single-chip headline leaves them at
zero; the multichip scaling sweep (__graft_entry__ stage D) reports the
same counters as bytes/edge per shard count, where the O(C/S + delta)
claim is asserted.

SpMV kernel-core keys (ISSUE 17; GELLY_BENCH_SPMV=0 skips):
``spmv_direction_speedup`` is force-push vs auto SSSP wall on a skewed
community graph (the direction-optimization headline),
``spmv_pagerank_eps`` the plus-times power iteration's edge-iterations/s,
``spmv_parity_ok`` bit-parity of the auto and forced answers, and
``spmv_recompiles_after_warm`` the retrace guard across density drift and
direction flips; the ``spmv_*`` registry counters
(utils/metrics.spmv_stats) ride along as info keys.

Fleet-tier keys (ISSUE 20; GELLY_BENCH_FLEET=0 skips):
``fleet_agg_eps_{1,2,4}`` is aggregate router-fronted throughput at 4
clients per backend over 1/2/4 subprocess backends
(``fleet_scaling_ratio`` the 4-vs-1 multiple), ``router_overhead_p50_ms``
the placed-verb RTT tax of the extra hop (results, not ping — the router
answers ping locally), ``fleet_failover_downtime_ms`` the SIGKILL ->
standby takeover -> first-accepted-push gap through one router address,
and ``fleet_warm_recompiles`` the same-shape retrace guard behind the
router (target 0).  GELLY_BENCH_FLEET_WINDOWS / _WIN_EDGES scale it.
"""

import ctypes
import json
import os
import statistics
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np

# Published per-chip HBM bandwidth, keyed by ``jax.Device.device_kind``
# (Google Cloud documentation, "TPU v5e": 819 GB/s).  A device kind not
# listed here is an error, not a default.
DEVICE_PEAKS = {
    "TPU v5 lite": {"hbm_gbps": 819.0},
}


def device_peaks(kind: str) -> dict:
    if kind not in DEVICE_PEAKS:
        raise KeyError(
            f"no published peaks for device kind {kind!r}: add them to "
            "DEVICE_PEAKS with their source"
        )
    return DEVICE_PEAKS[kind]


# phases that raised; the JSON line lists them and the run exits 1
_FAILED_PHASES = []


def _phase_failed(phase: str, err: Exception) -> None:
    _FAILED_PHASES.append(phase)
    _PARTIAL["failed_phases"] = list(_FAILED_PHASES)
    print(f"{phase} FAILED: {type(err).__name__}: {err}", file=sys.stderr)


def _device_fold_eps(agg, stream, trace_dir, reps: int = 48) -> float:
    """Device-only fold rate: re-fold one RESIDENT wire buffer reps times.

    No host->device transfer in the timed loop, so this isolates the data
    plane (device unpack + union-find fold, donated carry) from the
    host->device transfer — the number that shows how much ingest headroom
    the kernel leaves.  The timed loop is NOT profiler-traced; a short
    separate traced run afterwards exercises the tracing subsystem
    end-to-end (utils/metrics.profiled).
    """
    import jax

    from gelly_streaming_tpu.utils.metrics import profiled

    cfg = stream.cfg
    bufs, batch, width, _ = stream._wire_packed
    fused, _ = agg._wire_fused_step(stream, batch, width)
    buf = jax.device_put(bufs[0], jax.devices()[0])
    carry = jax.device_put(
        (
            tuple(stage.init(cfg) for stage in stream._stages),
            agg.initial_state(cfg),
        ),
        jax.devices()[0],
    )
    carry = fused(carry, buf)  # compile + warm
    jax.block_until_ready(carry)
    t0 = time.perf_counter()
    for _ in range(reps):
        carry = fused(carry, buf)
    jax.block_until_ready(carry)
    eps = reps * batch / (time.perf_counter() - t0)
    if trace_dir:
        with profiled(trace_dir):
            for _ in range(4):
                carry = fused(carry, buf)
            jax.block_until_ready(carry)
    return eps


def _triangle_latency(seed: int = 0, windows: int = 15, k: int = 4096):
    """Per-pane triangle-count latency through the pipelined pane runner
    (Pallas MXU kernel; 4 B/edge packed uploads ride the prefetcher under
    the previous pane's compute).

    Reports THREE views (see pipelined_pane_counts): close -> device
    completion p50 (the data plane: scatter + MXU kernel), close ->
    host-visible result p50/p95 (adds the device->host result delivery),
    and the pipelined pane THROUGHPUT (panes/s
    — readbacks of pane k overlap panes k+1.., so sustained rate is not
    latency-bound).  A sequential pass prints alongside for contrast."""
    import time as _time

    from gelly_streaming_tpu.library.triangles import (
        _pane_triangle_count,
        pipelined_pane_counts,
    )
    from gelly_streaming_tpu.utils.metrics import WindowLatencyRecorder

    rng = np.random.default_rng(seed)
    per_pane = 1 << 17
    panes = [
        (
            rng.integers(0, k, per_pane).astype(np.int32),
            rng.integers(0, k, per_pane).astype(np.int32),
        )
        for _ in range(windows + 1)
    ]
    _pane_triangle_count(*panes[0])  # compile/warm OUTSIDE the timed window
    rec = WindowLatencyRecorder()
    dev_rec = WindowLatencyRecorder()
    t0 = _time.perf_counter()
    counts = pipelined_pane_counts(
        panes, recorder=rec, warmup=1, depth=4, device_recorder=dev_rec
    )
    pane_rate = (windows + 1) / (_time.perf_counter() - t0)
    assert len(counts) == windows + 1
    seq = WindowLatencyRecorder()
    for src, dst in panes[1:5]:  # pane 0 already compiled/warmed everything
        seq.window_closed()
        _pane_triangle_count(src, dst)
        seq.result_emitted()
    print(
        f"triangle pane p50: device {dev_rec.percentile(50):.1f} ms, "
        f"host-visible {rec.percentile(50):.1f} ms, "
        f"{pane_rate:.1f} panes/s pipelined vs sequential "
        f"{seq.percentile(50):.1f} ms/pane",
        file=sys.stderr,
    )
    return {
        "triangle_p50_ms": rec.percentile(50),
        "triangle_p95_ms": rec.percentile(95),
        "triangle_device_p50_ms": dev_rec.percentile(50),
        "triangle_panes_per_sec": pane_rate,
    }


def _async_window_bench(
    windows: int = 100, win_edges: int = 1 << 13, capacity: int = 1 << 16
):
    """Windowed-plane throughput, sync vs async pipeline (ISSUE 2).

    Many small SAME-SHAPE event-time windows of CC through the windowed
    runtime (not the wire fast path), with a materializing consumer — every
    window's emission is fetched to host, the realistic sink contract
    (collect/CSV/checkpoint all materialize) and the regime the synchronous
    loop serializes: host windowing -> fold -> blocking fetch, one window
    at a time.  The async pipeline (cfg.async_windows) overlaps the three;
    emissions are compared for exact equality and recompiles are counted
    across the async windows (the executable-cache guard extended to the
    async plane: same shapes -> zero recompiles).
    """
    import dataclasses

    import jax

    from gelly_streaming_tpu.core import compile_cache
    from gelly_streaming_tpu.core.config import StreamConfig
    from gelly_streaming_tpu.core.stream import EdgeStream
    from gelly_streaming_tpu.core.types import EdgeBatch
    from gelly_streaming_tpu.library.connected_components import (
        ConnectedComponents,
    )
    from gelly_streaming_tpu.utils import metrics

    n = windows * win_edges
    rng = np.random.default_rng(3)
    src = rng.integers(0, capacity, n).astype(np.int64)
    dst = rng.integers(0, capacity, n).astype(np.int64)
    t_ms = (np.arange(n) // win_edges) * 100 + 50  # 100ms tumbling panes
    bs = win_edges // 2  # batches never align with window cuts

    cfg_sync = StreamConfig(vertex_capacity=capacity, batch_size=bs)
    cfg_async = dataclasses.replace(
        cfg_sync, async_windows=int(os.environ.get("GELLY_ASYNC_WINDOWS", 4))
    )
    # The env var is captured into cfg_async above and must NOT leak into
    # the sync oracle runs: with cfg_sync left at 0, resolve_depth would
    # fall through to the var and silently flip the "sync" baseline onto
    # the async path (a self-comparison reading ~1.0x).  Hold it cleared
    # for the whole stage — both modes are explicit via their configs.
    env_depth = os.environ.pop("GELLY_ASYNC_WINDOWS", None)

    def factory():
        for i in range(0, n, bs):
            yield EdgeBatch.from_arrays(
                src[i : i + bs], dst[i : i + bs], time=t_ms[i : i + bs]
            )

    def run(cfg):
        out = []
        stream = EdgeStream.from_batches(factory, cfg)
        for rec in ConnectedComponents(window_ms=100).run(stream):
            # materialize the emission (what any real sink does per window)
            out.append(np.asarray(rec[0].parent))
        return out

    try:
        run(cfg_sync)  # compile + warm both paths
        run(cfg_async)
        t0 = time.perf_counter()
        sync_out = run(cfg_sync)
        sync_eps = n / (time.perf_counter() - t0)
        metrics.reset_pipeline_stats()
        compile_cache.reset_stats()
        t0 = time.perf_counter()
        async_out = run(cfg_async)
        async_eps = n / (time.perf_counter() - t0)
        recompiles = compile_cache.stats()["recompiles"]
    finally:
        if env_depth is not None:
            os.environ["GELLY_ASYNC_WINDOWS"] = env_depth
    equal = len(sync_out) == len(async_out) and all(
        np.array_equal(a, b) for a, b in zip(sync_out, async_out)
    )
    return {
        "sync_window_eps": round(sync_eps, 1),
        "async_window_eps": round(async_eps, 1),
        "async_window_speedup": round(async_eps / sync_eps, 2),
        "async_windows_depth": cfg_async.async_windows,
        "async_emissions_equal": bool(equal),
        "async_cache_recompiles": recompiles,
        **metrics.pipeline_stats(),
    }


def _multi_tenant_bench(
    windows: int = 40, win_edges: int = 1 << 13, capacity: int = 1 << 16
):
    """Multi-tenant job runtime sweep (ISSUE 5): jobs in {1, 2, 4}.

    Same-shape streaming-CC queries over the wire fast path with running
    per-window emission, co-scheduled by the JobManager on one device
    pipeline.  Reported: aggregate eps per job count, per-job fairness at
    4 jobs (min/max job-throughput ratio — jobs are identical, so a fair
    scheduler finishes them at near-identical rates), scheduler overhead
    (1 runtime job vs the same query run directly), and the retrace guard
    (same-shape jobs must share executables: 0 recompiles after the
    single-job warmup).
    """
    from gelly_streaming_tpu.core import compile_cache
    from gelly_streaming_tpu.core.config import RuntimeConfig, StreamConfig
    from gelly_streaming_tpu.core.stream import EdgeStream
    from gelly_streaming_tpu.library.connected_components import (
        ConnectedComponents,
    )
    from gelly_streaming_tpu.runtime import JobManager
    from gelly_streaming_tpu.utils import metrics

    n = windows * win_edges
    bs = win_edges // 2  # aligned: windows cut on batch boundaries
    cfg = StreamConfig(
        vertex_capacity=capacity, batch_size=bs, ingest_window_edges=win_edges
    )
    rng = np.random.default_rng(11)
    datasets = [
        (
            rng.integers(0, capacity, n).astype(np.int32),
            rng.integers(0, capacity, n).astype(np.int32),
        )
        for _ in range(4)
    ]

    def direct_run():
        stream = EdgeStream.from_arrays(*datasets[0], cfg)
        for rec in stream.aggregate(ConnectedComponents()):
            np.asarray(rec[0].parent)  # materialize: the sink contract

    direct_run()  # the single job's warmup: compiles land here
    t0 = time.perf_counter()
    direct_run()
    single_eps = n / (time.perf_counter() - t0)

    compile_cache.reset_stats()
    out = {"multi_tenant_single_eps": round(single_eps, 1)}
    for n_jobs in (1, 2, 4):
        metrics.reset_job_stats()
        finish = {}
        t0 = time.perf_counter()
        # quantum 1: finest interleaving, so per-job finish-time skew (the
        # fairness figure) measures the scheduler, not the round size
        with JobManager(
            RuntimeConfig(max_jobs=8, fair_quantum=1)
        ) as manager:
            for i in range(n_jobs):
                def sink(rec, i=i):
                    np.asarray(rec[0].parent)  # materialize per emission
                    finish[i] = time.perf_counter()

                manager.submit_aggregation(
                    EdgeStream.from_arrays(*datasets[i], cfg),
                    ConnectedComponents(),
                    name=f"cc-{n_jobs}x-{i}",
                    sink=sink,
                )
            manager.wait_all()
        wall = time.perf_counter() - t0
        agg_eps = n_jobs * n / wall
        out[f"multi_tenant_eps_{n_jobs}"] = round(agg_eps, 1)
        per_job_eps = [n / (finish[i] - t0) for i in range(n_jobs)]
        out[f"multi_tenant_fairness_{n_jobs}"] = round(
            min(per_job_eps) / max(per_job_eps), 3
        )
    out["multi_tenant_overhead"] = round(
        out["multi_tenant_eps_1"] / single_eps, 3
    )
    out["multi_tenant_agg_ratio_4"] = round(
        out["multi_tenant_eps_4"] / single_eps, 3
    )
    out["multi_tenant_recompiles"] = compile_cache.stats()["recompiles"]
    out["multi_tenant_compiles_after_warm"] = compile_cache.stats()[
        "compiles"
    ]
    out.update(
        {
            f"multi_tenant_{k}": v
            for k, v in metrics.job_totals().items()
            if k in ("job_records", "job_queue_full_skips")
        }
    )
    out.update(_fused_dispatch_bench())
    return out


def _fused_dispatch_bench(windows: int = 64, win_edges: int = 256,
                          capacity: int = 1 << 12):
    """Cross-tenant fused dispatch quadrant (ISSUE 16): jobs in {1, 4, 16}
    with ``cfg.fused_dispatch`` off/on.

    Same-shape streaming-CC queries on the plain windowed plane (batch
    misaligned to the window cut, so the wire fast path does not claim
    them), small windows so per-dispatch overhead — the thing fused
    cohorts amortize — dominates device compute.  All jobs are submitted
    behind one shared ``ready`` gate and released together: per-job
    finish-time skew then measures the scheduler's fairness, not
    submission-order head start.  Sinks materialize only each job's final
    state; intermediate window partials stay device-resident, as a
    streaming consumer that reads the converged answer would leave them.

    Reported per (jobs, mode): aggregate eps; plus the 16-job
    fused-vs-solo speedup (the ISSUE 16 headline), 16-job fused fairness,
    bit-exact parity of every job's final component labels between the
    fused and solo planes, and the retrace guard across 1 -> 16 tenancy
    (pow2 row buckets: 0 compiles after warmup).
    """
    import dataclasses
    import threading

    import jax.numpy as jnp

    from gelly_streaming_tpu.core import compile_cache
    from gelly_streaming_tpu.core.config import RuntimeConfig, StreamConfig
    from gelly_streaming_tpu.core.stream import EdgeStream
    from gelly_streaming_tpu.library.connected_components import (
        ConnectedComponents,
    )
    from gelly_streaming_tpu.runtime import JobManager
    from gelly_streaming_tpu.utils import metrics

    n = windows * win_edges
    cfg_solo = StreamConfig(
        vertex_capacity=capacity,
        batch_size=(win_edges // 2) + 32,  # misaligned: windowed plane
        ingest_window_edges=win_edges,
        fused_dispatch=0,
    )
    cfg_fused = dataclasses.replace(cfg_solo, fused_dispatch=1)
    rng = np.random.default_rng(16)
    datasets = [
        (
            rng.integers(0, capacity, n).astype(np.int32),
            rng.integers(0, capacity, n).astype(np.int32),
        )
        for _ in range(16)
    ]

    def run(n_jobs, cfg):
        finish = {}
        finals = {}
        seen = [0] * n_jobs
        release = threading.Event()
        with JobManager(
            RuntimeConfig(max_jobs=16, fair_quantum=4)
        ) as manager:
            for i in range(n_jobs):
                def sink(rec, i=i):
                    seen[i] += 1
                    if seen[i] == windows:
                        finals[i] = np.asarray(rec[0].parent)
                        finish[i] = time.perf_counter()

                manager.submit_aggregation(
                    EdgeStream.from_arrays(*datasets[i], cfg),
                    ConnectedComponents(),
                    name=f"fd-{cfg.fused_dispatch}-{n_jobs}x-{i}",
                    sink=sink,
                    ready=release.is_set,
                )
            t0 = time.perf_counter()
            release.set()
            manager.poke()
            manager.wait_all()
        wall = time.perf_counter() - t0
        per_job_eps = [n / (finish[i] - t0) for i in range(n_jobs)]
        return (
            n_jobs * n / wall,
            min(per_job_eps) / max(per_job_eps),
            [finals[i] for i in range(n_jobs)],
        )

    # warmup: one solo-plane and one fused-plane job land the per-cfg
    # executables, then every pow2 row bucket lands its mega-fold +
    # cohort-split pair, so the sweep below must retrace nothing
    run(1, cfg_solo)
    run(1, cfg_fused)
    cc = ConnectedComponents()
    fold = cc._superpane_fold_fn(cfg_fused, False)
    for rows in (2, 4, 8, 16):
        states = fold(
            jnp.zeros((rows, win_edges), jnp.int32),
            jnp.zeros((rows, win_edges), jnp.int32),
            None,
            jnp.zeros((rows, win_edges), bool),
        )
        cc._superpane_split_fn(cfg_fused, rows)(states)
    compile_cache.reset_stats()
    metrics.reset_fused_dispatch_stats()

    out = {}
    finals = {}
    for n_jobs in (1, 4, 16):
        solo_eps, _, solo_finals = run(n_jobs, cfg_solo)
        fused_eps, fused_fair, fused_finals = run(n_jobs, cfg_fused)
        out[f"fused_off_agg_eps_{n_jobs}"] = round(solo_eps, 1)
        out[f"fused_agg_eps_{n_jobs}"] = round(fused_eps, 1)
        finals[n_jobs] = (solo_finals, fused_finals)
        if n_jobs == 16:
            out["fused_vs_solo_speedup"] = round(fused_eps / solo_eps, 3)
            out["fairness_min_max_fused"] = round(fused_fair, 3)
    out["fused_parity_ok"] = int(
        all(
            np.array_equal(s, f)
            for solo_finals, fused_finals in finals.values()
            for s, f in zip(solo_finals, fused_finals)
        )
    )
    out["fused_recompiles_after_warm"] = compile_cache.stats()["recompiles"]
    out["fused_compiles_after_warm"] = compile_cache.stats()["compiles"]
    out.update(metrics.fused_dispatch_stats())
    return out


def _sketch_bench(
    windows: int = 16, win_edges: int = 1 << 12, capacity: int = 1 << 18
):
    """Sketch-summary tenancy quadrant (ISSUE 19): fixed-tiny-state
    approximate descriptors vs their exact twins on one chip.

    Three figures, all regression-gated:

    * ``sketch_tenancy_ratio`` — jobs ADMITTED under the same
      ``max_state_bytes`` cap, HLL degree-cardinality sketch vs the exact
      degree summary at the same vertex capacity (the >= 10x headline:
      sketch admission bytes are a function of (eps, delta), not of
      ``vertex_capacity``, so the exact job's O(C) budget buys dozens of
      sketch tenants).  Counted by real submits against a real
      ``JobManager`` byte cap — jobs are gated unreleased so completions
      can't free budget mid-count — not by arithmetic on declared sizes.
    * ``sketch_triangle_rel_err`` — the neighborhood-sampling triangle
      estimate vs the exact dense-adjacency count on a seeded
      hub-clustered graph.  Seeded stream + salted hashing make the
      estimate DETERMINISTIC per platform, so the gate pins a constant,
      not a random draw.
    * ``sketch_recompiles_after_warm`` — 1 -> 16 sketch-job tenancy drift
      with fused dispatch on, after a single-job warmup: same-contract
      tenants share ``cache_token`` and must retrace nothing.

    Plus ``sketch_agg_eps_{1,16}`` (aggregate fold throughput of the
    sketch tenancy with ``fused_dispatch=1``) for the eps ledger.
    """
    import threading

    from gelly_streaming_tpu.core import compile_cache
    from gelly_streaming_tpu.core.config import RuntimeConfig, StreamConfig
    from gelly_streaming_tpu.core.stream import EdgeStream
    from gelly_streaming_tpu.library.degree_distribution import (
        DegreeDistributionSummary,
    )
    from gelly_streaming_tpu.library.sketches import (
        HLLDegreeSummary,
        SketchTriangleCount,
    )
    from gelly_streaming_tpu.runtime import JobManager
    from gelly_streaming_tpu.runtime.job import AdmissionError

    out = {}
    rng = np.random.default_rng(19)

    # ---- tenancy under one byte cap: exact degree vs HLL degree sketch ----
    tiny_n = win_edges  # one window per admission probe: admission is the
    # contended resource here, not fold volume
    cfg = StreamConfig(
        vertex_capacity=capacity,
        batch_size=win_edges // 2,
        ingest_window_edges=win_edges,
    )
    tiny = (
        rng.integers(0, capacity, tiny_n).astype(np.int32),
        rng.integers(0, capacity, tiny_n).astype(np.int32),
    )
    exact_bytes = DegreeDistributionSummary().admission_nbytes(cfg)
    cap_bytes = 2 * exact_bytes  # exactly two exact jobs fit

    def admitted(make_desc, tag):
        release = threading.Event()
        count = 0
        with JobManager(
            RuntimeConfig(max_jobs=600, max_state_bytes=cap_bytes)
        ) as manager:
            for i in range(600):
                try:
                    manager.submit_aggregation(
                        EdgeStream.from_arrays(*tiny, cfg),
                        make_desc(),
                        name=f"adm-{tag}-{i}",
                        sink=lambda rec: None,
                        ready=release.is_set,
                    )
                except AdmissionError:
                    break
                count += 1
            release.set()
            manager.poke()
            manager.wait_all()
        return count

    n_exact = admitted(DegreeDistributionSummary, "exact")
    n_sketch = admitted(HLLDegreeSummary, "hll")
    out["sketch_exact_admitted"] = n_exact
    out["sketch_admitted"] = n_sketch
    out["sketch_tenancy_ratio"] = round(n_sketch / max(n_exact, 1), 2)

    # ---- triangle estimate vs the exact count (seeded, deterministic) -----
    tri_cap = 256
    tri_n = 40 << 10
    ts, td = _skewed_sample(np.random.default_rng(7), tri_n, tri_cap)
    tri_cfg = StreamConfig(
        vertex_capacity=tri_cap,
        batch_size=1 << 12,
        ingest_window_edges=tri_n,
    )
    tri = SketchTriangleCount(eps=0.05, delta=0.05)
    est = None
    for rec in EdgeStream.from_arrays(ts, td, tri_cfg).aggregate(tri):
        est = float(np.asarray(rec[0]))
    adj = np.zeros((tri_cap, tri_cap), dtype=np.int64)
    keep = ts != td
    adj[ts[keep], td[keep]] = 1
    adj = np.maximum(adj, adj.T)
    exact_tri = int(np.trace(adj @ adj @ adj)) // 6
    out["sketch_triangle_exact"] = exact_tri
    out["sketch_triangle_est"] = round(est, 1)
    out["sketch_triangle_rel_err"] = round(
        abs(est - exact_tri) / max(exact_tri, 1), 4
    )

    # ---- 1 -> 16 sketch tenancy, fused dispatch on, retrace guard ---------
    n = windows * win_edges
    fused_cfg = StreamConfig(
        vertex_capacity=1 << 16,
        # misaligned to the window cut: the wire fast path declines, the
        # windowed plane runs, and fused cohorts get to form
        batch_size=(win_edges // 2) + 32,
        ingest_window_edges=win_edges,
        fused_dispatch=1,
    )
    datasets = [
        (
            rng.integers(0, 1 << 16, n).astype(np.int32),
            rng.integers(0, 1 << 16, n).astype(np.int32),
        )
        for _ in range(16)
    ]

    def run(n_jobs):
        release = threading.Event()
        with JobManager(
            RuntimeConfig(max_jobs=16, fair_quantum=4)
        ) as manager:
            for i in range(n_jobs):
                manager.submit_aggregation(
                    EdgeStream.from_arrays(*datasets[i], fused_cfg),
                    HLLDegreeSummary(),
                    name=f"sk-{n_jobs}x-{i}",
                    sink=lambda rec: np.asarray(rec[0]),
                    ready=release.is_set,
                )
            t0 = time.perf_counter()
            release.set()
            manager.poke()
            manager.wait_all()
        return n_jobs * n / (time.perf_counter() - t0)

    run(1)  # warmup: the sketch fold + transform executables land here
    compile_cache.reset_stats()
    out["sketch_agg_eps_1"] = round(run(1), 1)
    out["sketch_agg_eps_16"] = round(run(16), 1)
    out["sketch_recompiles_after_warm"] = compile_cache.stats()["recompiles"]
    out["sketch_compiles_after_warm"] = compile_cache.stats()["compiles"]
    return out


def _spmv_bench(capacity: int = 1 << 15, num_edges: int = 1 << 18):
    """Masked-semiring SpMV kernel core (ISSUE 17): direction optimization
    on a skewed community graph.

    SSSP (min-plus fixpoint) from the heaviest zipf hub on a graph whose
    frontier saturates within a couple of hops: nearly every iteration is
    dense, where the pull lowering's sorted segment reduce beats the push
    expansion's full-width scatter by ~3x per iteration.  Reported: the
    force-push-vs-auto wall ratio (the ISSUE 17 headline,
    ``spmv_direction_speedup``), pagerank edge-iteration throughput via
    the kernel core, bit-parity of the auto and forced answers, the
    retrace guard (0 recompiles across density drift and direction flips
    — the traced threshold is the only thing that changes between modes),
    and the spmv_stats registry (push/pull iteration split, density
    histogram, direction switches).
    """
    import jax
    import jax.numpy as jnp

    from gelly_streaming_tpu.core import compile_cache
    from gelly_streaming_tpu.ops import spmv
    from gelly_streaming_tpu.utils import metrics

    rng = np.random.default_rng(17)
    src = ((rng.zipf(1.2, num_edges) - 1) % capacity).astype(np.int32)
    dst = rng.integers(0, capacity, num_edges).astype(np.int32)
    w = rng.random(num_edges).astype(np.float32)
    msk = np.ones((num_edges,), bool)
    op = spmv.prepare_pane(src, dst, w, msk, capacity)
    dist0 = (
        jnp.full((capacity,), spmv.MIN_PLUS.identity, jnp.float32)
        .at[0].set(0.0)
    )

    def run(direction):
        res = spmv.fixpoint(
            spmv.MIN_PLUS, op, dist0, max_iters=capacity - 1,
            direction=direction,
        )
        jax.block_until_ready(res.x)
        return res

    op_pr = spmv.prepare_pane(src, dst, None, msk, capacity)

    def run_pr():
        r, _, iters = spmv.pagerank_fixpoint(
            op_pr, damping=0.85, tol=1e-6, max_iters=50
        )
        jax.block_until_ready(r)
        return int(iters)

    # warmup: land every (bucket, direction) executable the sweep uses —
    # the timed section below must then retrace nothing
    for d in ("auto", "push", "pull"):
        run(d)
    run_pr()
    compile_cache.reset_stats()
    metrics.reset_spmv_stats()

    def wall(fn):
        t0 = time.perf_counter()
        out = fn()
        return out, time.perf_counter() - t0

    trials = [
        (wall(lambda: run("auto")), wall(lambda: run("push")))
        for _ in range(3)
    ]
    auto_w = min(t for (_, t), _ in trials)
    push_w = min(t for _, (_, t) in trials)
    res_auto = trials[-1][0][0]
    res_push = trials[-1][1][0]
    pr_iters, pr_w = wall(run_pr)

    out = {
        "spmv_direction_speedup": round(push_w / auto_w, 3),
        "spmv_pagerank_eps": round(num_edges * pr_iters / pr_w, 1),
        "spmv_parity_ok": int(
            np.array_equal(np.asarray(res_auto.x), np.asarray(res_push.x))
        ),
        "spmv_recompiles_after_warm": compile_cache.stats()["recompiles"],
    }
    out.update(metrics.spmv_stats())
    return out


def _serving_bench(
    clients=(1, 4, 16), windows: int = 16, win_edges: int = 1 << 12,
    capacity: int = 1 << 14,
):
    """Streaming RPC serving plane sweep (ISSUE 8): connection scaling.

    For each client count k, k threads each open their own connection to a
    loopback StreamServer, submit a same-shape streaming-CC job, push the
    edge stream as BDV-compressed wire batches, and consume the emission
    records.  Reported: aggregate eps per client count, p50/p99
    submit-to-first-emission latency across every client, the
    server-vs-in-process throughput ratio at 4 clients (the serving tax:
    framing + sockets + the results plane over the same scheduler), and
    the per-tenant ingest ledger beside it.
    """
    import threading

    from gelly_streaming_tpu.core.config import (
        RuntimeConfig,
        ServerConfig,
        StreamConfig,
    )
    from gelly_streaming_tpu.core.stream import EdgeStream
    from gelly_streaming_tpu.core.types import EdgeBatch
    from gelly_streaming_tpu.library.connected_components import (
        ConnectedComponents,
    )
    from gelly_streaming_tpu.runtime import JobManager
    from gelly_streaming_tpu.runtime.client import GellyClient
    from gelly_streaming_tpu.runtime.server import StreamServer
    from gelly_streaming_tpu.utils import metrics

    if windows < 2:
        # the first-emission probe pushes one window plus its closing
        # boundary batch; a single-window stream would never close it
        raise ValueError("serving bench needs windows >= 2")
    n = windows * win_edges
    bs = win_edges // 2
    cfg = StreamConfig(
        vertex_capacity=capacity, batch_size=bs, ingest_window_edges=win_edges
    )
    rng = np.random.default_rng(17)
    max_k = max(clients)
    datasets = [
        (
            rng.integers(0, capacity, n).astype(np.int32),
            rng.integers(0, capacity, n).astype(np.int32),
        )
        for _ in range(max_k)
    ]

    # in-process baseline over the SAME plane the remote jobs ride (the
    # windowed ingestion-pane runtime over decoded batches), 4 jobs
    def batches_stream(i):
        s, d = datasets[i]

        def factory():
            for o in range(0, n, bs):
                yield EdgeBatch.from_arrays(
                    s[o : o + bs], d[o : o + bs], pad_to=bs
                )

        return EdgeStream.from_batches(factory, cfg)

    def inproc_run(k):
        with JobManager(RuntimeConfig(max_jobs=max(8, k))) as jm:
            jobs = [
                jm.submit_aggregation(
                    batches_stream(i),
                    ConnectedComponents(),
                    name=f"inproc-{k}-{i}",
                    sink=lambda rec: np.asarray(rec[0].parent),
                )
                for i in range(k)
            ]
            t0 = time.perf_counter()
            jm.wait_all()
            del jobs
            return k * n / (time.perf_counter() - t0)

    inproc_run(4)  # warmup: compiles land here
    inproc_eps_4 = inproc_run(4)

    metrics.reset_tenant_stats()
    # the server-side histograms are the bench's second latency source:
    # reset them so the sweep's quantiles cover exactly these runs
    metrics.reset_histograms()
    out = {"serving_inprocess_eps_4": round(inproc_eps_4, 1)}
    latencies = []
    server_snap = None
    server_status = None
    for k in clients:
        first_emit = {}
        errors = []
        with JobManager(
            RuntimeConfig(max_jobs=max(8, k))
        ) as jm, StreamServer(jm, ServerConfig()) as server:

            def run_client(i):
                try:
                    s, d = datasets[i]
                    with GellyClient("127.0.0.1", server.port) as c:
                        name = f"cc-{k}x-{i}"
                        t_submit = time.perf_counter()
                        c.submit(
                            name=name,
                            query="cc",
                            capacity=capacity,
                            window_edges=win_edges,
                            batch=bs,
                        )
                        # first window + its closing boundary, then wait
                        # for the first emission: submit-to-first-emission
                        # measures the serving plane's latency floor, not
                        # the wall time of pushing the whole stream
                        head = win_edges + bs
                        c.push_edges(
                            name, s[:head], d[:head], batch=bs,
                            capacity=capacity, bdv=True, close=False,
                        )
                        probe_deadline = time.monotonic() + 120
                        while True:
                            recs, state, eos = c.results(
                                name, timeout_ms=5_000
                            )
                            if recs:
                                first_emit[i] = (
                                    time.perf_counter() - t_submit
                                )
                                break
                            if eos or state in ("FAILED", "CANCELLED"):
                                raise RuntimeError(
                                    f"{name} ended ({state}) before its "
                                    "first emission"
                                )
                            if time.monotonic() > probe_deadline:
                                raise RuntimeError(
                                    f"{name} produced no first emission "
                                    "within 120s"
                                )
                        c.push_edges(
                            name, s, d, batch=bs, capacity=capacity,
                            bdv=True, start=head,
                        )
                        for _rec in c.iter_results(name, deadline_s=600):
                            pass
                except BaseException as e:
                    errors.append(e)

            t0 = time.perf_counter()
            threads = [
                threading.Thread(target=run_client, args=(i,))
                for i in range(k)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            wall = time.perf_counter() - t0
            if k == max(clients) and not errors:
                # source the sweep's latency quantiles from the SERVER'S
                # own bounded histograms through the metrics verb — the
                # cross-check for the client-side probe above, and the
                # path gelly-top reads in production
                try:
                    with GellyClient("127.0.0.1", server.port) as mc:
                        server_snap = mc.metrics()
                        server_status = mc.status().get("server", {})
                except Exception:
                    server_snap = None  # probe numbers still stand
                    server_status = None
        if errors:
            raise errors[0]
        out[f"serving_eps_{k}"] = round(k * n / wall, 1)
        latencies.extend(first_emit.values())
    lat_ms = sorted(1e3 * x for x in latencies)
    out["serving_submit_to_first_emission_p50_ms"] = round(
        lat_ms[len(lat_ms) // 2], 1
    )
    out["serving_submit_to_first_emission_p99_ms"] = round(
        lat_ms[min(len(lat_ms) - 1, int(len(lat_ms) * 0.99))], 1
    )
    out["serving_vs_inprocess_ratio_4"] = round(
        out["serving_eps_4"] / inproc_eps_4, 3
    )
    # the ROADMAP item-1 headline under its canonical name too (the 0.4 ->
    # 0.8 climb this PR pins): same figure, the name the issue/regression
    # gate track — `_ratio` suffix = higher-better direction rule
    out["serving_vs_inprocess_ratio"] = out["serving_vs_inprocess_ratio_4"]
    totals = metrics.tenant_totals()
    out.update(
        {
            f"serving_{key}": totals[key]
            for key in (
                "tenant_requests",
                "tenant_ingest_edges",
                "tenant_ingest_wire_bytes",
                "tenant_ingest_raw_bytes",
                "tenant_admission_rejections",
                "tenant_ingest_queue_hwm",
            )
        }
    )
    out["serving_wire_bytes_per_edge"] = round(
        totals["tenant_ingest_wire_bytes"]
        / max(totals["tenant_ingest_edges"], 1),
        3,
    )
    # histogram-derived quantiles BESIDE the probe numbers (never instead:
    # the probe measures what a client saw, the histograms what the server
    # measured itself; the ratio is the cross-check).  The tenant-scoped
    # submit-to-first row is stamped at the server's sink, so it excludes
    # the final results-fetch RTT the probe pays — expect hist <= probe.
    hist_row = None
    if server_snap is not None:
        hist_row = (
            server_snap.get("histograms", {})
            .get("tenants", {})
            .get("default", {})
            .get("submit_to_first_emission_ms")
        )
    if hist_row and hist_row.get("count"):
        out["serving_hist_submit_to_first_emission_p50_ms"] = hist_row[
            "p50_ms"
        ]
        out["serving_hist_submit_to_first_emission_p99_ms"] = hist_row[
            "p99_ms"
        ]
        out["serving_hist_vs_probe_p50_ratio"] = round(
            hist_row["p50_ms"]
            / max(out["serving_submit_to_first_emission_p50_ms"], 1e-9),
            3,
        )
    # push-to-fold latency as FIRST-CLASS keys (ISSUE 14): how long a
    # pushed batch sat between the socket and the scheduler's fold — the
    # serving data plane's own residency, the figure the decode pool
    # exists to shrink.  Sourced from the server's bounded histogram
    # (io/sources.py stamps enqueue time per batch); `_ms` suffix =
    # lower-better under --check-regression.  _PARTIAL-safe: when the
    # metrics fetch failed the keys are simply absent (SKIP, not a fail).
    ptf_row = None
    if server_snap is not None:
        ptf_row = (
            server_snap.get("histograms", {})
            .get("global", {})
            .get("push_to_fold_ms")
        )
    if ptf_row and ptf_row.get("count"):
        out["serving_push_to_fold_p50_ms"] = ptf_row["p50_ms"]
        out["serving_push_to_fold_p99_ms"] = ptf_row["p99_ms"]
    if server_status:
        # the decode plane the sweep actually rode: pool size and
        # native-vs-twin served counts (informational, not direction-tracked)
        if "decode_workers" in server_status:
            out["serving_decode_workers"] = server_status["decode_workers"]
        if isinstance(server_status.get("decode"), dict):
            out["serving_decode_native"] = server_status["decode"].get(
                "native", 0
            )
    if server_snap is not None:
        # compact global-scope histogram snapshots for the bench JSON
        out["serving_histograms"] = {
            name: {
                "count": snap["count"],
                "p50_ms": snap["p50_ms"],
                "p99_ms": snap["p99_ms"],
                "max_ms": snap["max_ms"],
            }
            for name, snap in server_snap.get("histograms", {})
            .get("global", {})
            .items()
        }
    return out


def _rescale_bench(
    windows: int = 24, win_edges: int = 1 << 12, capacity: int = 1 << 14
):
    """Elastic control plane sub-bench (ISSUE 11): live re-shard cost.

    One checkpointed degree job on a loopback server: push + consume the
    first half of the stream at S=1 (the pre-rescale eps baseline), then
    drive the serving plane's rescale actuator directly (deterministic —
    no SLO timing in the measurement): drain -> re-route state into the
    2x geometry -> resubmit from the resume cursor.  Reported:

    * ``rescale_downtime_ms`` — the drain-to-first-post-rescale-emission
      gap (cold S=2 compiles included: that IS the downtime a tenant
      sees), lower-better via the ``_ms`` suffix rule;
    * ``rescale_post_eps_ratio`` — steady post-rescale eps over the
      pre-rescale baseline (on a many-core host with a real mesh this is
      the scale-out win; on this CPU image it tracks the mesh overhead),
      higher-better via the ``_ratio`` suffix rule;
    * ``rescale_exact`` — the final degree vector equals the full-stream
      oracle (non-idempotent counts exact across the rescale).
    """
    import tempfile
    import threading

    from gelly_streaming_tpu.core.config import RuntimeConfig, ServerConfig
    from gelly_streaming_tpu.runtime import JobManager
    from gelly_streaming_tpu.runtime.client import GellyClient
    from gelly_streaming_tpu.runtime.server import (
        StreamServer,
        _ServedRescaleTarget,
    )

    if windows < 6:
        raise ValueError("rescale bench needs windows >= 6")
    n = windows * win_edges
    bs = win_edges // 2
    rng = np.random.default_rng(23)
    src = rng.integers(0, capacity, n).astype(np.int32)
    dst = rng.integers(0, capacity, n).astype(np.int32)
    half = (windows // 2) * win_edges
    out = {}
    with tempfile.TemporaryDirectory() as td:
        with JobManager(RuntimeConfig()) as jm, StreamServer(
            jm, ServerConfig(checkpoint_prefix=os.path.join(td, "ck"))
        ) as server:
            with GellyClient("127.0.0.1", server.port) as c:
                c.submit(
                    name="rb",
                    query="degree",
                    capacity=capacity,
                    window_edges=win_edges,
                    batch=bs,
                    checkpoint=True,
                )
                t0 = time.perf_counter()
                c.push_edges(
                    "rb", src[:half], dst[:half], batch=bs,
                    capacity=capacity, close=False,
                )
                # exactly half pushed: the last pre-rescale window is held
                # open, so half/W - 1 records are deliverable
                expect_pre = half // win_edges - 1
                got = 0
                while got < expect_pre:
                    recs, state, _eos = c.results("rb", timeout_ms=5000)
                    got += len(recs)
                    if state in ("FAILED", "CANCELLED"):
                        raise RuntimeError(f"pre-rescale job ended {state}")
                pre_eps = half / (time.perf_counter() - t0)
                # drain stragglers so the post-phase's first record is NEW
                while True:
                    recs, _state, _eos = c.results("rb", timeout_ms=200)
                    if not recs:
                        break
                with server._lock:
                    sj = server._jobs["default/rb"]
                handle = _ServedRescaleTarget(server, sj)
                t_drain = time.perf_counter()
                res = handle.rescale(2, "bench")
                resume = int(res["resume_edges"])

                def repush():
                    deadline = time.monotonic() + 300
                    with GellyClient("127.0.0.1", server.port) as c2:
                        while True:
                            try:
                                c2.push_edges(
                                    "rb", src, dst, batch=bs,
                                    capacity=capacity, start=resume,
                                )
                                return
                            except Exception:
                                if time.monotonic() > deadline:
                                    raise
                                time.sleep(0.05)

                th = threading.Thread(target=repush)
                th.start()
                first_new = None
                last = None
                for rec in c.iter_results("rb", deadline_s=600):
                    if first_new is None:
                        first_new = time.perf_counter()
                    last = rec
                th.join(60)
                t_end = time.perf_counter()
                final = np.asarray(last[0])
                oracle = np.bincount(src, minlength=capacity) + np.bincount(
                    dst, minlength=capacity
                )
                post_edges = n - resume
                out = {
                    "rescale_pre_eps": round(pre_eps, 1),
                    # steady-state: first post-rescale emission -> eos
                    # (the downtime key owns the cold-compile gap)
                    "rescale_post_eps": round(
                        post_edges / max(t_end - first_new, 1e-9), 1
                    ),
                    "rescale_downtime_ms": round(
                        (first_new - t_drain) * 1e3, 1
                    ),
                    "rescale_resume_edges": resume,
                    "rescale_exact": bool(
                        np.array_equal(final, oracle.astype(final.dtype))
                    ),
                }
                out["rescale_post_eps_ratio"] = round(
                    out["rescale_post_eps"] / max(pre_eps, 1e-9), 3
                )
    return out


def _fleet_bench(
    backends=(1, 2, 4), windows: int = 8, win_edges: int = 1 << 12,
    capacity: int = 1 << 14, clients_per_backend: int = 4,
):
    """Fleet serving tier sweep (ISSUE 20): router scaling + failover.

    Four figures, all through one ``gelly-router`` front address:

    * ``fleet_agg_eps_{1,2,4}`` — aggregate throughput with 4 clients per
      backend over 1/2/4 SUBPROCESS backends (separate interpreters =
      real compute scaling, not GIL-shared threads), placement spread by
      the rendezvous hash; ``fleet_scaling_ratio`` pins the 4-vs-1
      multiple the tier exists to deliver.
    * ``router_overhead_p50_ms`` — the extra hop's tax on a PLACED verb
      (``results`` with ``timeout_ms=0``): p50 RTT through the router
      minus p50 RTT direct to the same backend.  NOT measured on ping,
      which the router answers locally without touching a backend.
    * ``fleet_failover_downtime_ms`` — SIGKILL the only serving backend
      mid-stream, let the probe->failover->takeover chain run, and time
      kill -> first ACCEPTED push of the resilient client through the
      same router address (includes the standby's resubmit + resync).
    * ``fleet_warm_recompiles`` — the 0-recompile guarantee survives the
      router hop: a second same-shape job behind an in-process backend
      must land entirely in the executable cache.
    """
    import shutil
    import subprocess
    import threading

    from gelly_streaming_tpu.core import compile_cache
    from gelly_streaming_tpu.core.config import RuntimeConfig, ServerConfig
    from gelly_streaming_tpu.runtime import JobManager
    from gelly_streaming_tpu.runtime.client import GellyClient
    from gelly_streaming_tpu.runtime.fleet import (
        BackendSpec,
        Fleet,
        FleetConfig,
    )
    from gelly_streaming_tpu.runtime.router import GLYRouter, RouterConfig
    from gelly_streaming_tpu.runtime.server import StreamServer

    n = windows * win_edges
    bs = win_edges // 2
    repo = os.path.dirname(os.path.abspath(__file__))
    env = dict(
        os.environ,
        XLA_FLAGS="--xla_force_host_platform_device_count=1",
        JAX_PLATFORMS="cpu",
        PYTHONPATH=repo + os.pathsep + os.environ.get("PYTHONPATH", ""),
    )

    def spawn(bdir, extra=()):
        os.makedirs(bdir, exist_ok=True)
        proc = subprocess.Popen(
            [
                sys.executable, "-m", "gelly_streaming_tpu.runtime.serve",
                "--listen", "127.0.0.1:0",
                "--checkpoint-prefix", os.path.join(bdir, "ck"),
                "--status-interval", "0", *extra,
            ],
            env=env, stderr=subprocess.PIPE, stdout=subprocess.DEVNULL,
        )
        port = None
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            line = proc.stderr.readline().decode()
            if "listening on" in line:
                port = int(line.rsplit(":", 1)[1])
                break
            if not line and proc.poll() is not None:
                break
        if port is None:
            proc.kill()
            raise RuntimeError("fleet bench backend never reported its port")
        return proc, port

    rng = np.random.default_rng(23)
    max_k = max(backends) * clients_per_backend
    datasets = [
        (
            rng.integers(0, capacity, n).astype(np.int32),
            rng.integers(0, capacity, n).astype(np.int32),
        )
        for _ in range(max_k)
    ]
    out = {}
    td = tempfile.mkdtemp(prefix="fleet_bench_")
    procs = []
    try:
        # ---- subprocess pool: spawn once, warm once, sweep subsets ----
        ports = []
        for b in range(max(backends)):
            proc, port = spawn(os.path.join(td, f"b{b + 1}"))
            procs.append(proc)
            ports.append(port)
        for b, port in enumerate(ports):
            ws, wd = datasets[b % max_k]
            with GellyClient("127.0.0.1", port) as c:
                c.submit(
                    name="warm", query="edges", capacity=capacity,
                    window_edges=win_edges, batch=bs,
                )
                c.push_edges(
                    "warm", ws[: 2 * win_edges], wd[: 2 * win_edges],
                    batch=bs, capacity=capacity, bdv=True,
                )
                for _rec in c.iter_results("warm", deadline_s=300):
                    pass

        # ---- placed-verb router tax (backend 1, live unfed job) ----
        with GellyClient("127.0.0.1", ports[0]) as c:
            c.submit(
                name="ovh", query="edges", capacity=capacity,
                window_edges=win_edges, batch=bs,
            )

        def rtt_p50(port, reps=200):
            samples = []
            with GellyClient("127.0.0.1", port) as c:
                for _ in range(reps):
                    t0 = time.perf_counter()
                    c.results("ovh", timeout_ms=0)
                    samples.append(time.perf_counter() - t0)
            samples.sort()
            return 1e3 * samples[len(samples) // 2]

        direct_p50 = rtt_p50(ports[0])
        spec_one = BackendSpec("b1", "127.0.0.1", ports[0])
        fleet_one = Fleet(
            FleetConfig(backends=(spec_one,), probe_interval_s=3600.0)
        )
        with GLYRouter(fleet_one, RouterConfig()) as router:
            routed_p50 = rtt_p50(router.port)
        out["router_overhead_p50_ms"] = round(routed_p50 - direct_p50, 3)

        # ---- aggregate eps over 1/2/4 backends, 4 clients each ----
        for nb in backends:
            specs = tuple(
                BackendSpec(f"b{i + 1}", "127.0.0.1", ports[i])
                for i in range(nb)
            )
            fleet = Fleet(
                FleetConfig(backends=specs, probe_interval_s=3600.0)
            )
            k = nb * clients_per_backend
            errors = []

            def run_client(i, port):
                try:
                    s, d = datasets[i]
                    name = f"fl{nb}x{i}"
                    with GellyClient("127.0.0.1", port) as c:
                        c.submit(
                            name=name, query="edges", capacity=capacity,
                            window_edges=win_edges, batch=bs,
                        )
                        c.push_edges(
                            name, s, d, batch=bs, capacity=capacity,
                            bdv=True,
                        )
                        for _rec in c.iter_results(name, deadline_s=600):
                            pass
                except BaseException as e:
                    errors.append(e)

            with GLYRouter(fleet, RouterConfig()) as router:
                t0 = time.perf_counter()
                threads = [
                    threading.Thread(target=run_client, args=(i, router.port))
                    for i in range(k)
                ]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join()
                wall = time.perf_counter() - t0
            if errors:
                raise errors[0]
            out[f"fleet_agg_eps_{nb}"] = round(k * n / wall, 1)
        out["fleet_scaling_ratio"] = round(
            out[f"fleet_agg_eps_{max(backends)}"]
            / max(out[f"fleet_agg_eps_{min(backends)}"], 1e-9),
            3,
        )

        # ---- failover: kill -> takeover -> first accepted push ----
        fdir = os.path.join(td, "fo")
        fproc, fport = spawn(
            os.path.join(fdir, "bf"),
            ("--events-path", os.path.join(fdir, "bf", "journal.jsonl")),
        )
        procs.append(fproc)
        sproc, sport = spawn(
            os.path.join(fdir, "sb"),
            ("--events-path", os.path.join(fdir, "sb", "journal.jsonl")),
        )
        procs.append(sproc)
        fo_specs = (
            BackendSpec(
                "bf", "127.0.0.1", fport,
                journal_path=os.path.join(fdir, "bf", "journal.jsonl"),
                checkpoint_prefix=os.path.join(fdir, "bf", "ck"),
            ),
            BackendSpec(
                "sb", "127.0.0.1", sport,
                journal_path=os.path.join(fdir, "sb", "journal.jsonl"),
                checkpoint_prefix=os.path.join(fdir, "sb", "ck"),
                standby=True,
            ),
        )
        fleet = Fleet(
            FleetConfig(
                backends=fo_specs,
                replica_dir=os.path.join(fdir, "replica"),
                probe_interval_s=0.05,
                probe_timeout_s=1.0,
                fail_threshold=2,
                replicate_interval_s=3600.0,
            )
        )
        src, dst = datasets[0]
        half = n // 2
        with GLYRouter(fleet, RouterConfig()) as router:
            with GellyClient("127.0.0.1", router.port) as c:
                c.submit(
                    name="fo", query="edges", capacity=capacity,
                    window_edges=win_edges, batch=bs, checkpoint=True,
                )
                c.push_edges(
                    "fo", src[:half], dst[:half], batch=bs,
                    capacity=capacity, bdv=True, close=False,
                )
                # drain every closed window so the checkpoint cursor is
                # on disk before the kill (half/W edges close half/W - 1
                # windows: the last needs its boundary-crossing edge)
                closed = half // win_edges - 1
                got = 0
                deadline = time.monotonic() + 120
                while got < closed and time.monotonic() < deadline:
                    recs, _state, _eos = c.results("fo", timeout_ms=2000)
                    got += len(recs)
                fleet.replicate_once()
                t_kill = time.perf_counter()
                fproc.kill()
                # the resilient push rides rerouted -> reconnect ->
                # out-of-sync resync onto the standby; it returns at the
                # first ACCEPTED batch past the resume cursor
                c.push_edges_resilient(
                    "fo", src[: half + bs], dst[: half + bs], batch=bs,
                    capacity=capacity, start=half, close=False,
                    deadline_s=180.0, backoff_s=0.05,
                )
                out["fleet_failover_downtime_ms"] = round(
                    (time.perf_counter() - t_kill) * 1e3, 1
                )

        # ---- the 0-recompile guarantee behind the router hop ----
        with JobManager(RuntimeConfig(max_jobs=8)) as jm, StreamServer(
            jm, ServerConfig()
        ) as srv:
            inproc = Fleet(
                FleetConfig(
                    backends=(BackendSpec("inb", "127.0.0.1", srv.port),),
                    probe_interval_s=3600.0,
                )
            )
            with GLYRouter(inproc, RouterConfig()) as router:

                def one_job(name):
                    s, d = datasets[1]
                    with GellyClient("127.0.0.1", router.port) as c:
                        c.submit(
                            name=name, query="edges", capacity=capacity,
                            window_edges=win_edges, batch=bs,
                        )
                        c.push_edges(
                            name, s, d, batch=bs, capacity=capacity,
                            bdv=True,
                        )
                        for _rec in c.iter_results(name, deadline_s=300):
                            pass

                one_job("rc-warm")
                rc0 = compile_cache.stats()["recompiles"]
                one_job("rc-measure")
                out["fleet_warm_recompiles"] = (
                    compile_cache.stats()["recompiles"] - rc0
                )
    finally:
        for proc in procs:
            if proc.poll() is None:
                proc.kill()
        for proc in procs:
            try:
                proc.wait(timeout=30)
            except Exception:
                pass
        shutil.rmtree(td, ignore_errors=True)
    return out


_PARTIAL = {}  # best results so far, emitted by the deadline watchdog


# ---------------------------------------------------------------------------
# --check-regression: compare a fresh bench JSON against the best-so-far
# per key across the recorded BENCH_r*.json artifacts (ISSUE 10).  CI's
# keep-up check for the bench itself: a fresh run whose tracked keys fall
# beyond tolerance of the historical best exits nonzero with a per-key
# verdict table.  _PARTIAL-safe by construction — keys missing from the
# fresh run (a watchdog's partial line) or from every baseline are
# SKIP/NEW, never failures.

# direction rules by suffix/name: "higher" keys regress downward, "lower"
# keys regress upward; anything unclassified (or non-scalar) is skipped
_HIGHER_KEYS = {
    "value",
    "value_wall",
    "vs_baseline",
    "vs_baseline_wall",
    # the serving headline at its historical client-count-suffixed name:
    # `_ratio_4` evades the `_ratio` suffix rule, and this figure is the
    # ROADMAP item-1 target the regression gate must hold
    "serving_vs_inprocess_ratio_4",
    # ISSUE 16 fused-dispatch headlines: the job-count suffix evades the
    # `_eps` rule, and fairness/parity carry no classified suffix at all
    "fused_agg_eps_16",
    # ISSUE 19 sketch tenancy: same job-count-suffix evasion
    "sketch_agg_eps_16",
    "fairness_min_max_fused",
    "fused_parity_ok",
    # ISSUE 17 spmv kernel core: answer parity across directions carries
    # no classified suffix (the _eps/_speedup/recompiles keys classify
    # themselves)
    "spmv_parity_ok",
    # ISSUE 20 fleet tier: the backend-count suffix evades the `_eps`
    # rule (scaling_ratio/overhead_ms/downtime_ms/recompiles classify
    # themselves)
    "fleet_agg_eps_1",
    "fleet_agg_eps_2",
    "fleet_agg_eps_4",
}
_HIGHER_SUFFIXES = (
    "_eps",
    "_speedup",
    "_gbps",
    "_ratio",
    "_spread",
    "_util_lower_bound",
)
_LOWER_SUFFIXES = (
    "_ms",
    "_bytes_per_edge",
    "_spilled",
    "_findings",
    # ISSUE 19 sketch accuracy: a relative-error figure regresses UPWARD
    # (the seeded streams make it deterministic per platform, so the gate
    # pins a constant, not a random draw)
    "_rel_err",
)
_LOWER_SUBSTRINGS = ("recompiles", "_stall_s")


def _bench_direction(key):
    """'higher' / 'lower' / None (= not a tracked perf key)."""
    if key in _HIGHER_KEYS or key.endswith(_HIGHER_SUFFIXES):
        return "higher"
    if key.endswith(_LOWER_SUFFIXES) or any(
        s in key for s in _LOWER_SUBSTRINGS
    ):
        return "lower"
    return None


def _load_bench_json(path):
    """A bench artifact's metric dict: either the raw JSON line main()
    prints, or the driver wrapper whose ``parsed`` key holds it."""
    with open(path) as f:
        doc = json.load(f)
    if isinstance(doc.get("parsed"), dict):
        doc = doc["parsed"]
    return doc if isinstance(doc, dict) else {}


def _bench_scalars(doc):
    return {
        k: float(v)
        for k, v in doc.items()
        if isinstance(v, (int, float)) and not isinstance(v, bool)
    }


def check_regression(fresh_path, baseline_glob="BENCH_r*.json", tolerance=0.05):
    """Per-key verdicts of ``fresh_path`` vs the best-so-far baselines.

    Returns the process exit code: 1 iff any tracked key regressed beyond
    ``tolerance`` (relative; absolute for a 0 lower-better best, so a
    recompile count creeping off 0 is caught).
    """
    import glob as _glob

    fresh = _bench_scalars(_load_bench_json(fresh_path))
    best = {}
    baselines = sorted(_glob.glob(baseline_glob))
    for path in baselines:
        try:
            scalars = _bench_scalars(_load_bench_json(path))
        except (OSError, ValueError):
            continue  # a torn/partial artifact is skipped, never fatal
        for key, val in scalars.items():
            direction = _bench_direction(key)
            if direction is None:
                continue
            if key not in best:
                best[key] = val
            elif direction == "higher":
                best[key] = max(best[key], val)
            else:
                best[key] = min(best[key], val)
    rows = []
    failed = 0
    for key in sorted(set(best) | set(fresh)):
        direction = _bench_direction(key)
        if direction is None:
            continue
        b, f = best.get(key), fresh.get(key)
        if f is None:
            verdict = "SKIP (missing in fresh — partial run)"
        elif b is None:
            verdict = "NEW (no baseline)"
        elif direction == "higher":
            verdict = "REGRESS" if f < b * (1.0 - tolerance) else "OK"
        elif b == 0:
            verdict = "REGRESS" if f > tolerance else "OK"
        else:
            verdict = "REGRESS" if f > b * (1.0 + tolerance) else "OK"
        failed += verdict == "REGRESS"
        rows.append((key, direction, b, f, verdict))
    width = max([len(r[0]) for r in rows], default=10)

    def fmt(x):
        return "-" if x is None else f"{x:.4g}"

    print(
        f"{'key':<{width}}  {'dir':<6} {'best':>12} {'fresh':>12}  verdict"
    )
    for key, direction, b, f, verdict in rows:
        print(
            f"{key:<{width}}  {direction:<6} {fmt(b):>12} {fmt(f):>12}  "
            f"{verdict}"
        )
    print(
        f"check-regression: {len(rows)} tracked key(s) vs "
        f"{len(baselines)} baseline artifact(s), tolerance "
        f"{tolerance:.0%}, {failed} regression(s)"
    )
    return 1 if failed else 0


def _check_regression_cli(argv):
    import argparse

    parser = argparse.ArgumentParser(
        prog="bench.py --check-regression",
        description="compare a fresh bench JSON against the best-so-far "
        "per key across BENCH_r*.json; exit 1 on regression",
    )
    parser.add_argument("--check-regression", dest="fresh", required=True,
                        metavar="FRESH_JSON")
    parser.add_argument("--tolerance", type=float, default=0.05,
                        help="relative slack before a key regresses")
    parser.add_argument("--glob", default="BENCH_r*.json",
                        help="baseline artifact glob")
    args = parser.parse_args(argv)
    return check_regression(args.fresh, args.glob, args.tolerance)


def _print_partial(error: str) -> None:
    """Print the JSON line with ``error`` and whatever ``_PARTIAL`` holds."""
    partial = dict(_PARTIAL)
    # a fully-measured headline survives a later-phase wedge
    value = partial.pop("value_so_far", None)
    print(
        json.dumps(
            {
                "error": error,
                "metric": "streaming_cc_edges_per_sec",
                "value": value,
                "unit": "edges/s",
                "vs_baseline": None,
                **partial,
            }
        ),
        flush=True,
    )


def _watchdog(seconds: float, what: str, exit_code: int):
    """Emit the partial JSON line and exit ``exit_code`` if ``what`` wedges.

    Without this a hung device init or a hung collect() would block the run
    forever with no artifact.  The emitted line carries whatever metrics
    were already measured (``_PARTIAL``).  Returns a cancel().
    """
    import threading

    done = threading.Event()

    def watch():
        if not done.wait(seconds):
            _print_partial(f"{what} exceeded {seconds:.0f}s; partial results only")
            os._exit(exit_code)

    threading.Thread(target=watch, daemon=True).start()
    return done.set


def _cpu_baseline(src, dst, capacity: int, trials: int, sample: int):
    """Pinned native single-core union-find denominator.

    Runs BEFORE any device/JAX work so nothing competes for the host core
    (round 3's denominator swung 45->93M eps between runs measured after
    device phases).  Fixed data (seed 0), ``trials`` timed passes over the
    same ``sample`` prefix, median + every trial reported.
    """
    from gelly_streaming_tpu.utils.native import load_ingest_lib

    lib = load_ingest_lib()
    if lib is None:
        return None, []
    cpu_trials = []
    for _ in range(trials):
        parent = np.arange(capacity, dtype=np.int32)
        ns = lib.cc_baseline(
            src.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            dst.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            sample,
            parent.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            capacity,
        )
        cpu_trials.append(sample / (ns / 1e9))
    return statistics.median(cpu_trials), cpu_trials


def _flink_proxy(src, dst, capacity: int, trials: int, sample: int):
    """Measured Flink-shaped record-at-a-time baseline (VERDICT r4 item 2).

    The pinned ``cpu_baseline_eps`` is a deliberately strong array union-find
    with none of the costs the reference actually pays per record.  This
    measures those costs in this image: per-record Tuple2 big-endian
    serialization + key-group selection, a kernel AF_UNIX socketpair shuffle
    hop in 32 KiB network buffers, record-at-a-time deserialization, and a
    HashMap-backed DisjointSet fold (native/edge_parser.cpp flink_proxy_cc —
    optimized C++, so still an UPPER bound on the JVM stack it mimics:
    pom.xml:38-63 provided runtime, SimpleEdgeStream.java:461-478,
    DisjointSet.java:92-118).  Labels are cross-checked against cc_baseline's
    on the same sample.  Runs pre-device like the pinned denominator.
    """
    from gelly_streaming_tpu.utils.native import load_ingest_lib

    lib = load_ingest_lib()
    if lib is None or not hasattr(lib, "flink_proxy_cc"):
        return None, [], None
    proxy_trials = []
    labels = np.empty(capacity, np.int32)
    for _ in range(trials):
        ns = lib.flink_proxy_cc(
            src.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            dst.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            sample,
            labels.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            capacity,
        )
        if ns <= 0:
            return None, [], None
        proxy_trials.append(sample / (ns / 1e9))
    parent = np.arange(capacity, dtype=np.int32)
    lib.cc_baseline(
        src.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        dst.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        sample,
        parent.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        capacity,
    )
    return (
        statistics.median(proxy_trials),
        proxy_trials,
        bool(np.array_equal(labels, parent)),
    )


def _ingest_scaling(src, dst, capacity: int, sample: int, batch: int):
    """Pre-device host ingest throughput by worker count (no JAX anywhere).

    Measures the two CPU-bound ingest stages the parallel worker pool
    (io/ingest.py) shards: text PARSING (native byte-range workers over a
    generated edge file) and wire PACKING (arena rows packed in parallel).
    Reports edges/s per worker count plus the multi-worker speedup over the
    single-threaded path — the ISSUE-1 acceptance number.  Worker counts
    beyond the host's usable cores still run (threads timeshare); the
    per-count report makes the scaling curve — and any core-bound plateau —
    visible instead of hiding it in one number.
    """
    from gelly_streaming_tpu.io import ingest, wire

    cores = ingest.resolve_workers(0)
    counts = sorted({1, 2, 4, max(4, cores)})
    width = wire.width_for_capacity(capacity)
    s = src[:sample]
    d = dst[:sample]

    pack_eps = {}
    for w in counts:
        t0 = time.perf_counter()
        bufs, _ = ingest.parallel_pack_stream(s, d, batch, width, workers=w)
        pack_eps[str(w)] = round(len(s) / (time.perf_counter() - t0), 1)
        del bufs

    parse_eps = {}
    parse_sample = min(sample, 4 << 20)
    path = None
    try:
        import tempfile as _tf

        fd, path = _tf.mkstemp(suffix=".edges")
        with os.fdopen(fd, "w") as f:
            f.write(
                "\n".join(
                    f"{a} {b}"
                    for a, b in zip(
                        s[:parse_sample].tolist(), d[:parse_sample].tolist()
                    )
                )
                + "\n"
            )
        for w in counts:
            t0 = time.perf_counter()
            out = ingest.parse_edge_file_parallel(path, workers=w)
            parse_eps[str(w)] = round(len(out[0]) / (time.perf_counter() - t0), 1)
    finally:
        if path:
            os.unlink(path)

    # ---- propagation-blocking pack + compressed wire bytes (ISSUE 6) ------
    # Measured on a skewed, community-clustered sample — the workload the
    # destination-binned delta/varint format exists for (uniform-random
    # endpoints have no locality for deltas to exploit).  Pure host, like
    # the rest of this sub-benchmark: sort+encode rate by worker count plus
    # the shipped bytes/edge against the plain fixed-width pack and the raw
    # 8 B/edge int32 columns.
    from gelly_streaming_tpu.utils import metrics as _metrics

    sk_s, sk_d = _skewed_sample(np.random.default_rng(6), sample, capacity)
    # small smoke runs can have sample < batch: shrink the BDV batch rather
    # than skipping (n_bdv of 0 would have no rows to measure)
    bdv_batch = max(min(batch, sample), 1)
    n_bdv = max(sample // bdv_batch, 1)
    binned_pack_eps = {}
    comp_bytes = 0
    # pack_bdv_group bumps the process-global bin-occupancy high-water;
    # this synthetic hub-heavy sample must not masquerade as drive skew in
    # the headline JSON, so snapshot/restore around the measurement
    wire_base = _metrics.wire_stats()
    try:
        for w in counts:
            t0 = time.perf_counter()
            arena = ingest.pack_bdv_group(
                sk_s, sk_d, 0, n_bdv, bdv_batch, capacity, workers=w
            )
            binned_pack_eps[str(w)] = round(
                (n_bdv * bdv_batch) / (time.perf_counter() - t0), 1
            )
            del arena
        # per-batch shipped bytes (no group-max padding): the fast path's
        # figure
        comp_bytes = sum(
            wire.pack_edges_bdv(
                sk_s[i * bdv_batch : (i + 1) * bdv_batch],
                sk_d[i * bdv_batch : (i + 1) * bdv_batch],
                capacity,
            ).nbytes
            for i in range(n_bdv)
        )
    finally:
        _restore_wire_stats(_metrics, wire_base)
    plain_bpe = wire.wire_nbytes(bdv_batch, width) / bdv_batch
    comp_bpe = comp_bytes / (n_bdv * bdv_batch)

    best = max((k for k in pack_eps if int(k) >= 4), key=int)
    return {
        "ingest_workers_available": cores,
        "ingest_pack_eps_by_workers": pack_eps,
        "ingest_parse_eps_by_workers": parse_eps,
        "ingest_pack_speedup_at_4plus": round(
            pack_eps[best] / pack_eps["1"], 2
        ),
        "ingest_parse_speedup_at_4plus": round(
            parse_eps[best] / parse_eps["1"], 2
        ),
        "binned_pack_eps_by_workers": binned_pack_eps,
        "binned_pack_eps": max(binned_pack_eps.values()),
        "bytes_per_edge": {
            "raw": 8.0,
            "plain": round(plain_bpe, 3),
            "compressed": round(comp_bpe, 3),
        },
        "wire_compress_ratio_vs_raw": round(8.0 / comp_bpe, 2),
        "wire_compress_ratio_vs_plain": round(plain_bpe / comp_bpe, 2),
    }


def _restore_wire_stats(_metrics, base: dict) -> None:
    """Reset the process-global wire counters back to a ``wire_stats()``
    snapshot — sub-benchmarks measure through the shared registry but must
    not leak their synthetic traffic into the headline drive's figures."""
    _metrics.reset_wire_stats()
    _metrics.wire_record_batch(
        base["wire_batches"], base["wire_edges_total"], base["wire_bytes_total"]
    )
    _metrics.wire_high_water(
        "wire_bin_occupancy_hwm", base["wire_bin_occupancy_hwm"]
    )


def _skewed_sample(rng, n: int, capacity: int):
    """Community-clustered, hub-heavy edges: the propagation-blocking target
    workload (real graphs have locality; uniform-random ids are the
    adversarial case for any delta format)."""
    comm = max(capacity >> 14, 64)
    cbase = ((capacity * rng.random(n) ** 2).astype(np.int64) // comm) * comm
    s = cbase + (comm * rng.random(n) ** 2).astype(np.int64)
    d = cbase + (comm * rng.random(n) ** 4).astype(np.int64)
    return (s % capacity).astype(np.int32), (d % capacity).astype(np.int32)


def _binned_wire_bench(num_edges: int, capacity: int, batch: int):
    """Binned+compressed ingest on vs off through the REAL wire fast path
    (ISSUE 6 acceptance): same skewed sample, same descriptor, bit-identical
    emissions; reports measured edges/s both ways plus the byte economy.

    On this CPU image the device fold is scatter-OVERHEAD-bound (XLA CPU
    scatters cost ~200 ns/update however local), so the measured speedup
    here understates the binned format; the link-bound figure
    (``wire_link_bound_speedup`` — bytes_plain / bytes_compressed, the
    exact factor a byte-limited link gains).
    """
    from gelly_streaming_tpu.core.config import StreamConfig
    from gelly_streaming_tpu.core.stream import EdgeStream
    from gelly_streaming_tpu.library.degree_distribution import (
        DegreeDistributionSummary,
    )
    from gelly_streaming_tpu.utils import metrics as _metrics

    src, dst = _skewed_sample(np.random.default_rng(6), num_edges, capacity)

    # the per-run measurements below reset the process-global wire counters;
    # snapshot what the drive accumulated so far and restore it on the way
    # out, so the headline JSON's cumulative wire_stats stay cumulative
    base = _metrics.wire_stats()

    def run(**kw):
        cfg = StreamConfig(vertex_capacity=capacity, batch_size=batch, **kw)

        def once():
            return list(
                DegreeDistributionSummary().run(
                    EdgeStream.from_arrays(src, dst, cfg)
                )
            )

        once()  # compile warmup
        _metrics.reset_wire_stats()
        t0 = time.perf_counter()
        recs = once()
        dt = time.perf_counter() - t0
        return num_edges / dt, _metrics.wire_stats(), recs

    # "off" = the plain fixed-width arrival-order layout — the ISSUE's
    # uncompressed equivalence oracle (auto mode may pick EF40 on multi-core
    # hosts, which is itself a compressed format; the explicit 0s pin the
    # baseline against ambient GELLY_BINNED_INGEST/GELLY_WIRE_COMPRESS env,
    # which would otherwise silently compress the "off" run too)
    try:
        plain_eps, plain_w, plain_recs = run(
            wire_encoding="plain", binned_ingest=0, wire_compress=0
        )
        comp_eps, comp_w, comp_recs = run(wire_compress=1)
    finally:
        _restore_wire_stats(_metrics, base)
    equal = len(plain_recs) == len(comp_recs) and all(
        np.array_equal(np.asarray(a[0]), np.asarray(b[0]))
        for a, b in zip(plain_recs, comp_recs)
    )
    return {
        "plain_wire_eps": round(plain_eps, 1),
        "compressed_wire_eps": round(comp_eps, 1),
        "binned_wire_speedup": round(comp_eps / plain_eps, 2),
        "wire_bytes_per_edge_plain": plain_w["wire_bytes_per_edge"],
        "wire_bytes_per_edge_compressed": comp_w["wire_bytes_per_edge"],
        "wire_link_bound_speedup": round(
            plain_w["wire_bytes_per_edge"]
            / max(comp_w["wire_bytes_per_edge"], 1e-9),
            2,
        ),
        "binned_emissions_equal": equal,
        # sub-bench-scoped key: the headline "wire_bin_occupancy_hwm" is the
        # DRIVE's figure (this synthetic sample must neither leak into a
        # partial JSON under that name nor clobber/get clobbered by the
        # final wire_stats spread)
        "binned_bench_bin_occupancy_hwm": comp_w["wire_bin_occupancy_hwm"],
    }


def main():
    num_edges = int(os.environ.get("GELLY_BENCH_EDGES", 50 << 21))
    capacity = int(os.environ.get("GELLY_BENCH_VERTICES", 1 << 20))
    batch = int(os.environ.get("GELLY_BENCH_BATCH", 1 << 21))
    chunk_bufs = max(1, int(os.environ.get("GELLY_BENCH_CHUNK_BUFS", 5)))
    cpu_trials_n = max(1, int(os.environ.get("GELLY_BENCH_CPU_TRIALS", 5)))
    e2e_edges = int(os.environ.get("GELLY_BENCH_E2E_EDGES", 1 << 22))
    batch = min(batch, num_edges)
    # a full-batch stream keeps every timed transfer in wire format (a raw
    # padded tail would ship 9 B/edge for its remainder)
    num_edges -= num_edges % batch

    rng = np.random.default_rng(0)
    src = rng.integers(0, capacity, num_edges).astype(np.int32)
    dst = rng.integers(0, capacity, num_edges).astype(np.int32)

    # ---- pinned CPU denominator: FIRST, before any device/JAX threads ------
    cpu_sample = min(num_edges, 4 << 20)
    cpu_eps, cpu_trials = _cpu_baseline(
        src, dst, capacity, cpu_trials_n, cpu_sample
    )
    if cpu_eps:
        _PARTIAL["cpu_baseline_eps"] = round(cpu_eps, 1)
        _PARTIAL["cpu_trials"] = [round(t, 1) for t in cpu_trials]
        _PARTIAL["cpu_spread"] = round(min(cpu_trials) / max(cpu_trials), 3)
        print(
            f"cpu trials (edges/s, pre-device, sample {cpu_sample >> 20}M): "
            f"{[round(t / 1e6, 1) for t in cpu_trials]}M "
            f"spread {_PARTIAL['cpu_spread']}",
            file=sys.stderr,
        )

    # ---- measured Flink-shaped record-at-a-time baseline (also pre-device) --
    proxy_sample = min(num_edges, 2 << 20)
    proxy_eps, proxy_trials, proxy_labels_ok = _flink_proxy(
        src, dst, capacity, max(1, cpu_trials_n - 2), proxy_sample
    )
    if proxy_eps:
        _PARTIAL["flink_proxy_eps"] = round(proxy_eps, 1)
        _PARTIAL["flink_proxy_trials"] = [round(t, 1) for t in proxy_trials]
        _PARTIAL["flink_proxy_labels_ok"] = proxy_labels_ok
        print(
            f"flink proxy trials (edges/s, sample {proxy_sample >> 20}M): "
            f"{[round(t / 1e6, 2) for t in proxy_trials]}M "
            f"labels_ok={proxy_labels_ok}",
            file=sys.stderr,
        )

    # ---- ingest-throughput sub-benchmark (pre-device, pure host) -----------
    ingest_stats = {}
    try:
        if os.environ.get("GELLY_BENCH_INGEST", "1") != "0":
            ingest_sample = min(num_edges, 8 << 20)
            ingest_stats = _ingest_scaling(
                src, dst, capacity, ingest_sample, min(batch, 1 << 20)
            )
            _PARTIAL.update(ingest_stats)
            print(
                f"ingest scaling (pre-device): pack "
                f"{ingest_stats['ingest_pack_eps_by_workers']} eps, parse "
                f"{ingest_stats['ingest_parse_eps_by_workers']} eps, "
                f"pack speedup x{ingest_stats['ingest_pack_speedup_at_4plus']}"
                f" / parse x{ingest_stats['ingest_parse_speedup_at_4plus']} "
                f"at 4+ workers on {ingest_stats['ingest_workers_available']} "
                "usable cores",
                file=sys.stderr,
            )
    except Exception as e:
        _phase_failed("ingest scaling", e)

    cancel_init_watchdog = _watchdog(
        float(os.environ.get("GELLY_BENCH_INIT_TIMEOUT", 600)),
        "device backend init",
        3,
    )
    from gelly_streaming_tpu.core import compile_cache

    compile_cache.use_persistent_cache()
    import jax

    from gelly_streaming_tpu.core.config import StreamConfig
    from gelly_streaming_tpu.core.stream import EdgeStream
    from gelly_streaming_tpu.io import wire
    from gelly_streaming_tpu.library.connected_components import ConnectedComponents
    from gelly_streaming_tpu.ops import unionfind as uf
    from gelly_streaming_tpu.utils.native import load_ingest_lib

    device = jax.devices()[0]  # force backend init under the watchdog
    cancel_init_watchdog()
    # no CPU fallback: the headline is a TPU number or nothing
    if device.platform != "tpu":
        _print_partial(f"no TPU: JAX came up on {device.platform!r}")
        return 3
    try:
        hbm_peak_gbps = device_peaks(device.device_kind)["hbm_gbps"]
    except KeyError as e:
        _print_partial(str(e))
        return 3
    # a second watchdog bounds the WHOLE bench: a wedge mid-run would
    # otherwise hang a collect() forever and leave the driver artifact-less
    deadline_s = float(os.environ.get("GELLY_BENCH_DEADLINE", 1500))
    _watchdog(deadline_s, "bench run", 4)
    t_bench0 = time.monotonic()

    # wire_checkpoint_batches only matters when a checkpoint_path is passed
    # (the ckpt_eps stage); keeping it on the ONE cfg lets that stage reuse
    # the headline's compiled fused step
    cfg = StreamConfig(
        vertex_capacity=capacity,
        batch_size=batch,
        wire_checkpoint_batches=2,
        # opt-in superbatch dispatch coalescing for the drive (results are
        # identical either way — tests/test_superbatch.py); default 0 keeps
        # the headline comparable with earlier rounds
        superbatch=int(os.environ.get("GELLY_BENCH_SUPERBATCH", "0")),
    )
    agg = ConnectedComponents()
    # CC's fold is order-free, so the replay stream ships whichever legal
    # encoding is fewest bytes at this (capacity, batch) — EF40's ~2.7
    # B/edge at the defaults; fixed-width when capacity >> batch or ids
    # exceed 20 bits (io.wire.replay_width)
    width = wire.replay_width(capacity, batch)

    # ---- producer cost (untimed for the replay metric, reported) -----------
    t0 = time.perf_counter()
    bufs, tail = wire.pack_stream(src, dst, batch, width)
    pack_eps = num_edges / (time.perf_counter() - t0)
    _PARTIAL["pack_eps"] = round(pack_eps, 1)
    assert tail is None
    stream_bytes = sum(b.nbytes for b in bufs)
    bpe = stream_bytes / num_edges
    _PARTIAL["wire_bytes_per_edge"] = round(bpe, 3)
    _PARTIAL["edges"] = num_edges

    # ---- warmup (untimed): compile the fused step, warm the transfer path --
    prefix = EdgeStream.from_wire(bufs[:1], batch, width, cfg)
    out0 = prefix.aggregate(agg)
    assert agg._wire_eligible(prefix), "bench must ride the product fast path"
    out0.collect()

    # ---- executable cache: zero recompiles across 100 same-shape windows ---
    # The ISSUE-1 acceptance guard, measured in-process: a small wire stream
    # emitting one running window per batch, run once to compile and once
    # metered — re-created stream AND descriptor, so any unstable kernel
    # identity would recompile and the counter would catch it.
    from gelly_streaming_tpu.core import compile_cache

    cache_guard = {}
    try:
        bs_small = 1 << 12
        cap_small = min(capacity, 1 << 16)
        cfg_cc = StreamConfig(
            vertex_capacity=cap_small,
            batch_size=bs_small,
            ingest_window_edges=bs_small,
        )
        s_small = (src[: 100 * bs_small] % cap_small).astype(np.int32)
        d_small = (dst[: 100 * bs_small] % cap_small).astype(np.int32)

        def run_100_windows():
            return (
                EdgeStream.from_arrays(s_small, d_small, cfg_cc)
                .aggregate(ConnectedComponents())
                .collect()
            )

        run_100_windows()  # compiles land here
        compile_cache.reset_stats()
        n_windows = len(run_100_windows())
        cstats = compile_cache.stats()
        cache_guard = {
            "cache_windows": n_windows,
            "cache_recompiles": cstats["recompiles"],
            "cache_compiles_after_warm": cstats["compiles"],
            "cache_compile_time_s": cstats["compile_time_s"],
        }
        _PARTIAL.update(cache_guard)
        print(
            f"executable cache: {n_windows} same-shape windows, "
            f"{cstats['compiles']} compiles / {cstats['recompiles']} "
            "recompiles after warmup (target: 0)",
            file=sys.stderr,
        )
    except Exception as e:
        _phase_failed("executable cache guard", e)

    # ---- windowed-plane async pipeline: sync vs async, same emissions ------
    # (ISSUE 2 acceptance: many small same-shape windows, >= 1.2x with
    # async_windows on, bit-identical emission sequence, zero recompiles,
    # occupancy counters reported next to the compile-cache keys)
    async_stats = {}
    try:
        if os.environ.get("GELLY_BENCH_ASYNC", "1") != "0":
            async_stats = _async_window_bench(
                windows=int(os.environ.get("GELLY_BENCH_ASYNC_WINDOWS_N", 100)),
                win_edges=int(
                    os.environ.get("GELLY_BENCH_ASYNC_WIN_EDGES", 1 << 13)
                ),
            )
            _PARTIAL.update(async_stats)
            print(
                f"async windows: sync "
                f"{async_stats['sync_window_eps'] / 1e6:.2f}M eps vs async "
                f"{async_stats['async_window_eps'] / 1e6:.2f}M eps "
                f"(x{async_stats['async_window_speedup']}, depth "
                f"{async_stats['async_windows_depth']}), emissions equal: "
                f"{async_stats['async_emissions_equal']}, recompiles "
                f"{async_stats['async_cache_recompiles']}, in-flight HWM "
                f"{async_stats['pipeline_inflight_high_water']}",
                file=sys.stderr,
            )
    except Exception as e:
        _phase_failed("async window bench", e)

    # ---- binned + compressed ingest: on vs off through the fast path -------
    # (ISSUE 6 acceptance: skewed sample, bit-identical emissions, measured
    # eps both ways, bytes/edge economy + the link-bound factor)
    binned_stats = {}
    try:
        if os.environ.get("GELLY_BENCH_BINNED", "1") != "0":
            binned_stats = _binned_wire_bench(
                num_edges=int(
                    os.environ.get("GELLY_BENCH_BINNED_EDGES", 1 << 21)
                ),
                capacity=min(capacity, 1 << 20),
                batch=min(batch, 1 << 18),
            )
            _PARTIAL.update(binned_stats)
            print(
                f"binned ingest: plain "
                f"{binned_stats['plain_wire_eps'] / 1e6:.2f}M eps at "
                f"{binned_stats['wire_bytes_per_edge_plain']} B/e vs "
                f"binned+compressed "
                f"{binned_stats['compressed_wire_eps'] / 1e6:.2f}M eps at "
                f"{binned_stats['wire_bytes_per_edge_compressed']} B/e "
                f"(measured x{binned_stats['binned_wire_speedup']}, "
                f"link-bound x{binned_stats['wire_link_bound_speedup']}), "
                f"emissions equal: {binned_stats['binned_emissions_equal']}",
                file=sys.stderr,
            )
    except Exception as e:
        _phase_failed("binned ingest bench", e)

    # ---- multi-tenant job runtime: jobs in {1, 2, 4} over one pipeline -----
    # (ISSUE 5 acceptance: 4 same-shape jobs at >= 0.8x the single-job
    # baseline with 0 recompiles after warmup and near-1.0 fairness)
    mt_stats = {}
    try:
        if os.environ.get("GELLY_BENCH_MULTITENANT", "1") != "0":
            mt_stats = _multi_tenant_bench(
                windows=int(os.environ.get("GELLY_BENCH_MT_WINDOWS", 40)),
                win_edges=int(
                    os.environ.get("GELLY_BENCH_MT_WIN_EDGES", 1 << 13)
                ),
            )
            _PARTIAL.update(mt_stats)
            print(
                f"multi-tenant: single {mt_stats['multi_tenant_single_eps'] / 1e6:.2f}M"
                f" eps; 1/2/4 jobs "
                f"{mt_stats['multi_tenant_eps_1'] / 1e6:.2f}/"
                f"{mt_stats['multi_tenant_eps_2'] / 1e6:.2f}/"
                f"{mt_stats['multi_tenant_eps_4'] / 1e6:.2f}M eps aggregate "
                f"(x{mt_stats['multi_tenant_agg_ratio_4']} of single at 4), "
                f"fairness {mt_stats['multi_tenant_fairness_4']}, "
                f"recompiles {mt_stats['multi_tenant_recompiles']}",
                file=sys.stderr,
            )
            print(
                f"fused dispatch: 16 jobs "
                f"{mt_stats['fused_off_agg_eps_16'] / 1e3:.0f}K eps solo vs "
                f"{mt_stats['fused_agg_eps_16'] / 1e3:.0f}K eps fused "
                f"(x{mt_stats['fused_vs_solo_speedup']}), fairness "
                f"{mt_stats['fairness_min_max_fused']}, parity "
                f"{mt_stats['fused_parity_ok']}, cohort mean "
                f"{mt_stats['fused_jobs_per_dispatch_mean']} hwm "
                f"{mt_stats['fused_jobs_per_dispatch_hwm']}, recompiles "
                f"{mt_stats['fused_recompiles_after_warm']} compiles "
                f"{mt_stats['fused_compiles_after_warm']}",
                file=sys.stderr,
            )
    except Exception as e:
        _phase_failed("multi-tenant bench", e)

    # ---- sketch summaries: tenancy ratio, accuracy, retrace guard ----------
    # (ISSUE 19 acceptance: >= 10x sketch-vs-exact admissions under one
    # max_state_bytes cap, triangle estimate within its declared (eps,
    # delta) on the seeded stream, 0 recompiles across 1 -> 16 tenancy)
    sketch_stats = {}
    try:
        if os.environ.get("GELLY_BENCH_SKETCH", "1") != "0":
            sketch_stats = _sketch_bench(
                windows=int(os.environ.get("GELLY_BENCH_SKETCH_WINDOWS", 16)),
                win_edges=int(
                    os.environ.get("GELLY_BENCH_SKETCH_WIN_EDGES", 1 << 12)
                ),
            )
            _PARTIAL.update(sketch_stats)
            print(
                f"sketch tenancy: {sketch_stats['sketch_admitted']} sketch "
                f"vs {sketch_stats['sketch_exact_admitted']} exact jobs "
                f"under one cap (x{sketch_stats['sketch_tenancy_ratio']}); "
                f"triangles {sketch_stats['sketch_triangle_est']} vs exact "
                f"{sketch_stats['sketch_triangle_exact']} (rel err "
                f"{sketch_stats['sketch_triangle_rel_err']}); 1/16 jobs "
                f"{sketch_stats['sketch_agg_eps_1'] / 1e6:.2f}/"
                f"{sketch_stats['sketch_agg_eps_16'] / 1e6:.2f}M eps, "
                f"recompiles {sketch_stats['sketch_recompiles_after_warm']}",
                file=sys.stderr,
            )
    except Exception as e:
        _phase_failed("sketch bench", e)

    # ---- streaming RPC serving plane: clients in {1, 4, 16} over loopback --
    # (ISSUE 8 acceptance: connection-scaling eps and p50/p99
    # submit-to-first-emission latency, plus the server-vs-in-process ratio)
    serving_stats = {}
    try:
        if os.environ.get("GELLY_BENCH_SERVING", "1") != "0":
            serving_stats = _serving_bench(
                windows=int(os.environ.get("GELLY_BENCH_SERVING_WINDOWS", 16)),
                win_edges=int(
                    os.environ.get("GELLY_BENCH_SERVING_WIN_EDGES", 1 << 12)
                ),
            )
            _PARTIAL.update(serving_stats)
            print(
                f"serving: 1/4/16 clients "
                f"{serving_stats['serving_eps_1'] / 1e6:.2f}/"
                f"{serving_stats['serving_eps_4'] / 1e6:.2f}/"
                f"{serving_stats['serving_eps_16'] / 1e6:.2f}M eps aggregate"
                f" (x{serving_stats['serving_vs_inprocess_ratio_4']} of "
                f"in-process at 4), submit->first-emission p50/p99 "
                f"{serving_stats['serving_submit_to_first_emission_p50_ms']}/"
                f"{serving_stats['serving_submit_to_first_emission_p99_ms']}"
                f" ms, "
                f"{serving_stats['serving_wire_bytes_per_edge']} B/e on the "
                "socket, push->fold p50/p99 "
                f"{serving_stats.get('serving_push_to_fold_p50_ms', '-')}/"
                f"{serving_stats.get('serving_push_to_fold_p99_ms', '-')} ms "
                f"(decode pool: "
                f"{serving_stats.get('serving_decode_workers', '-')} workers)",
                file=sys.stderr,
            )
    except Exception as e:
        _phase_failed("serving bench", e)

    # ---- elastic control plane: live re-shard downtime + post-rescale eps --
    # (ISSUE 11 acceptance: the drain->first-emission gap a tenant sees
    # across a 1 -> 2 shard rescale, the steady post-rescale rate, and the
    # exact non-idempotent counts across it)
    rescale_stats = {}
    try:
        if os.environ.get("GELLY_BENCH_RESCALE", "1") != "0":
            rescale_stats = _rescale_bench(
                windows=int(os.environ.get("GELLY_BENCH_RESCALE_WINDOWS", 24)),
                win_edges=int(
                    os.environ.get("GELLY_BENCH_RESCALE_WIN_EDGES", 1 << 12)
                ),
            )
            _PARTIAL.update(rescale_stats)
            print(
                f"rescale: 1->2 shards in "
                f"{rescale_stats['rescale_downtime_ms']} ms "
                f"(drain->first emission), pre "
                f"{rescale_stats['rescale_pre_eps'] / 1e6:.2f}M eps vs post "
                f"{rescale_stats['rescale_post_eps'] / 1e6:.2f}M eps "
                f"(x{rescale_stats['rescale_post_eps_ratio']}), counts "
                f"exact: {rescale_stats['rescale_exact']}",
                file=sys.stderr,
            )
    except Exception as e:
        _phase_failed("rescale bench", e)

    # ---- fleet serving tier: router scaling + warm-standby failover ------
    # (ISSUE 20 acceptance: aggregate eps monotonic over 1 -> 4 backends,
    # sub-ms placed-verb router tax, SIGKILL -> standby -> first accepted
    # push downtime, and 0 recompiles behind the router after warmup)
    try:
        if os.environ.get("GELLY_BENCH_FLEET", "1") != "0":
            fleet_stats = _fleet_bench(
                windows=int(os.environ.get("GELLY_BENCH_FLEET_WINDOWS", 8)),
                win_edges=int(
                    os.environ.get("GELLY_BENCH_FLEET_WIN_EDGES", 1 << 12)
                ),
            )
            _PARTIAL.update(fleet_stats)
            print(
                f"fleet: 1/2/4 backends "
                f"{fleet_stats['fleet_agg_eps_1'] / 1e6:.2f}/"
                f"{fleet_stats['fleet_agg_eps_2'] / 1e6:.2f}/"
                f"{fleet_stats['fleet_agg_eps_4'] / 1e6:.2f}M eps aggregate "
                f"(x{fleet_stats['fleet_scaling_ratio']} at 4), router tax "
                f"{fleet_stats['router_overhead_p50_ms']} ms p50 on placed "
                f"verbs, failover {fleet_stats['fleet_failover_downtime_ms']}"
                f" ms kill->first accepted push, "
                f"{fleet_stats['fleet_warm_recompiles']} recompiles warm",
                file=sys.stderr,
            )
    except Exception as e:
        _phase_failed("fleet bench", e)

    # ---- static-analysis attestation: the artifact doubles as a proof the
    # measured tree passes graftcheck (0 = clean; a positive count means the
    # bench ran on a tree whose invariants the suite no longer pins)
    # mesh-comms counters (owner-sharded summary plane, ISSUE 4): zero on
    # the single-chip headline, populated when a mesh plane ran in-process —
    # the keys are first-class so the artifact schema is stable either way
    from gelly_streaming_tpu.utils import metrics as _metrics

    comms_stats = _metrics.comms_stats()
    _PARTIAL.update(comms_stats)
    # wire-path transfer accounting (binned + compressed ingest, ISSUE 6):
    # cumulative over every wire stream the drive shipped; _PARTIAL-safe
    # (pure host counters, readable even when the device never came up)
    wire_stats = _metrics.wire_stats()
    _PARTIAL.update(wire_stats)

    analysis_stats = {}
    try:
        from gelly_streaming_tpu import analysis as _analysis

        _aroot = _analysis.package_root()
        _afindings = _analysis.analyze_paths(
            [
                os.path.join(_aroot, d)
                for d in (
                    "core",
                    "io",
                    "library",
                    # the C++ byte path rides the same attestation: the
                    # nativecheck passes (#10-#13) pick it up from here
                    "native_src",
                    "parallel",
                    "runtime",
                    "utils",
                )
            ],
            root=os.path.dirname(_aroot),
        )
        _anew, _ = _analysis.apply_baseline(
            _afindings, _analysis.load_baseline(_analysis.default_baseline_path())
        )
        analysis_stats = {"analysis_findings": len(_anew)}
        _PARTIAL.update(analysis_stats)
        print(
            f"graftcheck: {len(_anew)} unsuppressed finding(s)",
            file=sys.stderr,
        )
    except Exception as e:
        _phase_failed("static-analysis attestation", e)

    # ---- device-only fold rate + roofline against the chip's HBM peak ----
    device_eps = None
    try:
        trace_dir = os.environ.get("GELLY_BENCH_TRACE")
        if trace_dir is None:
            trace_dir = os.path.join(tempfile.mkdtemp(), "jax_trace")
        elif trace_dir in ("0", "off"):
            trace_dir = None
        device_eps = _device_fold_eps(agg, prefix, trace_dir)
        _PARTIAL["device_eps"] = round(device_eps, 1)
        # roofline: wire bytes the fold reads per edge give a LOWER bound on
        # achieved HBM bandwidth (parent/seen scatters add more traffic)
        dev_gbps = device_eps * bpe / 1e9
        _PARTIAL["device_wire_gbps"] = round(dev_gbps, 1)
        _PARTIAL["hbm_util_lower_bound"] = round(dev_gbps / hbm_peak_gbps, 3)
        print(
            f"device-only fold: {device_eps / 1e9:.2f}B edges/s = "
            f"{dev_gbps:.0f} GB/s wire read >= "
            f"{100 * dev_gbps / hbm_peak_gbps:.0f}% of HBM peak"
            + (f" (trace: {trace_dir})" if trace_dir else ""),
            file=sys.stderr,
        )
    except Exception as e:
        _phase_failed("device fold rate", e)

    # ---- HEADLINE: chunked wire-replay drive -------------------------------
    # The stream folds ONCE; chunk summaries merge through the descriptor's
    # combine (order-free CC), exactly the windowed partial-fold + combine
    # model of the reference (SummaryBulkAggregation.java:76-83).
    chunk_rates = []
    chunk_gbps = []
    summaries = []
    t_phase0 = time.perf_counter()
    active_s = 0.0
    for start in range(0, len(bufs), chunk_bufs):
        part = bufs[start : start + chunk_bufs]
        stream = EdgeStream.from_wire(part, batch, width, cfg)
        out = stream.aggregate(agg)
        t0 = time.perf_counter()
        result = out.collect()
        # the emitted summary's arrays are async; the chunk ends only when
        # the device has finished its folds
        jax.block_until_ready((result[-1][0].parent, result[-1][0].seen))
        dt = time.perf_counter() - t0
        active_s += dt
        n_chunk = len(part) * batch
        chunk_rates.append(round(n_chunk / dt, 1))
        chunk_gbps.append(round(n_chunk * bpe / dt / 1e9, 2))
        summaries.append(result[-1][0])
        _PARTIAL["chunks"] = chunk_rates
        _PARTIAL["chunk_gbps"] = chunk_gbps
        _PARTIAL["value_so_far"] = round(
            (start + len(part)) * batch / active_s, 1
        )
    wall_s = time.perf_counter() - t_phase0
    tpu_eps = num_edges / active_s
    tpu_eps_wall = num_edges / wall_s
    _PARTIAL["value_so_far"] = round(tpu_eps, 1)
    _PARTIAL["active_s"] = round(active_s, 2)
    _PARTIAL["wall_s"] = round(wall_s, 2)
    print(
        f"chunk rates (edges/s): {[round(c / 1e6, 1) for c in chunk_rates]}M; "
        f"wire {chunk_gbps} GB/s ({bpe:.2f} B/edge); "
        f"active {active_s:.2f}s wall {wall_s:.2f}s; pack "
        f"{pack_eps / 1e6:.1f}M eps",
        file=sys.stderr,
    )

    # merge chunk summaries via the product combine; labels for cross-check
    merged = summaries[0]
    state_of = lambda s: type(agg.initial_state(cfg))(  # noqa: E731
        parent=s.parent, seen=s.seen
    )
    acc = state_of(merged)
    for s in summaries[1:]:
        acc = agg._combine_j(acc, state_of(s))
    labels_tpu = np.asarray(jax.jit(uf.compress)(acc.parent))

    # ---- second BASELINE.json metric: window triangle latency --------------
    # keys stay present (as null) when skipped — the schema is the contract
    tri = {
        "triangle_p50_ms": None,
        "triangle_p95_ms": None,
        "triangle_device_p50_ms": None,
        "triangle_panes_per_sec": None,
    }
    try:
        if os.environ.get("GELLY_BENCH_TRIANGLES", "1") != "0":
            tri.update(_triangle_latency())
            _PARTIAL.update(
                {k: round(v, 2) for k, v in tri.items() if v is not None}
            )
    except Exception as e:
        _phase_failed("triangle latency", e)

    # ---- GraphSAGE MXU pane kernel ------------------------------------------
    # Device-only latency of the [K, D, F] masked neighbor mean + two bf16
    # MXU projections on a representative pane (VERDICT r4 item 4: the one
    # BASELINE workload that had no bench key).  Inputs stay resident (~8 MB
    # features).
    sage = {
        "sage_device_p50_ms": None,
        "sage_feature_gather_gbps": None,
        "sage_train_step_p50_ms": None,
    }
    try:
        if os.environ.get("GELLY_BENCH_SAGE", "1") != "0":
            from gelly_streaming_tpu.library.graphsage import (
                init_params,
                sage_kernel_jit,
            )

            K, D, F = 4096, 32, 128
            s_rng = np.random.default_rng(9)
            feats = jax.device_put(
                s_rng.normal(size=(1 << 14, F)).astype(np.float32)
            )
            params = init_params(jax.random.PRNGKey(0), F, F)
            keys_a = jax.device_put(
                s_rng.integers(0, 1 << 14, K).astype(np.int32)
            )
            nbrs_a = jax.device_put(
                s_rng.integers(0, 1 << 14, (K, D)).astype(np.int32)
            )
            valid_a = jax.device_put(
                s_rng.random((K, D)) < 0.8
            )
            jax.block_until_ready(
                sage_kernel_jit(params, feats, keys_a, nbrs_a, valid_a)
            )  # compile
            times = []
            for _ in range(7):
                t0 = time.perf_counter()
                jax.block_until_ready(
                    sage_kernel_jit(params, feats, keys_a, nbrs_a, valid_a)
                )
                times.append((time.perf_counter() - t0) * 1e3)
            p50 = float(np.percentile(times, 50))
            sage = {
                "sage_device_p50_ms": round(p50, 3),
                # gathered [K,(1+D),F] f32 rows per device-second: HBM read
                # lower bound of the gather+mean stage
                "sage_feature_gather_gbps": round(
                    K * (1 + D) * F * 4 / (p50 / 1e3) / 1e9, 2
                ),
            }
            _PARTIAL.update(sage)  # device metrics land even if training fails
            # one resident TRAINING step on the same shapes (unsupervised
            # loss + adam; library/graphsage.py) — BASELINE row 5's model
            # family has a training path, so the bench times it too
            try:
                import functools

                import optax

                from gelly_streaming_tpu.library import graphsage as gs

                tx = optax.adam(1e-2)
                t_state = gs.sage_init_train(jax.random.PRNGKey(1), F, F, tx)
                pos_a, has_a, neg_a = gs.sample_pairs(
                    jax.random.PRNGKey(2), nbrs_a, valid_a, 1 << 14
                )
                t_step = jax.jit(functools.partial(gs.sage_train_step, tx))
                t_batch = (feats, keys_a, nbrs_a, valid_a, pos_a, has_a, neg_a)
                t_state, t_loss = t_step(t_state, *t_batch)  # compile
                jax.block_until_ready(t_loss)
                t_times = []
                for _ in range(5):
                    t0 = time.perf_counter()
                    t_state, t_loss = t_step(t_state, *t_batch)
                    jax.block_until_ready(t_loss)
                    t_times.append((time.perf_counter() - t0) * 1e3)
                sage["sage_train_step_p50_ms"] = round(
                    float(np.percentile(t_times, 50)), 3
                )
                _PARTIAL.update(sage)
            except Exception as e:
                _phase_failed("sage train step", e)
            print(
                f"sage pane [K={K},D={D},F={F}]: device p50 {p50:.2f} ms, "
                f"gather >= {sage['sage_feature_gather_gbps']} GB/s, "
                f"train step p50 {sage['sage_train_step_p50_ms']} ms",
                file=sys.stderr,
            )
    except Exception as e:
        _phase_failed("sage stage", e)

    def time_left() -> float:
        return deadline_s - (time.monotonic() - t_bench0)

    # ---- ISSUE 17: masked-semiring SpMV kernel core ------------------------
    # Synthetic skewed graph, fully device-resident.
    try:
        if os.environ.get("GELLY_BENCH_SPMV", "1") != "0":
            spmv_out = _spmv_bench()
            _PARTIAL.update(spmv_out)
            print(
                f"spmv kernel core: direction speedup "
                f"{spmv_out['spmv_direction_speedup']}x (auto vs "
                f"force-push), pagerank "
                f"{spmv_out['spmv_pagerank_eps'] / 1e6:.1f}M edge-iters/s, "
                f"parity {spmv_out['spmv_parity_ok']}, "
                f"{spmv_out['spmv_recompiles_after_warm']} recompiles "
                f"after warm",
                file=sys.stderr,
            )
    except Exception as e:
        _phase_failed("spmv stage", e)

    # ---- secondary: checkpointing ON the replay fast path ------------------
    # VERDICT r2 item 2's criterion: throughput with checkpointing within 10%
    # of without.  Snapshots are asynchronous (core/aggregation.py): the fold
    # pays a device clone + dispatch per snapshot; the downlink copy and the
    # atomic save ride a writer thread.  Runs on a chunk-sized subset.
    ckpt_eps = None
    try:
        if time_left() < 120:
            raise RuntimeError("deadline budget exhausted")
        import shutil
        import tempfile as _tf

        ck_bufs = bufs[: min(len(bufs), 4)]
        ck_edges = len(ck_bufs) * batch
        ck_dir = _tf.mkdtemp()
        try:
            # same agg/cfg as the headline -> the fused step is already
            # compiled and cached; only the tiny snapshot-clone jit is new,
            # so no compile lands in the timed window
            ck_stream = EdgeStream.from_wire(ck_bufs, batch, width, cfg)
            ck_out = ck_stream.aggregate(
                agg, checkpoint_path=os.path.join(ck_dir, "ck")
            )
            t0 = time.perf_counter()
            rck = ck_out.collect()
            jax.block_until_ready((rck[-1][0].parent,))
            ckpt_eps = ck_edges / (time.perf_counter() - t0)
        finally:
            shutil.rmtree(ck_dir, ignore_errors=True)
        _PARTIAL["ckpt_eps"] = round(ckpt_eps, 1)
        print(
            f"checkpointed replay ({ck_edges >> 20}M edges, snapshot every "
            f"{cfg.wire_checkpoint_batches} batches, async): "
            f"{ckpt_eps / 1e6:.1f}M eps",
            file=sys.stderr,
        )
    except Exception as e:
        _phase_failed("checkpointed rate", e)

    # ---- secondary: everything-on-one-host (pack inside the timed loop) ----
    e2e_eps = None
    e2e_breakdown = None
    try:
        if time_left() < 90:
            raise RuntimeError("deadline budget exhausted")
        n2 = min(e2e_edges, num_edges)
        e2e_stream = EdgeStream.from_arrays(src[:n2], dst[:n2], cfg)
        e2e_out = e2e_stream.aggregate(ConnectedComponents())
        e2e_out.collect()  # compile + warm
        t0 = time.perf_counter()
        r2 = e2e_out.collect()
        jax.block_until_ready((r2[-1][0].parent,))
        e2e_wall = time.perf_counter() - t0
        e2e_eps = n2 / e2e_wall
        _PARTIAL["e2e_eps"] = round(e2e_eps, 1)
        # decomposition (VERDICT r4 item 5): time each term of the in-loop
        # pipeline ALONE on the same edges — host pack, host->device
        # transfer, device fold (the last from the measured device_eps
        # roofline; same fused step, resident buffer).  On this 1-core host
        # pack competes with transfer for CPU, so the terms mostly ADD; on a
        # multi-core PCIe host pack pipelines behind transfer and e2e
        # approaches the transfer bound.  overlap_ratio = sum(terms)/wall:
        # ~1 means fully serialized (the single-core roofline), >1 means the
        # pipeline recovered some overlap.
        t0 = time.perf_counter()
        b2, _ = wire.pack_stream(src[:n2], dst[:n2], batch, width)
        pack_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        jax.block_until_ready([jax.device_put(b) for b in b2])
        transfer_s = time.perf_counter() - t0
        fold_s = n2 / device_eps if device_eps else None
        e2e_breakdown = {
            "e2e_wall_s": round(e2e_wall, 4),
            "e2e_pack_s": round(pack_s, 4),
            "e2e_transfer_s": round(transfer_s, 4),
            "e2e_fold_s": round(fold_s, 4) if fold_s else None,
            "e2e_overlap_ratio": round(
                (pack_s + transfer_s + (fold_s or 0.0)) / e2e_wall, 2
            ),
        }
        _PARTIAL.update(e2e_breakdown)
        print(
            f"e2e (pack in loop, {n2 >> 20}M edges): {e2e_eps / 1e6:.1f}M eps"
            f" — pack {pack_s:.2f}s + transfer {transfer_s:.2f}s + fold "
            f"{(fold_s or 0.0) * 1e3:.1f}ms vs wall {e2e_wall:.2f}s",
            file=sys.stderr,
        )
    except Exception as e:
        _phase_failed("e2e rate", e)

    # ---- label cross-check: merged chunk summaries vs native full fold -----
    lib = load_ingest_lib()
    vs_baseline = None
    vs_baseline_wall = None
    if lib is not None:
        check_parent = np.arange(capacity, dtype=np.int32)
        lib.cc_baseline(
            src.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            dst.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            num_edges,
            check_parent.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            capacity,
        )
        if not np.array_equal(check_parent, labels_tpu):
            print(
                json.dumps({"error": "label mismatch between TPU and CPU baseline"}),
                file=sys.stderr,
            )
            sys.exit(1)
    if cpu_eps:
        vs_baseline = tpu_eps / cpu_eps
        vs_baseline_wall = tpu_eps_wall / cpu_eps

    print(
        json.dumps(
            {
                "metric": "streaming_cc_edges_per_sec",
                "value": round(tpu_eps, 1),
                "unit": "edges/s",
                "vs_baseline": round(vs_baseline, 2) if vs_baseline else None,
                "value_wall": round(tpu_eps_wall, 1),
                "vs_baseline_wall": round(vs_baseline_wall, 2)
                if vs_baseline_wall
                else None,
                "edges": num_edges,
                "chunks": chunk_rates,
                "chunk_gbps": chunk_gbps,
                "active_s": round(active_s, 2),
                "wall_s": round(wall_s, 2),
                "wire_bytes_per_edge": round(bpe, 3),
                "cpu_baseline_eps": round(cpu_eps, 1) if cpu_eps else None,
                # the denominator is a deliberately STRONG stand-in: a native
                # single-core union-find with no serialization/shuffle.
                # flink_proxy_eps below MEASURES the reference's real
                # per-record cost structure in this image (serialize + socket
                # shuffle + HashMap state; still optimized C++, so an upper
                # bound on the JVM stack) — vs_flink_proxy grounds the
                # "vs Flink" multiple in a number, not a citation.
                # Round 3's 45M-eps denominator was contention-depressed
                # (measured after device phases on the 1-core host); the
                # pinned pre-device measurement reads ~90M on an idle host.
                "baseline_note": "cpu_baseline_eps = native 1-core union-find "
                "(strong proxy); flink_proxy_eps = measured record-at-a-time "
                "Flink-shaped stack (Tuple2 serialize + socketpair shuffle + "
                "HashMap DisjointSet, C++ upper bound on the JVM original); "
                "both pinned pre-device",
                "flink_proxy_eps": round(proxy_eps, 1) if proxy_eps else None,
                "flink_proxy_trials": [round(t, 1) for t in proxy_trials],
                "flink_proxy_labels_ok": proxy_labels_ok,
                "vs_flink_proxy": round(tpu_eps / proxy_eps, 1)
                if proxy_eps
                else None,
                "cpu_trials": [round(t, 1) for t in cpu_trials],
                "cpu_spread": round(min(cpu_trials) / max(cpu_trials), 3)
                if cpu_trials
                else None,
                "pack_eps": round(pack_eps, 1),
                "ckpt_eps": round(ckpt_eps, 1) if ckpt_eps else None,
                "e2e_eps": round(e2e_eps, 1) if e2e_eps else None,
                **(e2e_breakdown or {}),
                "device_eps": round(device_eps, 1) if device_eps else None,
                "device_wire_gbps": round(device_eps * bpe / 1e9, 1)
                if device_eps
                else None,
                "hbm_peak_gbps": hbm_peak_gbps,
                "hbm_util_lower_bound": round(
                    device_eps * bpe / 1e9 / hbm_peak_gbps, 3
                )
                if device_eps and hbm_peak_gbps
                else None,
                **{
                    key: round(v, 2) if v is not None else None
                    for key, v in tri.items()
                },
                **sage,
                **ingest_stats,
                **cache_guard,
                **async_stats,
                **binned_stats,
                # the job-runtime planes were _PARTIAL-only before ISSUE 16:
                # a normal completion DROPPED the multi-tenant / fused /
                # serving / rescale keys from the artifact, so their
                # regression gates only ever saw watchdog dumps
                **mt_stats,
                **serving_stats,
                **rescale_stats,
                **analysis_stats,
                **comms_stats,
                # re-read at exit: the headline drive's wire streams ship
                # after the mid-drive snapshot above
                **_metrics.wire_stats(),
                "failed_phases": list(_FAILED_PHASES),
            }
        )
    )
    return 1 if _FAILED_PHASES else 0


if __name__ == "__main__":
    if any(a.startswith("--check-regression") for a in sys.argv[1:]):
        sys.exit(_check_regression_cli(sys.argv[1:]))
    sys.exit(main())
