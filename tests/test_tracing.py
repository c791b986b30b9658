"""The observability plane (ISSUE 9): per-window span tracing, the
bounded log-bucketed latency histograms, the flight recorder, and the
metrics/trace exposition surface.

The two regression pins the satellites name:

* percentile math — proper NEAREST-RANK (p50 of [1, 2] is 1; p100 is the
  max with no index clamp), exact-value tested on both the recorder shim
  and the histogram;
* zero-overhead off path — with ``trace_sample`` at its default (off),
  the windowed planes add no recompiles and their emissions are
  bit-identical with tracing on vs off.
"""

import numpy as np
import pytest

from gelly_streaming_tpu.core.config import StreamConfig
from gelly_streaming_tpu.utils import metrics, tracing


@pytest.fixture(autouse=True)
def _clean_registries():
    yield
    tracing.reset_tracing()
    metrics.reset_histograms()


# ---------------------------------------------------------------------------
# nearest-rank percentile math (the off-by-one satellite)


def test_nearest_rank_exact_values():
    assert tracing.nearest_rank([1.0, 2.0], 50) == 1.0
    assert tracing.nearest_rank([1.0, 2.0], 100) == 2.0  # no IndexError
    assert tracing.nearest_rank([1.0, 2.0], 0) == 1.0
    xs = [10.0, 20.0, 30.0, 40.0]
    assert tracing.nearest_rank(xs, 25) == 10.0
    assert tracing.nearest_rank(xs, 50) == 20.0
    assert tracing.nearest_rank(xs, 75) == 30.0
    assert tracing.nearest_rank(xs, 99) == 40.0
    assert tracing.nearest_rank(xs, 51) == 30.0  # rank ceil(2.04) = 3
    assert tracing.nearest_rank([], 50) == 0.0
    assert tracing.nearest_rank([7.0], 100) == 7.0


def test_recorder_percentile_nearest_rank():
    rec = metrics.WindowLatencyRecorder()
    rec.record(1.0)
    rec.record(2.0)
    # the old int(len*p/100) index returned 2 for p50 and needed a clamp
    # at p100; nearest-rank gives the rank-1 value and the exact max
    assert rec.percentile(50) == 1.0
    assert rec.percentile(100) == 2.0
    assert rec.p50_ms == 1.0


def test_recorder_is_bounded_and_feeds_histogram():
    rec = metrics.WindowLatencyRecorder(max_samples=64)
    for i in range(1000):
        rec.latencies_ms.append(float(i + 1))  # the legacy direct-append API
    # the raw window is bounded; the histogram kept every sample
    assert len(rec.latencies_ms) == 64
    assert rec.histogram.count == 1000
    # percentiles still work over the retained window (the newest 64)
    assert rec.percentile(100) == 1000.0
    # and window_closed/result_emitted still drive it
    rec2 = metrics.WindowLatencyRecorder()
    rec2.window_closed()
    rec2.result_emitted()
    assert len(rec2.latencies_ms) == 1
    assert rec2.histogram.count == 1


# ---------------------------------------------------------------------------
# the bounded histogram


def test_histogram_exact_quantiles_on_bucket_boundaries():
    h = tracing.LatencyHistogram()
    # 1.0 / 2.0 / 4.0 ms are exact bucket lower bounds (LO_MS = 2^-10),
    # so nearest-rank quantiles return them exactly
    for v in (1.0, 2.0, 4.0):
        h.record(v)
    assert h.quantile(0) == 1.0
    assert h.quantile(34) == 2.0  # rank ceil(1.02) = 2
    assert h.quantile(50) == 2.0
    assert h.quantile(67) == 4.0  # rank ceil(2.01) = 3
    assert h.quantile(100) == 4.0
    assert h.count == 3


def test_histogram_is_bounded_and_clamps():
    h = tracing.LatencyHistogram()
    for _ in range(10_000):
        h.record(1e12)  # way past the top bucket
        h.record(1e-4)  # below the bottom bucket
    snap = h.snapshot()
    assert snap["count"] == 20_000
    assert len(snap["buckets"]) == 2  # first and last bucket only
    assert snap["max_ms"] == 1e12  # exact extrema survive bucketing
    assert snap["min_ms"] == 1e-4
    # relative bucket error bound: a quantile is at most one bucket
    # (2^(1/8)) below the true value
    h2 = tracing.LatencyHistogram()
    h2.record(37.3)
    q = h2.quantile(50)
    assert q <= 37.3 < q * 2 ** (1 / tracing.LatencyHistogram.PER_OCTAVE)


def test_histogram_registry_scopes_and_eviction():
    metrics.reset_histograms()
    metrics.hist_record("window_close_to_emission_ms", 5.0, job="a/j1")
    metrics.hist_record("window_close_to_emission_ms", 7.0, job="a/j2")
    snap = metrics.hist_snapshot()
    assert snap["global"]["window_close_to_emission_ms"]["count"] == 2
    assert snap["jobs"]["a/j1"]["window_close_to_emission_ms"]["count"] == 1
    # thread-local job tagging
    metrics.set_hist_job("a/j1")
    try:
        metrics.hist_record("push_to_fold_ms", 1.0)
    finally:
        metrics.set_hist_job(None)
    assert metrics.hist_snapshot()["jobs"]["a/j1"]["push_to_fold_ms"][
        "count"
    ] == 1
    # job eviction drops the job rows, keeps the global scope
    metrics.drop_job_stats("a/j1")
    snap = metrics.hist_snapshot()
    assert "a/j1" not in snap["jobs"]
    assert snap["global"]["window_close_to_emission_ms"]["count"] == 2


# ---------------------------------------------------------------------------
# spans, sampling, the flight recorder


def test_flight_recorder_ring_keeps_last_capacity():
    rec = tracing.FlightRecorder(capacity=8)
    for i in range(20):
        span = tracing.WindowSpan(i + 1, "test", i)
        rec.record(span)
    spans = rec.last(100)
    assert len(spans) == 8
    assert [s["window"] for s in spans] == list(range(12, 20))  # oldest first
    assert rec.stats()["recorded"] == 20
    assert rec.stats()["held"] == 8
    rec.clear()
    assert rec.last(100) == []
    assert rec.stats()["recorded"] == 0


def test_span_stage_sum_equals_total():
    import time

    span = tracing.WindowSpan(1, "test", 0)
    t0 = time.perf_counter()
    time.sleep(0.002)
    span.mark("pack", t0)
    t1 = time.perf_counter()
    time.sleep(0.002)
    span.mark("dispatch", t1)
    entry = span.finish()
    total = sum(s["ms"] for s in entry["stages"])
    # the "queued" residual makes the stage sum the total by construction
    assert entry["stages"][-1]["stage"] == "queued"
    assert abs(total - entry["total_ms"]) < 0.01
    assert entry["total_ms"] >= 4.0


def test_sampler_stride_is_deterministic():
    cfg_on = StreamConfig(trace_sample=1.0)
    s = tracing.sampler(cfg_on, "t")
    assert all(s.begin(i) is not None for i in range(5))
    cfg_half = StreamConfig(trace_sample=0.5)
    s2 = tracing.sampler(cfg_half, "t")
    hits = [s2.begin(i) is not None for i in range(6)]
    assert hits == [True, False, True, False, True, False]
    cfg_off = StreamConfig()
    assert tracing.sampler(cfg_off, "t") is None


def test_resolve_sample_config_beats_env(monkeypatch):
    monkeypatch.setenv("GELLY_TRACE_SAMPLE", "0.25")
    assert tracing.resolve_sample(StreamConfig()) == 0.25
    assert tracing.resolve_sample(StreamConfig(trace_sample=1.0)) == 1.0
    monkeypatch.delenv("GELLY_TRACE_SAMPLE")
    assert tracing.resolve_sample(StreamConfig()) == 0.0
    monkeypatch.setenv("GELLY_TRACE_SAMPLE", "not-a-float")
    assert tracing.resolve_sample(StreamConfig()) == 0.0


def test_trace_sample_validation():
    with pytest.raises(ValueError):
        StreamConfig(trace_sample=1.5)
    with pytest.raises(ValueError):
        StreamConfig(trace_sample=-0.1)


def test_find_span_depth_limited():
    span = tracing.WindowSpan(1, "t", 0)
    assert tracing.find_span(span) is span
    assert tracing.find_span((("pane", "arenas", span), "dev")) is span
    assert tracing.find_span(("no", "span")) is None
    assert tracing.find_span(np.zeros(4)) is None


# ---------------------------------------------------------------------------
# end-to-end: the windowed planes


def _windowed_stream(cfg, src, dst, bs):
    from gelly_streaming_tpu.core.stream import EdgeStream
    from gelly_streaming_tpu.core.types import EdgeBatch

    def factory():
        for o in range(0, len(src), bs):
            yield EdgeBatch.from_arrays(src[o : o + bs], dst[o : o + bs], pad_to=bs)

    return EdgeStream.from_batches(factory, cfg)


def _run_cc(trace_sample, async_windows=0, n=1 << 13, cap=1 << 10, bs=512):
    from gelly_streaming_tpu.library.connected_components import (
        ConnectedComponents,
    )

    rng = np.random.default_rng(11)
    src = rng.integers(0, cap, n).astype(np.int32)
    dst = rng.integers(0, cap, n).astype(np.int32)
    cfg = StreamConfig(
        vertex_capacity=cap,
        batch_size=bs,
        ingest_window_edges=2 * bs,
        async_windows=async_windows,
        trace_sample=trace_sample,
    )
    recs = list(
        ConnectedComponents().run(_windowed_stream(cfg, src, dst, bs))
    )
    return [np.asarray(r[0].parent) for r in recs]


def _served_cc(trace_sample=0.0, name="spans", n=1 << 14, w=1 << 12):
    """One served push job (JobManager + StreamServer on loopback): its
    records as the client receives them (lists of host leaves)."""
    from gelly_streaming_tpu.core.config import ServerConfig
    from gelly_streaming_tpu.runtime.client import GellyClient
    from gelly_streaming_tpu.runtime.manager import JobManager
    from gelly_streaming_tpu.runtime.server import StreamServer

    cap, bs = 1 << 10, w // 2
    rng = np.random.default_rng(11)
    src = rng.integers(0, cap, n).astype(np.int32)
    dst = rng.integers(0, cap, n).astype(np.int32)
    with JobManager() as jm, StreamServer(jm, ServerConfig()) as server:
        with GellyClient("127.0.0.1", server.port) as c:
            spec = dict(
                name=name, query="cc", capacity=cap, window_edges=w, batch=bs
            )
            if trace_sample:
                spec["trace_sample"] = trace_sample
            c.submit(**spec)
            c.push_edges(name, src, dst, batch=bs, capacity=cap)
            return list(c.iter_results(name, deadline_s=240))


def _profiled(run, trace_dir):
    """``run()`` under the JAX profiler; returns its result and the
    ``SPANS`` events of the trace's host planes as ``(name, start_ns,
    end_ns, stats)``."""
    import glob

    import jax
    from jax.profiler import ProfileData

    with jax.profiler.trace(str(trace_dir)):
        out = run()
    (path,) = glob.glob(str(trace_dir / "**" / "*.xplane.pb"), recursive=True)
    events = [
        (e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
        for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/host:")
        for line in plane.lines
        for e in line.events
        if e.name in tracing.SPANS
    ]
    return out, events


@pytest.mark.timeout_cap(300)
@pytest.mark.parametrize("depth", [0, 3, "served"])
def test_tracing_off_is_no_op_and_emissions_bit_identical(depth, tmp_path):
    """The overhead-regression satellite: with no profiler and
    trace_sample=0 the flight recorder stays untouched and no compile is
    added, and a traced run's emissions are bit-identical to the untraced
    oracle's.  On the served plane the traced run also runs under the
    profiler, so every span is open there."""
    if depth == "served":
        run = lambda sample: [  # noqa: E731
            np.asarray(r[0]) for r in _served_cc(sample)
        ]
    else:
        run = lambda sample: _run_cc(sample, async_windows=depth)  # noqa: E731
    base = run(0.0)  # warmup: compiles land here
    recorded_before = tracing.span_stats()["recorded"]
    cc_before = metrics.compile_cache_stats()
    off = run(0.0)
    cc_mid = metrics.compile_cache_stats()
    assert tracing.span_stats()["recorded"] == recorded_before
    assert cc_mid["compiles"] == cc_before["compiles"]
    if depth == "served":
        on, events = _profiled(lambda: run(1.0), tmp_path)
        assert events
    else:
        on = run(1.0)
    cc_after = metrics.compile_cache_stats()
    # tracing on: same executables (0 new compiles, 0 recompiles)...
    assert cc_after["compiles"] == cc_mid["compiles"]
    assert cc_after["recompiles"] == cc_mid["recompiles"]
    # ...and bit-identical emissions
    assert len(base) == len(off) == len(on)
    for a, b, c in zip(base, off, on):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(a, c)
    # the traced run actually recorded one span per window
    assert tracing.span_stats()["recorded"] - recorded_before == len(on)


@pytest.mark.timeout_cap(300)
def test_async_spans_cover_all_stages_and_sum_to_total():
    tracing.reset_tracing()
    out = _run_cc(1.0, async_windows=3)
    spans = tracing.flight_recorder().last(64)
    assert len(spans) == len(out)
    for span in spans:
        assert span["plane"] == "windowed"
        stages = {s["stage"] for s in span["stages"]}
        assert {"pack", "transfer", "dispatch", "drain", "emit", "queued"} <= stages
        total = sum(s["ms"] for s in span["stages"])
        # the queued residual makes this exact up to rounding
        assert abs(total - span["total_ms"]) <= 0.05 + 0.01 * len(
            span["stages"]
        )
    # trace ids are unique and monotonic in record order
    ids = [s["trace_id"] for s in spans]
    assert ids == sorted(ids) and len(set(ids)) == len(ids)


@pytest.mark.timeout_cap(300)
def test_sync_plane_spans_and_close_to_emission_histogram():
    """A served job on the sync plane: each sampled window's span runs from
    its close to its record on the host (the sink pump records it), and
    the close-to-emission histogram holds one job-scoped sample a window,
    recorded once the sink returned."""
    tracing.reset_tracing()
    metrics.reset_histograms()
    out = _served_cc(1.0, name="hist")
    spans = tracing.flight_recorder().last(64)
    assert len(spans) == len(out)
    assert all(s["plane"] == "merge" for s in spans)
    assert [s["window"] for s in spans] == list(range(len(out)))
    for s in spans:
        stages = {x["stage"] for x in s["stages"]}
        assert {"pack", "transfer", "dispatch", "drain", "emit"} <= stages
        assert s["stages"][0]["stage"] == "pack"
        assert s["stages"][0]["start_ms"] == 0.0  # anchored at the close
    snap = metrics.hist_snapshot()
    for hist in (
        snap["global"]["window_close_to_emission_ms"],
        snap["jobs"]["default/hist"]["window_close_to_emission_ms"],
    ):
        assert hist["count"] == len(out)
        assert hist["p99_ms"] >= hist["p50_ms"] > 0


@pytest.mark.timeout_cap(300)
def test_served_spans_share_job_and_window_on_the_profilers_clock(tmp_path):
    """Under the profiler, a served push job (2^12-edge windows) emits every
    span of the vocabulary; each emitted window has its cut, put, both
    dispatches and the sink's wait and copy under one (job, window), and
    its sink spans start after its cut."""
    out, events = _profiled(lambda: _served_cc(0.0, name="prof"), tmp_path)
    assert {name for name, *_ in events} == set(tracing.SPANS)
    per_window: dict = {}
    for name, t0, t1, stats in events:
        if "window" in stats and stats.get("job") == "default/prof":
            per_window.setdefault(stats["window"], {}).setdefault(
                name, []
            ).append((t0, t1))
    need = {
        "gelly.pane.cut",
        "gelly.pane.put",
        "gelly.fold.dispatch",
        "gelly.combine.dispatch",
        "gelly.sink.wait",
        "gelly.sink.copy",
        "gelly.sched.pull",
    }
    for w in range(len(out)):
        got = per_window.get(w, {})
        assert need <= set(got), (w, sorted(got))
        cut_start = got["gelly.pane.cut"][-1][0]
        for name in ("gelly.sink.wait", "gelly.sink.copy"):
            assert got[name][0][0] >= cut_start, (w, name)
    # the results frames name the window of their first record
    frames = [s for n, *_, s in events if n == "gelly.results.frame"]
    assert any(f.get("window") == 0 and f["records"] >= 1 for f in frames)
    pushes = [s for n, *_, s in events if n == "gelly.push"]
    assert all(p["job"] == "default/prof" for p in pushes)
    assert sorted(p["offset"] for p in pushes)[0] == 0


@pytest.mark.parametrize("query", ["cc", "degree"])
def test_fold_and_combine_lower_under_stable_names(query):
    """The fold and combine executables carry stable names on every plane
    that uses them, whatever the descriptor's methods are called."""
    import jax.numpy as jnp

    from gelly_streaming_tpu.runtime.server import descriptor_for

    cfg = StreamConfig(vertex_capacity=1 << 8, batch_size=64)
    agg = descriptor_for(query)
    state = agg.initial_state(cfg)
    ids = jnp.zeros((64,), jnp.int32)
    mask = jnp.ones((64,), bool)
    fold = agg._update_j.lower(state, ids, ids, None, mask).as_text()
    combine = agg._combine_j.lower(state, state).as_text()
    assert fold.splitlines()[0].startswith("module @jit_gelly_fold ")
    assert combine.splitlines()[0].startswith("module @jit_gelly_combine ")


@pytest.mark.timeout_cap(300)
def test_sampling_rate_traces_subset():
    tracing.reset_tracing()
    out = _run_cc(0.5, async_windows=0)
    spans = tracing.flight_recorder().last(64)
    assert len(spans) == (len(out) + 1) // 2
    windows = [s["window"] for s in spans]
    assert windows == sorted(windows)


# ---------------------------------------------------------------------------
# exposition: snapshot + Prometheus text format


def test_metrics_snapshot_shape_and_prometheus_render():
    metrics.reset_histograms()
    metrics.hist_record("sched_queue_wait_ms", 2.0, job="t/j")
    snap = metrics.metrics_snapshot()
    for key in (
        "pipeline",
        "comms",
        "wire",
        "compile_cache",
        "jobs",
        "tenants",
        "histograms",
        "spans",
    ):
        assert key in snap
    text = metrics.render_prometheus(snap)
    lines = text.splitlines()
    # samples are gelly_-prefixed; HELP/TYPE metadata lines ride above
    # each family (the strict-format contract tests/test_prometheus_lint
    # pins in full)
    assert all(
        l.startswith(("gelly_", "# HELP gelly_", "# TYPE gelly_"))
        for l in lines
        if l
    )
    # histogram series: cumulative buckets end at +Inf == count
    inf = [l for l in lines if 'le="+Inf"' in l and "sched_queue_wait" in l]
    assert inf and inf[0].endswith(" 1")
    assert any(l.startswith("gelly_sched_queue_wait_ms_count") for l in lines)
    # JSON-serializable end to end (the metrics verb ships it as JSON)
    import json

    json.dumps(snap)
