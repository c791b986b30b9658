"""Cross-tenant fused dispatch (ISSUE 16): same-shape windows from N jobs
stack into one vmapped mega-fold.

The contract under test: with ``cfg.fused_dispatch`` on, jobs on the plain
windowed plane emit BIT-IDENTICAL record sequences to the solo-dispatch
oracle (``fused_dispatch=0`` — today's path, unchanged); jobs on every
other plane (wire, async, sharded) are untouched by the flag; mixed-shape
cohorts fuse peers and solo loners; a slow sink only skips its own rows;
cancel and pause/resume mid-cohort never drop or duplicate a window; and
tenancy varying 1..16 jobs-per-dispatch causes 0 recompiles once the pow2
row buckets are warm.

Every threaded test carries ``timeout_cap`` (tests/conftest.py): a wedged
scheduler or cohort cycle must FAIL the test, not hang tier-1.
"""

import dataclasses
import threading

import numpy as np
import pytest

import jax.numpy as jnp

from gelly_streaming_tpu.core import compile_cache
from gelly_streaming_tpu.core.aggregation import SummaryBulkAggregation
from gelly_streaming_tpu.core.config import RuntimeConfig, StreamConfig
from gelly_streaming_tpu.core.output import record_leaves
from gelly_streaming_tpu.core.stream import EdgeStream
from gelly_streaming_tpu.core.windows import FoldRequest
from gelly_streaming_tpu.library.connected_components import (
    ConnectedComponents,
)
from gelly_streaming_tpu.runtime import JobManager, JobState
from gelly_streaming_tpu.utils import metrics

pytestmark = pytest.mark.timeout_cap(300)

CAP = 1 << 12
WIN = 1 << 10
# misaligned batch -> the windowed runtime's ingestion panes (the one plane
# fused dispatch replaces); fused_dispatch pinned explicitly both ways so
# ambient GELLY_FUSED_DISPATCH can never flip the oracle
CFG_SOLO = StreamConfig(
    vertex_capacity=CAP,
    batch_size=(1 << 9) + 96,
    ingest_window_edges=WIN,
    fused_dispatch=0,
)
CFG_FUSED = dataclasses.replace(CFG_SOLO, fused_dispatch=1)


def _graph(seed: int, n: int, cap: int = CAP):
    rng = np.random.default_rng(seed)
    return (
        rng.integers(0, cap, n).astype(np.int32),
        rng.integers(0, cap, n).astype(np.int32),
    )


def _cc_serial(cfg, s, d):
    out = EdgeStream.from_arrays(s, d, cfg).aggregate(ConnectedComponents())
    return [np.asarray(rec[0].parent) for rec in out]


def _materialize_cc(records):
    return [np.asarray(rec[0].parent) for rec in records]


def _assert_windows_equal(want, got, label):
    assert len(want) == len(got), (label, len(want), len(got))
    for w, (a, b) in enumerate(zip(want, got)):
        assert np.array_equal(a, b), f"{label} window {w} diverged"


class EdgeCount(SummaryBulkAggregation):
    """A second descriptor family (distinct cache token): its windows must
    never share a cohort with ConnectedComponents'."""

    order_free = True

    @property
    def cache_token(self):
        return type(self)

    def initial_state(self, cfg):
        return jnp.zeros((), jnp.int32)

    def update(self, state, src, dst, val, mask):
        return state + jnp.sum(mask.astype(jnp.int32))

    def combine(self, a, b):
        return a + b


# ---------------------------------------------------------------------------
# fused vs solo emission parity, per plane
# ---------------------------------------------------------------------------


def _gated_stream(s, d, cfg, release):
    """A windowed-plane stream whose first batch waits for ``release``:
    jobs submitted before the release all reach their first window
    together, so cohort formation is deterministic rather than a race
    against submission latency."""
    from gelly_streaming_tpu.core.types import EdgeBatch

    bs = cfg.batch_size

    def factory():
        release.wait(timeout=60)
        for o in range(0, len(s), bs):
            yield EdgeBatch.from_arrays(s[o : o + bs], d[o : o + bs], pad_to=bs)

    return EdgeStream.from_batches(factory, cfg)


@pytest.mark.parametrize("n_jobs", [2, 4, 16])
def test_fused_matches_solo_windowed_plane(n_jobs):
    windows = 4 if n_jobs == 16 else 8
    datasets = [_graph(seed, windows * WIN) for seed in range(n_jobs)]
    serial = [_cc_serial(CFG_SOLO, s, d) for s, d in datasets]
    metrics.reset_fused_dispatch_stats()
    release = threading.Event()
    with JobManager(RuntimeConfig(max_jobs=n_jobs)) as jm:
        jobs = [
            jm.submit_aggregation(
                _gated_stream(s, d, CFG_FUSED, release),
                ConnectedComponents(),
                name=f"cc-{i}",
            )
            for i, (s, d) in enumerate(datasets)
        ]
        release.set()
        outs = [_materialize_cc(job.results()) for job in jobs]
        states = [job.state for job in jobs]
        status = jm.status()
    assert states == [JobState.DONE] * n_jobs
    for i, (want, got) in enumerate(zip(serial, outs)):
        _assert_windows_equal(want, got, f"job {i}")
    stats = metrics.fused_dispatch_stats()
    assert stats["fused_dispatches"] >= 1, stats
    assert stats["fused_jobs_per_dispatch_hwm"] <= n_jobs, stats
    # the per-job attribution satellite: every fused window is credited to
    # its own job's status row
    total_fused = sum(
        row["fused_windows"] for row in status["jobs"].values()
    )
    assert total_fused == stats["fused_jobs_total"], (status, stats)


@pytest.mark.parametrize(
    "plane,cfg",
    [
        (
            "wire",  # aligned batch -> packed-wire fast path
            StreamConfig(
                vertex_capacity=CAP,
                batch_size=1 << 9,
                ingest_window_edges=WIN,
                fused_dispatch=1,
            ),
        ),
        (
            "async",  # async window pipeline keeps its own plane
            dataclasses.replace(CFG_FUSED, async_windows=2),
        ),
        (
            "sharded",  # owner-sharded mesh plane keeps its own plane
            StreamConfig(
                vertex_capacity=CAP,
                batch_size=1 << 9,
                num_shards=2,
                fused_dispatch=1,
            ),
        ),
    ],
)
def test_fused_flag_leaves_other_planes_bit_identical(plane, cfg):
    """``fused_dispatch=1`` on non-windowed planes is a no-op: those jobs
    are not fused-eligible, run their own (already batched or pipelined)
    dispatch paths, and match the flag-off oracle bit for bit."""
    solo_cfg = dataclasses.replace(cfg, fused_dispatch=0)
    n = 4 * WIN
    datasets = [_graph(seed, n) for seed in (3, 5)]
    serial = [_cc_serial(solo_cfg, s, d) for s, d in datasets]
    with JobManager() as jm:
        jobs = [
            jm.submit_aggregation(
                EdgeStream.from_arrays(s, d, cfg),
                ConnectedComponents(),
                name=f"{plane}-{i}",
            )
            for i, (s, d) in enumerate(datasets)
        ]
        outs = [_materialize_cc(job.results()) for job in jobs]
    for i, (want, got) in enumerate(zip(serial, outs)):
        _assert_windows_equal(want, got, f"{plane} job {i}")


def test_mixed_shape_cohorts_fuse_peers_and_solo_loners():
    """Three shape/descriptor classes in one fused manager: the 1024-edge
    CC jobs may fuse with each other only; the 512-edge CC job and the
    EdgeCount job have no same-key peers and must solo — all four streams
    bit-identical to their oracles."""
    big = [_graph(seed, 8 * WIN) for seed in (11, 13, 17)]
    small_cfg_solo = dataclasses.replace(CFG_SOLO, ingest_window_edges=512)
    small_cfg = dataclasses.replace(small_cfg_solo, fused_dispatch=1)
    small = _graph(19, 8 * 512)
    count = _graph(23, 8 * WIN)
    want_big = [_cc_serial(CFG_SOLO, s, d) for s, d in big]
    want_small = _cc_serial(small_cfg_solo, *small)
    want_count = [
        rec
        for rec in EdgeStream.from_arrays(*count, CFG_SOLO).aggregate(
            EdgeCount()
        )
    ]
    metrics.reset_fused_dispatch_stats()
    with JobManager() as jm:
        big_jobs = [
            jm.submit_aggregation(
                EdgeStream.from_arrays(s, d, CFG_FUSED),
                ConnectedComponents(),
                name=f"big-{i}",
            )
            for i, (s, d) in enumerate(big)
        ]
        small_job = jm.submit_aggregation(
            EdgeStream.from_arrays(*small, small_cfg),
            ConnectedComponents(),
            name="small",
        )
        count_job = jm.submit_aggregation(
            EdgeStream.from_arrays(*count, CFG_FUSED),
            EdgeCount(),
            name="count",
        )
        got_big = [_materialize_cc(job.results()) for job in big_jobs]
        got_small = _materialize_cc(small_job.results())
        got_count = list(count_job.results())
    for i, (want, got) in enumerate(zip(want_big, got_big)):
        _assert_windows_equal(want, got, f"big {i}")
    _assert_windows_equal(want_small, got_small, "small")
    assert [int(r[0]) for r in want_count] == [int(r[0]) for r in got_count]
    stats = metrics.fused_dispatch_stats()
    # loners solo'd; a 512-edge or EdgeCount row inside a CC-1024 cohort
    # would have broken the parity assertions above
    assert stats["fused_solo_fallbacks"] >= 1, stats
    assert stats["fused_jobs_per_dispatch_hwm"] <= 3, stats


# ---------------------------------------------------------------------------
# isolation: slow sinks, cancel, pause/resume mid-cohort
# ---------------------------------------------------------------------------


def test_slow_sink_skips_only_its_own_rows():
    """A wedged sink stalls ITS job's windows (never collected into a
    cohort while its queue is full) while fused peers complete with
    bit-identical output; releasing the sink completes the slow job with
    bit-identical output too — nothing was dropped with it."""
    slow_data = _graph(43, 8 * WIN)
    fast_data = [_graph(seed, 8 * WIN) for seed in (47, 53)]
    want_slow = _cc_serial(CFG_SOLO, *slow_data)
    want_fast = [_cc_serial(CFG_SOLO, s, d) for s, d in fast_data]
    gate = threading.Event()
    slow_records = []

    def slow_sink(rec):
        gate.wait(120)
        slow_records.append(rec)

    with JobManager(RuntimeConfig(job_queue_depth=2)) as jm:
        slow = jm.submit_aggregation(
            EdgeStream.from_arrays(*slow_data, CFG_FUSED),
            ConnectedComponents(),
            name="slow",
            sink=slow_sink,
        )
        fasts = [
            jm.submit_aggregation(
                EdgeStream.from_arrays(s, d, CFG_FUSED),
                ConnectedComponents(),
                name=f"fast-{i}",
            )
            for i, (s, d) in enumerate(fast_data)
        ]
        got_fast = [_materialize_cc(job.results()) for job in fasts]
        assert [j.state for j in fasts] == [JobState.DONE] * 2
        assert not slow.wait(0), "slow job should still be in flight"
        assert jm.status()["jobs"]["slow"]["job_queue_full_skips"] >= 1
        gate.set()
        assert slow.wait(60)
        assert slow.state == JobState.DONE
    for i, (want, got) in enumerate(zip(want_fast, got_fast)):
        _assert_windows_equal(want, got, f"fast {i}")
    _assert_windows_equal(want_slow, _materialize_cc(slow_records), "slow")


def test_cancel_mid_cohort_no_drop_no_duplicate():
    """Cancelling one cohort member mid-stream leaves its peers'
    emissions bit-identical and its own delivered records an exact PREFIX
    of the solo oracle — every delivered window exactly once, in order."""
    datasets = [_graph(seed, 16 * WIN) for seed in (61, 67, 71, 73)]
    serial = [_cc_serial(CFG_SOLO, s, d) for s, d in datasets]
    with JobManager() as jm:
        jobs = [
            jm.submit_aggregation(
                EdgeStream.from_arrays(s, d, CFG_FUSED),
                ConnectedComponents(),
                name=f"cc-{i}",
            )
            for i, (s, d) in enumerate(datasets)
        ]
        victim = jobs[0]
        it = victim.results()
        first = np.asarray(next(it)[0].parent)  # mid-stream, cohorts live
        victim.cancel(wait=True)
        rest = _materialize_cc(it)
        got_victim = [first] + rest
        got_peers = [_materialize_cc(job.results()) for job in jobs[1:]]
        assert victim.state == JobState.CANCELLED
    for i, (want, got) in enumerate(zip(serial[1:], got_peers)):
        _assert_windows_equal(want, got, f"peer {i}")
    assert len(got_victim) <= len(serial[0])
    _assert_windows_equal(
        serial[0][: len(got_victim)], got_victim, "victim prefix"
    )


def test_pause_resume_mid_cohort_parity():
    """Pausing a cohort member suspends its iterator in place; peers keep
    fusing among themselves; resume continues bit-exact (the FoldRequest
    protocol self-heals: a resume that reaches a parked yield via plain
    ``next()`` solo-folds instead of dropping the window)."""
    datasets = [_graph(seed, 8 * WIN) for seed in (79, 83, 89)]
    serial = [_cc_serial(CFG_SOLO, s, d) for s, d in datasets]
    with JobManager() as jm:
        jobs = [
            jm.submit_aggregation(
                EdgeStream.from_arrays(s, d, CFG_FUSED),
                ConnectedComponents(),
                name=f"cc-{i}",
            )
            for i, (s, d) in enumerate(datasets)
        ]
        paused = jobs[0]
        it = paused.results()
        first = np.asarray(next(it)[0].parent)
        paused.pause()
        got_peers = [_materialize_cc(job.results()) for job in jobs[1:]]
        assert paused.resume()
        got_paused = [first] + _materialize_cc(it)
        assert paused.state == JobState.DONE
    _assert_windows_equal(serial[0], got_paused, "paused job")
    for i, (want, got) in enumerate(zip(serial[1:], got_peers)):
        _assert_windows_equal(want, got, f"peer {i}")


# ---------------------------------------------------------------------------
# compile economy: pow2 row buckets across tenancy variation
# ---------------------------------------------------------------------------


def test_zero_recompiles_across_jobs_per_batch_1_to_16():
    """Once the solo executable and the pow2 row buckets are warm, tenancy
    varying 1 -> 16 jobs per dispatch compiles NOTHING: every cohort size
    buckets to a warmed row shape of the one shared executable."""
    cc = ConnectedComponents()
    # warm the solo/windowed chain (update + combine + transform)
    _cc_serial(CFG_FUSED, *_graph(97, 2 * WIN))
    # warm every row bucket a 1..16-job cohort can hit (singletons never
    # dispatch the vmapped executable — they solo — so buckets start at 2),
    # and the matching cohort-drain split executables
    fold = cc._superpane_fold_fn(CFG_FUSED, False)
    for rows in (2, 4, 8, 16):
        states = fold(
            jnp.zeros((rows, WIN), jnp.int32),
            jnp.zeros((rows, WIN), jnp.int32),
            None,
            jnp.zeros((rows, WIN), bool),
        )
        cc._superpane_split_fn(CFG_FUSED, rows)(states)
    compile_cache.reset_stats()
    for n_jobs in (1, 2, 4, 8, 16):
        datasets = [
            _graph(100 + n_jobs + seed, 2 * WIN) for seed in range(n_jobs)
        ]
        with JobManager(RuntimeConfig(max_jobs=n_jobs)) as jm:
            jobs = [
                jm.submit_aggregation(
                    EdgeStream.from_arrays(s, d, CFG_FUSED),
                    ConnectedComponents(),
                    name=f"t{n_jobs}-{i}",
                )
                for i, (s, d) in enumerate(datasets)
            ]
            for job in jobs:
                job.collect()
    stats = compile_cache.stats()
    assert stats["recompiles"] == 0, stats
    assert stats["compiles"] == 0, (
        "tenancy variation over warm buckets must not compile",
        stats,
    )


# ---------------------------------------------------------------------------
# the FoldRequest protocol itself
# ---------------------------------------------------------------------------


def test_run_fused_protocol_and_solo_fallback_oracle():
    """White-box: the cohort-member generator yields FoldRequests with the
    advertised padded layout, accepts ``send(None)`` as the solo-fallback
    signal, and a protocol-naive plain ``next()`` consumer still gets the
    correct emission (self-healing) — both bit-identical to run()."""
    s, d = _graph(101, 4 * WIN)
    want = _cc_serial(CFG_SOLO, s, d)
    cc = ConnectedComponents()
    gen = cc.run_fused(EdgeStream.from_arrays(s, d, CFG_FUSED))
    got = []
    req = next(gen)
    while True:
        assert type(req) is FoldRequest
        assert req.src.shape == (WIN,) and req.mask.all()
        assert req.edges == WIN
        token, cfg_key, has_val, e_pad = req.key
        assert token is type(cc) and e_pad == WIN and not has_val
        # alternate the two legal resume forms: explicit solo signal and
        # the protocol-naive plain next() (Python: send(None))
        if len(got) % 2 == 0:
            rec = gen.send(None)
        else:
            rec = next(gen)
        got.append(np.asarray(rec[0].parent))
        try:
            req = next(gen)
        except StopIteration:
            break
    _assert_windows_equal(want, got, "protocol")


class TransientEdgeCount(EdgeCount):
    """Per-window edge counts: the Merger resets after each emission."""

    transient_state = True


def _drive_fused(gen, stop_after=None):
    """Run one cohort member alone: even windows take a one-row fused
    partial from the request's own executables, odd ones the solo
    fallback.  With ``stop_after``, the consumer dies holding that many
    records (the generator is closed at its last yield: a crash between
    the emission and its snapshot)."""
    from gelly_streaming_tpu.core.windows import stack_fold_rows

    out, reply = [], None
    while True:
        try:
            item = gen.send(reply)
        except StopIteration:
            return out
        reply = None
        if isinstance(item, FoldRequest):
            if item.window_id % 2 == 0:
                src, dst, _val, msk, pad = stack_fold_rows([item])
                states = item.fold(
                    jnp.asarray(src), jnp.asarray(dst), None, jnp.asarray(msk)
                )
                reply = item.split(1 + pad)(states)[0]
            continue
        out.append(record_leaves(item))
        if len(out) == stop_after:
            gen.close()
            return out


@pytest.mark.parametrize(
    "make", [ConnectedComponents, TransientEdgeCount], ids=["cc", "transient"]
)
def test_fused_resume_after_crash_matches_solo(tmp_path, make):
    """The fused plane's positional checkpoints: a consumer that dies
    holding window 3's record (before its snapshot) and a resume from the
    snapshot give an uninterrupted solo run's records — window 3 again
    (at-least-once emission), every other window once."""
    s, d = _graph(17, 8 * WIN)
    want = [
        record_leaves(rec)
        for rec in EdgeStream.from_arrays(s, d, CFG_SOLO).aggregate(make())
    ]
    path = str(tmp_path / "fused")

    def run():
        stream = EdgeStream.from_arrays(s, d, CFG_FUSED)
        return make().run_fused(stream, checkpoint_path=path)

    first = _drive_fused(run(), stop_after=4)
    resumed = _drive_fused(run())
    got = first + resumed
    assert len(want) == 8 and len(got) == len(want) + 1
    for w, (a, b) in enumerate(zip(want[:4] + want[3:], got)):
        assert all(np.array_equal(x, y) for x, y in zip(a, b)), f"record {w}"


def test_fused_dispatch_stats_exposed():
    """The satellite surfaces: metrics_snapshot carries the fused section
    and the Prometheus exposition renders its counters."""
    metrics.reset_fused_dispatch_stats()
    metrics.fused_add("fused_dispatches", 2)
    metrics.fused_add("fused_jobs_total", 7)
    metrics.fused_high_water("fused_jobs_per_dispatch_hwm", 4)
    snap = metrics.metrics_snapshot()
    assert snap["fused"]["fused_dispatches"] == 2
    assert snap["fused"]["fused_jobs_per_dispatch_mean"] == 3.5
    prom = metrics.render_prometheus(snap)
    assert "gelly_fused_dispatches 2" in prom
    assert "gelly_fused_jobs_per_dispatch_hwm 4" in prom
    metrics.reset_fused_dispatch_stats()
    assert metrics.fused_dispatch_stats()["fused_dispatches"] == 0


def test_fused_dispatch_config_validation():
    with pytest.raises(ValueError, match="fused_dispatch"):
        StreamConfig(vertex_capacity=CAP, fused_dispatch=2)
