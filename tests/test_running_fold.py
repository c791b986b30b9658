"""Folding each pane straight into the running summary.

On the synchronous windowed plane an order-free, non-transient descriptor
with one partition folds every closed pane into the running summary and
skips the Merger's combine.  The contract under test: its records are
BIT-IDENTICAL to the fold-from-the-initial-state-then-combine route, which
the oracle below replays from the same panes through the same executables;
checkpoints resume to the same records; the held records of earlier windows
stay intact (the running summary is never donated); and every other route
(non-order-free, transient, simulated partitions) still combines, as the
``merge_running_folds`` / ``merge_combines`` counters show.
"""

import dataclasses
import os

import numpy as np
import pytest

import jax.numpy as jnp

from gelly_streaming_tpu.core import compile_cache
from gelly_streaming_tpu.core.aggregation import SummaryBulkAggregation
from gelly_streaming_tpu.core.config import ServerConfig, StreamConfig
from gelly_streaming_tpu.core.stream import EdgeStream
from gelly_streaming_tpu.core.windows import stream_panes
from gelly_streaming_tpu.library.bipartiteness import BipartitenessCheck
from gelly_streaming_tpu.library.connected_components import (
    ConnectedComponents,
    ConnectedComponentsTree,
)
from gelly_streaming_tpu.library.spanner import Spanner
from gelly_streaming_tpu.runtime import JobManager
from gelly_streaming_tpu.runtime.client import GellyClient
from gelly_streaming_tpu.runtime.server import (
    StreamServer,
    descriptor_for,
    record_leaves,
)
from gelly_streaming_tpu.utils import metrics

pytestmark = pytest.mark.timeout_cap(300)

CAP = 1 << 10
WIN = 1 << 8
# seven ingestion windows, the last one 77 edges (masked up to 128); the
# misaligned batch keeps the stream off the packed-wire fast path, so the
# windowed plane cuts the panes
N = 6 * WIN + 77
CFG = StreamConfig(
    vertex_capacity=CAP,
    max_degree=16,
    batch_size=(1 << 7) + 24,
    ingest_window_edges=WIN,
)
CATALOG = (
    "cc",
    "degree",
    "edges",
    "sketch_triangles",
    "hll_degree",
    "cm_heavy_hitters",
)


def _skewed(seed: int, n: int = N, cap: int = CAP):
    """Zipf-skewed sources (a few hubs hold most edges), uniform targets."""
    rng = np.random.default_rng(seed)
    src = (rng.zipf(1.6, n) % cap).astype(np.int32)
    dst = rng.integers(0, cap, n).astype(np.int32)
    return src, dst


def _timed(seed: int, n: int = 600, tmax: int = 1000):
    rng = np.random.default_rng(seed)
    src = rng.zipf(1.6, n) % 64
    dst = rng.integers(0, 64, n)
    ts = np.sort(rng.integers(0, tmax, n))
    return [(int(a), int(b), 0, int(t)) for a, b, t in zip(src, dst, ts)]


def _timed_stream(edges, cfg=CFG):
    return EdgeStream.from_collection(edges, cfg, batch_size=16, with_time=True)


def _descriptor(name: str, window_ms=None):
    if name == "cc_tree":
        agg = ConnectedComponentsTree()
    elif name == "bipartiteness":
        agg = BipartitenessCheck()
    else:
        agg = descriptor_for(name)
    agg.window_ms = window_ms
    return agg


def _oracle(agg, stream, window_ms=None):
    """Each pane folded from the initial state, then combined into the
    running summary: the route the Merger took before folding into the
    running summary, through the same executables."""
    cfg = stream.cfg
    running, out = None, []
    for pane in stream_panes(stream, window_ms or cfg.window_ms):
        n = pane.num_edges
        if n == 0:
            continue
        e_pad = max(1, 1 << (n - 1).bit_length())

        def pad(a):
            buf = np.zeros((e_pad,), a.dtype)
            buf[:n] = a
            return buf

        mask = np.zeros((e_pad,), bool)
        mask[:n] = True
        partial = agg._update_j(
            agg.initial_state(cfg),
            jnp.asarray(pad(pane.src), jnp.int32),
            jnp.asarray(pad(pane.dst), jnp.int32),
            None,
            jnp.asarray(mask),
        )
        if running is None or agg.transient_state:
            running = partial
        else:
            running = agg._combine_j(running, partial)
        rec = agg.transform(running)
        out.append(record_leaves(rec if isinstance(rec, tuple) else (rec,)))
    return out


def _assert_records_equal(want, got, label):
    assert len(want) == len(got), (label, len(want), len(got))
    for w, (a, b) in enumerate(zip(want, got)):
        assert len(a) == len(b), (label, w)
        for x, y in zip(a, b):
            assert np.array_equal(x, y), f"{label}: window {w} diverged"


def _run_held(agg, stream, **kw):
    """Every record of the run, held as the plane emitted it (device
    leaves included) until the stream ends, then flattened to host."""
    metrics.reset_pipeline_stats()
    held = list(agg.run(stream, **kw))
    stats = metrics.pipeline_stats()
    return [record_leaves(rec) for rec in held], stats


# ---------------------------------------------------------------------------
# records: fold into the running summary == fold from empty, then combine
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", CATALOG + ("cc_tree", "bipartiteness"))
def test_records_match_fold_then_combine_oracle(name):
    src, dst = _skewed(11)
    agg = _descriptor(name)
    assert agg.order_free and not agg.transient_state
    got, stats = _run_held(agg, EdgeStream.from_arrays(src, dst, CFG))
    want = _oracle(_descriptor(name), EdgeStream.from_arrays(src, dst, CFG))
    assert len(want) == 7
    _assert_records_equal(want, got, name)
    assert stats["merge_running_folds"] == len(want), stats
    assert stats["merge_combines"] == 0, stats


@pytest.mark.parametrize("name", ["cc", "degree", "bipartiteness"])
def test_time_windowed_records_match_oracle(name):
    edges = _timed(5)
    cfg = dataclasses.replace(CFG, ingest_window_edges=0)
    got, stats = _run_held(_descriptor(name, 100), _timed_stream(edges, cfg))
    want = _oracle(_descriptor(name, 100), _timed_stream(edges, cfg), window_ms=100)
    assert len(want) >= 6
    _assert_records_equal(want, got, name)
    assert stats["merge_running_folds"] == len(want), stats


def test_held_records_are_not_overwritten_by_later_folds():
    # the fold reads the running summary that the previous window's record
    # wraps: were it donated or updated in place, the held CC records would
    # all read as the last window's forest
    src, dst = _skewed(23)
    held = list(ConnectedComponents().run(EdgeStream.from_arrays(src, dst, CFG)))
    want = _oracle(ConnectedComponents(), EdgeStream.from_arrays(src, dst, CFG))
    # read in reverse: the oldest record last, after every later one
    got = [record_leaves(rec) for rec in reversed(held)][::-1]
    _assert_records_equal(want, got, "held cc records")
    first, last = held[0][0].parent, held[-1][0].parent
    assert not np.array_equal(np.asarray(first), np.asarray(last))


# ---------------------------------------------------------------------------
# checkpoints: a killed run resumes into the restored summary
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", ["cc", "degree"])
def test_resume_mid_stream_folds_into_the_restored_summary(tmp_path, name):
    from gelly_streaming_tpu.utils.checkpoint import load_state

    src, dst = _skewed(31)
    ckpt = os.path.join(str(tmp_path), f"{name}.npz")
    full = [
        record_leaves(r)
        for r in _descriptor(name).run(EdgeStream.from_arrays(src, dst, CFG))
    ]
    # the killed run: three records consumed, then the generator dropped
    it = iter(
        _descriptor(name).run(
            EdgeStream.from_arrays(src, dst, CFG), checkpoint_path=ckpt
        )
    )
    first = [record_leaves(next(it)) for _ in range(3)]
    it.close()
    _assert_records_equal(full[:3], first, f"{name} before the kill")
    agg = _descriptor(name)
    last = int(load_state(ckpt, agg._checkpoint_like(CFG))["last_window"])
    assert last == 1  # record 2 was emitted, its snapshot not yet taken
    resumed, stats = _run_held(
        agg, EdgeStream.from_arrays(src, dst, CFG), checkpoint_path=ckpt
    )
    # position parity: the replay skips the snapshot's windows and re-emits
    # the rest (at-least-once), each record the uninterrupted run's
    _assert_records_equal(full[last + 1 :], resumed, f"{name} resumed")
    assert stats["merge_running_folds"] == len(full) - (last + 1), stats
    assert stats["merge_combines"] == 0, stats


# ---------------------------------------------------------------------------
# the routes that still combine
# ---------------------------------------------------------------------------


class TransientEdgeCount(SummaryBulkAggregation):
    """Order-free, but reset after every emission: each window's count."""

    order_free = True
    transient_state = True

    @property
    def cache_token(self):
        return type(self)

    def initial_state(self, cfg):
        return jnp.zeros((), jnp.int32)

    def update(self, state, src, dst, val, mask):
        return state + jnp.sum(mask.astype(jnp.int32))

    def combine(self, a, b):
        return a + b


@pytest.mark.parametrize(
    "make",
    [
        lambda: Spanner(window_ms=1000, k=2),
        TransientEdgeCount,
    ],
    ids=["spanner", "transient"],
)
def test_non_order_free_and_transient_descriptors_still_combine(make):
    src, dst = _skewed(41, cap=64)
    src, dst = src % 64, dst % 64
    cfg = dataclasses.replace(CFG, vertex_capacity=64)
    got, stats = _run_held(make(), EdgeStream.from_arrays(src, dst, cfg))
    want = _oracle(make(), EdgeStream.from_arrays(src, dst, cfg))
    _assert_records_equal(want, got, "combine route")
    assert stats["merge_combines"] == len(want) == 7, stats
    assert stats["merge_running_folds"] == 0, stats


def test_simulated_partitions_still_combine():
    # more partitions than devices: the plane simulates them in turn, each
    # folding from the initial state, and combines the partials
    import jax

    parts = 2 * len(jax.devices())
    cfg = dataclasses.replace(CFG, num_shards=parts)
    src, dst = _skewed(43)
    got, stats = _run_held(ConnectedComponents(), EdgeStream.from_arrays(src, dst, cfg))
    want, _ = _run_held(ConnectedComponents(), EdgeStream.from_arrays(src, dst, CFG))
    _assert_records_equal(want, got, "simulated partitions")
    assert stats["merge_combines"] == 7 and stats["merge_running_folds"] == 0


# ---------------------------------------------------------------------------
# the served cc job: one fold executable, no combine, counters per window
# ---------------------------------------------------------------------------


def test_served_cc_job_folds_into_the_running_summary():
    cfg = dataclasses.replace(CFG, batch_size=1 << 7)
    data = [_skewed(seed, n=6 * WIN) for seed in (51, 53)]
    compile_cache.reset_stats()
    with JobManager() as jm, StreamServer(jm, ServerConfig()) as server:
        with GellyClient("127.0.0.1", server.port) as c:
            for i, (s, d) in enumerate(data):
                if i == 1:  # the first job was the warm-up
                    warm = compile_cache.stats()
                    metrics.reset_pipeline_stats()
                c.submit(
                    name=f"cc-{i}",
                    query="cc",
                    capacity=CAP,
                    window_edges=WIN,
                    batch=cfg.batch_size,
                )
                c.push_edges(f"cc-{i}", s, d, batch=cfg.batch_size, capacity=CAP)
                records = list(c.iter_results(f"cc-{i}", deadline_s=240))
                assert len(records) == 6
    stats = compile_cache.stats()
    assert stats["compiles"] == warm["compiles"], (warm, stats)
    assert stats["recompiles"] == 0, stats
    combine = compile_cache.label_stats("agg_combine")
    assert combine["compiles"] == 0 and combine["calls"] == 0, combine
    # one fold executable for every window of both jobs (compiled here, or
    # already by an earlier test of this process)
    fold = compile_cache.label_stats("agg_update")
    assert fold["calls"] == 12 and fold["compiles"] <= 1, fold
    pipe = metrics.pipeline_stats()
    assert pipe["merge_running_folds"] == 6, pipe
    assert pipe["merge_combines"] == 0, pipe
    # the served records are the in-process oracle's
    want = _oracle(
        ConnectedComponents(), EdgeStream.from_arrays(*data[1], cfg)
    )
    _assert_records_equal(want, [list(r) for r in records], "served cc")
