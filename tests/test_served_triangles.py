"""The served ``triangles`` query: ``descriptor_for`` builds it, a job runs
through ``StreamServer`` + ``GellyClient`` like any push job, and each
window's record is that window's exact count (the plain reference over the
window's edges alone), reset between windows, with the window's kernel
counters in ``pipeline_stats``."""

import jax.numpy as jnp
import numpy as np
import pytest

import triangle_reference as ref
from fixtures import kronecker
from gelly_streaming_tpu.core import compile_cache
from gelly_streaming_tpu.core.config import ServerConfig
from gelly_streaming_tpu.library.triangles import WindowTriangleCount, WindowTriangles
from gelly_streaming_tpu.ops import oriented_triangles as ot
from gelly_streaming_tpu.runtime import JobManager
from gelly_streaming_tpu.runtime.client import GellyClient, ServerRefused
from gelly_streaming_tpu.runtime.server import (
    StreamServer,
    _Refused,
    descriptor_for,
    record_leaves,
)
from gelly_streaming_tpu.utils import metrics

CAP = 1 << 11
WIN = 1 << 12
BATCH = 1 << 10
WINDOWS = 4

pytestmark = pytest.mark.timeout_cap(300)


def test_descriptor_for_serves_triangles_on_one_chip():
    assert isinstance(descriptor_for("triangles"), WindowTriangleCount)
    assert descriptor_for("triangles").transient_state
    with pytest.raises(_Refused) as e:
        descriptor_for("triangles", {"num_shards": 2})
    assert e.value.code == "bad-spec"
    with pytest.raises(_Refused) as e:
        descriptor_for("no-such-query")
    assert "cc/degree/edges/triangles" in str(e.value)


def test_record_ships_the_count_alone():
    stats = np.zeros(len(ot.STATS), np.int32)
    stats[:2] = [7, 3]
    stats[ot.STATS.index("edges")] = 12
    metrics.reset_pipeline_stats()
    (leaf,) = record_leaves((WindowTriangles(jnp.asarray(stats)),))
    assert leaf.dtype == np.int32 and list(leaf) == [7, 3]
    assert ref.canon([leaf], CAP) == 7 + (3 << 30)
    pipe = metrics.pipeline_stats()
    assert pipe["tri_windows"] == 1 and pipe["tri_edges"] == 12


def test_combine_refuses():
    with pytest.raises(NotImplementedError):
        WindowTriangleCount().combine(None, None)


def test_served_job_emits_one_exact_count_per_window():
    src, dst = kronecker(11, WINDOWS * WIN, seed=21)
    want = [ref.count(src[k * WIN : (k + 1) * WIN], dst[k * WIN : (k + 1) * WIN])
            for k in range(WINDOWS)]
    # a count carried across windows would read the running sums instead
    assert all(w > 0 for w in want)
    metrics.reset_pipeline_stats()
    compile_cache.reset_stats()
    with JobManager() as jm, StreamServer(jm, ServerConfig()) as server:
        with GellyClient("127.0.0.1", server.port) as c:
            reply = c.submit(
                name="tri", query="triangles", capacity=CAP,
                window_edges=WIN, batch=BATCH,
            )
            assert not reply["accept_bdv"]  # arrival-order wire only
            c.push_edges("tri", src, dst, batch=BATCH, capacity=CAP)
            records = list(c.iter_results("tri", deadline_s=240))
            with pytest.raises(ServerRefused) as e:
                c.submit(name="tri4", query="triangles", capacity=CAP,
                         window_edges=WIN, batch=BATCH, num_shards=4)
            assert e.value.code == "bad-spec"
    assert [ref.canon(r, CAP) for r in records] == want
    assert all(len(r) == 1 and r[0].shape == (2,) for r in records)
    pipe = metrics.pipeline_stats()
    # each pane folded once from the initial state, emitted alone
    assert pipe["merge_transient_folds"] == WINDOWS, pipe
    assert pipe["merge_combines"] == 0 and pipe["merge_running_folds"] == 0, pipe
    assert pipe["tri_windows"] == WINDOWS, pipe
    edges = sum(len(ref._simple_edges(src[k * WIN : (k + 1) * WIN],
                                      dst[k * WIN : (k + 1) * WIN])[0])
                for k in range(WINDOWS))
    assert pipe["tri_edges"] == edges, pipe
    assert pipe["tri_slots"] >= pipe["tri_wedges"] > 0, pipe
    assert pipe["tri_bucket_high_water"] >= 2, pipe
    fold = compile_cache.label_stats("agg_update")
    assert fold["calls"] == WINDOWS and fold["compiles"] <= 1, fold
    assert compile_cache.label_stats("agg_combine")["calls"] == 0


@pytest.mark.parametrize("window", [0, 1000])
def test_in_process_counts_whole_panes(window):
    # an in-process run of the descriptor: per ingestion window, or one
    # pane for the whole stream (never the per-batch wire fold)
    from gelly_streaming_tpu.core.config import StreamConfig
    from gelly_streaming_tpu.core.stream import EdgeStream

    src, dst = kronecker(9, 3000, seed=5)
    cfg = StreamConfig(vertex_capacity=1 << 9, batch_size=200,
                       ingest_window_edges=window)
    recs = WindowTriangleCount().run(EdgeStream.from_arrays(src, dst, cfg)).collect()
    got = [r[0].count for r in recs]
    if window:
        want = [ref.count(src[k : k + window], dst[k : k + window])
                for k in range(0, 3000, window)]
    else:
        want = [ref.count(src, dst)]
    assert got == want and all(w > 0 for w in want)
