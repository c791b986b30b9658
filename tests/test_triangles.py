"""Triangle counting tests.

Window variant mirrors WindowTrianglesITCase (19-edge timestamped dataset,
util/ExamplesTestData.java:21-34, golden TRIANGLES_RESULT); the streaming exact
variant mirrors TriangleCountTest's record-by-record counter semantics."""

import numpy as np
import pytest

from gelly_streaming_tpu.core.config import StreamConfig
from gelly_streaming_tpu.core.stream import EdgeStream
from gelly_streaming_tpu.library.triangles import (
    ExactTriangleCount,
    GLOBAL_KEY,
    window_triangles,
)

CFG = StreamConfig(vertex_capacity=16, max_degree=16)

# ExamplesTestData.TRIANGLES_DATA (:21-31): "src dst timestamp"
TRIANGLES_DATA = [
    (1, 2, 100), (1, 3, 150), (3, 2, 200), (2, 4, 250), (3, 4, 300),
    (3, 5, 350), (4, 5, 400), (4, 6, 450), (6, 5, 500), (5, 7, 550),
    (6, 7, 600), (8, 6, 650), (7, 8, 700), (7, 9, 750), (8, 9, 800),
    (10, 8, 850), (9, 10, 900), (9, 11, 950), (10, 11, 1000),
]


def test_window_triangles_golden():
    edges = [(s, d, 0, t) for s, d, t in TRIANGLES_DATA]
    stream = EdgeStream.from_collection(edges, CFG, batch_size=4, with_time=True)
    got = sorted(window_triangles(stream, 400).collect())
    # TRIANGLES_RESULT (:33-34): (2,399) (3,799) (2,1199)
    assert got == [(2, 399), (2, 1199), (3, 799)]


def test_window_triangles_no_triangles():
    edges = [(1, 2, 0, 10), (3, 4, 0, 20)]
    stream = EdgeStream.from_collection(edges, CFG, with_time=True)
    assert window_triangles(stream, 1000).collect() == [(0, 999)]


@pytest.mark.parametrize("bs", [1, 3, 7])
def test_exact_triangle_count_fixture(bs):
    # 7-edge fixture has triangles {1,2,3}, {3,4,5}, {1,3,5}
    edges = [(1, 2), (1, 3), (2, 3), (3, 4), (3, 5), (4, 5), (5, 1)]
    stream = EdgeStream.from_collection(edges, CFG, batch_size=bs)
    algo = ExactTriangleCount()
    recs = algo.run(stream).collect()
    finals = {}
    for k, c in recs:
        finals[k] = c
    assert finals[GLOBAL_KEY] == 3
    local = np.asarray(algo.final_state.local)
    assert local[1] == 2 and local[2] == 1 and local[3] == 3
    assert local[4] == 1 and local[5] == 2


def test_exact_triangle_count_ignores_duplicates():
    edges = [(1, 2), (2, 3), (1, 3), (1, 3), (2, 1)]
    stream = EdgeStream.from_collection(edges, CFG)
    algo = ExactTriangleCount()
    recs = algo.run(stream).collect()
    assert dict((k, c) for k, c in recs)[GLOBAL_KEY] == 1


def test_block_kernel_matches_scan_final_state():
    """triangle_update_block (chunk-vectorized) must reach the exact final
    state of the per-edge scan on random multigraphs with dups/self-loops."""
    import jax
    import jax.numpy as jnp

    from gelly_streaming_tpu.library.triangles import (
        init_triangle_state,
        triangle_update,
        triangle_update_block,
    )

    cfg = StreamConfig(vertex_capacity=32, max_degree=32, batch_size=128)
    rng = np.random.default_rng(17)
    for trial in range(3):
        src = rng.integers(0, 20, 128).astype(np.int32)
        dst = rng.integers(0, 20, 128).astype(np.int32)  # dups + self loops
        mask = rng.random(128) < 0.9
        s, d, m = jnp.asarray(src), jnp.asarray(dst), jnp.asarray(mask)
        scan_state, _, _ = jax.jit(triangle_update)(
            init_triangle_state(cfg), s, d, m
        )
        for chunk in (16, 64, 128):
            blk = jax.jit(
                lambda st, a, b, c: triangle_update_block(st, a, b, c, chunk)
            )(init_triangle_state(cfg), s, d, m)
            assert int(blk.global_count) == int(scan_state.global_count)
            assert np.array_equal(
                np.asarray(blk.local), np.asarray(scan_state.local)
            )
            assert np.array_equal(
                np.sort(np.asarray(blk.table.deg)),
                np.sort(np.asarray(scan_state.table.deg)),
            )


def test_block_mode_emits_running_counts():
    from gelly_streaming_tpu.library.triangles import ExactTriangleCount

    cfg = StreamConfig(vertex_capacity=16, max_degree=16, batch_size=4)
    edges = [(1, 2), (2, 3), (1, 3), (3, 4), (2, 4)]  # 2 triangles
    stream = EdgeStream.from_collection(edges, cfg, batch_size=4)
    algo = ExactTriangleCount(mode="block")
    recs = algo.run(stream).collect()
    finals = {k: v for k, v in recs}  # last write per key wins
    assert finals[-1] == 2
    assert finals[2] == 2 and finals[3] == 2  # vertices on both triangles
    assert finals[1] == 1 and finals[4] == 1


def test_pipelined_pane_counts_match_sequential():
    from gelly_streaming_tpu.library.triangles import (
        _pane_triangle_count,
        pipelined_pane_counts,
    )
    from gelly_streaming_tpu.utils.metrics import WindowLatencyRecorder

    import triangle_reference

    rng = np.random.default_rng(3)
    # small ids take the dense kernel; 200 vertices drawn from a 2^20 id
    # space, with too many edges to compact on the host, take the
    # degree-ordered sparse count
    ids = [np.arange(64), rng.choice(1 << 20, 200, replace=False)]
    panes = [
        (
            ids[sparse][rng.integers(0, len(ids[sparse]), n)].astype(np.int32),
            ids[sparse][rng.integers(0, len(ids[sparse]), n)].astype(np.int32),
        )
        for sparse, n in [(0, 300)] * 5 + [(1, 2000), (0, 4000), (1, 900)]
    ] + [(np.zeros(0, np.int32), np.zeros(0, np.int32))]
    rec = WindowLatencyRecorder()
    piped = pipelined_pane_counts(panes, recorder=rec, warmup=1)
    seq = [_pane_triangle_count(s, d) for s, d in panes]
    assert piped == seq == [triangle_reference.count(s, d) for s, d in panes]
    assert len(rec.latencies_ms) == len(panes) - 1  # warmup pane unrecorded
