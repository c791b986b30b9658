"""The AOT executable cache (core/compile_cache.py): entry identity,
hit/miss/compile counters, and the RETRACE GUARD — the CC hot loop must
compile at most once per bucketed shape, however many streams, descriptors,
or windows re-create their closures.
"""

import numpy as np

from gelly_streaming_tpu.core import compile_cache
from gelly_streaming_tpu.core.config import StreamConfig
from gelly_streaming_tpu.core.stream import EdgeStream
from gelly_streaming_tpu.library.connected_components import ConnectedComponents


def test_key_hit_returns_same_entry_and_counts():
    compile_cache.reset_stats()
    a = compile_cache.cached_jit(("tcc", "k1"), lambda: (lambda x: x + 1))
    b = compile_cache.cached_jit(("tcc", "k1"), lambda: (lambda x: x + 99))
    assert a is b  # key hit: the first build wins, the second never traces
    x = np.ones(4, np.float32)
    assert float(a(x)[0]) == 2.0
    s = compile_cache.stats()
    assert s["key_misses"] >= 1 and s["key_hits"] >= 1


def test_compile_counted_once_per_shape():
    compile_cache.reset_stats()
    f = compile_cache.cached_jit(("tcc", "shapes"), lambda: (lambda x: x * 2))
    for _ in range(5):
        f(np.ones(8, np.float32))
    f(np.ones(16, np.float32))
    assert f.compiles == 2  # one per distinct shape
    assert compile_cache.recompiles() == 0


def test_retrace_guard_cc_hot_loop_100_same_shape_windows():
    """100 same-shape running windows over the wire fast path, with the
    stream AND the descriptor re-created mid-run: zero recompiles."""
    rng = np.random.default_rng(11)
    src = rng.integers(0, 64, 100 * 64).astype(np.int32)
    dst = rng.integers(0, 64, 100 * 64).astype(np.int32)
    cfg = StreamConfig(
        vertex_capacity=64, batch_size=64, ingest_window_edges=64
    )

    def run():
        out = (
            EdgeStream.from_arrays(src, dst, cfg)
            .aggregate(ConnectedComponents())
            .collect()
        )
        assert len(out) == 100  # one record per same-shape window
        return out

    run()  # warmup: compiles land here
    compile_cache.reset_stats()
    run()  # fresh EdgeStream + fresh ConnectedComponents (class cache token)
    stats = compile_cache.stats()
    assert stats["compiles"] == 0, stats
    assert stats["recompiles"] == 0, stats
    assert stats["dispatch_hits"] >= 100


def test_retrace_guard_superbatched_cc():
    rng = np.random.default_rng(12)
    src = rng.integers(0, 64, 64 * 64).astype(np.int32)
    dst = rng.integers(0, 64, 64 * 64).astype(np.int32)
    cfg = StreamConfig(vertex_capacity=64, batch_size=64, superbatch=8)

    def run():
        return (
            EdgeStream.from_arrays(src, dst, cfg)
            .aggregate(ConnectedComponents())
            .collect()
        )

    run()
    compile_cache.reset_stats()
    run()
    stats = compile_cache.stats()
    assert stats["compiles"] == 0, stats
    assert stats["recompiles"] == 0, stats


def test_property_streams_share_executables_across_streams():
    """Re-created property streams (stable kernel keys) never retrace."""
    rng = np.random.default_rng(13)
    cfg = StreamConfig(vertex_capacity=32, batch_size=32)

    def degrees():
        src = rng.integers(0, 32, 128).astype(np.int32)
        dst = rng.integers(0, 32, 128).astype(np.int32)
        return (
            EdgeStream.from_arrays(src, dst, cfg).get_degrees().collect()
        )

    degrees()
    compile_cache.reset_stats()
    degrees()  # same shapes, fresh stream + fresh kernel closure
    stats = compile_cache.stats()
    assert stats["compiles"] == 0, stats


def test_stats_shape():
    s = compile_cache.stats()
    for key in (
        "entries",
        "key_hits",
        "key_misses",
        "compiles",
        "compile_time_s",
        "dispatch_hits",
        "recompiles",
    ):
        assert key in s


def test_persistent_cache_defaults_to_the_checkout(monkeypatch):
    import os

    import jax

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    was = jax.config.jax_compilation_cache_dir
    try:
        compile_cache.use_persistent_cache()
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert jax.config.jax_compilation_cache_dir == os.path.join(
            root, ".jax_cache"
        )
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_persistent_cache_env_dir_wins(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR is JAX's own: the helper sets nothing."""
    import jax

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    was = jax.config.jax_compilation_cache_dir
    compile_cache.use_persistent_cache()
    assert jax.config.jax_compilation_cache_dir == was
