"""Wire-format tests: pack/unpack roundtrips, native/numpy agreement, prefetch.

The wire format (io/wire.py) is the host->device serialization boundary — the
analog of the reference's Flink/Netty record serialization, which is covered
there by the runtime, not the library.  Here it is in-repo code, so it gets
direct tests: exact roundtrips at every width, byte-identical native vs numpy
packing, ordered prefetching, and error propagation.
"""

import numpy as np
import pytest

from gelly_streaming_tpu.io import wire
from gelly_streaming_tpu.utils.native import load_ingest_lib


def _random_edges(n, capacity, seed=0):
    rng = np.random.default_rng(seed)
    return (
        rng.integers(0, capacity, n).astype(np.int32),
        rng.integers(0, capacity, n).astype(np.int32),
    )


def test_width_for_capacity_boundaries():
    assert wire.width_for_capacity(1 << 16) == 2
    assert wire.width_for_capacity((1 << 16) + 1) == wire.PAIR40
    assert wire.width_for_capacity(1 << 20) == wire.PAIR40
    assert wire.width_for_capacity((1 << 20) + 1) == 3
    assert wire.width_for_capacity(1 << 24) == 3
    assert wire.width_for_capacity((1 << 24) + 1) == 4


def test_pair40_roundtrip_and_size():
    import jax.numpy as jnp

    src, dst = _random_edges(513, 1 << 20, seed=11)
    buf = wire.pack_edges(src, dst, wire.PAIR40)
    assert buf.shape == (5 * 513,)  # 5 bytes per edge
    s, d = wire.unpack_edges(jnp.asarray(buf), 513, wire.PAIR40)
    np.testing.assert_array_equal(np.asarray(s), src)
    np.testing.assert_array_equal(np.asarray(d), dst)


def test_pair40_native_matches_numpy(monkeypatch):
    lib = load_ingest_lib()
    if lib is None or not hasattr(lib, "pack_edges40"):
        pytest.skip("native pack_edges40 unavailable")
    src, dst = _random_edges(1000, 1 << 20, seed=12)
    native_buf = wire.pack_edges(src, dst, wire.PAIR40)
    monkeypatch.setattr(wire, "load_ingest_lib", lambda: None)
    fallback_buf = wire.pack_edges(src, dst, wire.PAIR40)
    np.testing.assert_array_equal(native_buf, fallback_buf)


@pytest.mark.parametrize("width", [2, 3, 4])
def test_pack_unpack_roundtrip(width):
    import jax.numpy as jnp

    capacity = 1 << (8 * width - 1)  # exercise the high bit of the top byte
    src, dst = _random_edges(257, capacity, seed=width)
    buf = wire.pack_edges(src, dst, width)
    assert buf.dtype == np.uint8 and buf.shape == (2 * 257 * width,)
    s, d = wire.unpack_edges(jnp.asarray(buf), 257, width)
    np.testing.assert_array_equal(np.asarray(s), src)
    np.testing.assert_array_equal(np.asarray(d), dst)


@pytest.mark.parametrize("width", [2, 3, 4])
def test_native_matches_numpy_fallback(width, monkeypatch):
    lib = load_ingest_lib()
    if lib is None:
        pytest.skip("native library unavailable")
    src, dst = _random_edges(1000, 1 << (8 * width - 1), seed=7)
    native_buf = wire.pack_edges(src, dst, width)

    # run the module's own numpy fallback branch by hiding the native lib
    monkeypatch.setattr(wire, "load_ingest_lib", lambda: None)
    fallback_buf = wire.pack_edges(src, dst, width)
    np.testing.assert_array_equal(native_buf, fallback_buf)


def test_pack_rejects_bad_width_and_mismatch():
    src, dst = _random_edges(8, 100)
    with pytest.raises(ValueError):
        wire.pack_edges(src, dst, 5)
    with pytest.raises(ValueError):
        wire.pack_edges(src, dst[:4], 3)


def test_unpack_fuses_into_union_fold():
    """The bench path: unpack inside a jitted union-find fold is exact."""
    import jax
    import jax.numpy as jnp

    from gelly_streaming_tpu.ops import unionfind as uf

    capacity, batch = 1 << 10, 64
    src, dst = _random_edges(batch, capacity, seed=3)

    def fold(parent, seen, buf):
        s, d = wire.unpack_edges(buf, batch, 2)
        return uf.union_edges_with_seen(parent, seen, s, d, None)[:2]

    parent = uf.init_parent(capacity)
    seen = jnp.zeros((capacity,), bool)
    p1, s1 = jax.jit(fold)(parent, seen, jnp.asarray(wire.pack_edges(src, dst, 2)))
    p2, s2, _ = uf.union_edges_with_seen(parent, seen, src, dst, None)
    np.testing.assert_array_equal(np.asarray(p1), np.asarray(p2))
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))


def test_prefetcher_yields_in_order():
    n, batch = 10, 32
    batches = [_random_edges(batch, 1 << 12, seed=i) for i in range(n)]
    out = list(wire.WirePrefetcher(iter(batches), width=2, depth=3))
    assert len(out) == n
    for (buf, count), (src, dst) in zip(out, batches):
        assert count == batch
        s, d = wire.unpack_edges(buf, batch, 2)
        np.testing.assert_array_equal(np.asarray(s), src)
        np.testing.assert_array_equal(np.asarray(d), dst)


def test_prefetcher_early_close_releases_producer():
    def endless():
        i = 0
        while True:
            yield _random_edges(16, 1 << 10, seed=i)
            i += 1

    pf = wire.WirePrefetcher(endless(), width=2, depth=2)
    it = iter(pf)
    next(it)
    pf.close()
    for t in pf._threads:
        t.join(timeout=5.0)
    assert not any(t.is_alive() for t in pf._threads)


def test_prefetcher_propagates_errors():
    def gen():
        yield _random_edges(8, 100)
        raise RuntimeError("source failed")

    it = iter(wire.WirePrefetcher(gen(), width=2, depth=2))
    next(it)
    with pytest.raises(RuntimeError, match="source failed"):
        list(it)


# ---------------------------------------------------------------------------
# EF40: sorted Elias-Fano multiset encoding (order-free folds)


def test_ef40_roundtrip_sorted_multiset():
    import jax.numpy as jnp

    cap = 1 << 12
    src, dst = _random_edges(777, cap, seed=13)
    buf = wire.pack_edges(src, dst, (wire.EF40, cap))
    assert buf.shape == (wire.ef40_nbytes(777, cap),)
    s, d = wire.unpack_edges_ef40(jnp.asarray(buf), 777, cap)
    s, d = np.asarray(s), np.asarray(d)
    # the batch comes back GROUPED by src (nondecreasing): same multiset,
    # not the arrival sequence
    assert (np.diff(s) >= 0).all()
    w_in = np.sort(src.astype(np.int64) << 20 | dst.astype(np.int64))
    w_out = np.sort(s.astype(np.int64) << 20 | d.astype(np.int64))
    np.testing.assert_array_equal(w_out, w_in)


def test_ef40_native_matches_numpy(monkeypatch):
    lib = load_ingest_lib()
    if lib is None or not hasattr(lib, "pack_edges_ef40"):
        pytest.skip("native pack_edges_ef40 unavailable")
    cap = 1 << 10
    src, dst = _random_edges(500, cap, seed=14)
    native_buf = wire.pack_edges(src, dst, (wire.EF40, cap))
    monkeypatch.setattr(wire, "load_ingest_lib", lambda: None)
    numpy_buf = wire.pack_edges(src, dst, (wire.EF40, cap))
    np.testing.assert_array_equal(native_buf, numpy_buf)


def test_ef40_native_blocked_path_matches_numpy(monkeypatch):
    """Parity on the cache-blocked native sort (capacity > 2^14, n >= 2^16).

    The native pack switches to a two-level bucketed counting sort at scale;
    these shapes force that path — including a capacity that is not a
    multiple of the 2^12 bucket span (partial last bucket) and odd n — so
    a regression in the bucket scatter or the done-based prefix cannot ship
    behind the small-shape parity test above.
    """
    lib = load_ingest_lib()
    if lib is None or not hasattr(lib, "pack_edges_ef40"):
        pytest.skip("native pack_edges_ef40 unavailable")
    for n, cap, seed in [
        ((1 << 16) + 1, 1 << 20, 15),       # blocked, odd n, full capacity
        (1 << 16, (1 << 20) - 333, 16),     # partial last bucket
        ((1 << 16) + 7, (1 << 15) + 5, 17), # small odd capacity, odd n
    ]:
        src, dst = _random_edges(n, cap, seed=seed)
        src[: n // 8] = 42  # skewed hot vertex crossing bucket boundaries
        native_buf = wire.pack_edges(src, dst, (wire.EF40, cap))
        with monkeypatch.context() as m:
            m.setattr(wire, "load_ingest_lib", lambda: None)
            numpy_buf = wire.pack_edges(src, dst, (wire.EF40, cap))
        np.testing.assert_array_equal(native_buf, numpy_buf)


def test_ef40_odd_and_duplicate_edges():
    import jax.numpy as jnp

    cap = 64
    src = np.array([3, 3, 3, 0, 63], np.int32)
    dst = np.array([5, 5, 1, 0, 63], np.int32)  # duplicates + self loops
    buf = wire.pack_edges(src, dst, (wire.EF40, cap))
    s, d = wire.unpack_edges_ef40(jnp.asarray(buf), 5, cap)
    np.testing.assert_array_equal(np.asarray(s), [0, 3, 3, 3, 63])
    # dst within a src group keeps arrival order (stable grouping)
    np.testing.assert_array_equal(np.asarray(d), [0, 5, 5, 1, 63])


def test_ef40_bytes_beat_pair40_at_scale():
    n, cap = 1 << 16, 1 << 16
    assert wire.ef40_nbytes(n, cap) < 5 * n * 0.6  # < 3 B/edge here


def test_records48_roundtrip():
    import jax

    rng = np.random.default_rng(17)
    ids = rng.integers(0, 1 << 20, 1000).astype(np.int32)
    vals = rng.integers(0, 1 << 28, 1000).astype(np.int32)
    mask = rng.random(1000) < 0.7
    import jax.numpy as jnp

    packed = jax.jit(wire.pack_records48)(jnp.asarray(ids), jnp.asarray(vals))
    bits = jax.jit(wire.pack_mask_bits)(jnp.asarray(mask))
    assert packed.shape == (6000,) and bits.shape == (125,)
    i2, v2, m2 = wire.unpack_records48(np.asarray(packed), np.asarray(bits), 1000)
    np.testing.assert_array_equal(i2, ids)
    np.testing.assert_array_equal(v2, vals)
    np.testing.assert_array_equal(m2, mask)


def test_replay_width_picks_cheapest_legal_encoding():
    """EF40 only wins while its per-batch bitvector is outweighed by the
    2.5 B/edge dst stream; capacity >> batch must fall back to fixed width."""
    from gelly_streaming_tpu.io import wire

    # capacity small vs batch: EF40 strictly smaller
    assert wire.replay_width(1 << 10, 4096) == (wire.EF40, 1 << 10)
    # capacity 2^20 with a tiny batch: the bitvector alone is ~32 B/edge
    assert wire.replay_width(1 << 20, 4096) == wire.PAIR40
    # order-sensitive folds never get the multiset encoding
    assert wire.replay_width(1 << 10, 4096, order_free=False) == 2
    # ids beyond 20 bits: EF40 illegal regardless
    assert wire.replay_width((1 << 20) + 1, 1 << 22) == 3
    # the chosen encoding really is the cheaper of the two at the boundary
    for cap, batch in [(1 << 16, 1 << 14), (1 << 20, 1 << 21), (1 << 18, 1 << 16)]:
        w = wire.replay_width(cap, batch)
        fixed = wire.width_for_capacity(cap)
        best = min(
            wire.wire_nbytes(batch, fixed), wire.ef40_nbytes(batch, cap)
        )
        assert wire.wire_nbytes(batch, w) == best
