"""The degree-ordered triangle count (ops/oriented_triangles.py) against the
plain reference (tests/triangle_reference.py, scipy over the simple graph):
Graph 500 Kronecker windows, a hub above every other degree, repeats, self
loops and empty windows, and windows that cross an out-degree bucket."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import triangle_reference as ref
from fixtures import kronecker
from gelly_streaming_tpu.core import compile_cache
from gelly_streaming_tpu.library import triangles
from gelly_streaming_tpu.ops import oriented_triangles as ot

_count = jax.jit(ot.count_stats, static_argnames=("tile",))


def stats(src, dst, mask=None, tile=ot.TILE):
    src = np.asarray(src, np.int32)
    dst = np.asarray(dst, np.int32)
    mask = np.ones(len(src), bool) if mask is None else mask
    return np.asarray(_count(jnp.asarray(src), jnp.asarray(dst), jnp.asarray(mask), tile=tile))


def oriented_wedges(src, dst) -> int:
    u, v = ref._simple_edges(np.asarray(src), np.asarray(dst))
    if len(u) == 0:
        return 0
    _, inv = np.unique(np.concatenate([u, v]), return_inverse=True)
    cu, cv = inv[: len(u)], inv[len(u) :]
    deg = np.bincount(inv)
    up = (deg[cu] < deg[cv]) | ((deg[cu] == deg[cv]) & (cu < cv))
    out = np.bincount(np.where(up, cu, cv))
    return int((out * (out - 1) // 2).sum())


@pytest.mark.parametrize("scale,seed", [(10, 1), (11, 2), (12, 3)])
def test_kronecker_windows_match_the_reference(scale, seed):
    src, dst = kronecker(scale, 16 << scale, seed)
    for k in range(2):  # two windows of 8 x 2^scale edges
        s = src[k * (8 << scale) : (k + 1) * (8 << scale)]
        d = dst[k * (8 << scale) : (k + 1) * (8 << scale)]
        st = stats(s, d)
        assert ot.limbs(st, "triangles") == ref.count(s, d) > 0
        c = ot.counters(st)
        u, v = ref._simple_edges(s, d)
        assert c["tri_edges"] == len(u)
        assert c["tri_vertices"] == len(np.unique(np.concatenate([u, v])))
        assert c["tri_wedges"] == oriented_wedges(s, d)
        assert c["tri_slots"] >= c["tri_wedges"]


def test_a_hub_above_every_other_degree():
    # a 30-clique, and vertex 1000 joined to every clique vertex and to
    # 3000 leaves: its degree dwarfs all others, and its wedges close only
    # through the clique
    k = 30
    cs, cd = np.triu_indices(k, 1)
    src = np.concatenate([cs, np.full(k + 3000, 1000)])
    dst = np.concatenate([cd, np.arange(k), np.arange(2000, 5000)])
    st = stats(src, dst)
    want = k * (k - 1) * (k - 2) // 6 + k * (k - 1) // 2
    assert ot.limbs(st, "triangles") == ref.count(src, dst) == want
    # the hub ranks last: its 3030 edges all point at it, no row holds them
    assert ot.counters(st)["tri_bucket"] <= 32


def test_repeats_self_loops_direction_and_mask():
    src = np.array([1, 2, 3, 2, 1, 3, 3, 7, 9, 9], np.int32)
    dst = np.array([2, 3, 1, 1, 2, 2, 3, 7, 9, 8], np.int32)
    st = stats(src, dst)
    assert ot.limbs(st, "triangles") == 1
    assert ot.counters(st)["tri_edges"] == 4  # {1,2} {2,3} {1,3} {8,9}
    mask = np.ones(len(src), bool)
    mask[2] = mask[5] = False  # (3, 1) and (3, 2) out, (2, 3) still in
    st = stats(src, dst, mask)
    assert ot.limbs(st, "triangles") == ref.count(src[mask], dst[mask]) == 0


@pytest.mark.parametrize("n", [1, 8])
def test_an_empty_window_counts_nothing(n):
    st = stats(np.zeros(n), np.zeros(n), np.zeros(n, bool))
    assert ot.limbs(st, "triangles") == 0
    assert ot.counters(st) == {
        "tri_vertices": 0, "tri_edges": 0, "tri_wedges": 0,
        "tri_slots": 0, "tri_bucket": 1,
    }


@pytest.mark.parametrize("tile", [1, 16, 1 << 15])
def test_tiles_of_any_width_count_the_same(tile):
    src, dst = kronecker(9, 2048, 4)
    st = stats(src, dst, tile=tile)
    assert ot.limbs(st, "triangles") == ref.count(src, dst)
    assert ot.counters(st)["tri_wedges"] == oriented_wedges(src, dst)


def _clique_window(k: int, n: int, rng):
    """A k-clique among ids 0..k-1 padded with random edges over a large id
    space to ``n`` entries, in shuffled order."""
    cs, cd = np.triu_indices(k, 1)
    rest = n - len(cs)
    src = np.concatenate([cs, rng.integers(1000, 1 << 20, rest)]).astype(np.int32)
    dst = np.concatenate([cd, rng.integers(1000, 1 << 20, rest)]).astype(np.int32)
    order = rng.permutation(n)
    return src[order], dst[order]


def test_windows_cross_out_degree_buckets_without_recompiling_or_truncating():
    rng = np.random.default_rng(11)
    n = 4096
    windows = [_clique_window(k, n, rng) for k in (4, 70, 9, 40)]
    compile_cache.reset_stats()
    buckets, counts = [], []
    for src, dst in windows:
        s, d, mask = triangles._pad_pane(src, dst)
        st = np.asarray(triangles._sparse_count(s, d, mask))
        buckets.append(ot.counters(st)["tri_bucket"])
        counts.append(ot.limbs(st, "triangles"))
    assert counts == [ref.count(s, d) for s, d in windows]
    assert counts[1] >= 70 * 69 * 68 // 6
    # a k-clique's top row holds k - 1 out-neighbours: the windows move
    # up and down the buckets (4, 128, 16, 64), with the same executable
    assert buckets == [4, 128, 16, 64]
    st = compile_cache.stats()
    assert st["compiles"] <= 1 and st["recompiles"] == 0, st


def test_limbs_carry_exactly_past_int32():
    acc = (jnp.int32(0), jnp.int32(0))
    for _ in range(5):
        acc = ot._add(acc, jnp.int32((1 << 30) - 1))
    host = np.zeros(len(ot.STATS), np.int64)
    host[0], host[1] = int(acc[0]), int(acc[1])
    assert ot.limbs(host, "triangles") == 5 * ((1 << 30) - 1) > 2**31
