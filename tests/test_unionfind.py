"""Union-find kernel tests: fixed-point equivalence with a sequential reference.

Mirrors the reference's DisjointSetTest (util/DisjointSetTest.java) and adds
randomized equivalence checks of the batched kernel against a plain sequential
union-find.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fixtures import rounds_union_edges
from gelly_streaming_tpu.ops import unionfind as uf
from gelly_streaming_tpu.summaries.disjoint_set import DisjointSet


class _SeqUF:
    """Plain sequential union-find used as ground truth."""

    def __init__(self, n):
        self.p = list(range(n))

    def find(self, v):
        while self.p[v] != v:
            self.p[v] = self.p[self.p[v]]
            v = self.p[v]
        return v

    def union(self, a, b):
        ra, rb = self.find(a), self.find(b)
        if ra != rb:
            self.p[max(ra, rb)] = min(ra, rb)


def _labels(parent):
    p = np.asarray(uf.compress(jnp.asarray(parent)))
    return p


def test_union_edges_simple_chain():
    parent = uf.init_parent(8)
    src = jnp.array([0, 1, 2], jnp.int32)
    dst = jnp.array([1, 2, 3], jnp.int32)
    p = _labels(uf.union_edges(parent, src, dst))
    assert p[0] == p[1] == p[2] == p[3] == 0
    assert p[4] == 4 and p[7] == 7


def test_union_edges_masked_rows_do_nothing():
    parent = uf.init_parent(8)
    src = jnp.array([0, 5], jnp.int32)
    dst = jnp.array([1, 6], jnp.int32)
    mask = jnp.array([True, False])
    p = _labels(uf.union_edges(parent, src, dst, mask))
    assert p[0] == p[1] == 0
    assert p[5] == 5 and p[6] == 6


def test_union_edges_random_matches_sequential():
    rng = np.random.default_rng(42)
    n = 128
    for trial in range(5):
        m = int(rng.integers(1, 200))
        src = rng.integers(0, n, size=m).astype(np.int32)
        dst = rng.integers(0, n, size=m).astype(np.int32)
        seq = _SeqUF(n)
        for a, b in zip(src, dst):
            seq.union(int(a), int(b))
        want = np.array([seq.find(v) for v in range(n)])
        got = _labels(uf.union_edges(uf.init_parent(n), jnp.asarray(src), jnp.asarray(dst)))
        np.testing.assert_array_equal(got, want)


def test_incremental_batches_match_one_shot():
    rng = np.random.default_rng(7)
    n = 64
    src = rng.integers(0, n, size=60).astype(np.int32)
    dst = rng.integers(0, n, size=60).astype(np.int32)
    p_inc = uf.init_parent(n)
    for i in range(0, 60, 10):
        p_inc = uf.union_edges(p_inc, jnp.asarray(src[i : i + 10]), jnp.asarray(dst[i : i + 10]))
    p_one = uf.union_edges(uf.init_parent(n), jnp.asarray(src), jnp.asarray(dst))
    np.testing.assert_array_equal(_labels(p_inc), _labels(p_one))


def test_merge_parents():
    n = 32
    pa = uf.union_edges(uf.init_parent(n), jnp.array([1], jnp.int32), jnp.array([2], jnp.int32))
    pb = uf.union_edges(uf.init_parent(n), jnp.array([2], jnp.int32), jnp.array([3], jnp.int32))
    merged = _labels(uf.merge_parents(pa, pb))
    assert merged[1] == merged[2] == merged[3] == 1


# ---- DisjointSet API parity (mirrors util/DisjointSetTest.java) -------------


def _setup_ds():
    ds = DisjointSet(capacity=128)
    for i in range(8):
        ds.union(i, i + 2)  # DisjointSetTest.java:36-41
    return ds


def test_get_matches_size():
    ds = _setup_ds()
    assert len(ds.get_matches()) == 10  # DisjointSetTest.java:43-46


def test_find_parity():
    ds = _setup_ds()
    root1 = ds.find(0)
    root2 = ds.find(1)
    assert root1 != root2
    for i in range(10):
        assert ds.find(i) == (root1 if i % 2 == 0 else root2)


def test_merge_parity():
    ds = _setup_ds()
    ds2 = DisjointSet(capacity=128)
    for i in range(8):
        ds2.union(i, i + 100)
    ds2.merge(ds)
    assert len(ds2.get_matches()) == 18
    roots = {ds2.find(v) for v in ds2.get_matches()}
    assert len(roots) == 2  # DisjointSetTest.java:58-77


def test_tostring_format():
    ds = DisjointSet(capacity=16)
    for a, b in [(1, 2), (1, 3), (2, 3), (1, 5), (6, 7), (8, 9)]:
        ds.union(a, b)
    assert str(ds) == "{1=[1, 2, 3, 5], 6=[6, 7], 8=[8, 9]}"


# ---- parity (signed) union-find ---------------------------------------------


def test_parity_bipartite_path():
    c = 16
    p2 = uf.init_parity_parent(c)
    src = jnp.array([1, 2, 3], jnp.int32)
    dst = jnp.array([2, 3, 4], jnp.int32)
    p2 = uf.parity_union_edges(p2, src, dst)
    seen = jnp.zeros((c,), bool).at[jnp.array([1, 2, 3, 4])].set(True)
    assert bool(uf.is_bipartite(p2, seen))


def test_parity_odd_cycle_fails():
    c = 16
    p2 = uf.init_parity_parent(c)
    src = jnp.array([1, 2, 3], jnp.int32)
    dst = jnp.array([2, 3, 1], jnp.int32)
    p2 = uf.parity_union_edges(p2, src, dst)
    seen = jnp.zeros((c,), bool).at[jnp.array([1, 2, 3])].set(True)
    assert not bool(uf.is_bipartite(p2, seen))
    conflicts = np.asarray(uf.parity_conflicts(p2, seen))
    assert conflicts[[1, 2, 3]].all()


def test_parity_even_cycle_ok():
    c = 16
    p2 = uf.init_parity_parent(c)
    src = jnp.array([1, 2, 3, 4], jnp.int32)
    dst = jnp.array([2, 3, 4, 1], jnp.int32)
    p2 = uf.parity_union_edges(p2, src, dst)
    seen = jnp.zeros((c,), bool).at[jnp.array([1, 2, 3, 4])].set(True)
    assert bool(uf.is_bipartite(p2, seen))


# ---- the window-local fold against the hook-and-compress rounds -------------


def _benchmark_kronecker():
    """The benchmark's own Graph 500 generator (benchmark/generators)."""
    import importlib.util
    import pathlib

    path = pathlib.Path(__file__).parents[1] / "benchmark/generators/kronecker.py"
    spec = importlib.util.spec_from_file_location("bench_kronecker", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _random_windows(n=512, windows=6, m=256, seed=5):
    rng = np.random.default_rng(seed)
    return n, [
        (rng.integers(0, n, m), rng.integers(0, n, m), None) for _ in range(windows)
    ]


def _path_window(n=512, seed=6):
    # one window holding a path over shuffled ids: many hook rounds
    ids = np.random.default_rng(seed).permutation(n)
    return n, [(ids[:-1], ids[1:], None)]


def _ascending_path_window(n=512):
    # edges (k, k+1), as with ids numbered by arrival: one round hooks a
    # chain n - 1 deep (k+1 -> k)
    return n, [(np.arange(n - 1), np.arange(1, n), None)]


def _descending_path_window(n=512):
    # the same path listed from its far end, each edge (k+1, k)
    return n, [(np.arange(n - 1, 0, -1), np.arange(n - 2, -1, -1), None)]


def _star_window(n=256):
    # the hub has the largest id: every leaf hooks it, then each other
    leaves = np.arange(n - 1)
    return n, [(np.full(n - 1, n - 1), leaves, None)]


def _joined_window(n=256, seed=7):
    rng = np.random.default_rng(seed)
    w = (rng.integers(0, n, 200), rng.integers(0, n, 200), None)
    return n, [w, w]  # the second fold finds every edge joined


def _masked_windows(n=256, seed=8):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(3):
        mask = rng.random(128) < 0.5
        out.append((rng.integers(0, n, 128), rng.integers(0, n, 128), mask))
    return n, out


def _kronecker_windows(scale=16, windows=4):
    kr = _benchmark_kronecker()
    params = {"SCALE": scale, "edgefactor": 16, "A": 0.57, "B": 0.19, "C": 0.19}
    w = 1 << (scale - 2)  # the cell's window share: 2^21 edges at SCALE 23
    return 1 << scale, [
        (*kr.edges(params, 500, k * w, w), None) for k in range(windows)
    ]


_FOLD_CASES = {
    "random": _random_windows,
    "path": _path_window,
    "path-ascending": _ascending_path_window,
    "path-descending": _descending_path_window,
    "star": _star_window,
    "joined": _joined_window,
    "masked": _masked_windows,
    "kronecker-s16": _kronecker_windows,
}


def _root_steps_bound(n, rounds):
    # a round resolves its hooked roots in 2 + ceil(log2(depth)) gathers
    return rounds * (2 + int(np.ceil(np.log2(n))))


@pytest.mark.parametrize("case", sorted(_FOLD_CASES))
def test_window_fold_matches_rounds_reference(case):
    """The summary fold (``union_edges_with_seen``, what
    ``ConnectedComponents.update`` runs) gives the hook-and-compress
    rounds' parent and ``seen`` bit for bit, in as many hook rounds, with
    one whole-array pass when something hooked and none otherwise, and
    resolves each round's roots in log-depth steps."""
    n, windows = _FOLD_CASES[case]()
    fold = jax.jit(uf.union_edges_with_seen)
    reference = jax.jit(rounds_union_edges)
    parent = ref_parent = uf.init_parent(n)
    seen = ref_seen = jnp.zeros((n,), bool)
    for k, (src, dst, mask) in enumerate(windows):
        src = jnp.asarray(src, jnp.int32)
        dst = jnp.asarray(dst, jnp.int32)
        mask = None if mask is None else jnp.asarray(mask)
        parent, seen, stats = fold(parent, seen, src, dst, mask)
        ref_parent, ref_rounds = reference(ref_parent, src, dst, mask)
        ref_seen = ref_seen.at[src].max(True if mask is None else mask)
        ref_seen = ref_seen.at[dst].max(True if mask is None else mask)
        np.testing.assert_array_equal(np.asarray(parent), np.asarray(ref_parent), f"window {k}")
        np.testing.assert_array_equal(np.asarray(seen), np.asarray(ref_seen), f"window {k}")
        counts = dict(zip(uf.UF_STATS, np.asarray(stats).tolist()))
        assert counts["hook_rounds"] == int(ref_rounds), (k, counts)
        assert counts["full_passes"] == int(counts["hook_rounds"] > 0), (k, counts)
        assert (counts["root_steps"] > 0) == (counts["hook_rounds"] > 0), (k, counts)
        assert counts["root_steps"] <= _root_steps_bound(n, counts["hook_rounds"]), (k, counts)
    if case == "joined":
        assert counts == {"hook_rounds": 0, "full_passes": 0, "root_steps": 0}
    if case in ("path", "star"):
        assert counts["hook_rounds"] >= 2, counts
    if case.startswith("path-"):
        # one round hooks the whole chain; a chase would take n gathers
        assert counts["hook_rounds"] == 1, counts
        assert counts["root_steps"] <= 2 * np.log2(n) + 2, counts


def _pair_partitions(n):
    # a: {2k, 2k+1}; the edges (v, b[v]) of b: {2k+1, 2k+2}, as
    # ``merge_parents(a, b)`` folds them: one round hooks a chain n/2 deep
    v = np.arange(n)
    return v - v % 2, v, np.where(v % 2 == 0, np.maximum(v - 1, 0), v)


@pytest.mark.parametrize(
    "kind", ["chain", "pointers-up", "random-forest", "pair-partitions"]
)
def test_union_edges_on_uncompressed_input_keeps_its_result(kind):
    """``union_edges`` takes any parent-pointer array, compressed or not,
    and returns what the hook-and-compress rounds return, resolving each
    round's roots in log-depth steps."""
    n = 256
    rng = np.random.default_rng(9)
    src = rng.integers(0, n, 64)
    dst = rng.integers(0, n, 64)
    if kind == "chain":
        parent = np.maximum(np.arange(n) - 1, 0)  # depth n - 1
    elif kind == "pointers-up":
        parent = np.minimum(np.arange(n) + 1, n - 1)  # roots are not minima
    elif kind == "random-forest":
        parent = np.array([rng.integers(0, v + 1) for v in range(n)])
    else:
        parent, src, dst = _pair_partitions(n)
    parent = jnp.asarray(parent, jnp.int32)
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)
    want, rounds = rounds_union_edges(parent, src, dst)
    np.testing.assert_array_equal(
        np.asarray(uf.union_edges(parent, src, dst)), np.asarray(want)
    )
    _, stats = uf.fold_roots(uf.compress(parent), src, dst)  # union_edges' fold
    counts = dict(zip(uf.UF_STATS, np.asarray(stats).tolist()))
    assert counts["hook_rounds"] == int(rounds), counts
    assert counts["root_steps"] <= _root_steps_bound(n, counts["hook_rounds"]), counts
    if kind == "pair-partitions":
        assert counts["hook_rounds"] == 1, counts
        assert counts["root_steps"] <= 2 * np.log2(n) + 2, counts
        np.testing.assert_array_equal(
            np.asarray(uf.merge_parents(parent, dst)), np.zeros(n, np.int32)
        )
