"""The plain references against brute force, and their controls against
the references."""

import numpy as np

from benchmark import spec

cc = spec.reference(spec.BENCH_DIR, "cc")
degree = spec.reference(spec.BENCH_DIR, "degree")


def _edges_fn(src, dst):
    return lambda lo, hi: (src[lo:hi], dst[lo:hi])


def _brute_min_labels(src, dst, n):
    parent = list(range(n))

    def find(x):
        while parent[x] != x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x

    for a, b in zip(src.tolist(), dst.tolist()):
        ra, rb = find(a), find(b)
        if ra != rb:
            parent[max(ra, rb)] = min(ra, rb)
    roots = [find(v) for v in range(n)]
    smallest = {}
    for v, r in enumerate(roots):
        smallest.setdefault(r, v)
    return np.array([smallest[r] for r in roots])


def test_cc_states_match_brute_force_at_every_window():
    rng = np.random.default_rng(5)
    n, w = 300, 64
    src = rng.integers(0, n, 8 * w).astype(np.int32)
    dst = rng.integers(0, n, 8 * w).astype(np.int32)
    ks = [0, 3, 4, 7]
    for k, (labels, seen) in cc.states(_edges_fn(src, dst), ks, w, n):
        hi = (k + 1) * w
        np.testing.assert_array_equal(labels, _brute_min_labels(src[:hi], dst[:hi], n))
        want_seen = np.zeros(n, bool)
        want_seen[src[:hi]] = want_seen[dst[:hi]] = True
        np.testing.assert_array_equal(seen, want_seen)


def test_cc_canon_reads_a_parent_forest():
    # 0 <- 2 <- 4, 1 <- 3: chains of parents, roots 0 and 1
    parent = np.array([0, 1, 0, 1, 2, 5])
    seen = np.array([1, 1, 1, 1, 1, 0], bool)
    labels, s = cc.canon([6, parent, seen], 6)
    np.testing.assert_array_equal(labels, [0, 1, 0, 1, 0, 5])
    np.testing.assert_array_equal(s, seen)


def test_cc_control_is_not_correct_on_a_long_path():
    n, w = 64, 63
    src = np.arange(n - 1, dtype=np.int32)
    dst = src + 1
    want = dict(cc.states(_edges_fn(src, dst), [0], w, n))
    ctrl = dict(cc.control_states(_edges_fn(src, dst), [0], w, n))
    assert cc.mismatches(want[0], ctrl[0]) > 0
    assert cc.mismatches(want[0], want[0]) == 0


def test_degree_states_count_both_ends_and_self_loops_twice():
    src = np.array([0, 1, 2, 2], np.int32)
    dst = np.array([1, 1, 3, 0], np.int32)
    ((_, deg),) = degree.states(_edges_fn(src, dst), [1], 2, 5)
    np.testing.assert_array_equal(deg, [2, 3, 2, 1, 0])


def test_degree_control_wraps_past_int16():
    n = 4
    src = np.zeros(20000, np.int32)  # 20000 self loops: degree 40000
    dst = np.zeros(20000, np.int32)
    want = dict(degree.states(_edges_fn(src, dst), [0], 20000, n))
    ctrl = dict(degree.control_states(_edges_fn(src, dst), [0], 20000, n))
    assert want[0][0] == 40000
    assert degree.mismatches(want[0], ctrl[0]) == 1
