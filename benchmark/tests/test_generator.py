"""The Kronecker generator: deterministic by seed and edge index, and
drawing each level's quadrant with the spec's probabilities."""

import numpy as np
import pytest

from benchmark import spec

gen = spec.generator(spec.BENCH_DIR, "kronecker")
PARAMS = {"SCALE": 12, "edgefactor": 16, "A": 0.57, "B": 0.19, "C": 0.19}
SEED = 2**31 + 977  # past 32 signed bits, as the driver's seeds are


def test_slices_are_the_same_edges_wherever_they_start():
    src, dst = gen.edges(PARAMS, SEED, 0, 5 * gen.CHUNK + 123)
    for lo, hi in [(0, 10), (gen.CHUNK - 7, gen.CHUNK + 9), (3 * gen.CHUNK + 5, 5 * gen.CHUNK + 100)]:
        s, d = gen.edges(PARAMS, SEED, lo, hi - lo)
        np.testing.assert_array_equal(s, src[lo:hi])
        np.testing.assert_array_equal(d, dst[lo:hi])


def test_same_seed_same_edges_other_seed_other_edges():
    a = gen.edges(PARAMS, SEED, 1000, 4096)
    b = gen.edges(PARAMS, SEED, 1000, 4096)
    c = gen.edges(PARAMS, SEED + 1, 1000, 4096)
    np.testing.assert_array_equal(a[0], b[0])
    np.testing.assert_array_equal(a[1], b[1])
    assert np.mean(a[0] != c[0]) > 0.5


def test_ids_in_range_and_relabelled_by_a_permutation():
    src, dst = gen.edges(PARAMS, SEED, 0, 1 << 15)
    assert src.dtype == np.int32 and dst.dtype == np.int32
    assert 0 <= min(src.min(), dst.min()) and max(src.max(), dst.max()) < 1 << 12
    perm = gen.permutation(PARAMS, SEED)
    np.testing.assert_array_equal(np.sort(perm), np.arange(1 << 12))
    raw_s, raw_d = gen.raw_edges(PARAMS, SEED, 0, 1 << 15)
    np.testing.assert_array_equal(perm[raw_s], src)
    np.testing.assert_array_equal(perm[raw_d], dst)


def test_num_edges_is_edgefactor_times_vertices():
    assert gen.num_edges(PARAMS) == 16 << 12


@pytest.mark.parametrize("level", [0, 5, 11])
def test_quadrant_probabilities(level):
    n = 1 << 18
    src, dst = gen.raw_edges(PARAMS, SEED, 0, n)
    row = (src >> level) & 1
    col = (dst >> level) & 1
    d = 1 - 0.57 - 0.19 - 0.19
    for (r, c), p in {(0, 0): 0.57, (0, 1): 0.19, (1, 0): 0.19, (1, 1): d}.items():
        got = np.mean((row == r) & (col == c))
        sigma = np.sqrt(p * (1 - p) / n)
        assert abs(got - p) < 5 * sigma, (r, c, got, p)


def test_bad_probabilities_refused():
    with pytest.raises(ValueError):
        gen.edges({**PARAMS, "A": 0.9, "B": 0.1}, SEED, 0, 8)
