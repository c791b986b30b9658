"""The open-loop schedule and the stream's passes over the edge list."""

import numpy as np
import pytest

from benchmark import streams


def test_steady_schedule():
    t = {"rate_edges_per_s": 4e6}
    assert streams.due_offset_s(t, 0, 1 << 20) == 0
    assert abs(streams.due_offset_s(t, 3, 1 << 20) - 3 * (1 << 20) / 4e6) < 1e-12


def _list(m, window, seed):
    edges = streams.EdgeList(
        np.arange(m, dtype=np.int32), -np.arange(m, dtype=np.int32), window, seed
    )
    edges.ready.set()
    return edges


def test_each_window_holds_its_own_edges_in_a_seeded_order():
    m, w = 1 << 10, 1 << 6
    edges = _list(m, w, 2**31 + 9)
    s, d = edges.take(0, 3 * m)
    np.testing.assert_array_equal(d, -s)
    for k in range(3 * m // w):
        got = s[k * w:(k + 1) * w]
        np.testing.assert_array_equal(np.sort(got), np.arange((k % (m // w)) * w, (k % (m // w) + 1) * w))
    np.testing.assert_array_equal(edges.take(m - 5, m + 7)[0], s[m - 5:m + 7])
    for lo, hi in [(0, w), (m - 2 * w, m + 3 * w), (5 * m, 5 * m + 2 * w)]:
        cs, cd = edges.covered(lo, hi)
        ts, td = edges.take(lo, hi)
        np.testing.assert_array_equal(np.sort(cs), np.sort(ts))
        np.testing.assert_array_equal(np.sort(cd), np.sort(td))
    with pytest.raises(ValueError):
        edges.covered(1, w)
    a = _list(m, w, 1).take(0, w)[0]
    b = _list(m, w, 2).take(0, w)[0]
    assert np.mean(a != b) > 0.5


def test_window_must_divide_the_list():
    import pytest

    with pytest.raises(ValueError):
        streams.EdgeList(np.zeros(100, np.int32), np.zeros(100, np.int32), 16, 0)


def test_generated_list_is_the_generators_edges():
    from benchmark import spec

    params = {"generator": "kronecker", "SCALE": 12, "edgefactor": 512,
              "A": 0.57, "B": 0.19, "C": 0.19, "graph_seed": 77,
              "window_edges": 1 << 16}
    edges = streams.generate(spec.BENCH_DIR, params, 2**31 + 3)
    edges.wait()
    assert edges.m == 512 << 12 and edges.seconds is not None
    gen = spec.generator(spec.BENCH_DIR, "kronecker")
    lo = streams.TASK_EDGES - 5
    s, d = gen.edges(params, 77, lo, 10)
    np.testing.assert_array_equal(edges.src[lo:lo + 10], s)
    np.testing.assert_array_equal(edges.dst[lo:lo + 10], d)
