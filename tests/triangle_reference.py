"""Plain reference for the served ``triangles`` job: each window's count.

A copy of ``benchmark/references/triangles.py`` for the CPU tests, which
do not import the benchmark.

A record of window ``k`` holds the number of triangles in the simple
undirected graph of the stream's edges ``[k * W, (k + 1) * W)``: self
loops dropped, repeats merged, direction ignored.  Counted with scipy
sparse over that graph oriented from its lower to its higher (degree, id)
end: a triangle is then exactly one oriented wedge a -> b, a -> c closed
by b -> c, so the count is the sum of ``(L @ L) * L``, in int64.  It
imports nothing of the program.

The record carries the count as two int32 limbs, ``low + (high << 30)``.

The control keeps, of every vertex's neighbours, only the 64 of smallest
id, as a neighbour table capped at 64 slots would, and counts the graph of
the edges both ends kept: the hubs of a skewed window lose most of their
edges, and with them their triangles.
"""

from __future__ import annotations

import numpy as np
from scipy.sparse import csr_matrix

CONTROL_SLOTS = 64


def canon(leaves, capacity: int) -> int:
    (limbs,) = leaves
    low, high = (int(x) for x in np.asarray(limbs, np.int64))
    return low + (high << 30)


def mismatches(want, got) -> int:
    """1 where the window's count differs, else 0."""
    return int(want != got)


def _simple_edges(src, dst):
    """The distinct undirected non-loop edges as (low id, high id)."""
    lo = np.minimum(src, dst).astype(np.int64)
    hi = np.maximum(src, dst).astype(np.int64)
    keep = lo != hi
    key = np.unique((lo[keep] << 32) | hi[keep])
    return key >> 32, key & 0xFFFFFFFF


def _count(u, v) -> int:
    if len(u) == 0:
        return 0
    verts, inv = np.unique(np.concatenate([u, v]), return_inverse=True)
    cu, cv = inv[: len(u)], inv[len(u) :]
    n = len(verts)
    deg = np.bincount(inv, minlength=n)
    up = (deg[cu] < deg[cv]) | ((deg[cu] == deg[cv]) & (cu < cv))
    a = np.where(up, cu, cv)
    b = np.where(up, cv, cu)
    dag = csr_matrix((np.ones(len(a), np.int64), (a, b)), shape=(n, n))
    return int((dag @ dag).multiply(dag).sum())


def count(src, dst) -> int:
    """Triangles of the simple undirected graph of ``src``/``dst``."""
    return _count(*_simple_edges(src, dst))


def capped_count(src, dst, slots: int = CONTROL_SLOTS) -> int:
    """The control's count: only edges both ends hold among their
    ``slots`` smallest-id neighbours."""
    u, v = _simple_edges(src, dst)
    if len(u) == 0:
        return 0
    a = np.concatenate([u, v])
    b = np.concatenate([v, u])
    order = np.lexsort((b, a))
    a_s = a[order]
    starts = np.flatnonzero(np.r_[True, a_s[1:] != a_s[:-1]])
    run_start = np.repeat(starts, np.diff(np.r_[starts, len(a_s)]))
    held = np.empty(len(a), bool)
    held[order] = np.arange(len(a)) - run_start < slots
    both = held[: len(u)] & held[len(u) :]
    return _count(u[both], v[both])


def _windows(edges, ks, window_edges: int, counter):
    for k in ks:
        src, dst = edges(k * window_edges, (k + 1) * window_edges)
        yield k, counter(src, dst)


def states(edges, ks, window_edges: int, capacity: int):
    """Yield ``(k, count)`` for the sorted window indices ``ks``;
    ``edges(lo, hi)`` returns the stream's edges ``[lo, hi)``."""
    return _windows(edges, ks, window_edges, count)


def control_states(edges, ks, window_edges: int, capacity: int):
    """The control: each window counted with adjacency capped at 64."""
    return _windows(edges, ks, window_edges, capped_count)
