"""Plain reference for the served ``cc`` job: connected components.

A record of window ``k`` is the running summary after the first
``(k + 1) * W`` edges of the stream.  Its leaves are ``[capacity, parent,
seen]``: a union-find parent forest and the seen-vertex flags.  Both sides
are put in one canonical form before they are compared: the smallest
vertex id of each vertex's component, and the seen flags.  The reference
is scipy's ``connected_components`` (copied from ``chip_smoke.py``'s
oracle); it imports nothing of the program.

The control is the same reference stopped short: min-label propagation
for ``CONTROL_ROUNDS`` rounds instead of to its fixed point, the bounded
iteration count a faster union-find kernel would be tempted to take.
"""

from __future__ import annotations

import numpy as np

CONTROL_ROUNDS = 2


def _min_member(labels: np.ndarray, capacity: int) -> np.ndarray:
    first = np.full(capacity, capacity, np.int64)
    np.minimum.at(first, labels, np.arange(capacity))
    return first[labels]


def _components(src, dst, capacity: int) -> np.ndarray:
    """Smallest vertex id of each vertex's component (scipy, host)."""
    from scipy.sparse import coo_matrix
    from scipy.sparse.csgraph import connected_components

    adj = coo_matrix(
        (np.ones(len(src), np.int8), (src, dst)), shape=(capacity, capacity)
    ).tocsr()
    _, labels = connected_components(adj, directed=False)
    return _min_member(labels, capacity)


def canon(leaves, capacity: int):
    """Served record -> (min-member labels, seen): pointer jumping to the
    roots of the parent forest, then each root set's smallest id."""
    _cap, parent, seen = leaves
    roots = np.asarray(parent, np.int64)
    while True:
        nxt = roots[roots]
        if np.array_equal(nxt, roots):
            break
        roots = nxt
    return _min_member(roots, len(roots)), np.asarray(seen, bool)


def mismatches(want, got) -> int:
    """Vertices whose label or seen flag differs."""
    return int(np.count_nonzero(want[0] != got[0])) + int(
        np.count_nonzero(want[1] != got[1])
    )


def states(edges, ks, window_edges: int, capacity: int):
    """Yield ``(k, (labels, seen))`` for the sorted window indices ``ks``.

    ``edges(lo, hi)`` returns the stream's edges ``[lo, hi)``.  Each state
    builds on the last: the graph of the previous labels (each vertex
    joined to its component's smallest id) plus the edges since."""
    labels = np.arange(capacity, dtype=np.int64)
    seen = np.zeros(capacity, bool)
    done = 0
    for k in ks:
        hi = (k + 1) * window_edges
        src, dst = edges(done, hi)
        seen[src] = True
        seen[dst] = True
        ids = np.arange(capacity, dtype=np.int64)
        labels = _components(
            np.concatenate([src.astype(np.int64), ids]),
            np.concatenate([dst.astype(np.int64), labels]),
            capacity,
        )
        done = hi
        yield k, (labels, seen.copy())


def control_states(edges, ks, window_edges: int, capacity: int):
    """The control: min-label propagation over the prefix, stopped after
    ``CONTROL_ROUNDS`` rounds."""
    for k in ks:
        src, dst = edges(0, (k + 1) * window_edges)
        labels = np.arange(capacity, dtype=np.int64)
        for _ in range(CONTROL_ROUNDS):
            lab = labels.copy()
            np.minimum.at(lab, src, labels[dst])
            np.minimum.at(lab, dst, labels[src])
            labels = lab
        seen = np.zeros(capacity, bool)
        seen[src] = True
        seen[dst] = True
        yield k, (labels, seen)
