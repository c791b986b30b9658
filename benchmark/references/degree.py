"""Plain reference for the served ``degree`` job: per-vertex degrees.

A record of window ``k`` is the running degree vector after the first
``(k + 1) * W`` edges of the stream (each edge adds one to both ends, a
self loop two to its vertex): ``np.bincount`` over those endpoints.  It
imports nothing of the program.

The control counts in int16, the precision below the configuration's
int32: a hub's degree passes 32767 within a few windows and wraps.
"""

from __future__ import annotations

import numpy as np

# stream positions read at a time, so the check's memory stays bounded
SPAN = 1 << 24


def canon(leaves, capacity: int):
    (deg,) = leaves
    return np.asarray(deg, np.int64)


def mismatches(want, got) -> int:
    """Vertices whose degree differs."""
    return int(np.count_nonzero(want != got))


def _degrees(edges, ks, window_edges: int, capacity: int, dtype):
    deg = np.zeros(capacity, np.int64)
    done = 0
    for k in ks:
        hi = (k + 1) * window_edges
        for lo in range(done, hi, SPAN):
            src, dst = edges(lo, min(hi, lo + SPAN))
            deg += np.bincount(src, minlength=capacity)
            deg += np.bincount(dst, minlength=capacity)
        done = hi
        yield k, deg.astype(dtype).astype(np.int64)


def states(edges, ks, window_edges: int, capacity: int):
    """Yield ``(k, degrees)`` for the sorted window indices ``ks``;
    ``edges(lo, hi)`` returns the stream's edges ``[lo, hi)``."""
    return _degrees(edges, ks, window_edges, capacity, np.int64)


def control_states(edges, ks, window_edges: int, capacity: int):
    """The control: the same counts kept in int16."""
    return _degrees(edges, ks, window_edges, capacity, np.int16)
