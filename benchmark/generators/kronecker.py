"""Graph 500 Kronecker edge generator (Benchmark 1, the reference
``kronecker_generator.m``), made deterministic by seed and edge index.

Per edge and per level of ``SCALE`` the reference draws two uniforms and
sets the (row, column) bit pair to (0,0), (0,1), (1,0), (1,1) with
probabilities A, B, C and D = 1 - A - B - C.  One uniform per level, cut
at A, A+B and A+B+C, gives the same joint distribution; it is drawn as a
32-bit integer, so each threshold sits within 2**-32 of the spec's.
Vertex labels then go through a seeded permutation of ``2**SCALE``, as
the spec requires.  The spec also shuffles the edge list; here every block
of edges is drawn from its own counter of the seed's Philox stream, so the
list is already in a random order and any slice of it can be made on its
own.

Self loops and repeated edges stay in the list, as in the reference
generator's output.
"""

from __future__ import annotations

import threading

import numpy as np

# edges per counter block: edge i comes from block i // CHUNK of the
# seed's Philox stream, so a slice never depends on where it starts
CHUNK = 1 << 16

_PERM_CACHE: dict = {}
_PERM_LOCK = threading.Lock()


def num_edges(params: dict) -> int:
    """Edges in one pass over the list: edgefactor x 2**SCALE."""
    return int(params["edgefactor"]) << int(params["SCALE"])


def _key(seed: int, stream: int) -> np.ndarray:
    return np.array([seed % (1 << 64), stream], np.uint64)


def _thresholds(params: dict):
    a, b, c = float(params["A"]), float(params["B"]), float(params["C"])
    if not (0 < a and 0 < b and 0 < c and a + b + c < 1):
        raise ValueError(f"Kronecker A/B/C out of range: {a}, {b}, {c}")
    return (
        np.uint32(int(a * 2**32)),
        np.uint32(int((a + b) * 2**32)),
        np.uint32(int((a + b + c) * 2**32)),
    )


def permutation(params: dict, seed: int) -> np.ndarray:
    """The seeded relabelling of the ``2**SCALE`` vertices."""
    scale = int(params["SCALE"])
    with _PERM_LOCK:
        hit = _PERM_CACHE.get((scale, seed))
        if hit is None:
            gen = np.random.Generator(np.random.Philox(key=_key(seed, 1)))
            hit = gen.permutation(1 << scale).astype(np.int32)
            _PERM_CACHE.clear()
            _PERM_CACHE[(scale, seed)] = hit
    return hit


def _block(params: dict, seed: int, block: int):
    """Unpermuted (row, column) ids of the CHUNK edges of one block."""
    scale = int(params["SCALE"])
    t_a, t_ab, t_abc = _thresholds(params)
    bits = np.random.Philox(
        key=_key(seed, 0), counter=np.array([0, 0, 0, block], np.uint64)
    )
    draws = bits.random_raw(scale * CHUNK // 2).view(np.uint32)
    draws = draws.reshape(scale, CHUNK)
    src = np.zeros(CHUNK, np.uint32)
    dst = np.zeros(CHUNK, np.uint32)
    for level in range(scale):
        u = draws[level]
        row = u >= t_ab
        col = (u >= t_abc) | ((u >= t_a) & ~row)
        src |= row.astype(np.uint32) << np.uint32(level)
        dst |= col.astype(np.uint32) << np.uint32(level)
    return src, dst


def raw_edges(params: dict, seed: int, start: int, count: int):
    """Edges ``[start, start + count)`` before the vertex permutation."""
    src = np.empty(count, np.uint32)
    dst = np.empty(count, np.uint32)
    pos = start
    while pos < start + count:
        block, off = divmod(pos, CHUNK)
        take = min(CHUNK - off, start + count - pos)
        s, d = _block(params, seed, block)
        src[pos - start : pos - start + take] = s[off : off + take]
        dst[pos - start : pos - start + take] = d[off : off + take]
        pos += take
    return src, dst


def edges(params: dict, seed: int, start: int, count: int):
    """Edges ``[start, start + count)`` of the list, as int32 ids in
    ``[0, 2**SCALE)``."""
    src, dst = raw_edges(params, seed, start, count)
    perm = permutation(params, seed)
    return perm[src], perm[dst]
