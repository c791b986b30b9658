"""Exact triangle count of one window's edges, by degree ordering, on the device.

The dense MXU kernel (ops/pallas_triangles.py) needs the window's vertices
to fit a [K, K] adjacency, K <= 8192.  A skewed window of 2^21 edges has
~10^6 vertices and hubs of degree ~7,000, so a padded row per vertex would
be [V, d_max] (tens of GB) and an edge-by-edge [E, D, D] compare far more.
Here the count follows the window's *oriented wedges* instead:

1. canonicalise every edge to ``(min, max)``, sort, and drop self loops and
   repeats (the simple undirected graph);
2. sort the 2E endpoints: a vertex's endpoints form one run, whose length is
   its degree; sort them again by (degree, id) and number the runs: the
   vertex's rank, which also compacts the ids to ``[0, V)``;
3. orient every edge toward its higher-ranked end and sort by (tail, head):
   each vertex's out-neighbours form one row, ascending by rank.  The
   ordering bounds every out-degree by sqrt(2E) (44-46 on the Graph 500
   SCALE 23 windows, against a max degree of ~7,000);
4. a triangle x < y < z (by rank) is exactly one oriented wedge at x — the
   pair (y, z) of x's row, y first — whose closing edge y -> z exists.  Row
   entry ``e`` pairs with the entry ``j`` places after it for j = 1, 2, ...;
   sorting the entries by how many later entries their row holds makes the
   entries of step ``j`` a prefix, so the lanes evaluated are the oriented
   wedges themselves (Σ d+(d+ - 1) / 2), in tiles of ``TILE`` lanes.  Each
   lane binary-searches the head of its partner entry in its own head's row:
   one gather of a rank per step.

Everything is sorts, run numbering, scatters, gathers and compares over E or
2E entries; nothing is sized by the vertex capacity or by a degree.  The
largest out-degree sets the search's depth, ``bit_length(d+max)`` steps:
the out-degree *bucket* is a loop count, not a shape, so a window that moves
to a larger bucket runs the same executable — one compile per window size,
never a truncated row.

``count_stats`` returns one int32 vector (``STATS``): the count, the
oriented wedges and the compare slots evaluated as exact two-limb integers
(``LIMB_BITS`` each, as ``pallas_triangles`` splits its running total), then
the window's vertices, distinct edges and largest out-degree.  The count and
its counters come back to the host in one copy.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# lanes per tile of the wedge loop
TILE = 1 << 14

# exact integers travel as (low, high) int32 limbs: value = low + high << 30
LIMB_BITS = 30
_LIMB_MASK = (1 << LIMB_BITS) - 1

STATS = (
    "triangles_lo",
    "triangles_hi",
    "wedges_lo",
    "wedges_hi",
    "slots_lo",
    "slots_hi",
    "vertices",
    "edges",
    "max_out_degree",
)
_I = {name: i for i, name in enumerate(STATS)}

# a vertex id no window holds: padding, self loops and repeats sort last
_NONE = np.iinfo(np.int32).max


def _add(acc, x):
    """``acc`` (low, high) plus ``x`` (0 <= x < 2^30), carried exactly."""
    low = acc[0] + x
    return low & _LIMB_MASK, acc[1] + (low >> LIMB_BITS)


def _run_ids(keys):
    """Sorted ``keys`` numbered by run: per entry its run's number, and per
    run (indexed by number) its length."""
    n = keys.shape[0]
    head = jnp.concatenate([jnp.ones((1,), bool), keys[1:] != keys[:-1]])
    run = jnp.cumsum(head, dtype=jnp.int32) - 1
    at = jnp.where(head, run, n)
    first = (
        jnp.full((n + 1,), n, jnp.int32)
        .at[at]
        .set(jnp.arange(n, dtype=jnp.int32), mode="drop")
    )
    return run, first[1:] - first[:-1]


def _oriented_rows(src, dst, mask):
    """Steps 1-3: the window's distinct edges as (tail, head) ranks, the
    rank being a vertex's place in (degree, id) order and the tail the
    lower, sorted — each tail's row of out-neighbours, ascending — with
    unused entries ``_NONE`` at the end; and the counts of distinct edges
    and vertices."""
    n = src.shape[0]
    lo = jnp.minimum(src, dst)
    hi = jnp.maximum(src, dst)
    ok = mask & (lo != hi)
    lo = jnp.where(ok, lo, _NONE)
    hi = jnp.where(ok, hi, _NONE)
    lo, hi = lax.sort((lo, hi), num_keys=2)
    repeat = jnp.concatenate(
        [jnp.zeros((1,), bool), (lo[1:] == lo[:-1]) & (hi[1:] == hi[:-1])]
    )
    ok = (lo != _NONE) & ~repeat
    n_edges = jnp.sum(ok, dtype=jnp.int32)

    # a vertex's endpoints are one run of the sorted ends: the run's length
    # is its degree, the run's number its compact id (ids keep their order)
    ends = jnp.concatenate([jnp.where(ok, lo, _NONE), jnp.where(ok, hi, _NONE)])
    where_from = jnp.arange(2 * n, dtype=jnp.int32)
    ends, where_from = lax.sort((ends, where_from), num_keys=1)
    vertex, degree = _run_ids(ends)
    real = ends != _NONE
    n_vertices = jnp.max(jnp.where(real, vertex + 1, 0))
    # sorted again by (degree, compact id), a vertex's endpoints stay one
    # run, and the runs come in rank order
    degree = jnp.where(real, degree[vertex], _NONE)
    _, vertex, where_from = lax.sort((degree, vertex, where_from), num_keys=2)
    rank, _ = _run_ids(vertex)
    rank = jnp.zeros((2 * n,), jnp.int32).at[where_from].set(rank, unique_indices=True)
    ru, rv = rank[:n], rank[n:]

    # orient each edge toward its higher-ranked end
    tail = jnp.where(ok, jnp.minimum(ru, rv), _NONE)
    head = jnp.where(ok, jnp.maximum(ru, rv), _NONE)
    tail, head = lax.sort((tail, head), num_keys=2)
    return tail, head, n_edges, n_vertices


def count_stats(src, dst, mask, *, tile: int = TILE):
    """The ``STATS`` vector of the window ``src``/``dst`` (int32 ids, any
    order, repeats and self loops allowed) where ``mask`` is set."""
    n = src.shape[0]
    if n >= 1 << 29:
        raise ValueError(f"window of {n} entries: the limbs need fewer than 2^29")
    with jax.named_scope("oriented_triangles"):
        tail, head, n_edges, n_vertices = _oriented_rows(
            src.astype(jnp.int32), dst.astype(jnp.int32), mask
        )
        idx = jnp.arange(n, dtype=jnp.int32)
        valid = tail != _NONE
        # each vertex's row (ranks < 2n): where it starts, its length
        at = jnp.where(valid, tail, 2 * n)
        row_off = jnp.full((2 * n,), n, jnp.int32).at[at].min(idx, mode="drop")
        row_len = jnp.zeros((2 * n,), jnp.int32).at[at].add(1, mode="drop")
        t_ = jnp.where(valid, tail, 0)
        later = jnp.where(valid, row_off[t_] + row_len[t_] - 1 - idx, 0)
        max_out = jnp.max(row_len)
        h = jnp.where(valid, head, 0)
        off, length = row_off[h], jnp.where(valid, row_len[h], 0)
        # entries by later-entries descending: step j's lanes are a prefix
        neg_later, entry, off, length = lax.sort(
            (-later, idx, off, length), num_keys=1
        )
        t = min(tile, max(1, 1 << (n - 1).bit_length()))
        pad = jnp.zeros((t,), jnp.int32)
        entry, off, length = (jnp.concatenate([a, pad]) for a in (entry, off, length))
        steps = 32 - lax.clz(max_out)  # binary-search depth: the bucket
        lane = jnp.arange(t, dtype=jnp.int32)

        def lanes(j):
            return jnp.searchsorted(neg_later, -j, side="right").astype(jnp.int32)

        def tile_hits(j, i0, n_lanes):
            # lane: entry e of a row and the entry j places after it; the
            # pair closes a triangle if the second's head is in the first
            # head's row (binary search: the row ascends by rank)
            want = jnp.take(head, lax.dynamic_slice(entry, (i0,), (t,)) + j, mode="clip")
            base = lax.dynamic_slice(off, (i0,), (t,))
            left = lax.dynamic_slice(length, (i0,), (t,))
            left = jnp.where(i0 + lane < n_lanes, left, 0)

            def search(_, c):
                base, left, found = c
                half = left >> 1
                mid = base + half
                got = jnp.take(head, mid, mode="clip")
                live = left > 0
                less = got < want
                found = found | (live & (got == want))
                base = jnp.where(live & less, mid + 1, base)
                left = jnp.where(live, jnp.where(less, left - half - 1, half), 0)
                return base, left, found

            _, _, found = lax.fori_loop(
                0, steps, search, (base, left, jnp.zeros((t,), bool))
            )
            return jnp.sum(found, dtype=jnp.int32)

        def step(c):
            j, n_lanes, tri, wedges, slots = c

            def one_tile(ct):
                i0, tri, slots = ct
                tri = _add(tri, tile_hits(j, i0, n_lanes))
                slots = _add(slots, t * steps)
                return i0 + t, tri, slots

            _, tri, slots = lax.while_loop(
                lambda ct: ct[0] < n_lanes, one_tile, (jnp.int32(0), tri, slots)
            )
            wedges = _add(wedges, n_lanes)
            return j + 1, lanes(j + 1), tri, wedges, slots

        zero = (jnp.int32(0), jnp.int32(0))
        _, _, tri, wedges, slots = lax.while_loop(
            lambda c: c[1] > 0, step, (jnp.int32(1), lanes(1), zero, zero, zero)
        )
        return jnp.stack(
            [*tri, *wedges, *slots, n_vertices, n_edges, max_out]
        ).astype(jnp.int32)


def limbs(stats, name: str) -> int:
    """The exact integer ``name`` (``triangles``, ``wedges``, ``slots``)
    from a host copy of the ``STATS`` vector."""
    lo = int(stats[_I[name + "_lo"]])
    hi = int(stats[_I[name + "_hi"]])
    return lo + (hi << LIMB_BITS)


def counters(stats) -> dict:
    """A host copy of the ``STATS`` vector as the window's counters: the
    vertices, distinct edges, oriented wedges, compare slots evaluated
    (padding included) and the out-degree bucket (the power of two the
    search depth covers)."""
    max_out = int(stats[_I["max_out_degree"]])
    return {
        "tri_vertices": int(stats[_I["vertices"]]),
        "tri_edges": int(stats[_I["edges"]]),
        "tri_wedges": limbs(stats, "wedges"),
        "tri_slots": limbs(stats, "slots"),
        "tri_bucket": 1 << max_out.bit_length(),
    }
