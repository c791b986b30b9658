"""Batched array union-find: the framework's hot kernel.

The reference's streaming Connected Components folds every edge through a
pointer-chasing, recursively path-compressing ``DisjointSet``
(summaries/DisjointSet.java:66-118) — inherently sequential, one edge at a time.
The TPU-native replacement operates on a dense ``parent: int32[C]`` array and
processes a whole edge micro-batch with scatter-min *hooking* of the batch's
endpoint roots (Shiloach–Vishkin style) and one pointer-doubling pass,
converging to the same fixed point: ``parent[v]`` is the minimum vertex id in
v's component.

All functions are pure and jittable; state threads through functionally.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def init_parent(capacity: int) -> jax.Array:
    """Every vertex its own singleton root."""
    return jnp.arange(capacity, dtype=jnp.int32)


# The counters of one union-find fold, in the order of its ``stats`` vector:
# hook rounds; whole-array (capacity-long) passes, which is 1 when some round
# hooked and 0 otherwise (derived from the rounds, not counted apart); and
# pointer-jumping steps, each a gather over a round's hooked roots.
UF_STATS = ("hook_rounds", "full_passes", "root_steps")


def compress(parent: jax.Array) -> jax.Array:
    """Full pointer-doubling until every entry points at its root.

    Replaces the recursive find+path-compression of DisjointSet.java:66-81 with a
    log-depth whole-array iteration.
    """

    def cond(state):
        p, changed = state
        return changed

    def body(state):
        p, _ = state
        p2 = p[p]
        return p2, jnp.any(p2 != p)

    p, _ = jax.lax.while_loop(cond, body, (parent, jnp.array(True)))
    return p


def jump_hooked(parent: jax.Array, hooked: jax.Array):
    """Point every vertex of ``hooked`` at its root, by pointer jumping
    over ``hooked`` alone; returns the parent and the steps made (gathers
    over ``hooked``).

    ``hooked`` must hold every non-root vertex its entries reach (as a
    hook round's ``hi`` array does: the vertices it hooked, each onto a
    root or another of them), so ``p[h] <- p[p[h]]`` over it halves every
    chain: log2 of the longest chain steps, each a gather and a scatter
    over ``hooked``, where a plain chase takes one gather per level.
    """
    up = parent[hooked]

    def cond(state):
        _, up, upup, _ = state
        return jnp.any(upup != up)

    def body(state):
        p, _, upup, steps = state
        p = p.at[hooked].set(upup)
        return p, upup, p[upup], steps + 1

    p, _, _, steps = jax.lax.while_loop(
        cond, body, (parent, up, parent[up], jnp.int32(2))
    )
    return p, steps


def fold_roots(
    parent: jax.Array,
    src: jax.Array,
    dst: jax.Array,
    mask: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Merge the components of every valid (src, dst) edge into a FULLY
    COMPRESSED ``parent`` (each entry its root); returns the parent, again
    fully compressed, and the fold's counters (``UF_STATS``).

    Window-local: the work scales with the batch, plus at most one pass
    over the whole array.

      entry:  read the endpoints' roots once (the 2E "slots")
      round:  while some edge's slot roots differ, hook
              parent[max(root_s, root_d)] <- min over edges (scatter-min),
              point the hooked roots at their roots by pointer jumping
              over them (``jump_hooked``), then gather the slots' roots
      exit:   if anything hooked, point every entry slot root at its final
              root, then one pass parent <- parent[parent]

    Only the entry slot roots are ever hooked (a hook writes a slot root
    onto a slot root, and resolving one stays among them), so after the
    exit write every vertex is within two steps of its root and the one
    pass compresses fully.  The hooks are those of hooking then fully
    compressing every round, so the fixed point is the same array, bit for
    bit: each entry the minimum root of its component.  Masked rows are
    turned into self-loops and cannot affect state.
    """
    if mask is not None:
        src = jnp.where(mask, src, 0)
        dst = jnp.where(mask, dst, 0)
    e = src.shape[0]
    roots0 = parent[jnp.concatenate([src, dst])]

    def unjoined(state):
        r = state[1]
        return jnp.any(r[:e] != r[e:])

    def hook(state):
        p, r, rounds, steps = state
        rs, rd = r[:e], r[e:]
        hi = jnp.maximum(rs, rd)
        p, n = jump_hooked(p.at[hi].min(jnp.minimum(rs, rd)), hi)
        return p, p[r], rounds + 1, steps + n

    zero = jnp.int32(0)
    p, roots, rounds, steps = jax.lax.while_loop(
        unjoined, hook, (parent, roots0, zero, zero)
    )

    def settle(p):
        # duplicate slot roots carry the same final root
        p = p.at[roots0].set(roots)
        return p[p]

    hooked = rounds > 0
    p = jax.lax.cond(hooked, settle, lambda p: p, p)
    return p, jnp.stack([rounds, hooked.astype(jnp.int32), steps])


def union_edges(
    parent: jax.Array,
    src: jax.Array,
    dst: jax.Array,
    mask: Optional[jax.Array] = None,
) -> jax.Array:
    """Merge the components of every valid (src, dst) edge in the batch.

    Equivalent fixed point to folding each edge through DisjointSet.union
    (summaries/DisjointSet.java:92-118), but order-free and batched.  Any
    parent-pointer forest: it is fully compressed first, then folded by
    ``fold_roots``.  Masked rows cannot affect state.
    """
    return fold_roots(compress(parent), src, dst, mask)[0]


def merge_parents(parent_a: jax.Array, parent_b: jax.Array) -> jax.Array:
    """Combine two union-find summaries over the same vertex space.

    The reference merges two DisjointSets by re-unioning every (elem -> parent)
    entry of the smaller into the larger (DisjointSet.java:127-131).  Array-form:
    treat b's pointers as edges (v, parent_b[v]) and apply them to a.  Since both
    arrays are total over [0, C), this is one batched union over C edges.
    """
    v = jnp.arange(parent_a.shape[0], dtype=jnp.int32)
    return union_edges(parent_a, v, parent_b, mask=None)


def mark_seen(
    seen: jax.Array,
    src: jax.Array,
    dst: jax.Array,
    mask: Optional[jax.Array] = None,
) -> jax.Array:
    """Mark every valid edge's endpoints as seen.

    ``seen`` distinguishes real components from untouched identity entries when
    enumerating components (DisjointSet's map only contains added elements,
    DisjointSet.java:40-46; a dense array must track membership explicitly).
    """
    if mask is None:
        mask = jnp.ones(src.shape, bool)
    seen = seen.at[jnp.where(mask, src, 0)].max(mask)
    return seen.at[jnp.where(mask, dst, 0)].max(mask)


def union_edges_with_seen(
    parent: jax.Array,
    seen: jax.Array,
    src: jax.Array,
    dst: jax.Array,
    mask: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The summary fold: ``fold_roots`` into a fully compressed ``parent``
    (every summary this package makes is one) plus ``mark_seen``; returns
    the parent, ``seen`` and the fold's counters (``UF_STATS``)."""
    parent, stats = fold_roots(parent, src, dst, mask)
    return parent, mark_seen(seen, src, dst, mask), stats


# ---------------------------------------------------------------------------
# Signed (parity) union-find — the bipartiteness summary.
# ---------------------------------------------------------------------------
#
# The reference's Candidates summary tracks per-vertex signs inside per-component
# maps and fails on sign conflicts (summaries/Candidates.java:61-139).  The
# array-native re-derivation uses the classic doubled-vertex construction: each
# vertex v becomes two nodes (2v = "v on side A", 2v+1 = "v on side B"); an edge
# (u, w) asserts u and w are on opposite sides, i.e. union(2u, 2w+1) and
# union(2u+1, 2w).  The graph is non-bipartite iff some vertex's two sides end up
# in the same component.  Same fixed point as Candidates' merge-with-sign-flip,
# with no nested maps.


def init_parity_parent(capacity: int) -> jax.Array:
    return init_parent(2 * capacity)


def parity_union_edges(
    parent2: jax.Array,
    src: jax.Array,
    dst: jax.Array,
    mask: Optional[jax.Array] = None,
) -> jax.Array:
    """Apply opposite-side constraints for a batch of edges to the doubled space."""
    if mask is not None:
        # masked rows become (0, 0) self-unions
        a1 = jnp.where(mask, 2 * src, 0)
        b1 = jnp.where(mask, 2 * dst + 1, 0)
        a2 = jnp.where(mask, 2 * src + 1, 0)
        b2 = jnp.where(mask, 2 * dst, 0)
    else:
        a1, b1, a2, b2 = 2 * src, 2 * dst + 1, 2 * src + 1, 2 * dst
    s = jnp.concatenate([a1, a2])
    d = jnp.concatenate([b1, b2])
    return union_edges(parent2, s, d)


def parity_conflicts(parent2: jax.Array, seen: jax.Array) -> jax.Array:
    """True where a seen vertex's two sides collapsed (odd cycle through v)."""
    c = parent2.shape[0] // 2
    even = parent2[2 * jnp.arange(c)]
    odd = parent2[2 * jnp.arange(c) + 1]
    return seen & (even == odd)


def is_bipartite(parent2: jax.Array, seen: jax.Array) -> jax.Array:
    return ~jnp.any(parity_conflicts(parent2, seen))
