"""Union-find summary with API parity to the reference's DisjointSet.

Reference: summaries/DisjointSet.java (makeSet :53, find :66-81, union :92-118,
merge :127-131, toString :134-150).  Here the summary *is* a pair of dense arrays
(``parent: int32[C]``, ``seen: bool[C]``) updated by the batched kernel in
ops/unionfind.py; this class is a thin host-facing wrapper providing the
reference's object API for algorithms, sinks, and tests.  As a pytree-of-arrays
it is directly checkpointable and psum/all_gather-combinable (fixing the
reference's un-checkpointed-state gap, SURVEY.md §5.3-4).
"""

from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from gelly_streaming_tpu.ops import unionfind as uf

# Compiled once per shape: the host wrappers below are called per edge in tests
# and per batch in pipelines; eager dispatch of the lax loops is prohibitive.
# Deliberately raw jax.jit: these executables back ConnectedComponents' fold
# chain, and the process-global LRU may evict them mid-stream under multi-job
# cache churn, which reorders async-plane dispatch against in-flight panes.
# Module-level jits pin them for the process lifetime instead.
_union_edges_seen_j = jax.jit(uf.union_edges_with_seen)  # graft: disable=RAWJIT — pinned for process lifetime, see above
_merge_parents_j = jax.jit(uf.merge_parents)  # graft: disable=RAWJIT — pinned for process lifetime, see above
_compress_j = jax.jit(uf.compress)  # graft: disable=RAWJIT — pinned for process lifetime, see above


class DisjointSet:
    """Host wrapper over (parent, seen) arrays; one component = one min-root.

    ``parent`` is fully compressed (each entry its root), as every summary
    this package makes is: the batched union folds into it window-locally
    (``uf.union_edges_with_seen``) and keeps it so."""

    def __init__(self, capacity: int, parent=None, seen=None):
        self.capacity = capacity
        self.parent = uf.init_parent(capacity) if parent is None else parent
        self.seen = (
            jnp.zeros((capacity,), dtype=bool) if seen is None else seen
        )

    # ---- mutation (functional core, in-place wrapper) -----------------------

    def union(self, a: int, b: int) -> None:
        """Single-edge union (reference: DisjointSet.java:92-118)."""
        self.union_batch(
            jnp.asarray([a], jnp.int32), jnp.asarray([b], jnp.int32)
        )

    def union_batch(self, src, dst, mask: Optional[jnp.ndarray] = None) -> None:
        """Batched union of a whole edge micro-batch (the TPU hot path)."""
        self.parent, self.seen, _ = _union_edges_seen_j(
            self.parent, self.seen, src, dst, mask
        )

    def merge(self, other: "DisjointSet") -> None:
        """Combine with another summary (reference: DisjointSet.java:127-131)."""
        self.parent = _merge_parents_j(self.parent, other.parent)
        self.seen = self.seen | other.seen

    # ---- queries ------------------------------------------------------------

    def find(self, v: int) -> int:
        """Root (minimum member id) of v's component (DisjointSet.java:66-81)."""
        p = np.asarray(_compress_j(self.parent))
        return int(p[v])

    def get_matches(self) -> Dict[int, int]:
        """vertex -> parent for all seen vertices (DisjointSet.java:40-46)."""
        p = np.asarray(_compress_j(self.parent))
        seen = np.asarray(self.seen)
        return {int(v): int(p[v]) for v in np.nonzero(seen)[0]}

    def components(self) -> Dict[int, List[int]]:
        """root -> sorted member list, for seen vertices only."""
        p = np.asarray(_compress_j(self.parent))
        seen = np.asarray(self.seen)
        comps: Dict[int, List[int]] = {}
        for v in np.nonzero(seen)[0]:
            comps.setdefault(int(p[v]), []).append(int(v))
        return comps

    def __str__(self) -> str:
        """Mirror the Java Map<R, List<R>> rendering (DisjointSet.java:134-150),
        e.g. ``{1=[1, 2, 3, 5], 6=[6, 7], 8=[8, 9]}``."""
        comps = self.components()
        parts = [
            f"{root}=[{', '.join(str(v) for v in members)}]"
            for root, members in sorted(comps.items())
        ]
        return "{" + ", ".join(parts) + "}"
