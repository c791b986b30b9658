"""Process-global AOT executable cache for the streaming hot loops.

Every dispatch plane in the framework used to call ``jax.jit`` at its own
call site, holding the compiled executable in whatever object happened to
own the closure (an EdgeStream, an OutputStream, a SummaryAggregation
instance).  Re-creating any of those — a new stream over the same arrays, a
fresh descriptor per window, the bench's chunk loop — silently retraced and
recompiled the identical kernel: seconds per compile on a TPU, repeated for
every (kernel, shape) the stream runtime produces.

This module is the single home for those executables.  A cache entry is
keyed by a caller-supplied *kernel identity* (a hashable tuple naming the
kernel and everything its traced behavior depends on: stage tuples, configs,
batch shapes, wire widths); the entry owns ONE ``jax.jit`` callable, so every
stream/descriptor/window that resolves to the same key shares the compiled
executables for all argument shapes.  The cache also meters itself:

  * ``key_hits`` / ``key_misses`` — entry-level reuse (a miss builds and
    jits a new callable; a hit reuses executables across streams).
  * ``compiles`` / ``compile_time_s`` — actual XLA trace+compile events,
    detected via the jitted callable's own signature cache growth, with the
    wall time of the compiling call attributed to compilation.
  * ``recompiles()`` — the retrace guard: number of compile events beyond
    the first for the same (kernel identity, abstract-signature) pair.  A
    healthy streaming run compiles each bucketed shape ONCE per kernel;
    anything above zero means the same kernel+shape was traced again —
    eviction churn of a hot entry, or a jit-internal retrace.  (Unstable
    kernel identities — fresh closures per call — surface as ``key_misses``
    growth instead: distinct keys are distinct kernels by definition.)

Counters are exposed through ``stats()`` here and re-exported by
``utils/metrics.py`` next to the throughput meters.

Key discipline for MESH kernels (the owner-sharded summary plane): a
``jax.sharding.Mesh`` object is not a guaranteed-stable identity across
re-created runners, so sharded shard_map steps key on
``parallel.mesh.mesh_cache_key(mesh)`` — device (platform, id) pairs plus
axis names — alongside the descriptor's ``cache_token``, the frozen config,
and every pow2-bucketed capacity the trace bakes in (pane cap, delta-buffer
cap, wire width).  That puts the whole mesh plane under this cache's
retrace guard: rebuilding a MeshAggregationRunner over the same devices
resolves to the same executables, and ``recompiles()`` stays 0 across
same-bucket panes (tests/test_sharded_state.py pins it).
"""

from __future__ import annotations

import os
import threading
import time
from typing import Any, Callable, Dict, Optional, Tuple

# A LEAF of the runtime's lock order: builds and jit-traces run OUTSIDE
# the lock by design (they may import/trace arbitrarily), so nothing
# here may take a runtime lock; the scheduler, holding the manager lock,
# may reach the cache counters but never the reverse.
# lock-order: manager._lock < compile_cache._LOCK
_LOCK = threading.RLock()
# Shared across every dispatch thread (sync loops, the async pipeline's
# dispatch + drain, the mesh runners): the ``# guarded-by: _LOCK``
# annotations below are enforced by the lock-discipline analyzer pass
# (gelly_streaming_tpu/analysis/locks.py).
_ENTRIES: "Dict[Any, _CachedFn]" = {}  # guarded-by: _LOCK
_CAPACITY = 128

_KEY_HITS = 0  # guarded-by: _LOCK
_KEY_MISSES = 0  # guarded-by: _LOCK
# (kernel cache key, abstract signature) -> number of XLA compiles observed;
# >1 for any pair means the SAME kernel+shape was traced more than once (an
# eviction rebuild or a jit-internal retrace) — distinct kernels sharing
# shapes never collide here.  Bounded (oldest-first eviction) so per-call
# closure keys from long-running processes cannot pin memory forever.
_COMPILE_LOG: Dict[Tuple[Any, Any], int] = {}  # guarded-by: _LOCK
_COMPILE_LOG_CAP = 4096
_COMPILES = 0  # guarded-by: _LOCK
_COMPILE_TIME_S = 0.0  # guarded-by: _LOCK
_DISPATCH_HITS = 0  # guarded-by: _LOCK


def _abstract_sig(args, kwargs):
    """Shape/dtype signature of a call's array leaves (hashable).

    Computed ONLY on compile events (cache growth), so the cost never lands
    on the steady-state dispatch path.
    """
    import jax

    def leaf_sig(x):
        shape = getattr(x, "shape", None)
        dtype = getattr(x, "dtype", None)
        if shape is None or dtype is None:
            return repr(type(x))
        return (tuple(shape), str(dtype))

    leaves = jax.tree.leaves((args, kwargs))
    return tuple(leaf_sig(leaf) for leaf in leaves)


class _CachedFn:
    """A jitted callable that meters its own trace/compile events.

    ``jax.jit`` already caches one executable per abstract signature; what
    it cannot see is the same LOGICAL kernel being re-jitted under a fresh
    closure.  The entry detects real compiles by watching the jit signature
    cache grow across a call and logs them under the entry's label, which is
    what makes ``recompiles()`` a process-wide retrace guard.
    """

    __slots__ = (
        "_jit",
        "label",
        "log_key",
        "compiles",
        "compile_time_s",
        "calls",
    )

    def __init__(self, fn: Callable, label: Any, jit_kwargs: dict, log_key: Any = None):
        import jax

        self._jit = jax.jit(fn, **jit_kwargs)
        self.label = label
        self.log_key = log_key if log_key is not None else label
        self.compiles = 0
        self.compile_time_s = 0.0
        self.calls = 0

    def _record_compile(self, n: int, dt: float, sig) -> None:
        global _COMPILES, _COMPILE_TIME_S
        with _LOCK:
            self.compiles += n
            self.compile_time_s += dt
            _COMPILES += n
            _COMPILE_TIME_S += dt
            _COMPILE_LOG[(self.log_key, sig)] = (
                _COMPILE_LOG.get((self.log_key, sig), 0) + 1
            )
            while len(_COMPILE_LOG) > _COMPILE_LOG_CAP:
                _COMPILE_LOG.pop(next(iter(_COMPILE_LOG)))

    def __call__(self, *args, **kwargs):
        global _DISPATCH_HITS
        self.calls += 1
        # the jit's own signature cache (a private hook of the installed
        # jax) grows exactly when this call traced and compiled
        before = self._jit._cache_size()
        t0 = time.perf_counter()
        out = self._jit(*args, **kwargs)
        after = self._jit._cache_size()
        if after > before:
            self._record_compile(
                after - before,
                time.perf_counter() - t0,
                _abstract_sig(args, kwargs),
            )
        else:
            with _LOCK:
                _DISPATCH_HITS += 1
        return out

    def lower(self, *args, **kwargs):
        """Expose AOT lowering for callers that want to pre-compile."""
        return self._jit.lower(*args, **kwargs)


def cached_jit(
    key: Any,
    build: Callable[[], Callable],
    *,
    static_argnums=None,
    donate_argnums=None,
    label: Optional[str] = None,
) -> _CachedFn:
    """The process-global executable for kernel identity ``key``.

    ``build()`` produces the python callable to jit — invoked only on a key
    miss, so hot paths can pass cheap closure factories.  ``key`` must be
    hashable and must determine the traced behavior completely (include
    stage tuples, configs, static shapes, widths — anything the closure
    reads).  ``label`` names the kernel family for the retrace guard;
    defaults to the first element of a tuple key.

    Lifetime note: entries hold STRONG references to their key components
    (user callables, stage objects) and executables, bounded by the cache
    capacity with LRU eviction — callers whose keys are per-call closures
    (never re-hit) simply churn the cold end of the cache; stable keys (the
    streaming hot loops) stay resident.
    """
    global _KEY_HITS, _KEY_MISSES
    with _LOCK:
        entry = _ENTRIES.get(key)
        if entry is not None:
            _KEY_HITS += 1
            # LRU: hot kernels move to the back so capacity pressure from
            # one-shot keys (per-call closures) evicts cold entries, not the
            # streaming hot loop (an evicted+rebuilt kernel is a REAL
            # recompile and would rightly trip the retrace guard)
            _ENTRIES[key] = _ENTRIES.pop(key)
            return entry
        _KEY_MISSES += 1
    # Build + jit outside the lock: builds may import/trace arbitrarily.
    jit_kwargs = {}
    if static_argnums is not None:
        jit_kwargs["static_argnums"] = static_argnums
    if donate_argnums is not None:
        jit_kwargs["donate_argnums"] = donate_argnums
    if label is None:
        label = key[0] if isinstance(key, tuple) and key else repr(key)
    fresh = _CachedFn(build(), label, jit_kwargs, log_key=key)
    with _LOCK:
        entry = _ENTRIES.get(key)
        if entry is not None:  # lost a benign race; keep the first
            return entry
        while len(_ENTRIES) >= _CAPACITY:
            _ENTRIES.pop(next(iter(_ENTRIES)))
        _ENTRIES[key] = fresh
    return fresh


def recompiles() -> int:
    """Compile events beyond the first per (kernel identity, signature):
    the retrace count a healthy streaming process keeps at zero."""
    with _LOCK:
        return sum(c - 1 for c in _COMPILE_LOG.values() if c > 1)


def stats() -> dict:
    """Process-wide cache counters (see module docstring)."""
    with _LOCK:
        return {
            "entries": len(_ENTRIES),
            "key_hits": _KEY_HITS,
            "key_misses": _KEY_MISSES,
            "compiles": _COMPILES,
            "compile_time_s": round(_COMPILE_TIME_S, 4),
            "dispatch_hits": _DISPATCH_HITS,
            "recompiles": recompiles(),
        }


def label_stats(label: Any) -> dict:
    """Per-kernel-family counters: every live entry whose ``label`` matches,
    summed.  The fused-dispatch plane uses this to report the shared
    superpane executable's call/compile economy separately from the
    process-wide totals (one cohort dispatch = one ``calls`` tick here,
    however many tenant rows it folded)."""
    with _LOCK:
        entries = [e for e in _ENTRIES.values() if e.label == label]
        return {
            "entries": len(entries),
            "calls": sum(e.calls for e in entries),
            "compiles": sum(e.compiles for e in entries),
            "compile_time_s": round(
                sum(e.compile_time_s for e in entries), 4
            ),
        }


def reset_stats() -> None:
    """Zero the counters (entries and their executables stay cached)."""
    global _KEY_HITS, _KEY_MISSES, _COMPILES, _COMPILE_TIME_S, _DISPATCH_HITS
    with _LOCK:
        _KEY_HITS = _KEY_MISSES = _COMPILES = _DISPATCH_HITS = 0
        _COMPILE_TIME_S = 0.0
        _COMPILE_LOG.clear()
        for e in _ENTRIES.values():
            e.compiles = 0
            e.compile_time_s = 0.0
            e.calls = 0


def clear() -> None:
    """Drop every cached executable AND the counters (tests only: compiled
    kernels are expensive to rebuild)."""
    with _LOCK:
        _ENTRIES.clear()
    reset_stats()


# <checkout>/.jax_cache: a fixed path, since the path is part of what the
# persistent cache keys on (git ignores it)
PERSISTENT_CACHE_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)


def use_persistent_cache() -> None:
    """Keep XLA's compiled programs across processes (entry points call
    this before their first compile).  ``JAX_COMPILATION_CACHE_DIR``, when
    set, is JAX's own choice and wins; otherwise the cache lives at
    ``PERSISTENT_CACHE_DIR``."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax

    jax.config.update("jax_compilation_cache_dir", PERSISTENT_CACHE_DIR)
