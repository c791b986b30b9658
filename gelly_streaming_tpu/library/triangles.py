"""Triangle counting: windowed exact and insertion-only streaming exact.

Window variant — reference example/WindowTriangles.java:50-65: slice(ALL) ->
per-vertex candidate wedges (O(d^2), :82-115) -> keyBy(candidate edge) window
join against real edges (:118-139) -> all-window sum.  The TPU-native
re-design skips the wedge materialization entirely: per closed pane it builds
the deduped undirected CSR and counts, for every canonical edge (u, v), the
common neighbors |N(u) & N(v)| with one [E, D, D] masked equality reduction —
each triangle is counted once per its three edges, so count = sum / 3.  Same
result, no candidate shuffle.

Streaming variant — reference example/ExactTriangleCount.java:43-56
(KDD'16-style single pass): buildNeighborhood + canonical edges + stateful
neighborhood intersection emitting per-vertex and global counter updates
(:74-134).  Here the state is the device NeighborTable plus dense counter
arrays; each edge's intersection is a masked row comparison, applied in batch
arrival order via lax.scan (intersections must see the adjacency as of the
edge's arrival).
"""

from __future__ import annotations

from typing import Iterator, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from gelly_streaming_tpu.core.config import StreamConfig
from gelly_streaming_tpu.core.output import OutputStream
from gelly_streaming_tpu.core.types import EdgeDirection
from gelly_streaming_tpu.core.windows import (
    stream_panes,
    validate_slide,
    windowed_panes,
)
from gelly_streaming_tpu.ops import neighbors as nbr_ops
from gelly_streaming_tpu.ops import pallas_triangles


# ---------------------------------------------------------------------------
# Windowed exact count
# ---------------------------------------------------------------------------


# Panes whose compacted vertex count fits this bound use the dense MXU kernel
# (ops/pallas_triangles.py) — the largest K it compiles for the chip; larger
# panes take the padded-CSR path.  On the CPU the kernel runs in the Pallas
# interpreter (slow), so there the dense path stays test-sized.
DENSE_PANE_MAX_VERTICES = pallas_triangles.MAX_K
DENSE_PANE_MAX_VERTICES_INTERPRET = 512


def _dense_pane_bound() -> int:
    return (
        DENSE_PANE_MAX_VERTICES_INTERPRET
        if pallas_triangles._use_interpret()
        else DENSE_PANE_MAX_VERTICES
    )


def _pane_prepare(pane):
    """Host side of a pane submission: classify + pack, NO device calls.

    Returns ``(meta, host_arrays)`` fit for the prefetching pipeline
    (io/wire.py Prefetcher): the transfer thread device_puts
    ``host_arrays`` and ``_pane_dispatch`` turns the pair into an async
    count handle.  Dense-eligible panes ship the 4 B/edge packed wire form
    (ops/pallas_triangles.pack_pane); sparse id spaces are compacted here
    (the host work overlaps the previous pane's transfer/compute)."""
    src, dst = pane
    if len(src) == 0:
        return ("const", 0), None
    max_id = int(max(src.max(), dst.max()))
    if max_id < _dense_pane_bound():
        # Ids already fit the dense kernel: ship packed words and let the
        # device scatter canonicalize/dedup (no host unique).
        w, n = pallas_triangles.pack_pane(
            src.astype(np.int32), dst.astype(np.int32)
        )
        return ("packed", max_id + 1), (w, n)
    # Sparse id space: compact vertices on the host first.
    lo = np.minimum(src, dst)
    hi = np.maximum(src, dst)
    keep = lo != hi
    pairs = np.unique(np.stack([lo[keep], hi[keep]], axis=1), axis=0)
    if len(pairs) == 0:
        return ("const", 0), None
    u, v = pairs[:, 0].astype(np.int32), pairs[:, 1].astype(np.int32)
    verts, inv = np.unique(np.concatenate([u, v]), return_inverse=True)
    cu, cv = inv[: len(u)].astype(np.int32), inv[len(u) :].astype(np.int32)
    k_n = len(verts)
    if k_n <= _dense_pane_bound():
        w, n = pallas_triangles.pack_pane(cu, cv)
        return ("packed", k_n), (w, n)
    deg = np.bincount(np.concatenate([cu, cv]), minlength=k_n)
    d_max = int(deg.max())
    return ("csr", k_n, d_max), (cu, cv)


def _pane_dispatch(meta, arrays):
    """Device side: dispatch a prepared pane, returning an async handle."""
    if meta[0] == "const":
        return ("const", meta[1])
    if meta[0] == "packed":
        w, n = arrays
        return (
            "halves",
            pallas_triangles.pane_triangles_submit_packed(w, n, meta[1]),
        )
    _, k_n, d_max = meta
    cu, cv = arrays
    return ("scalar", _count_kernel(jnp.asarray(cu), jnp.asarray(cv), k_n, d_max))


def _pane_triangle_submit(src: np.ndarray, dst: np.ndarray):
    """Upload + dispatch a pane's triangle count without waiting.

    Returns an opaque handle for ``_pane_triangle_finish``; splitting the two
    lets consecutive panes pipeline (the next pane's transfer and compute run
    while this one's scalar rides the readback RTT home).
    """
    meta, arrays = _pane_prepare((src, dst))
    return _pane_dispatch(meta, arrays)


def _pane_triangle_finish(handle) -> int:
    """Blocking fetch of a submitted pane count."""
    kind, payload = handle
    if kind == "const":
        return payload
    if kind == "halves":
        return pallas_triangles.triangles_from_halves(payload)
    return int(payload)


def _pane_triangle_count(src: np.ndarray, dst: np.ndarray) -> int:
    """Exact triangles among a pane's edges (host orchestration, device count)."""
    return _pane_triangle_finish(_pane_triangle_submit(src, dst))


def pipelined_pane_counts(
    panes, recorder=None, warmup: int = 0, depth: int = 2, device_recorder=None
):
    """Triangle counts for a sequence of panes with submit/readback overlap.

    The sequential loop pays (upload + compute + readback-RTT) per pane.
    Here up to ``depth`` panes are in flight: pane k's scalar rides the readback link
    home while pane k+1 transfers and computes, so steady-state per-pane
    latency approaches max(upload + compute, RTT) instead of their sum.

    ``panes``: iterable of (src, dst) numpy id arrays.  ``recorder``: optional
    WindowLatencyRecorder; per pane, close = submission time, emit = host
    fetch completion (panes with index < ``warmup`` are not recorded —
    compile/first-touch).  Returns the list of counts in pane order.

    Latency accounting is per *window*: with pipelining a pane's measured
    close->result interval includes the next pane's submission — that is the
    steady-state cost a continuously sliced stream actually observes
    (WindowTriangles.java:60-65 panes close back-to-back the same way).

    The host pack/compaction and the device upload run on the Prefetcher's
    two background threads (io/wire.py), so a pane's 4 B/edge wire transfer
    hides under the previous pane's kernel: the measured latency is
    dispatch + MXU compute + readback, not the upload.

    ``device_recorder`` (optional WindowLatencyRecorder) captures the
    close -> DEVICE-completion interval separately from ``recorder``'s
    close -> host-visible-result interval.  The two differ by the device->
    host result delivery, which no pipelining removes, while pane
    *throughput* still pipelines (the async readback of pane k rides under
    panes k+1..).
    """
    import time as _time

    import jax as _jax

    from gelly_streaming_tpu.io.wire import Prefetcher

    counts = []
    pending = []  # (index, t_close, handle)
    # A pane "closes" when it ENTERS the Prefetcher — so the recorded
    # latency covers host pack/compaction + upload + dispatch + compute (+
    # readback for ``recorder``), not just the post-upload tail.  Caveat:
    # with panes arriving back-to-back (as in the bench) the pack thread pulls ahead,
    # so a pane's measured interval also includes its residence in the
    # depth-bounded prefetch queues — the number is the SATURATED-pipeline
    # latency and scales with ``depth``; a stream whose windows close slower
    # than the pipeline drains sees no queueing and a smaller number.
    enter_t = {}

    def stamped():
        for k, p in enumerate(panes):
            enter_t[k] = _time.perf_counter()
            yield p

    def drain_one():
        k, t_close, handle = pending.pop(0)
        if device_recorder is not None and handle[0] != "const":
            _jax.block_until_ready(handle[1])
            if k >= warmup:
                device_recorder.record(
                    (_time.perf_counter() - t_close) * 1e3
                )
        counts.append(_pane_triangle_finish(handle))
        if recorder is not None and k >= warmup:
            recorder.record((_time.perf_counter() - t_close) * 1e3)

    with Prefetcher(stamped(), _pane_prepare, depth=max(depth, 2)) as pf:
        for k, (meta, dev) in enumerate(pf):
            t_close = enter_t.pop(k)
            pending.append((k, t_close, _pane_dispatch(meta, dev)))
            if len(pending) >= depth:
                drain_one()
    while pending:
        drain_one()
    return counts


from gelly_streaming_tpu.core import compile_cache


def _superpane_count_fn(k: int, e_pad: int, num_vertices: int, max_deg: int):
    """Compiled K-pane triangle counter: one vmapped masked-CSR dispatch
    over ``k`` panes' canonical edges (padded to shared static shapes) —
    the superbatch form of the per-pane ``_count_kernel`` dispatch.  Exact:
    per pane it is the same |N(u) & N(v)| equality reduction, with padding
    rows masked out of both the insert and the reduction."""
    from gelly_streaming_tpu.core import compile_cache

    def make():
        def one(u, v, ok):
            table = nbr_ops.init_table(num_vertices, max_deg)
            both_src = jnp.concatenate([u, v])
            both_dst = jnp.concatenate([v, u])
            table = nbr_ops.insert_batch(
                table, both_src, both_dst, jnp.concatenate([ok, ok])
            )
            rows_u, valid_u = nbr_ops.gather_rows(table, u)
            rows_v, valid_v = nbr_ops.gather_rows(table, v)
            eq = (
                (rows_u[:, :, None] == rows_v[:, None, :])
                & valid_u[:, :, None]
                & valid_v[:, None, :]
                & ok[:, None, None]
            )
            return jnp.sum(eq.astype(jnp.int32)) // 3

        return jax.vmap(one)

    return compile_cache.cached_jit(
        ("superpane_triangles", k, e_pad, num_vertices, max_deg), make
    )


def _superpane_canonical(pane_edges):
    """Canonicalize one pane's edges for the masked-CSR counter: dedup'd
    undirected (lo, hi) pairs, self-loops dropped, ids COMPACTED to the
    pane's vertex set (the same host prep as _pane_prepare's CSR path)."""
    src, dst = pane_edges
    if len(src) == 0:
        return None
    lo = np.minimum(src, dst)
    hi = np.maximum(src, dst)
    keep = lo != hi
    pairs = np.unique(np.stack([lo[keep], hi[keep]], axis=1), axis=0)
    if len(pairs) == 0:
        return None
    u, v = pairs[:, 0].astype(np.int32), pairs[:, 1].astype(np.int32)
    verts, inv = np.unique(np.concatenate([u, v]), return_inverse=True)
    cu = inv[: len(u)].astype(np.int32)
    cv = inv[len(u) :].astype(np.int32)
    deg = np.bincount(np.concatenate([cu, cv]), minlength=len(verts))
    return cu, cv, len(verts), int(deg.max())


def _superbatched_window_counts(panes, k: int):
    """(count, max_timestamp) per pane, up to ``k`` panes per dispatch.

    Pane boundaries live in the stacked leading axis; shapes are shared
    per group (bucketed powers of two), so successive groups of similar
    panes reuse executables via the compile cache.
    """
    from gelly_streaming_tpu.core.windows import group_panes

    # keep_empty: this consumer emits (0, max_timestamp) even for panes
    # with no edges, exactly as the per-pane dispatch path does
    for group in group_panes(iter(panes), k, keep_empty=True):
        prepped = [_superpane_canonical((p.src, p.dst)) for p in group]
        live = [i for i, pr in enumerate(prepped) if pr is not None]
        counts = [0] * len(group)
        if live:
            e_pad = max(1, 1 << (max(len(prepped[i][0]) for i in live) - 1).bit_length())
            n_v = max(1, 1 << (max(prepped[i][2] for i in live) - 1).bit_length())
            d_max = max(1, 1 << (max(prepped[i][3] for i in live) - 1).bit_length())
            # pow2 row bucket (matching the docstring + the aggregation
            # path): varying group occupancy must not mint new compiled
            # variants per count — extra rows are fully masked, count 0
            kk = max(1, 1 << (len(live) - 1).bit_length())
            u = np.zeros((kk, e_pad), np.int32)
            v = np.zeros((kk, e_pad), np.int32)
            ok = np.zeros((kk, e_pad), bool)
            for row, i in enumerate(live):
                cu, cv, _, _ = prepped[i]
                u[row, : len(cu)] = cu
                v[row, : len(cv)] = cv
                ok[row, : len(cu)] = True
            fn = _superpane_count_fn(kk, e_pad, n_v, d_max)
            out = np.asarray(fn(jnp.asarray(u), jnp.asarray(v), jnp.asarray(ok)))
            for row, i in enumerate(live):
                counts[i] = int(out[row])
        for i, pane in enumerate(group):
            yield counts[i], pane.max_timestamp


def _count_kernel_impl(u: jax.Array, v: jax.Array, num_vertices: int, max_deg: int):
    """sum over edges |N(u) & N(v)| / 3 with a padded-CSR equality reduction."""
    e = u.shape[0]
    table = nbr_ops.init_table(num_vertices, max_deg)
    both_src = jnp.concatenate([u, v])
    both_dst = jnp.concatenate([v, u])
    table = nbr_ops.insert_batch(
        table, both_src, both_dst, jnp.ones((2 * e,), bool)
    )
    rows_u, valid_u = nbr_ops.gather_rows(table, u)  # [E, D]
    rows_v, valid_v = nbr_ops.gather_rows(table, v)
    eq = (
        (rows_u[:, :, None] == rows_v[:, None, :])
        & valid_u[:, :, None]
        & valid_v[:, None, :]
    )
    return jnp.sum(eq.astype(jnp.int32)) // 3


# shared executable for the per-pane count: (num_vertices, max_deg) are
# pow2-bucketed by the caller, so each bucket compiles once process-wide
_count_kernel = compile_cache.cached_jit(
    ("tri_count_kernel",), lambda: _count_kernel_impl, static_argnums=(2, 3)
)


def window_triangles(
    stream, window_ms: int, slide_ms: "int | None" = None
) -> OutputStream:
    """(triangle_count, window_max_timestamp) per closed pane
    (output shape of WindowTriangles.java:60-65's final sum).

    Panes pipeline one deep: pane k+1's upload/compute is submitted before
    pane k's count is fetched, hiding the readback RTT behind device work.
    ``slide_ms`` (must divide ``window_ms``) counts sliding windows via
    pane-sharing (core/windows.sliding_panes) — beyond the tumbling-only
    reference.
    """
    validate_slide(window_ms, slide_ms)
    from gelly_streaming_tpu.core import async_exec

    depth = async_exec.resolve_depth(stream.cfg)
    if depth > 0 and stream.cfg.superbatch <= 1:
        # asynchronous window pipeline (core/async_exec.py): pane
        # pack/compaction on the pack thread, uploads on the transfer
        # thread, counts dispatched without waiting and fetched through the
        # completion queue in window order — the deep generalization of the
        # one-deep submit/finish overlap below, with cfg.async_windows
        # panes in flight.  Counts are identical to the sequential path
        # (pinned by tests/test_async_windows.py).
        def records_async() -> Iterator[tuple]:
            def prepare(pane):
                meta, arrays = _pane_prepare((pane.src, pane.dst))
                return (pane.max_timestamp, meta), arrays

            def dispatch(meta, dev):
                return _pane_dispatch(meta[1], dev)

            def finish(meta, handle):
                return (_pane_triangle_finish(handle), meta[0])

            yield from async_exec.pipelined(
                windowed_panes(stream, window_ms, slide_ms),
                prepare,
                dispatch,
                finish,
                depth,
                prefetch_depth=max(2, depth),
            )

        return OutputStream(records_async)

    if stream.cfg.superbatch > 1:
        # superbatch dispatch coalescing: up to K panes count in ONE
        # vmapped masked-CSR dispatch (exact same counts — pinned by
        # tests/test_superbatch.py against the per-pane path)
        def records_sb() -> Iterator[tuple]:
            yield from _superbatched_window_counts(
                windowed_panes(stream, window_ms, slide_ms),
                stream.cfg.superbatch,
            )

        return OutputStream(records_sb)

    def records() -> Iterator[tuple]:
        pending = None  # (handle, timestamp) of the previous pane
        for pane in windowed_panes(stream, window_ms, slide_ms):
            try:
                handle = _pane_triangle_submit(pane.src, pane.dst)
            except BaseException:
                # pane k's count is already computed — deliver it before
                # propagating pane k+1's failure (the sequential version
                # emitted it first)
                if pending is not None:
                    yield (_pane_triangle_finish(pending[0]), pending[1])
                    pending = None
                raise
            if pending is not None:
                yield (_pane_triangle_finish(pending[0]), pending[1])
            pending = (handle, pane.max_timestamp)
        if pending is not None:
            yield (_pane_triangle_finish(pending[0]), pending[1])

    return OutputStream(records)


# ---------------------------------------------------------------------------
# Streaming exact count (insertion-only)
# ---------------------------------------------------------------------------

GLOBAL_KEY = -1  # reference routes the global counter under key -1
# (ExactTriangleCount.java:108-110)


class TriangleCountState(NamedTuple):
    table: nbr_ops.NeighborTable  # undirected adjacency over the whole stream
    local: jax.Array  # int32[C] per-vertex triangle counts
    global_count: jax.Array  # int32[]


def init_triangle_state(cfg: StreamConfig) -> TriangleCountState:
    return TriangleCountState(
        table=nbr_ops.init_table(cfg.vertex_capacity, cfg.max_degree),
        local=jnp.zeros((cfg.vertex_capacity,), jnp.int32),
        global_count=jnp.zeros((), jnp.int32),
    )


def triangle_update(
    state: TriangleCountState, src, dst, mask
) -> Tuple[TriangleCountState, jax.Array, jax.Array]:
    """Fold an edge batch; returns (state, local_after[B,2], global_after[B]).

    Per edge (in arrival order): count common neighbors c of the canonical
    endpoints in the adjacency-so-far, bump local[u], local[v] by c, local[w]
    by 1 for each common w, and the global count by c — then insert the edge
    (IntersectNeighborhoods + SumAndEmitCounters semantics,
    ExactTriangleCount.java:74-134, with duplicate edges ignored).
    """
    capacity, max_degree = state.table.nbrs.shape

    def step(carry, inp):
        table, local, glob = carry
        u, v, ok = inp
        lo = jnp.minimum(u, v)
        hi = jnp.maximum(u, v)
        dup = nbr_ops.contains_batch(table, lo[None], hi[None])[0] | (lo == hi)
        ok = ok & ~dup
        row_u = table.nbrs[lo]
        row_v = table.nbrs[hi]
        valid_u = jnp.arange(max_degree) < table.deg[lo]
        valid_v = jnp.arange(max_degree) < table.deg[hi]
        eq = (
            (row_u[:, None] == row_v[None, :])
            & valid_u[:, None]
            & valid_v[None, :]
        )
        c = jnp.where(ok, jnp.sum(eq.astype(jnp.int32)), 0)
        common_mask = jnp.any(eq, axis=1) & ok  # [D] over row_u slots
        local = local.at[jnp.where(common_mask, row_u, 0)].add(
            common_mask.astype(jnp.int32)
        )
        local = local.at[lo].add(c)
        local = local.at[hi].add(c)
        glob = glob + c
        table = nbr_ops.insert_batch(
            table,
            jnp.stack([lo, hi]),
            jnp.stack([hi, lo]),
            jnp.stack([ok, ok]),
        )
        return (table, local, glob), (
            jnp.stack([local[lo], local[hi]]),
            glob,
        )

    (table, local, glob), (local_trace, global_trace) = jax.lax.scan(
        step, (state.table, state.local, state.global_count), (src, dst, mask)
    )
    return TriangleCountState(table, local, glob), local_trace, global_trace


def triangle_update_block(
    state: TriangleCountState, src, dst, mask, chunk: int = 64
) -> TriangleCountState:
    """Batch-vectorized exact triangle fold — same final state as
    ``triangle_update``, without the per-edge trace (VERDICT r1 item 7).

    The per-edge scan pays a [D] gather + [D, D] comparison per edge,
    sequentially.  Here the batch folds in chunks of ``chunk`` edges; per
    chunk ONE set of dense tensor ops handles all three ways a chunk edge
    (u, v) can close a wedge u–w–v (attribution to the LAST arriving edge of
    each triangle, as in the single-pass algorithm,
    ExactTriangleCount.java:74-116):

      old-old:  both wedge edges pre-chunk — a [r, D, D] masked equality
                reduction over the endpoints' adjacency rows;
      old-new:  one wedge edge earlier in the chunk, the other pre-chunk —
                a [r, r, D] membership test against the gathered rows;
      new-new:  both wedge edges earlier in the chunk — a [r, r, r]
                elementwise condition tensor (no lookups at all).

    Cross-chunk dependencies need nothing special: chunks fold sequentially
    and earlier chunks are already in the table ("old").  Duplicate edges are
    ignored exactly as in the scan path (table membership + first-occurrence
    within the chunk).
    """
    capacity, max_degree = state.table.nbrs.shape
    from gelly_streaming_tpu.ops import segments

    b = src.shape[0]
    r = min(chunk, b)
    pad = (-b) % r
    if pad:
        src = jnp.concatenate([src, jnp.zeros((pad,), src.dtype)])
        dst = jnp.concatenate([dst, jnp.zeros((pad,), dst.dtype)])
        mask = jnp.concatenate([mask, jnp.zeros((pad,), bool)])
    n_chunks = (b + pad) // r
    lo = jnp.minimum(src, dst).reshape(n_chunks, r)
    hi = jnp.maximum(src, dst).reshape(n_chunks, r)
    ok0 = (mask & (jnp.minimum(src, dst) != jnp.maximum(src, dst))).reshape(
        n_chunks, r
    )

    lower = jnp.tril(jnp.ones((r, r), bool), -1)  # [j, i]: i < j

    def step(carry, inp):
        table, local, glob = carry
        lo, hi, ok = inp
        ok = (
            ok
            & ~nbr_ops.contains_batch(table, lo, hi)
            & segments.first_occurrence_mask_pairs(lo, hi, ok)
        )
        row_lo, valid_lo = nbr_ops.gather_rows(table, lo)  # [r, D]
        row_hi, valid_hi = nbr_ops.gather_rows(table, hi)

        # -- old-old: [r, D, D]
        eq = (
            (row_lo[:, :, None] == row_hi[:, None, :])
            & valid_lo[:, :, None]
            & valid_hi[:, None, :]
        )
        c1 = jnp.where(ok, jnp.sum(eq, axis=(1, 2)), 0)
        common1 = eq.any(axis=2) & ok[:, None]  # marks on row_lo slots

        # pair geometry among chunk edges: does e_i touch e_j's endpoints?
        pair_ok = lower & ok[:, None] & ok[None, :]  # [j, i]
        i_lo, i_hi = lo[None, :], hi[None, :]  # e_i endpoints, broadcast on j
        shares_lo = (i_lo == lo[:, None]) | (i_hi == lo[:, None])  # e_i ∋ lo_j
        shares_hi = (i_lo == hi[:, None]) | (i_hi == hi[:, None])  # e_i ∋ hi_j
        w_lo = jnp.where(i_lo == lo[:, None], i_hi, i_lo)  # other end of e_i
        w_hi = jnp.where(i_lo == hi[:, None], i_hi, i_lo)

        # -- old-new: wedge edge e_i in chunk (earlier), mate edge pre-chunk.
        # (lo_j, w)=e_i and (hi_j, w) old  <=>  w in row_hi[j]; and symmetric.
        def member(rows, valid, w):  # [j, D] rows vs [j, i] queries
            return jnp.any(
                (rows[:, None, :] == w[:, :, None]) & valid[:, None, :], axis=2
            )

        c2a = pair_ok & shares_lo & member(row_hi, valid_hi, w_lo)
        c2b = pair_ok & shares_hi & member(row_lo, valid_lo, w_hi)
        c2 = jnp.sum(c2a, axis=1) + jnp.sum(c2b, axis=1)

        # -- new-new: wedge edges e_i (∋ lo_j) and e_k (∋ hi_j), both earlier,
        # meeting at the same w: [j, i, k]
        a3 = pair_ok & shares_lo  # [j, i]
        b3 = pair_ok & shares_hi  # [j, k]
        cond3 = (
            a3[:, :, None]
            & b3[:, None, :]
            & (w_lo[:, :, None] == w_hi[:, None, :])
        )
        c3 = jnp.sum(cond3, axis=(1, 2))
        w3_weight = jnp.sum(cond3, axis=2)  # per (j, i): marks on w_lo[j, i]

        c = c1 + c2 + c3
        # counter updates (SumAndEmitCounters semantics): endpoints get c,
        # each common w gets +1, the global key accumulates everything
        local = local.at[jnp.where(common1, row_lo, 0)].add(
            common1.astype(jnp.int32)
        )
        local = local.at[jnp.where(c2a, w_lo, 0)].add(c2a.astype(jnp.int32))
        local = local.at[jnp.where(c2b, w_hi, 0)].add(c2b.astype(jnp.int32))
        local = local.at[jnp.where(w3_weight > 0, w_lo, 0)].add(w3_weight)
        local = local.at[jnp.where(ok, lo, 0)].add(jnp.where(ok, c, 0))
        local = local.at[jnp.where(ok, hi, 0)].add(jnp.where(ok, c, 0))
        glob = glob + jnp.sum(c)
        table = nbr_ops.insert_batch(
            table,
            jnp.concatenate([lo, hi]),
            jnp.concatenate([hi, lo]),
            jnp.concatenate([ok, ok]),
        )
        return (table, local, glob), None

    (table, local, glob), _ = jax.lax.scan(
        step, (state.table, state.local, state.global_count), (lo, hi, ok0)
    )
    return TriangleCountState(table, local, glob)


class ExactTriangleCount:
    """Host-facing runner: continuous (key, count) updates, key -1 = global.

    ``mode="block"`` (default) rides the chunk-vectorized fold
    (triangle_update_block) and emits one block of running (key, count)
    records per micro-batch — the endpoints it touched plus the global key —
    the per-batch relaxation SURVEY §7 anticipates for batched execution.
    ``mode="trace"`` opts into the reference's exact per-edge running trace
    via the sequential scan kernel (golden parity; ~B times more device
    round-trips and per-record Python, so not the production default —
    VERDICT r2 weak #5).
    """

    def __init__(self, cfg: Optional[StreamConfig] = None, mode: str = "block"):
        if mode not in ("trace", "block"):
            raise ValueError(f"unknown mode {mode!r}")
        from gelly_streaming_tpu.core import compile_cache

        self.mode = mode
        # module-level kernels: every runner instance shares the executables
        self._kernel = compile_cache.cached_jit(
            ("triangle_update",), lambda: triangle_update
        )
        self._block_kernel = compile_cache.cached_jit(
            ("triangle_update_block",), lambda: triangle_update_block
        )

    def run(self, stream) -> OutputStream:
        if self.mode == "block":
            return self._run_blocks(stream)

        def records():
            state = init_triangle_state(stream.cfg)
            for batch in stream.batches():
                state, local_trace, global_trace = self._kernel(
                    state, batch.src, batch.dst, batch.mask
                )
                l_h = np.asarray(local_trace)
                g_h = np.asarray(global_trace)
                m_h = np.asarray(batch.mask)
                s_h = np.asarray(batch.src)
                d_h = np.asarray(batch.dst)
                for i in np.nonzero(m_h)[0]:
                    u, v = int(min(s_h[i], d_h[i])), int(max(s_h[i], d_h[i]))
                    yield (u, int(l_h[i, 0]))
                    yield (v, int(l_h[i, 1]))
                    yield (GLOBAL_KEY, int(g_h[i]))
            self.final_state = state

        return OutputStream(records)

    def _run_blocks(self, stream) -> OutputStream:
        from gelly_streaming_tpu.core.output import RecordBlock

        def blocks():
            state = init_triangle_state(stream.cfg)
            prev_local = np.asarray(state.local)
            for batch in stream.batches():
                state = self._block_kernel(
                    state, batch.src, batch.dst, batch.mask
                )
                m_h = np.asarray(batch.mask)
                local_h = np.asarray(state.local)
                # endpoints of the batch plus every vertex whose counter moved
                # (common neighbors w also get updates in the reference,
                # ExactTriangleCount.java:95-104)
                touched = np.unique(
                    np.concatenate(
                        [
                            np.asarray(batch.src)[m_h],
                            np.asarray(batch.dst)[m_h],
                            np.nonzero(local_h != prev_local)[0],
                        ]
                    )
                )
                prev_local = local_h
                keys = np.concatenate([touched, [GLOBAL_KEY]]).astype(np.int64)
                counts = np.concatenate(
                    [local_h[touched], [int(state.global_count)]]
                )
                yield RecordBlock((keys, counts))
            self.final_state = state

        return OutputStream(blocks_fn=blocks)
