"""Host-side window discretization: the time plane of the framework.

The reference delegates windowing to Flink (``timeWindow`` over event/ingestion
time, SimpleEdgeStream.java:135-167; every aggregation is windowed,
SummaryBulkAggregation.java:79-81).  In the TPU design the *host owns time*
(SURVEY.md §7): sources attach timestamps, this module assigns edges to tumbling
panes and flushes a pane when the (ascending) watermark passes its end — the
device only ever sees fixed-shape pane micro-batches.

Timestamps are assumed ascending, mirroring the reference's event-time ctor
with an ``AscendingTimestampExtractor`` (SimpleEdgeStream.java:86-90).  Streams
without timestamps form a single global pane flushed at end-of-stream (the
finite-test analog of "one ingestion-time window", e.g. TestSlice's 1s window
over a 7-edge collection).
"""

from __future__ import annotations

from typing import Iterator, NamedTuple, Optional, Tuple

import numpy as np

from gelly_streaming_tpu.core.types import EdgeBatch


class WindowPane(NamedTuple):
    """A closed tumbling window's edges, materialized as host arrays."""

    window_id: int
    max_timestamp: int  # inclusive window end (end_ms - 1); -1 for global pane
    src: np.ndarray
    dst: np.ndarray
    val: Optional[object]  # np array or pytree of np arrays, aligned with src
    time: Optional[np.ndarray]

    @property
    def num_edges(self) -> int:
        return int(self.src.shape[0])


def _batch_to_host(batch: EdgeBatch):
    mask = np.asarray(batch.mask)
    idx = np.nonzero(mask)[0]
    src = np.asarray(batch.src)[idx]
    dst = np.asarray(batch.dst)[idx]
    val = None
    if batch.val is not None:
        import jax

        val = jax.tree.map(lambda a: np.asarray(a)[idx], batch.val)
    time = None if batch.time is None else np.asarray(batch.time)[idx]
    return src, dst, val, time


class PaneAssembler:
    """Accumulates per-window edge parts and assembles closed panes.

    Shared by the single-host assigner below and the multi-host gated
    assigners (parallel/multihost.py) so pane assembly semantics cannot
    diverge between the paths.
    """

    def __init__(self, window_ms: int, val_proto=None, has_time: bool = False):
        """``val_proto``/``has_time`` declare the stream's record structure up
        front (a pytree of zero-length arrays).  Pass them in multi-host runs:
        with inference only, a host closing an empty share before its first
        val-carrying batch would return val=None while peers return zero-length
        pytrees, breaking positional share pairing."""
        self.window_ms = window_ms
        self._open = {}  # window_id -> list of (src, dst, val, time)
        # declared or inferred stream structure for shape-compatible empties
        self._val_proto = val_proto  # pytree of zero-length arrays, or None
        self._has_time = has_time

    def _remember_structure(self, val, time) -> None:
        if val is not None and self._val_proto is None:
            import jax

            self._val_proto = jax.tree.map(lambda a: a[:0], val)
        self._has_time = self._has_time or time is not None

    def add(self, src, dst, val, time, wids) -> None:
        import jax

        self._remember_structure(val, time)
        for wid in np.unique(wids):
            sel = wids == wid
            self._open.setdefault(int(wid), []).append(
                (
                    src[sel],
                    dst[sel],
                    None if val is None else jax.tree.map(lambda a: a[sel], val),
                    None if time is None else time[sel],
                )
            )

    def add_untimed(self, src, dst, val) -> None:
        """Single global pane (ingestion-time finite stream)."""
        self._remember_structure(val, None)
        self._open.setdefault(-1, []).append((src, dst, val, None))

    def open_ids(self):
        return sorted(self._open)

    def close(self, wid: int) -> WindowPane:
        """Assemble pane ``wid``; an id with no edges yields an empty share
        whose val/time carry the stream's structure (zero-length arrays), so
        cross-host positional pairing of shares never mixes None with pytrees.
        """
        max_ts = (wid + 1) * self.window_ms - 1 if wid >= 0 else -1
        parts = self._open.pop(wid, None)
        if parts is None:
            empty = np.empty((0,), np.int32)
            return WindowPane(
                wid,
                max_ts,
                empty,
                empty.copy(),
                self._val_proto,
                np.empty((0,), np.int64) if self._has_time else None,
            )
        src = np.concatenate([p[0] for p in parts])
        dst = np.concatenate([p[1] for p in parts])
        val = None
        if parts[0][2] is not None:
            import jax

            val = jax.tree.map(
                lambda *leaves: np.concatenate(leaves), *[p[2] for p in parts]
            )
        time = (
            None if parts[0][3] is None else np.concatenate([p[3] for p in parts])
        )
        return WindowPane(wid, max_ts, src, dst, val, time)


def assign_tumbling_windows(
    batches: Iterator[EdgeBatch],
    window_ms: int,
    out_of_orderness_ms: int = 0,
    late_sink=None,
) -> Iterator[WindowPane]:
    """Group a timed batch stream into closed tumbling panes.

    With the default ``out_of_orderness_ms=0`` timestamps are assumed
    ascending (the reference's AscendingTimestampExtractor contract,
    SimpleEdgeStream.java:86-90).  A positive bound is the
    BoundedOutOfOrderness watermark Flink offers one call below the
    reference: the watermark trails the max seen timestamp by the bound,
    window ``w`` closes only once the watermark passes its end, and records
    later than the bound — whose window already closed — go to
    ``late_sink(src, dst, val, time)`` (dropped when None) instead of
    corrupting closed panes.  Pane emission stays ascending either way,
    which downstream sliding_panes relies on.
    """
    panes = PaneAssembler(window_ms)
    watermark = None  # max event time seen - bound

    for batch in batches:
        src, dst, val, time = _batch_to_host(batch)
        if len(src) == 0:
            continue
        if time is None:
            panes.add_untimed(src, dst, val)
            continue
        wids = time // window_ms
        if watermark is not None:
            # a record is late iff its window already fired: watermark has
            # passed the window's maxTimestamp (end - 1), Flink's trigger
            # boundary — see the close condition below
            late = (wids + 1) * window_ms - 1 <= watermark
            if late.any():
                if late_sink is not None:
                    import jax

                    sel = np.nonzero(late)[0]
                    late_sink(
                        src[sel],
                        dst[sel],
                        None
                        if val is None
                        else jax.tree.map(lambda a: a[sel], val),
                        time[sel],
                    )
                keep = ~late
                src, dst = src[keep], dst[keep]
                time, wids = time[keep], wids[keep]
                if val is not None:
                    import jax

                    val = jax.tree.map(lambda a: a[keep], val)
                if len(src) == 0:
                    continue
        panes.add(src, dst, val, time, wids)
        new_watermark = int(time.max()) - out_of_orderness_ms
        if watermark is None or new_watermark > watermark:
            watermark = new_watermark
            # fire at watermark >= maxTimestamp = end - 1 (Flink's
            # TumblingEventTimeWindows trigger boundary): a window whose
            # last possible record sits exactly at maxTimestamp closes the
            # tick the watermark reaches it, not one tick later
            for wid in [
                w
                for w in panes.open_ids()
                if 0 <= w and (w + 1) * window_ms - 1 <= watermark
            ]:
                yield panes.close(wid)

    for wid in panes.open_ids():
        yield panes.close(wid)


def assign_ingestion_windows(
    batches: Iterator[EdgeBatch],
    every_edges: int = 0,
    every_ms: int = 0,
    clock=None,
    mark_close: bool = False,
) -> Iterator[WindowPane]:
    """Tumbling panes for UNTIMED streams: the reference's default
    ingestion-time mode (SimpleEdgeStream.java:69-73; running emission per
    window, ConnectedComponentsExample.java:65-67).

    ``every_edges`` cuts a pane every N arrivals — deterministic, the right
    choice for tests and replayable streams.  ``every_ms`` cuts by
    wall-clock at BATCH boundaries (the host assigns each batch to the pane
    open at its arrival instant; a pane closes when a later batch arrives
    past its end — the un-timered approximation of Flink's processing-time
    triggers).  Any timestamps the batches carry are ignored: callers route
    timed streams to ``assign_tumbling_windows`` (event time precedes
    ingestion time, as in the reference's two ctors).

    Panes carry synthetic ascending window ids (0, 1, ...) and
    ``max_timestamp=-1`` (no event-time meaning), so the Merger's running
    merge works unchanged.  Positional checkpoints are sound only for
    ``every_edges`` (a replayed stream cuts the same panes); wall-clock
    panes are NOT replay-deterministic — a resume could skip edges the
    crashed run never folded — so checkpointed runs refuse ``every_ms``
    (enforced in SummaryAggregation.run / BlockShardedCC.run).

    ``mark_close`` (the synchronous windowed planes, which consume panes on
    the cutting thread) opens each pane's ``gelly.pane.cut`` span at the
    window's close for the merge loop to take (utils/tracing.py).
    """
    import time as _time

    from gelly_streaming_tpu.utils import tracing

    if bool(every_edges) == bool(every_ms):
        raise ValueError("set exactly one of every_edges / every_ms")
    clock = clock or _time.monotonic
    panes = PaneAssembler(0)  # window_ms=0 -> max_timestamp=-1 on close
    count = 0
    t0 = None

    for batch in batches:
        if mark_close and every_edges and count:
            # a batch reaching past the open window's end closes it: the
            # window's close is this pull's return, so its cut opens before
            # the batch is read (exact for full batches)
            open_wid = (count - 1) // every_edges
            if (count + batch.src.shape[0] - 1) // every_edges > open_wid:
                tracing.begin_cut(open_wid)
        src, dst, val, _time_ignored = _batch_to_host(batch)
        if len(src) == 0:
            continue
        if every_edges:
            wids = (count + np.arange(len(src), dtype=np.int64)) // every_edges
            count += len(src)
        else:
            now = clock()
            if t0 is None:
                t0 = now
            wid = int((now - t0) * 1000.0 // every_ms)
            wids = np.full((len(src),), wid, np.int64)
        panes.add(src, dst, val, None, wids)
        newest = int(wids.max())
        for wid in [w for w in panes.open_ids() if 0 <= w < newest]:
            if mark_close:
                tracing.begin_cut(wid)
            yield panes.close(wid)

    for wid in panes.open_ids():
        if mark_close:
            tracing.begin_cut(wid)
        yield panes.close(wid)


def sliding_panes(
    panes: Iterator[WindowPane], k: int, slide_ms: int
) -> Iterator[WindowPane]:
    """Sliding windows by pane-sharing: merge each run of ``k`` consecutive
    ``slide_ms``-wide tumbling panes into one emitted window.

    Beyond the reference (its ``slice`` is tumbling-only,
    SimpleEdgeStream.java:135-167), matching the sliding ``timeWindow(size,
    slide)`` Flink exposes one call below it: window ``w`` covers panes
    ``[w-k+1, w]`` and is emitted when pane ``w`` closes (the upstream
    assigner only yields final panes, so every pane <= w is final by then).
    Early windows covering the stream's first panes are partial, windows
    with no edges do not fire, and the trailing ``k-1`` windows after the
    last pane flush at end-of-stream — all as in Flink's sliding trigger.
    Each edge appears in up to ``k`` emitted windows; memory is bounded by
    the ``k`` cached panes.  An untimed stream's single global pane
    (``window_id=-1``) passes through unchanged.
    """
    if k <= 1:
        yield from panes
        return
    import jax

    cache = {}  # pane id -> WindowPane (the k most recent)
    last = None  # newest window id emitted

    def emit(wid: int) -> Optional[WindowPane]:
        parts = [cache[i] for i in range(wid - k + 1, wid + 1) if i in cache]
        if not parts or all(p.num_edges == 0 for p in parts):
            return None
        timed = any(p.max_timestamp >= 0 for p in parts)
        src = np.concatenate([p.src for p in parts])
        dst = np.concatenate([p.dst for p in parts])
        val = None
        if parts[0].val is not None:
            val = jax.tree.map(
                lambda *leaves: np.concatenate(leaves), *[p.val for p in parts]
            )
        time = (
            None
            if parts[0].time is None
            else np.concatenate([p.time for p in parts])
        )
        max_ts = (wid + 1) * slide_ms - 1 if timed else -1
        return WindowPane(wid, max_ts, src, dst, val, time)

    def evict(wid: int) -> None:
        for old in [i for i in cache if i <= wid + 1 - k]:
            del cache[old]

    for pane in panes:
        if pane.window_id < 0:  # untimed global pane: degenerate window
            yield pane
            continue
        w = pane.window_id
        cache[w] = pane
        # windows in (last+k-1, w) contain no cached pane (ids between last
        # and w never arrived), so a timestamp gap costs O(k) work, not
        # O(gap/slide) empty emit() calls
        if last is None:
            candidates = [w]
        else:
            candidates = [*range(last + 1, min(last + k, w)), w]
        for wid in candidates:
            out = emit(wid)
            if out is not None:
                yield out
            evict(wid)
        last = w

    if last is not None:
        for wid in range(last + 1, last + k):
            if not cache:
                break
            out = emit(wid)
            if out is not None:
                yield out
            evict(wid)


class SuperPane(NamedTuple):
    """Up to K consecutive closed panes coalesced for ONE device dispatch.

    Pane boundaries are preserved via PER-EDGE window ids (``wid``), not
    separate dispatches: a consumer folds the concatenated edge run once and
    recovers each window's contribution by masking ``wid == window_ids[k]``.
    Arrays are padded to a power-of-two bucket so successive superpanes hit
    a small set of compiled shapes (mask False marks padding; padded ``wid``
    rows carry -2, which is never a real window id — real ids are >= -1).
    """

    panes: Tuple[WindowPane, ...]  # constituents, ascending window order
    src: np.ndarray  # [E_pad] int32
    dst: np.ndarray  # [E_pad] int32
    val: Optional[object]  # pytree of [E_pad, ...] arrays, or None
    wid: np.ndarray  # [E_pad] int32 per-edge window id (-2 on padding)
    mask: np.ndarray  # [E_pad] bool
    window_ids: np.ndarray  # [k] int32, the panes' window ids


def _assemble_superpane(panes) -> SuperPane:
    import jax

    # window ids ride int32 device columns (the framework's time plane is
    # int32 ms end to end — EdgeBatch refuses epoch-scale timestamps, so
    # event-time ids always fit); fail loudly rather than wrap if a pathological
    # ingestion-time stream ever outruns the range
    if any(not (-2 < p.window_id <= np.iinfo(np.int32).max) for p in panes):
        raise ValueError(
            "superbatch window ids must fit int32 (rebase event timestamps "
            "to stream-relative ms, as EdgeBatch requires)"
        )
    e = sum(p.num_edges for p in panes)
    e_pad = max(1, 1 << (e - 1).bit_length()) if e else 1
    src = np.zeros((e_pad,), np.int32)
    dst = np.zeros((e_pad,), np.int32)
    wid = np.full((e_pad,), -2, np.int32)
    mask = np.zeros((e_pad,), bool)
    o = 0
    for p in panes:
        n = p.num_edges
        src[o : o + n] = p.src
        dst[o : o + n] = p.dst
        wid[o : o + n] = p.window_id
        mask[o : o + n] = True
        o += n
    val = None
    if any(p.val is not None for p in panes):

        def cat(*leaves):
            flat = np.concatenate(leaves)
            out = np.zeros((e_pad,) + flat.shape[1:], flat.dtype)
            out[: len(flat)] = flat
            return out

        val = jax.tree.map(cat, *[p.val for p in panes])
    return SuperPane(
        panes=tuple(panes),
        src=src,
        dst=dst,
        val=val,
        wid=wid,
        mask=mask,
        window_ids=np.array([p.window_id for p in panes], np.int32),
    )


def group_panes(panes: Iterator[WindowPane], k: int, keep_empty: bool = False):
    """Groups of up to ``k`` consecutive closed panes (as lists).

    The grouping primitive under superbatch dispatch: consumers that build
    their own device layout (the aggregation fold's [K, E] per-window rows,
    the triangles vmapped counter) iterate this directly and pay NO
    assembly copy; ``coalesce_panes`` below materializes the flat SuperPane
    view on top of it.  Panes with no edges are dropped by default (the
    per-pane aggregation consumers skip them the same way); consumers that
    emit a record per pane regardless (window triangles) pass
    ``keep_empty=True``.
    """
    k = max(1, k)
    buf = []
    for pane in panes:
        if pane.num_edges == 0 and not keep_empty:
            continue
        buf.append(pane)
        if len(buf) == k:
            yield buf
            buf = []
    if buf:
        yield buf


def coalesce_panes(panes: Iterator[WindowPane], k: int) -> Iterator[SuperPane]:
    """Group up to ``k`` consecutive non-empty closed panes into SuperPanes.

    The superbatch form of the time plane: per-dispatch overhead amortizes
    over ``k`` windows while window identity rides the per-edge ``wid``
    column (pane boundaries as data, not dispatches); ``k <= 1``
    degenerates to one pane per superpane.
    """
    for group in group_panes(panes, k):
        yield _assemble_superpane(group)


def pad_pane_edges(pane: WindowPane):
    """(src, dst, mask) int32/bool arrays padded to the next power of two —
    the shared pane->fixed-shape policy for per-pane device kernels
    (PageRank, SSSP), so successive similar panes reuse compiled steps."""
    e = pane.num_edges
    e_pad = max(1, 1 << (e - 1).bit_length())
    src = np.zeros((e_pad,), np.int32)
    dst = np.zeros((e_pad,), np.int32)
    msk = np.zeros((e_pad,), bool)
    src[:e], dst[:e], msk[:e] = pane.src, pane.dst, True
    return src, dst, msk


class FoldRequest(NamedTuple):
    """One job's parked window fold, offered to a cross-tenant cohort.

    The fused-dispatch handshake record (core/aggregation.py
    ``_fused_fold`` yields these; runtime/manager.py collects them):
    ``key`` identifies the shared executable + padded shape, so requests with
    equal keys from different jobs can stack into one vmapped mega-fold.
    The arrays are already pow2-padded host arrays of length ``e_pad`` —
    exactly the per-row layout of the superbatch plane — and ``fold`` is the
    process-global cached executable (one per key, not per job).  A consumer
    that does not understand the protocol simply ``next()``s past the yield,
    which the generator treats as "no fused partial" and solo-folds: the
    bit-exact fallback oracle.
    """

    key: tuple  # (cache_token, cfg, has_val, e_pad) — cohort compatibility
    fold: object  # the shared superpane fold executable (compile_cache entry)
    split: object  # rows -> the shared cohort-drain executable (one dispatch
    #   slices the stacked result into per-row partials; eager per-row
    #   slicing would cost one device call per job and undo the amortization)
    src: np.ndarray  # int32 [e_pad]
    dst: np.ndarray  # int32 [e_pad]
    val: Optional[object]  # pytree of [e_pad]-padded arrays, or None
    mask: np.ndarray  # bool [e_pad]; True on the first ``edges`` slots
    window_id: int
    edges: int  # true (unpadded) edge count


def stack_fold_rows(requests):
    """Stack N same-key FoldRequests into the [rows, e_pad] superpane layout.

    ``rows`` is pow2-bucketed over the cohort size so varying tenancy
    (1..16 jobs per dispatch) reuses one compiled executable; padding rows
    are all-masked-out zeros, which every SummaryAggregation update ignores
    by contract.  Returns ``(src, dst, val, mask, pad_rows)`` host arrays
    ready for the shared superpane fold.
    """
    n = len(requests)
    e_pad = requests[0].src.shape[0]
    rows = max(1, 1 << (n - 1).bit_length())
    src = np.zeros((rows, e_pad), np.int32)
    dst = np.zeros((rows, e_pad), np.int32)
    msk = np.zeros((rows, e_pad), bool)
    for i, req in enumerate(requests):
        src[i], dst[i], msk[i] = req.src, req.dst, req.mask
    val = None
    if requests[0].val is not None:
        import jax

        def _stack(*leaves):
            out = np.zeros((rows,) + leaves[0].shape, leaves[0].dtype)
            for i, leaf in enumerate(leaves):
                out[i] = leaf
            return out

        val = jax.tree.map(_stack, *[req.val for req in requests])
    return src, dst, val, msk, rows - n


def validate_slide(window_ms: int, slide_ms: Optional[int]) -> None:
    """Eager check of a sliding-window spec (shared by every slide entry
    point so the contract cannot diverge)."""
    if slide_ms is None:
        return
    if not 0 < slide_ms <= window_ms:
        raise ValueError(f"slide_ms must be in (0, window_ms]; got {slide_ms}")
    if window_ms % slide_ms:
        raise ValueError(
            "window_ms must be a multiple of slide_ms for pane-shared "
            f"sliding windows; got {window_ms} % {slide_ms}"
        )


def windowed_panes(
    stream, window_ms: int, slide_ms: Optional[int] = None
) -> Iterator[WindowPane]:
    """Validated window-pane source: tumbling panes, or pane-shared sliding
    windows when ``slide_ms`` (a divisor of ``window_ms``) is given.  The
    single dispatch point for slice() and window_triangles."""
    validate_slide(window_ms, slide_ms)
    if slide_ms and slide_ms != window_ms:
        cfg = stream.cfg
        if cfg.ingest_window_edges or cfg.ingest_window_ms:
            # ingestion-mode panes are cut by arrival count/wall clock, not
            # by slide_ms — a k derived from time knobs would be a lie
            raise ValueError(
                "sliding windows apply to event-time slices; this stream "
                "cuts ingestion-time panes (ingest_window_edges/_ms)"
            )
        return sliding_panes(
            stream_panes(stream, slide_ms), window_ms // slide_ms, slide_ms
        )
    return stream_panes(stream, window_ms)


def _array_backed_panes(
    src: np.ndarray, dst: np.ndarray, every_edges: int
) -> Iterator[WindowPane]:
    """Count-cut ingestion panes sliced straight off an array-backed
    stream's host arrays.

    Pane-content-identical to routing the stream's padded micro-batches
    through ``assign_ingestion_windows``: ``EdgeStream.from_arrays``
    chunks the SAME arrays contiguously (only the final chunk carries
    masked padding, which ``_batch_to_host`` drops), so count cuts land on
    the same edges in the same order — minus the per-batch device
    EdgeBatch construction and mask readback, which dominated the windowed
    plane's host time for array sources.  Array-backed streams are untimed
    and value-less by construction, so panes carry ``max_timestamp=-1``
    and ``val=time=None``.  Yields VIEWS of the caller's arrays — the same
    backing-store contract the packed-wire path already has."""
    n = len(src)
    for wid in range((n + every_edges - 1) // every_edges):
        lo = wid * every_edges
        yield WindowPane(
            wid,
            -1,
            src[lo : lo + every_edges],
            dst[lo : lo + every_edges],
            None,
            None,
        )


def stream_panes(
    stream, window_ms: int, mark_close: bool = False
) -> Iterator[WindowPane]:
    """The pane source for an aggregation over ``stream``: ingestion-time
    panes when the config asks for them, else event-time tumbling windows
    (untimed streams degrade to the single global pane there).  Shared by
    the simulated runtime, the mesh runner, and BlockShardedCC so the time
    plane cannot diverge between execution paths.  ``mark_close`` passes
    through to ``assign_ingestion_windows``."""
    cfg = stream.cfg
    if cfg.ingest_window_edges or cfg.ingest_window_ms:
        arrays = getattr(stream, "_wire_arrays", None)
        if (
            cfg.ingest_window_edges
            and arrays is not None
            and not getattr(stream, "_stages", ())
        ):
            # count-cut panes over an untransformed array-backed stream
            # slice straight off the backing host arrays: the micro-batch
            # route chunks those same arrays, round-trips each chunk
            # through a device EdgeBatch, and reads it back — identical
            # pane content, one device round trip per batch more expensive
            return _array_backed_panes(
                arrays[0], arrays[1], cfg.ingest_window_edges
            )
        return assign_ingestion_windows(
            stream.batches(),
            cfg.ingest_window_edges,
            cfg.ingest_window_ms,
            mark_close=mark_close,
        )
    return assign_tumbling_windows(
        stream.batches(),
        window_ms,
        out_of_orderness_ms=cfg.out_of_orderness_ms,
        late_sink=getattr(stream, "late_sink", None),
    )
