"""Asynchronous window execution pipeline: overlapped pack -> transfer ->
fold -> fetch with non-blocking result delivery.

The windowed plane's synchronous loop pays one full host round trip per
closed window: the pane is padded and packed inline on the dispatch thread,
its fold dispatched, and the emission fetched before the next pane is even
packed — so per-window latency is floored by the host->device link RTT while
the device idles (ARCHITECTURE.md performance model; the classic
"preprocessing/communication is the bottleneck" regime of propagation
blocking).  This module keeps a bounded number of windows in flight end to
end instead:

* **pack** — pane padding/packing runs on the prefetcher's pack thread
  (io/wire.Prefetcher), writing into reusable transfer-layout arenas
  (``ArenaPool``) with double-buffered, donation-safe ownership: an arena is
  recycled only after the fold that consumed it completed (device_put may
  zero-copy host memory on the CPU backend, so "transfer started" is not
  "safe to overwrite").
* **transfer** — ``device_put`` on the prefetcher's second thread, so
  packing window k+1 overlaps transferring window k.
* **dispatch** — the consumer thread dispatches folds without waiting (JAX
  dispatch is asynchronous); window emissions go into a completion queue
  with their device->host copies started (``copy_to_host_async``).
* **drain** — completion-queue entries resolve in window order, so the
  record sequence is bit-identical to the synchronous path; checkpoint
  saves ride the queue too (emit-before-snapshot is preserved per window).

``cfg.async_windows`` (or the ``GELLY_ASYNC_WINDOWS`` env var when the
config leaves it at 0) sets the in-flight depth; 0 keeps the synchronous
lockstep — the default and the equivalence oracle for
tests/test_async_windows.py.  Occupancy counters (in-flight high-water
mark, per-stage stall seconds) land in utils/metrics.pipeline_stats.
"""

from __future__ import annotations

import collections
import os
import threading
import time
from typing import Callable, Iterable, Iterator, Optional

import numpy as np

from gelly_streaming_tpu.utils import metrics, tracing


def resolve_depth(cfg) -> int:
    """Effective async-window depth: explicit config > env var > 0 (sync).

    ``cfg.async_windows`` wins when set; a config left at the 0 default
    defers to ``GELLY_ASYNC_WINDOWS`` so a whole process can be switched
    without threading the knob through every call site.
    """
    n = getattr(cfg, "async_windows", 0)
    if n:
        return max(0, int(n))
    env = os.environ.get("GELLY_ASYNC_WINDOWS")
    if env:
        try:
            return max(0, int(env))
        except ValueError:
            pass
    return 0


def start_host_fetch(tree) -> None:
    """Kick off the device->host copy of every array leaf (non-blocking).

    The completion-queue contract: emissions enter the queue with their
    downloads already in flight, so the drain's materialization waits on a
    copy that has been overlapping later windows' compute, not a fresh RTT.
    Host-side leaves (numpy, python scalars) need no copy and are skipped.
    """
    import jax

    for leaf in jax.tree.leaves(tree):
        try:
            leaf.copy_to_host_async()
        except AttributeError:
            pass


def wait_ready(tree) -> None:
    """Block until every device leaf of ``tree`` is computed.

    This is the completion-queue drain's synchronization point — the ONE
    place the async pipeline is allowed to wait on the device (hot-loop
    lint allowlist).  Used before recycling an arena whose host memory the
    fold may still be reading through a zero-copy transfer.
    """
    import jax

    t0 = time.perf_counter()
    for leaf in jax.tree.leaves(tree):
        try:
            leaf.block_until_ready()  # hot-loop-ok: completion-queue drain
        except AttributeError:
            pass
    metrics.pipeline_add(
        "pipeline_drain_stall_s", time.perf_counter() - t0
    )


class ArenaPool:
    """Reusable host transfer arenas with donation-safe ownership.

    ``acquire(shape, dtype)`` hands out a zeroed numpy buffer — recycled
    when one is free, freshly allocated otherwise; ``release`` returns
    buffers for reuse, keeping at most ``per_shape`` per (shape, dtype)
    class.  The pool itself NEVER blocks: the number of panes holding
    arenas is already bounded by the prefetcher's queues plus the
    completion queue's depth (that is the pipeline's backpressure), so the
    pool only has to cap how much recycled memory it retains — a blocking
    pool here can deadlock the pack thread against the very drain that
    would release its arenas.

    Ownership rule (why release happens at the completion-queue drain, not
    after device_put): on the CPU backend ``jax.device_put`` may alias the
    numpy buffer zero-copy, so the fold reads the arena's memory until the
    dispatch that consumed it completes.  Callers release an arena only
    after something downstream of its fold is known complete (e.g. the
    window's emission materialized) — double-buffered by construction:
    while window k's arenas are owned by its in-flight fold, window k+1
    packs into different buffers.
    """

    def __init__(self, per_shape: int = 8):
        self._per_shape = max(1, per_shape)
        # (shape, dtype str) -> free arrays; touched by the pack thread
        # (acquire) and the drain (release) concurrently
        self._free: dict = {}  # guarded-by: _lock
        self._lock = threading.Lock()

    def acquire(self, shape, dtype) -> np.ndarray:
        key = (tuple(shape), np.dtype(dtype).str)
        with self._lock:
            free = self._free.get(key)
            buf = free.pop() if free else None
        if buf is None:
            return np.zeros(shape, dtype)
        buf.fill(0)
        return buf

    def release(self, *bufs) -> None:
        with self._lock:
            for buf in bufs:
                if buf is None:
                    continue
                key = (tuple(buf.shape), buf.dtype.str)
                free = self._free.setdefault(key, [])
                if len(free) < self._per_shape:
                    free.append(buf)


def pipelined(
    items: Iterable,
    prepare: Callable,
    dispatch: Callable,
    finish: Callable,
    depth: int,
    prefetch_depth: int = 4,
    device=None,
) -> Iterator:
    """Run windows through pack -> transfer -> dispatch -> drain with up to
    ``depth`` dispatched-but-undrained windows in flight.

    ``prepare(item) -> (meta, host_arrays)`` runs on the prefetcher's pack
    thread; the ``device_put`` of ``host_arrays`` on its transfer thread;
    ``dispatch(meta, device_arrays) -> handle`` on the caller's thread (an
    asynchronous JAX dispatch — it must not block); ``finish(meta, handle)
    -> result`` resolves a completed window at drain time.  Results yield
    strictly in item order, so consumers observe the synchronous sequence.

    On an upstream failure, windows already dispatched are drained (their
    results were computed and the synchronous path would have delivered
    them) before the failure propagates — mirroring the sequential loop's
    emission-then-raise order.
    """
    from gelly_streaming_tpu.io import wire

    depth = max(1, depth)
    metrics.pipeline_high_water("pipeline_prefetch_depth", prefetch_depth)
    pending: "collections.deque" = collections.deque()

    def drain_one():
        meta, handle = pending.popleft()
        t0 = time.perf_counter()
        out = finish(meta, handle)
        metrics.pipeline_add(
            "pipeline_drain_stall_s", time.perf_counter() - t0
        )
        metrics.pipeline_add("pipeline_windows_drained", 1)
        span = tracing.find_span(meta) if tracing.active() else None
        if span is not None:
            span.mark("drain", t0)
            tracing.flight_recorder().record(span)
        return out

    with wire.Prefetcher(
        items, prepare, device=device, depth=prefetch_depth
    ) as pf:
        it = iter(pf)
        try:
            # hot-loop: async window dispatch (no per-window host syncs)
            while True:
                t0 = time.perf_counter()
                try:
                    meta, dev = next(it)
                except StopIteration:
                    break
                metrics.pipeline_add(
                    "pipeline_dispatch_stall_s", time.perf_counter() - t0
                )
                span = tracing.find_span(meta) if tracing.active() else None
                t_disp = time.perf_counter() if span is not None else 0.0
                handle = dispatch(meta, dev)
                if span is not None:
                    span.mark("dispatch", t_disp)
                pending.append((meta, handle))
                metrics.pipeline_add("pipeline_windows_dispatched", 1)
                metrics.pipeline_high_water(
                    "pipeline_inflight_high_water", len(pending)
                )
                while len(pending) > depth:
                    yield drain_one()
            # hot-loop-end
        except GeneratorExit:
            # consumer closed (a cancelled job, an abandoned run): no
            # further yields are legal, but dispatched windows still own
            # transfer arenas whose host memory their folds may be reading
            # (zero-copy device_put).  Drain the completion queue WITHOUT
            # yielding — finish() waits on each fold and recycles its
            # arenas — so cancellation neither leaks arenas nor recycles
            # one a fold still reads.
            while pending:
                drain_one()
            raise
        except BaseException:
            # deliver windows whose results already exist, then propagate
            # (the sequential path emitted them before hitting the failure)
            while pending:
                yield drain_one()
            raise
    while pending:
        yield drain_one()


def async_merge_loop(
    agg,
    cfg,
    panes: Iterator,
    fold_pane: Callable,
    checkpoint_path: Optional[str],
    restore: bool,
    unwrap: bool = False,
    depth: int = 2,
    release: Optional[Callable] = None,
    fold_is_running: bool = False,
) -> Iterator[tuple]:
    """The Merger with a non-blocking completion queue
    (SummaryAggregation._merge_loop's async form — same restore, merge,
    emission-order, and at-least-once semantics, pinned by
    tests/test_async_windows.py).

    ``fold_is_running`` mirrors the synchronous loop: the owner-sharded
    plane's folds accumulate into persistent cross-window blocks and return
    the running summary directly, so no combine is dispatched here — the
    double-buffered route -> fold -> exchange schedule stays non-blocking
    (each pane's exchange+gather chains behind its fold in the device queue
    while the NEXT pane's routing/packing runs on the prefetcher's pack
    thread).

    Window folds dispatch without waiting; each window's emission record
    enters a completion queue with its device->host copies started, and
    records yield in window order with the positional checkpoint saved
    immediately after its window's record is consumed — exactly the
    synchronous loop's emit-before-snapshot interleaving, so a crash at any
    drain point leaves the same snapshot/emission frontier as the sync path.

    ``release(payload)`` (optional) recycles a window's transfer arenas at
    drain time; it is called only after the window's FOLD OUTPUT is known
    complete (``wait_ready``), which proves the arena's host memory is no
    longer read (donation-safe ownership, see ArenaPool).  The fold output
    — not the emission record — is the wait target because transforms may
    wrap state in non-pytree host objects whose leaves wait_ready cannot
    block on.
    """
    running = None
    start_after = -1
    global_done = False
    if checkpoint_path and restore:
        from gelly_streaming_tpu.utils.checkpoint import (
            checkpoint_exists,
            load_state,
        )

        if checkpoint_exists(checkpoint_path):
            try:
                snap = load_state(checkpoint_path, agg._checkpoint_like(cfg))
                if bool(snap["has_summary"]):
                    running = snap["summary"]
                start_after = int(snap["last_window"])
                global_done = bool(snap["global_done"])
            except ValueError:
                # legacy snapshot layout: a bare summary pytree with no
                # stream position (pre-position checkpoints)
                running = load_state(checkpoint_path, agg.initial_state(cfg))

    # completion queue: (window_id, record, ckpt summary, global_done after
    # this window, release payload) in dispatch (= window) order
    pending: "collections.deque" = collections.deque()

    def save(wid_through: int, gdone: bool, summary) -> None:
        from gelly_streaming_tpu.utils.checkpoint import save_state

        t0 = time.perf_counter()
        save_state(
            checkpoint_path,
            {
                "summary": summary,
                "has_summary": np.full((), not agg.transient_state, bool),
                "last_window": np.full((), wid_through, np.int64),
                "global_done": np.full((), gdone, bool),
            },
        )
        metrics.pipeline_add(
            "pipeline_drain_stall_s", time.perf_counter() - t0
        )

    drained_through = start_after
    drained_global = global_done

    def drain_one():
        nonlocal drained_through, drained_global
        wid, rec, summary, payload, span, t_item, fold_out = pending.popleft()
        metrics.pipeline_add("pipeline_windows_drained", 1)
        t_drain = time.perf_counter()
        if release is not None and payload is not None:
            # wait on the FOLD OUTPUT, not the transformed record: transforms
            # may return non-pytree host wrappers (e.g. CC's DisjointSet)
            # whose opaque leaves wait_ready silently skips, which would
            # recycle the arena under a still-pending zero-copy fold
            wait_ready(fold_out)
            release(payload)  # arena-live-until: drain — this IS the drain
        t_emit = time.perf_counter()
        # emission latency for EVERY window (bounded histogram, one lock
        # per window — same cost class as the pipeline counters above);
        # span recording only for the sampled ones
        metrics.hist_record(
            "window_close_to_emission_ms", (t_emit - t_item) * 1e3
        )
        if span is not None:
            span.mark("drain", t_drain, t_emit)
            span.mark("emit", t_emit)
            tracing.flight_recorder().record(span)
        return wid, rec, summary

    panes_it = iter(panes)
    try:
        # hot-loop: async Merger dispatch (no per-window host syncs)
        while True:
            t_pull = time.perf_counter()
            try:
                item = next(panes_it)
            except StopIteration:
                break
            metrics.pipeline_add(
                "pipeline_dispatch_stall_s", time.perf_counter() - t_pull
            )
            pane, payload = item if unwrap else (item, item)
            already_folded = (0 <= pane.window_id <= start_after) or (
                pane.window_id == -1 and global_done
            )
            if already_folded:
                continue  # folded before the snapshot: replay-safe
            # the span (if this window was sampled at the pack thread)
            # rides the payload meta; its dispatch stage covers the fold
            # dispatch + transform + host-fetch kickoff below
            span = tracing.find_span(payload) if tracing.active() else None
            t_item = time.perf_counter()
            pane_summary = fold_pane(payload)
            if pane_summary is None:
                continue
            if fold_is_running:
                running = pane_summary
                metrics.pipeline_add("merge_running_folds", 1)
            else:
                if running is None or agg.transient_state:
                    running = pane_summary
                else:
                    running = agg._combine_j(running, pane_summary)
                metrics.pipeline_add("merge_combines", 1)
            out = agg.transform(running)
            rec = out if isinstance(out, tuple) else (out,)
            start_host_fetch(rec)
            ck = running if checkpoint_path else None
            if ck is not None:
                start_host_fetch(ck)
            if span is not None:
                span.mark("dispatch", t_item)
            pending.append(
                (
                    pane.window_id,
                    rec,
                    ck,
                    payload if release is not None else None,
                    span,
                    t_item,
                    pane_summary if release is not None else None,
                )
            )
            metrics.pipeline_add("pipeline_windows_dispatched", 1)
            metrics.pipeline_high_water(
                "pipeline_inflight_high_water", len(pending)
            )
            start_after = max(pane.window_id, start_after)
            global_done = global_done or pane.window_id == -1
            if agg.transient_state:
                running = None
            while len(pending) > depth:
                wid, rec_d, summary = drain_one()
                yield rec_d
                drained_through = max(wid, drained_through)
                drained_global = drained_global or wid == -1
                if checkpoint_path:
                    save(drained_through, drained_global, summary)
        # hot-loop-end
    except GeneratorExit:
        # consumer closed (JobManager.cancel / Job.close / an abandoned
        # run): yielding is illegal here, but the completion queue still
        # holds in-flight windows whose arenas are owned by dispatched
        # folds.  Run them through the NORMAL drain path — drain_one waits
        # on each window's emission (proving its fold consumed the arena's
        # host memory) and releases the arenas — discarding the records, so
        # a mid-flight cancel recycles every arena without corrupting one a
        # zero-copy transfer still reads.
        while pending:
            drain_one()
        raise
    except BaseException:
        # deliver windows whose folds already dispatched (the sync loop
        # emitted them before reaching the failure), then propagate
        while pending:
            wid, rec_d, summary = drain_one()
            yield rec_d
            drained_through = max(wid, drained_through)
            drained_global = drained_global or wid == -1
            if checkpoint_path:
                save(drained_through, drained_global, summary)
        raise
    while pending:
        wid, rec_d, summary = drain_one()
        yield rec_d
        drained_through = max(wid, drained_through)
        drained_global = drained_global or wid == -1
        if checkpoint_path:
            save(drained_through, drained_global, summary)
