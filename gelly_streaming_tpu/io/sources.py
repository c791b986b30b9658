"""Edge sources: files (native-accelerated), collections, and generators.

The host ingest plane (SURVEY.md §5.8): parse, intern, timestamp, and batch
edges into fixed-shape ``EdgeBatch``es for the device.  File parsing uses the
C++ parser (native/edge_parser.cpp via ctypes) when a compiler is available
and falls back to numpy text parsing otherwise — same arrays either way.
"""

from __future__ import annotations

import ctypes
import queue
import threading
import time
from typing import Callable, Iterator, Optional, Tuple

import numpy as np

from gelly_streaming_tpu.core.config import StreamConfig
from gelly_streaming_tpu.core.stream import EdgeStream
from gelly_streaming_tpu.core.types import EdgeBatch
from gelly_streaming_tpu.io.interning import IdentityInterner, VertexInterner
from gelly_streaming_tpu.utils import metrics, tracing
from gelly_streaming_tpu.utils.native import load_ingest_lib


def parse_edge_file(path: str, workers: int = 1):
    """Parse an edge-list file into host arrays.

    Returns (src i64, dst i64, val f64 | None, time i64 | None, sign i32 | None).
    Format per line: ``src dst [value|+|-] [timestamp]`` with space/tab/comma
    separators and #/% comments.

    ``workers`` > 1 (or 0 = auto: GELLY_INGEST_WORKERS env var, else the
    usable core count) shards the file into byte ranges parsed concurrently
    by the ingest worker pool (io/ingest.py) — bit-identical output, host
    parse rate scaling with cores.
    """
    if workers != 1:
        from gelly_streaming_tpu.io import ingest

        return ingest.parse_edge_file_parallel(path, workers)
    lib = load_ingest_lib()
    if lib is not None:
        n = lib.count_rows(path.encode())
        if n < 0:
            raise FileNotFoundError(path)
        src = np.empty(n, np.int64)
        dst = np.empty(n, np.int64)
        val = np.empty(n, np.float64)
        tim = np.empty(n, np.int64)
        sign = np.empty(n, np.int32)
        ncols = ctypes.c_int32(0)
        rows = lib.fill_edges(
            path.encode(),
            src.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            dst.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            val.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
            tim.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            sign.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            n,
            ctypes.byref(ncols),
        )
        if rows < 0:
            raise IOError(f"failed to parse {path}")
        nc = ncols.value
        has_sign = bool(nc & 0x100)
        nc &= 0xFF
        src, dst = src[:rows], dst[:rows]
        return (
            src,
            dst,
            val[:rows] if (nc >= 3 and not has_sign) else None,
            tim[:rows] if nc >= 4 else None,
            sign[:rows] if has_sign else None,
        )
    return _parse_edge_file_numpy(path)


def _parse_edge_file_numpy(path: str):
    """Pure-python fallback parser (same contract as the native path).

    ONE line-parsing implementation serves both the serial and the
    worker-pool fallback paths (io/ingest.py ``_parse_chunk_lines``), so
    the parallel path's bit-identical-output contract holds by
    construction, and the file streams chunk-by-chunk (never fully in
    memory)."""
    import itertools

    from gelly_streaming_tpu.io import ingest

    parts = []
    with open(path) as f:
        while True:
            chunk = list(itertools.islice(f, ingest.FALLBACK_CHUNK_LINES))
            if not chunk:
                break
            parts.append(ingest._parse_chunk_lines(chunk))
    if not parts:
        parts = [ingest._parse_chunk_lines([])]
    return ingest._merge_parsed(parts)


def _batched(
    src, dst, val, tim, sign, batch_size: int
) -> Callable[[], Iterator[EdgeBatch]]:
    def factory():
        for i in range(0, len(src), batch_size):
            j = min(i + batch_size, len(src))
            yield EdgeBatch.from_arrays(
                src[i:j],
                dst[i:j],
                val=None if val is None else val[i:j],
                time=None if tim is None else tim[i:j],
                sign=None if sign is None else sign[i:j],
                pad_to=batch_size,
            )

    return factory


def file_stream(
    path: str,
    cfg: StreamConfig,
    interner: Optional[VertexInterner] = None,
    batch_size: Optional[int] = None,
) -> Tuple[EdgeStream, object]:
    """EdgeStream over an edge-list file; returns (stream, interner).

    With no interner given, ids are checked-identity (dense ints) unless any id
    falls outside [0, capacity), in which case a VertexInterner is built.

    Parsing rides the parallel ingest pool (``cfg.ingest_workers``; 0 = auto
    via GELLY_INGEST_WORKERS / core count — see io/ingest.py).
    """
    src, dst, val, tim, sign = parse_edge_file(path, workers=cfg.ingest_workers)
    if interner is None:
        if len(src) and (
            min(src.min(), dst.min()) < 0
            or max(src.max(), dst.max()) >= cfg.vertex_capacity
        ):
            interner = VertexInterner(cfg.vertex_capacity)
        else:
            interner = IdentityInterner(cfg.vertex_capacity)
    src_i = interner.intern_ints(src)
    dst_i = interner.intern_ints(dst)
    bs = batch_size or cfg.batch_size
    if val is None and tim is None and sign is None:
        # Value-less untimed files ride the packed-wire fast ingest path.
        return EdgeStream.from_arrays(src_i, dst_i, cfg, batch_size=bs), interner
    # Timestamps ride through unchanged: tumbling windows are phase-aligned to
    # absolute time (t // window), so shifting would move window boundaries.
    # Device time is int32 ms — streams using epoch-ms should rebase at the
    # source to a recent origin that is a multiple of the window length.
    stream = EdgeStream.from_batches(
        _batched(src_i, dst_i, val, tim, sign, bs), cfg
    )
    return stream, interner


def generated_stream(
    cfg: StreamConfig,
    num_edges: int,
    num_vertices: Optional[int] = None,
    seed: int = 0,
    batch_size: Optional[int] = None,
) -> EdgeStream:
    """Uniform random edge stream (the examples' generated-input fallback,
    e.g. ConnectedComponentsExample.java:122-140)."""
    n_v = num_vertices or cfg.vertex_capacity
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n_v, num_edges).astype(np.int32)
    dst = rng.integers(0, n_v, num_edges).astype(np.int32)
    return EdgeStream.from_arrays(src, dst, cfg, batch_size=batch_size)


class SourceQuiesced(RuntimeError):
    """Push refused because the source is draining (or already closed).

    Explicit by contract, like ``AdmissionError``: a drain in progress must
    REFUSE further ingest loudly so the client knows exactly which edges
    the server will never fold (everything past the drain cursor is the
    client's to re-push after the restart), never absorb them silently.
    """


class PushOutOfSync(RuntimeError):
    """Push refused because its declared stream offset does not match the
    source's position.

    The live-rescale hole this closes (ISSUE 11): a pipelined push written
    BEFORE the client learned of a drain/rescale can reach the server
    AFTER the swap installed the job's new source — at face value a valid
    push, but positionally it belongs to the OLD stream, and accepting it
    at the new source's cursor would silently shift every replayed pane
    boundary.  Clients that stamp each frame with its global edge offset
    (``GellyClient.push_edges`` does) get exact positional verification;
    a mismatch is this typed refusal, and re-pushing from the advertised
    cursor (whose offsets then match) is the recovery — the same
    at-least-once overlap the drain contract already pins.

    ``expected`` / ``declared`` carry the two positions structurally so
    the serving plane can advertise the cursor IN the refusal (the
    ``out-of-sync`` reply's ``expected`` field) and a reconnecting client
    can re-declare its position without a second round trip — the fleet
    tier's failover resync rides exactly this.
    """

    def __init__(self, message: str, expected: int = None, declared: int = None):
        super().__init__(message)
        self.expected = expected
        self.declared = declared


class NetworkEdgeSource:
    """Feed a running job's record source from client-pushed wire batches.

    The serving plane's ingest boundary (ISSUE 8): connection handler
    threads ``push_wire``/``push_tail`` validated wire buffers in, the job's
    stream factory pulls decoded ``EdgeBatch``es out, and a bounded queue
    between them is the isolation contract both ways:

    * a FULL queue blocks the pushing connection's thread (TCP backpressure
      onto that client's socket) — the scheduler never produces into it;
    * an EMPTY queue never blocks the scheduler: ``ready()`` tells the
      weighted-fair scheduler whether an undelivered ingest window is
      closable from the queued edges (exact positional accounting — see
      its docstring), and the scheduler skips the job's round otherwise
      (``job_source_wait_skips``).  A slow or dead client therefore idles
      ITS job, never the round.

    Every pushed buffer passes the ``from_wire`` guards
    (core/stream.validate_wire_buffer) WITH the id-range decode check —
    unlike replay producers, a socket peer is untrusted, so each buffer is
    validated, and the decode doubles as the host-side unpack the windowed
    planes need anyway.

    Resume cursors: ``resume_edges`` (a multiple of the config's ingest
    window, derived from the job's positional checkpoint by the server)
    makes the factory synthesize that many filler edges first, so the
    replayed pane ids line up with the checkpoint and the merge loop skips
    them without device work — the client re-pushes from the cursor, not
    from the beginning, and the resumed fold is bit-exact (the same
    replay-skip contract every checkpointed plane already pins).
    """

    def __init__(
        self,
        cfg: StreamConfig,
        batch_size: Optional[int] = None,
        resume_edges: int = 0,
        max_queued_batches: int = 64,
        on_data: Optional[Callable[[], None]] = None,
    ):
        self.cfg = cfg
        self.batch = int(batch_size or cfg.batch_size)
        if self.batch <= 0:
            raise ValueError("batch_size must be positive")
        if cfg.ingest_window_edges and self.batch > cfg.ingest_window_edges:
            # one batch must close at most one window, so each scheduler
            # pull delivers exactly one record and ready()'s positional
            # accounting stays exact (a batch spanning several windows
            # would buffer closed panes behind a gate that can't see them)
            raise ValueError(
                f"batch_size ({self.batch}) must be <= ingest_window_edges "
                f"({cfg.ingest_window_edges}) for network-fed jobs"
            )
        # pipelined planes consume AHEAD of the records they deliver: the
        # async window pipeline dispatches depth+1 panes before its first
        # yield (and its pack thread prefetches further), and superbatch
        # grouping buffers up to K panes per dispatch — a pull is only
        # guaranteed non-blocking when that many windows are closable
        # BEYOND the consumer's position.  The cost of the headroom is
        # bounded emission lag on a trickling live stream (drained at the
        # next push, at end-of-stream, and by drain/cancel), never lost
        # records.
        from gelly_streaming_tpu.core import async_exec

        self._headroom = async_exec.resolve_depth(cfg) + (
            cfg.superbatch if cfg.superbatch > 1 else 0
        )
        resume_edges = int(resume_edges)
        if resume_edges < 0:
            raise ValueError("resume_edges must be >= 0")
        w = cfg.ingest_window_edges
        if resume_edges and (not w or resume_edges % w):
            raise ValueError(
                f"resume_edges ({resume_edges}) must be a multiple of "
                f"ingest_window_edges ({w}): checkpoint positions are whole "
                "closed windows, so a misaligned cursor would shift every "
                "replayed pane boundary"
            )
        self._resume_edges = resume_edges
        # decoded (src, dst) batches between the connection thread(s) and
        # the job's stream factory; the put side blocks (that is the
        # backpressure), the get side is guarded by ready()
        self._q: "queue.Queue" = queue.Queue(maxsize=max(1, max_queued_batches))
        # a deep LEAF of the runtime's lock order: push/scheduler/server
        # threads all take it bare, and the wake callback (on_data ->
        # JobManager.poke) runs with it RELEASED, so nothing here may
        # re-enter a runtime lock; the queue's own mutex is only ever
        # taken in SEQUENCE with it (progress()), never nested.
        # lock-order: server.StreamServer._admission < sources.NetworkEdgeSource._lock
        self._lock = threading.Lock()
        # edges accepted (resume filler counts as pre-accepted)
        self._edges_in = resume_edges  # guarded-by: _lock
        # edges the stream factory handed to the consumer (filler included)
        self._edges_out = 0  # guarded-by: _lock
        self._closed = False  # guarded-by: _lock
        self._quiesced = False  # guarded-by: _lock
        # called after every accepted push/close so the scheduler re-checks
        # ready() promptly (JobManager.poke); optional — the scheduler's
        # bounded park degrades a missed wake to polling, never a wedge
        self.on_data = on_data

    # -- producer side (connection threads) ---------------------------------

    def _refuse_if_not_open(self) -> None:
        with self._lock:
            if self._quiesced and not self._closed:
                raise SourceQuiesced(
                    "source is draining: re-push everything past the drain "
                    "cursor after the restart"
                )
            if self._closed:
                raise SourceQuiesced("source is closed (end-of-stream seen)")

    def check_open(self) -> None:
        """Raise ``SourceQuiesced`` unless pushes are currently accepted.

        The decode pool's pre-flight: the pooled push path must refuse a
        quiesced/closed source BEFORE spending a decode on the buffer —
        the same refusal precedence ``push_wire`` has by construction
        (its open check runs ahead of validation)."""
        self._refuse_if_not_open()

    def push_wire(
        self,
        buf,
        width,
        timeout: Optional[float] = None,
        offset: Optional[int] = None,
    ) -> int:
        """Validate + decode one full wire buffer and queue its batch.

        ``width`` is an io/wire encoding (fixed byte width or the
        ``(BDV, capacity)`` tuple); the buffer must hold exactly
        ``self.batch`` edges.  Blocks while the queue is full (the
        per-connection backpressure); raises ``queue.Full`` only when
        ``timeout`` elapses, ``ValueError`` on a buffer failing the
        ``from_wire`` guards, ``SourceQuiesced`` during/after drain,
        ``PushOutOfSync`` when ``offset`` (the batch's declared global
        edge position, resume filler included) does not match the
        source's accepted-edge count — the positional guard that keeps a
        stale pipelined push from landing past a live rescale's cursor.
        Returns the number of edges accepted.
        """
        from gelly_streaming_tpu.core.stream import (
            validate_wire_buffer,
            validate_wire_width,
        )

        self._refuse_if_not_open()
        validate_wire_width(width, self.cfg.vertex_capacity)
        with tracing.span("gelly.push.decode"):
            s, d = validate_wire_buffer(
                buf,
                self.batch,
                width,
                self.cfg.vertex_capacity,
                decode_ids=True,
            )
        self._accept(s, d, timeout, offset)
        return len(s)

    def push_tail(
        self,
        src,
        dst,
        timeout: Optional[float] = None,
        offset: Optional[int] = None,
    ) -> int:
        """Queue a raw partial batch (the stream remainder shorter than one
        wire buffer) — same id-bounds contract as ``from_wire``'s tail,
        same optional positional guard as ``push_wire``."""
        self._refuse_if_not_open()
        src = np.asarray(src)
        dst = np.asarray(dst)
        if src.shape != dst.shape or src.ndim != 1:
            raise ValueError("tail must be matching 1-d (src, dst) arrays")
        if len(src) == 0 or len(src) > self.batch:
            raise ValueError(
                f"tail must hold 1..{self.batch} edges, got {len(src)}"
            )
        cap = self.cfg.vertex_capacity
        # bounds BEFORE the int32 cast, like from_arrays/from_wire: a
        # cast-first check would let 64-bit ids wrap into range
        if (
            min(src.min(), dst.min()) < 0
            or max(src.max(), dst.max()) >= cap
        ):
            raise ValueError(
                f"tail vertex ids must be in [0, vertex_capacity={cap}); "
                "intern ids first (io.interning.VertexInterner)"
            )
        s = np.ascontiguousarray(src, dtype=np.int32)
        d = np.ascontiguousarray(dst, dtype=np.int32)
        self._accept(s, d, timeout, offset)
        return len(s)

    def push_decoded(
        self,
        src: np.ndarray,
        dst: np.ndarray,
        timeout: Optional[float] = None,
        offset: Optional[int] = None,
        release: Optional[Callable[[], None]] = None,
    ) -> int:
        """Queue one ALREADY-validated full batch — the decode pool's
        landing path (runtime/decode_pool.py).

        ``src``/``dst`` are int32[batch] rows of a pool transfer arena
        that already passed the full ``validate_wire_buffer`` guard set
        (size bounds, id decode, both ends of the id range) in the
        pool's native pass; re-validating here would put the decode back
        on this thread's interpreter time — exactly the cost the pool
        exists to remove.  ``release`` travels with the batch: the
        stream factory fires it after copying the rows out (the arena's
        donation fence), returning the arena to the pool's free-list.
        Same backpressure/refusal contract as ``push_wire`` otherwise.
        """
        self._refuse_if_not_open()
        if len(src) != self.batch or len(dst) != self.batch:
            raise ValueError(
                f"decoded push must hold exactly {self.batch} edges, got "
                f"{len(src)}/{len(dst)}"
            )
        self._accept(src, dst, timeout, offset, release)
        return len(src)

    def _check_offset(self, offset: Optional[int]) -> None:
        if offset is None:
            return
        with self._lock:
            expect = self._edges_in
        if int(offset) != expect:
            raise PushOutOfSync(
                f"push declares edge offset {int(offset)} but this source "
                f"is at {expect} accepted edges (resume filler included): "
                "the batch belongs to a stream position this source does "
                "not hold — re-push from the advertised resume cursor",
                expected=expect,
                declared=int(offset),
            )

    def _accept(
        self, s, d, timeout: Optional[float], offset=None, release=None
    ) -> None:
        # positional guard first: a stale pipelined frame must refuse, not
        # wait on (or worse, land in) a queue it has no position in.  The
        # check re-runs on blocked-push retries (the server's bounded-wait
        # slices), so the window between check and put stays harmless for
        # the one-pusher-per-job contract the accounting assumes.
        self._check_offset(offset)
        # enqueue timestamp: the consumer side records queue residency as
        # the push-to-fold latency histogram (how long a pushed batch
        # waited before the scheduler folded it).  ``release`` (decode-pool
        # batches only) rides along so the factory can return the arena.
        with tracing.span("gelly.push.enqueue"):
            self._q.put((s, d, time.perf_counter(), release), timeout=timeout)
        with self._lock:
            self._edges_in += len(s)
        wake = self.on_data
        if wake is not None:
            wake()

    @property
    def draining(self) -> bool:
        """True while the source is quiesced for a drain/rescale (pushes
        are being refused ``SourceQuiesced``); False once closed normally
        or while open."""
        with self._lock:
            return self._quiesced and not self._closed

    def resume_pushes(self) -> None:
        """Reopen a quiesced (not closed) source — the rescale's FAILURE
        path: the drain did not complete, the job keeps running at its
        old geometry, and its clients must be able to keep pushing
        instead of being told to await a restart that never comes."""
        with self._lock:
            self._quiesced = False

    def close(self) -> None:
        """Mark end-of-stream: queued batches drain, then the job's source
        ends normally (final pane flush, final checkpoint, DONE)."""
        with self._lock:
            self._closed = True
        wake = self.on_data
        if wake is not None:
            wake()

    def quiesce(self) -> None:
        """Drain step 1: stop accepting pushes AND stop the scheduler from
        starting new windows (``ready()`` goes False).  In-flight windows
        are the cancel path's to flush; queued-but-unfolded edges past the
        last closed window are abandoned — the client re-pushes them from
        the drain cursor (state stays exactly-once because those panes
        never reached a checkpoint)."""
        with self._lock:
            self._quiesced = True

    # -- scheduler side ------------------------------------------------------

    def ready(self) -> bool:
        """True when one scheduler pull is guaranteed not to block on the
        network: the source is closed (everything left is queued), or at
        least one UNDELIVERED ingest window is closable from the queued
        edges.

        Exact positional accounting, not a heuristic: batches arrive
        contiguously, so window ``k`` is closable once edge ``(k+1)*W``
        has been accepted (the pane cutter closes a window when the first
        edge of the NEXT one arrives), and the windows already pulled
        through are ``(edges_out - 1) // W`` (the consumer's position is
        past each closed window's boundary edge).  Restored (filler)
        windows never emit, so the floor is the resume cursor's window
        count — a pull before real data reached the next closable boundary
        would consume the filler and then block polling the empty queue.
        """
        with self._lock:
            if self._quiesced:
                return False
            if self._closed:
                return True
            w = self.cfg.ingest_window_edges
            if not w:
                # a single global pane only emits at end-of-stream: nothing
                # to schedule until the client closes
                return False
            closable = (self._edges_in - 1) // w if self._edges_in else 0
            pulled = (self._edges_out - 1) // w if self._edges_out else 0
            floor = max(pulled, self._resume_edges // w)
            return closable > floor + self._headroom

    @property
    def queued_batches(self) -> int:
        """Current ingest-queue occupancy (approximate, lock-free)."""
        return self._q.qsize()

    def progress(self) -> dict:
        """The health plane's progress probe (ISSUE 10): one consistent-
        enough snapshot of the source's positional accounting for the
        scheduler's gauge sampler (runtime/manager.py _sample_health).

        * ``backlog_age_s`` rides the enqueue timestamps the queue tuples
          already carry for the push-to-fold histogram — the OLDEST one
          is how long this job has not been keeping up (a depth gauge
          alone can't distinguish a 100 ms blip from a wedged minute).
        * ``closable_windows`` / ``delivered_windows`` are exactly
          ``ready()``'s accounting, surfaced: their gap is the job's
          watermark lag in ingest windows.

        Pure host counter reads under the two existing locks (taken in
        sequence, never nested) — called at the health sample rate, not
        per push or per pull, so it adds nothing to either hot path.
        """
        now = time.perf_counter()
        with self._q.mutex:  # qsize()'s own lock; peek needs it too
            depth = len(self._q.queue)
            oldest_t = self._q.queue[0][2] if depth else None
            cap_batches = self._q.maxsize
        with self._lock:
            edges_in = self._edges_in
            edges_out = self._edges_out
        w = self.cfg.ingest_window_edges
        closable = (edges_in - 1) // w if (w and edges_in) else 0
        delivered = (edges_out - 1) // w if (w and edges_out) else 0
        if w:
            # the same resume floor ready() applies: the checkpoint-covered
            # filler region counts as delivered (those windows replay-skip,
            # they are not lag) — without it every restore would page a
            # watermark-lag SLO until the client streamed past the cursor
            delivered = max(delivered, self._resume_edges // w)
        # age counts only while a closable window sits undelivered: a tail
        # batch the pane cutter is HOLDING for its window to fill is the
        # stream trickling, not the job falling behind — ageing it would
        # page on every live stream's boundary-straddling remainder
        lagging = closable > delivered and oldest_t is not None
        return {
            "edges_in": edges_in,
            "edges_out": edges_out,
            "backlog_batches": depth,
            "backlog_edges": depth * self.batch,
            "backlog_age_s": (now - oldest_t) if lagging else 0.0,
            "queue_capacity_edges": cap_batches * self.batch,
            "closable_windows": closable,
            "delivered_windows": delivered,
        }

    @property
    def edges_accepted(self) -> int:
        """Total edges accepted, resume filler included."""
        with self._lock:
            return self._edges_in

    def stream(self) -> EdgeStream:
        """The job-facing EdgeStream (one consumer: the job built over it).

        Rides ``from_batches`` — the windowed ingestion-pane planes (sync /
        async / superbatch / owner-sharded by config), which are exactly
        the planes with per-window running emission and positional
        checkpoints.  The pushed wire buffers already crossed the SOCKET
        compressed (that was the link, the measured bottleneck); host-side
        they decode once at validation time and re-enter the pane planes'
        normal pack/transfer machinery.
        """
        return EdgeStream.from_batches(self._factory, self.cfg)

    def _factory(self) -> Iterator[EdgeBatch]:
        # resume filler: synthesize the checkpoint-covered region so pane
        # ids line up; the merge loop skips these panes before any fold
        # (values never matter — zeros), the client pushes from the cursor
        left = self._resume_edges
        while left > 0:
            n = min(left, self.batch)
            zeros = np.zeros((n,), np.int32)
            with self._lock:
                self._edges_out += n
            left -= n
            yield EdgeBatch.from_host_arrays(zeros, zeros, pad_to=self.batch)
        # the window the next batch's first edge falls in: what a wait in
        # the queue's get is waiting to fill
        w = self.cfg.ingest_window_edges
        filling = self._resume_edges // w if w else -1
        while True:
            # end-of-stream must not cost a poll slice: once the source is
            # closed the queue can only drain, so a non-blocking get is
            # exact — the previous blocking get paid its full timeout ON
            # THE SCHEDULER THREAD at every job's end before noticing the
            # close (measured ~50 ms/job of serialized scheduler stall in
            # the serving bench's fold phase)
            with self._lock:
                closed = self._closed
            try:
                with tracing.span("gelly.source.wait", window=filling):
                    if closed:
                        s, d, t_pushed, release = self._q.get_nowait()
                    else:
                        s, d, t_pushed, release = self._q.get(timeout=0.05)
            except queue.Empty:
                with self._lock:
                    if self._closed and self._q.empty():
                        return
                continue
            # queue residency = push-to-fold latency: this factory is
            # pulled on the scheduler thread under the job's pull, so the
            # thread-local job tag scopes the sample to this job too
            metrics.hist_record(
                "push_to_fold_ms", (time.perf_counter() - t_pushed) * 1e3
            )
            with self._lock:
                self._edges_out += len(s)
                if w:
                    filling = self._edges_out // w
            if release is not None:
                # the arena's donation fence: the host batch aliases its
                # arrays (the ArenaPool ownership rule), so the rows are
                # copied out BEFORE the arena rejoins the pool's free-list
                s, d = np.array(s), np.array(d)
                release()
            # host-array batches: the pane cutter consumes numpy directly,
            # so the per-batch jnp round trip (the measured ceiling of
            # this path — ISSUE 14) never happens
            yield EdgeBatch.from_host_arrays(s, d, pad_to=self.batch)


def unbounded_generated_stream(
    cfg: StreamConfig,
    num_vertices: Optional[int] = None,
    seed: int = 0,
    max_batches: Optional[int] = None,
) -> EdgeStream:
    """UNBOUNDED uniform random edge stream (untimed).

    The reference's default mode is an endless ingestion-time stream with
    running per-window emission (SimpleEdgeStream.java:69-73); pair this
    source with ``cfg.ingest_window_edges`` (or ``ingest_window_ms``) so
    aggregations emit running summaries instead of waiting for an
    end-of-stream that never comes.  ``max_batches`` bounds the stream for
    tests/demos; None streams forever.
    """
    from gelly_streaming_tpu.core.types import EdgeBatch

    n_v = num_vertices or cfg.vertex_capacity

    def factory():
        rng = np.random.default_rng(seed)
        k = 0
        while max_batches is None or k < max_batches:
            src = rng.integers(0, n_v, cfg.batch_size).astype(np.int32)
            dst = rng.integers(0, n_v, cfg.batch_size).astype(np.int32)
            yield EdgeBatch.from_arrays(src, dst)
            k += 1

    return EdgeStream.from_batches(factory, cfg)
