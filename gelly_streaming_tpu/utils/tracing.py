"""Per-window span tracing and bounded latency histograms (ISSUE 9).

The reference delegates monitoring wholesale to the Flink runtime (latency
tracking, operator metrics — PAPER.md §1); this TPU-native rebuild supplies
that slice of the runtime itself.  Three primitives live here:

* **WindowSpan / FlightRecorder** — each sampled window (or micro-batch)
  gets a trace id at the pack thread and accumulates monotonic-clock stage
  intervals as it crosses pack -> transfer -> dispatch -> drain -> emit;
  finished spans land in a lock-guarded fixed-capacity ring buffer (the
  "flight recorder"), dumped by the server's ``trace`` verb and auto-
  attached to a FAILED job's status for post-mortems.  Sampling is
  per-run (``cfg.trace_sample`` / ``GELLY_TRACE_SAMPLE``, default 0 = off):
  planes resolve a :func:`sampler` ONCE outside their loops, so the off
  path costs one ``is not None`` branch per window — no allocation, no
  lock, no clock read (the overhead-regression test pins this).

* **LatencyHistogram** — log-bucketed fixed-size latency distribution
  replacing the unbounded per-sample lists: 240 buckets at 8 per octave
  from ~1 µs, so any value maps to a bucket whose lower bound is within
  2^(1/8)-1 ≈ 9% below it, in O(1) memory forever.  Quantiles use proper
  NEAREST-RANK math (rank ``ceil(p/100 * N)``, 1-based — the off-by-one
  the old ``WindowLatencyRecorder.percentile`` int-floor had is pinned
  fixed by tests/test_tracing.py's exact-value cases).

* **span** — the served path's one span primitive, on the profiler's
  clock.  Each step of a window (scheduler pull, pane cut, host->device
  put, fold and combine dispatch, sink wait and copy, push and results
  framing) opens a ``jax.profiler.TraceAnnotation`` tagged with the
  window's ``(job, window)``, which lands in the profiler's host plane
  beside the device trace when a profile is being taken and costs a
  ``TraceMe.is_enabled()`` check otherwise.  On a sampled window the same
  span also marks its flight-recorder stage (the ``SPANS`` table below),
  so the ``trace`` verb and the FAILED-job post-mortem read the same
  steps.  Spans never run inside native or jitted code and never wait on
  the device themselves.

A span's ``stages`` list is appended from several pipeline threads, but
never concurrently: each stage's thread hands the window to the next
through a queue (Prefetcher queues, the completion deque, a job's
emission queue), and that handoff is the synchronization — the same
ownership discipline transfer arenas ride.  Only the RING is shared for
real (drain threads of many jobs write, server/status threads read), so
only the ring is lock-guarded (the analyzer's lock-discipline pass pins
the annotation; tests/analysis_corpus/{good,bad}_tracing.py is the
corpus pair).
"""

from __future__ import annotations

import itertools
import math
import os
import sys
import threading
import time
from typing import List, Optional


# ---------------------------------------------------------------------------
# nearest-rank percentile (shared by the histogram and the recorder shim)


def nearest_rank(sorted_xs, p: float) -> float:
    """The p-th percentile of an ascending sequence by the nearest-rank
    definition: the value at 1-based rank ``ceil(p/100 * N)`` (floored at
    rank 1, so p=0 returns the minimum and p=100 the maximum with no
    index clamp needed).

    This is the fix for the old ``int(len * p / 100)`` index: that floors
    a MIDPOINT rank up into the next element (p50 of [1, 2] returned 2,
    not the rank-1 value 1) and overflows at p=100 without a clamp.
    """
    n = len(sorted_xs)
    if n == 0:
        return 0.0
    rank = max(1, math.ceil(p / 100.0 * n))
    return sorted_xs[min(rank, n) - 1]


# ---------------------------------------------------------------------------
# log-bucketed bounded latency histogram


class LatencyHistogram:
    """Fixed-size log-bucketed latency histogram (milliseconds).

    Bucket ``i`` covers ``[LO_MS * 2**(i/PER_OCTAVE), LO_MS *
    2**((i+1)/PER_OCTAVE))``; with ``LO_MS = 2**-10`` (~1 µs) and 240
    buckets the range tops out around 17 minutes, and values beyond clamp
    into the edge buckets.  Reported quantiles are the NEAREST-RANK
    bucket's lower bound — an underestimate by at most one bucket width
    (2^(1/8)-1 ≈ 9%) — which makes quantiles exact for values recorded
    precisely on bucket boundaries (the exact-value tests use this).

    Thread-safe: ``record`` takes one lock per sample; samples are
    per-window/per-request events, not per-edge, so this is the same cost
    class as the existing pipeline counters.
    """

    LO_MS = 2.0 ** -10  # ~0.98 µs: bucket boundaries land on powers of 2
    PER_OCTAVE = 8
    BUCKETS = 240

    __slots__ = ("_lock", "_counts", "_count", "_sum_ms", "_min_ms", "_max_ms")

    def __init__(self):
        self._lock = threading.Lock()
        self._counts = [0] * self.BUCKETS  # guarded-by: _lock
        self._count = 0  # guarded-by: _lock
        self._sum_ms = 0.0  # guarded-by: _lock
        self._min_ms = math.inf  # guarded-by: _lock
        self._max_ms = 0.0  # guarded-by: _lock

    @classmethod
    def bucket_index(cls, ms: float) -> int:
        if ms <= cls.LO_MS:
            return 0
        # the epsilon keeps values recorded exactly ON a boundary in the
        # bucket whose lower bound they are (float log2 may land a hair
        # under the integer)
        i = int(cls.PER_OCTAVE * math.log2(ms / cls.LO_MS) + 1e-9)
        return min(i, cls.BUCKETS - 1)

    @classmethod
    def bucket_lower(cls, i: int) -> float:
        return cls.LO_MS * 2.0 ** (i / cls.PER_OCTAVE)

    def record(self, ms: float) -> None:
        ms = float(ms)
        i = self.bucket_index(ms)
        with self._lock:
            self._counts[i] += 1
            self._count += 1
            self._sum_ms += ms
            if ms < self._min_ms:
                self._min_ms = ms
            if ms > self._max_ms:
                self._max_ms = ms

    @property
    def count(self) -> int:
        with self._lock:
            return self._count

    def totals_over(self, ms: float) -> "tuple[int, int]":
        """(total samples, samples above ``ms``) in ONE lock acquisition —
        the SLO monitors' burn-rate probe (runtime/slo.py) diffs these
        cumulative pairs across its fast/slow windows.  "Above" counts the
        buckets strictly past the one containing ``ms``, so a threshold on
        a bucket boundary is exact and any other is an underestimate of at
        most one bucket width (2^(1/8)-1 ≈ 9%) — the same tolerance the
        reported quantiles already carry."""
        i = self.bucket_index(ms)
        with self._lock:
            return self._count, sum(self._counts[i + 1:])

    def quantile(self, p: float) -> float:
        """Nearest-rank quantile over the buckets: the lower bound of the
        bucket holding the value at 1-based rank ``ceil(p/100 * N)``."""
        with self._lock:
            total = self._count
            counts = list(self._counts)
        if total == 0:
            return 0.0
        rank = max(1, math.ceil(p / 100.0 * total))
        cum = 0
        for i, c in enumerate(counts):
            cum += c
            if cum >= rank:
                return self.bucket_lower(i)
        return self.bucket_lower(self.BUCKETS - 1)

    def snapshot(self) -> dict:
        """JSON-ready summary: count/sum/min/max, p50/p90/p99, and the
        non-empty buckets as ``[bucket lower bound ms, count]`` pairs."""
        with self._lock:
            total = self._count
            counts = list(self._counts)
            sum_ms = self._sum_ms
            min_ms = self._min_ms
            max_ms = self._max_ms
        out = {
            "count": total,
            "sum_ms": round(sum_ms, 3),
            "min_ms": round(min_ms, 6) if total else 0.0,
            "max_ms": round(max_ms, 6),
        }
        for p, key in ((50, "p50_ms"), (90, "p90_ms"), (99, "p99_ms")):
            out[key] = round(self._quantile_of(counts, total, p), 6)
        out["buckets"] = [
            [round(self.bucket_lower(i), 6), c]
            for i, c in enumerate(counts)
            if c
        ]
        return out

    @classmethod
    def _quantile_of(cls, counts, total, p) -> float:
        if total == 0:
            return 0.0
        rank = max(1, math.ceil(p / 100.0 * total))
        cum = 0
        for i, c in enumerate(counts):
            cum += c
            if cum >= rank:
                return cls.bucket_lower(i)
        return cls.bucket_lower(cls.BUCKETS - 1)

# ---------------------------------------------------------------------------
# per-window spans


#: the canonical stage vocabulary, in pipeline order (the residual time a
#: window spends parked in queues between stages is reported as "queued",
#: so a span's stage durations always sum to its total wall clock)
STAGES = ("pack", "transfer", "dispatch", "drain", "emit")


class WindowSpan:
    """One window's trip through the pipeline: a trace id, the plane that
    created it, and (stage, start, duration) intervals marked by whichever
    thread owns the window at that stage (see the module docstring for why
    this needs no lock)."""

    __slots__ = ("trace_id", "plane", "window_id", "t0", "stages", "meta")

    def __init__(self, trace_id: int, plane: str, window_id: int):
        self.trace_id = trace_id
        self.plane = plane
        self.window_id = int(window_id)
        self.t0 = time.perf_counter()
        self.stages: list = []  # (name, start_s, dur_s); handoff-ordered
        self.meta: Optional[dict] = None

    def mark(self, stage: str, t_start: float, t_end: Optional[float] = None) -> None:
        """Record one stage interval from its owning thread."""
        end = time.perf_counter() if t_end is None else t_end
        self.stages.append((stage, t_start, end - t_start))

    def annotate(self, **kv) -> None:
        """Attach small JSON-able metadata (edge counts, shard ids...)."""
        if self.meta is None:
            self.meta = {}
        self.meta.update(kv)

    def finish(self, t_end: Optional[float] = None) -> dict:
        """Finalize to the JSON-ready dict the flight recorder stores.

        ``total_ms`` is creation-to-finish wall clock; the gap between the
        summed stage durations and the total — time spent parked in the
        prefetch/completion queues between stages — is reported as the
        ``queued`` stage, so the stage durations sum to ``total_ms`` by
        construction (the property the metrics-verb acceptance check
        leans on).
        """
        end = time.perf_counter() if t_end is None else t_end
        total_s = max(0.0, end - self.t0)
        stages = [
            {
                "stage": name,
                "start_ms": round((start - self.t0) * 1e3, 4),
                "ms": round(dur * 1e3, 4),
            }
            for name, start, dur in self.stages
        ]
        attributed = sum(s["ms"] for s in stages)
        queued = max(0.0, total_s * 1e3 - attributed)
        stages.append(
            {
                "stage": "queued",
                "start_ms": None,
                "ms": round(queued, 4),
            }
        )
        out = {
            "trace_id": self.trace_id,
            "plane": self.plane,
            "window": self.window_id,
            "total_ms": round(total_s * 1e3, 4),
            "stages": stages,
        }
        if self.meta:
            out["meta"] = dict(self.meta)
        return out


def find_span(obj, _depth: int = 2) -> Optional[WindowSpan]:
    """Locate a WindowSpan riding a pipeline meta tuple (depth-limited
    scan of tuples/lists only, so device-array pytrees are never walked).
    Instrumentation points that receive opaque metas (the Prefetcher's
    transfer thread, the merge loops' drain) use this instead of having a
    span parameter threaded through every plane's item shape."""
    if isinstance(obj, WindowSpan):
        return obj
    if _depth > 0 and isinstance(obj, (tuple, list)):
        for x in obj:
            span = find_span(x, _depth - 1)
            if span is not None:
                return span
    return None


# ---------------------------------------------------------------------------
# the flight recorder


def _capacity_from_env() -> int:
    try:
        return max(8, int(os.environ.get("GELLY_TRACE_CAPACITY", 256)))
    except ValueError:
        return 256


class FlightRecorder:
    """Fixed-capacity ring of finished window-span dicts.

    Shared for real across threads — every plane's drain records, server
    and status threads read — so every ring access holds the lock (the
    lock-discipline pass pins the annotations; the hammer test pins the
    no-lost-record behavior).  Recording also folds the span's stage
    durations into per-(plane, stage) aggregates, which is what the
    ``metrics`` verb reports as the per-stage timing breakdown.
    """

    def __init__(self, capacity: Optional[int] = None):
        self.capacity = capacity or _capacity_from_env()
        self._lock = threading.Lock()
        self._ring: list = [None] * self.capacity  # guarded-by: _lock
        self._next = 0  # guarded-by: _lock
        self._recorded = 0  # guarded-by: _lock
        # plane -> stage -> [count, total_ms]
        self._stage_totals: dict = {}  # guarded-by: _lock

    def record(self, span: WindowSpan, t_end: Optional[float] = None) -> dict:
        entry = span.finish(t_end)
        with self._lock:
            self._ring[self._next % self.capacity] = entry
            self._next += 1
            self._recorded += 1
            per_plane = self._stage_totals.setdefault(entry["plane"], {})
            for s in entry["stages"]:
                cell = per_plane.setdefault(s["stage"], [0, 0.0])
                cell[0] += 1
                cell[1] += s["ms"]
            per_total = per_plane.setdefault("total", [0, 0.0])
            per_total[0] += 1
            per_total[1] += entry["total_ms"]
        return entry

    def last(self, n: int = 32) -> List[dict]:
        """The most recent ``min(n, capacity)`` spans, oldest first."""
        with self._lock:
            end = self._next
            start = max(0, end - min(n, self.capacity))
            out = [
                self._ring[i % self.capacity] for i in range(start, end)
            ]
        return [e for e in out if e is not None]

    def stats(self) -> dict:
        """Aggregate view: spans recorded, ring occupancy, and the
        per-plane per-stage timing totals (count + total ms)."""
        with self._lock:
            recorded = self._recorded
            held = min(self._next, self.capacity)
            stages = {
                plane: {
                    stage: {"count": c, "total_ms": round(ms, 3)}
                    for stage, (c, ms) in per_plane.items()
                }
                for plane, per_plane in self._stage_totals.items()
            }
        return {"recorded": recorded, "held": held, "stages": stages}

    def clear(self) -> None:
        with self._lock:
            self._ring = [None] * self.capacity
            self._next = 0
            self._recorded = 0
            self._stage_totals = {}


# The recorder lock nests inside the manager lock and never the other
# way around (JobManager._fail snapshots the ring for a FAILED job's
# post-mortem) — declared so the inverse acquisition can never ship.
# lock-order: manager._lock < tracing._RECORDER_LOCK
_RECORDER_LOCK = threading.Lock()
_RECORDER: Optional[FlightRecorder] = None  # guarded-by: _RECORDER_LOCK


def flight_recorder() -> FlightRecorder:
    """The process-global flight recorder (capacity from
    ``GELLY_TRACE_CAPACITY``, default 256; created on first use)."""
    global _RECORDER
    with _RECORDER_LOCK:
        if _RECORDER is None:
            _RECORDER = FlightRecorder()
        return _RECORDER


def span_stats() -> dict:
    """``flight_recorder().stats()`` without forcing creation: zeros when
    tracing never ran (the metrics snapshot calls this unconditionally)."""
    with _RECORDER_LOCK:
        rec = _RECORDER
    if rec is None:
        return {"recorded": 0, "held": 0, "stages": {}}
    return rec.stats()


def reset_tracing() -> None:
    """Clear the flight recorder (call before a measurement window)."""
    with _RECORDER_LOCK:
        rec = _RECORDER
    if rec is not None:
        rec.clear()


# ---------------------------------------------------------------------------
# sampling


# Sticky process flag: flips True the first time any run resolves an
# active sampler, and stays up.  Read LOCK-FREE on hot paths (``active()``)
# as a cheap pre-filter for find_span scans: a stale False only delays the
# first few transfer marks of the first traced run, a stale True only
# costs a no-op scan — both benign, like queue.qsize()-style approximate
# reads elsewhere in the tree.  Writes go through _RECORDER_LOCK anyway.
_EVER_ACTIVE = False


def active() -> bool:
    """Cheap hot-path gate: has ANY tracing run ever started?"""
    return _EVER_ACTIVE


class Sampler:
    """Per-run sampling gate + span factory for one plane.

    ``begin(window_id)`` returns a WindowSpan for sampled windows and None
    otherwise, using a DETERMINISTIC stride (every ``round(1/rate)``-th
    window) so traces are reproducible run to run — no RNG in the pack
    thread.  One sampler belongs to one run's pack thread (its counter is
    single-producer by construction, like the pane cutter it sits next
    to).
    """

    __slots__ = ("plane", "rate", "_stride", "_seen")

    def __init__(self, plane: str, rate: float):
        self.plane = plane
        self.rate = float(rate)
        self._stride = max(1, round(1.0 / self.rate))
        self._seen = 0

    def begin(self, window_id: int) -> Optional[WindowSpan]:
        self._seen += 1
        if (self._seen - 1) % self._stride:
            return None
        return WindowSpan(next(_TRACE_IDS), self.plane, window_id)


_TRACE_IDS = itertools.count(1)


def resolve_sample(cfg) -> float:
    """Effective trace-sample rate: explicit config > env var > 0 (off).

    Mirrors ``async_exec.resolve_depth``: ``cfg.trace_sample`` wins when
    set; a config left at the 0 default defers to ``GELLY_TRACE_SAMPLE``
    so a whole process can be switched without threading the knob through
    every call site.
    """
    rate = float(getattr(cfg, "trace_sample", 0.0) or 0.0)
    if rate > 0:
        return min(rate, 1.0)
    env = os.environ.get("GELLY_TRACE_SAMPLE")
    if env:
        try:
            return min(max(float(env), 0.0), 1.0)
        except ValueError:
            pass
    return 0.0


def sampler(cfg, plane: str) -> Optional[Sampler]:
    """Resolve a plane's sampler ONCE, outside its dispatch loop: None
    when sampling is off, so the loop's per-window cost on the off path is
    a single ``is not None`` branch (the graftcheck-clean contract)."""
    rate = resolve_sample(cfg)
    if rate <= 0:
        return None
    global _EVER_ACTIVE
    with _RECORDER_LOCK:
        _EVER_ACTIVE = True
    return Sampler(plane, rate)


def record_event(plane: str, stage: str, t_start: float, **meta) -> None:
    """One-shot event into the flight recorder (setup-time happenings like
    a mesh build — not per-window, so it bypasses sampling; no-op until
    tracing has been activated by some run)."""
    if not active():
        return
    span = WindowSpan(next(_TRACE_IDS), plane, -1)
    span.t0 = t_start
    span.mark(stage, t_start)
    if meta:
        span.annotate(**meta)
    flight_recorder().record(span)


# ---------------------------------------------------------------------------
# served-path spans on the profiler's clock


#: The served windowed plane's span vocabulary: span name -> (the
#: flight-recorder stage it marks on a sampled window, or None; the thread
#: it runs on).  Every span of one window carries the same ``(job,
#: window)``: scheduler and sink-pump spans read it from their thread's
#: scope (``scope_job`` / ``enter_window`` / ``scope_window``), connection
#: spans carry it explicitly.
SPANS = {
    # one scheduler pull that yields a record
    "gelly.sched.pull": (None, "scheduler"),
    # blocked on the job's source, tagged with the window being filled:
    # in the source queue's get, or parked while no window is closable
    "gelly.source.wait": (None, "scheduler"),
    # the window's close (the return of its last source pull) until its
    # host arrays are ready: concatenate, bin, pad
    "gelly.pane.cut": ("pack", "scheduler"),
    # the host->device put of the pane's src / dst / mask
    "gelly.pane.put": ("transfer", "scheduler"),
    # the host enqueue of the fold executable
    "gelly.fold.dispatch": ("dispatch", "scheduler"),
    # the host enqueue of the running merge (combine) and the transform
    "gelly.combine.dispatch": ("dispatch", "scheduler"),
    # waiting for the record's device leaves to be computed
    "gelly.sink.wait": ("drain", "sink pump"),
    # the record's device->host copy
    "gelly.sink.copy": ("emit", "sink pump"),
    # one push frame (job, offset); its children decode the wire buffer
    # and enqueue the batch into the job's source (full-queue
    # backpressure waits included)
    "gelly.push": (None, "connection"),
    "gelly.push.decode": (None, "connection"),
    "gelly.push.enqueue": (None, "connection"),
    # a results fetch: waiting for records, then framing them (leaf
    # metadata and the payload join)
    "gelly.results.wait": (None, "connection"),
    "gelly.results.frame": (None, "connection"),
}

_TRACEME = None  # jax.profiler.TraceAnnotation, once jax is imported


def profiling() -> bool:
    """True while a profile is being taken in this process.  Until jax is
    imported this is one dict lookup: a process that never imported jax
    cannot be profiling (the client and the server's start-up never do)."""
    global _TRACEME
    tm = _TRACEME
    if tm is None:
        if "jax" not in sys.modules:
            return False
        from jax.profiler import TraceAnnotation

        _TRACEME = tm = TraceAnnotation
    return tm.is_enabled()


# The thread's window scope: the job its scheduler pull or sink pump
# serves, the window in hand and that window's sampled WindowSpan, the
# window's open cut span, and the emission handed to the record's
# consumer.  Thread-local, so it needs no lock: a window's scheduler-side
# spans all run on the scheduler thread, its sink spans on its job's pump.
_SCOPE = threading.local()


class Span:
    """One open span of the ``SPANS`` vocabulary (see :func:`span`)."""

    __slots__ = ("t0", "_stage", "_sampled", "_me")

    def __init__(self, name, job=None, window=None, **stats):
        self.t0 = time.perf_counter()
        self._stage = SPANS[name][0]
        self._sampled = getattr(_SCOPE, "sampled", None)
        self._me = None
        if profiling():
            if job is None:
                job = getattr(_SCOPE, "job", None)
            if window is None:
                window = getattr(_SCOPE, "window", None)
            if job is not None:
                stats["job"] = job
            if window is not None:
                stats["window"] = int(window)
            me = _TRACEME(
                name, **{k: v for k, v in stats.items() if v is not None}
            )
            me.__enter__()
            self._me = me

    def tag(self, **stats) -> None:
        """Add event stats known only after the span opened."""
        if self._me is not None:
            self._me.set_metadata(
                **{k: v for k, v in stats.items() if v is not None}
            )

    def close(self) -> None:
        """End the span (idempotent); on a sampled window, mark its stage."""
        me = self._me
        if me is not None:
            self._me = None
            me.__exit__(None, None, None)
        if self._stage is not None and self._sampled is not None:
            self._sampled.mark(self._stage, self.t0)
        self._stage = None

    def __enter__(self) -> "Span":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def span(name: str, *, job=None, window=None, **stats) -> Span:
    """Open span ``name`` (a ``SPANS`` key) now; use as a context manager
    or ``close()`` it.  ``job`` / ``window`` default to the thread's scope,
    as does the sampled WindowSpan whose stage it marks; further keyword
    arguments become event stats."""
    return Span(name, job, window, **stats)


def scope_job(job: Optional[str]) -> Optional[str]:
    """Tag this thread's spans with ``job``; returns the previous tag."""
    old = getattr(_SCOPE, "job", None)
    _SCOPE.job = job
    return old


def scope_window(window: Optional[int], sampled=None) -> None:
    """Tag this thread's spans with ``window`` and its sampled span."""
    _SCOPE.window, _SCOPE.sampled = window, sampled


def current_window() -> Optional[int]:
    return getattr(_SCOPE, "window", None)


def begin_cut(window: int) -> None:
    """The pane cutter: ``window`` closes now.  Opens its
    ``gelly.pane.cut`` for the merge loop to take (a cut already open for
    the same window keeps its earlier start)."""
    pending = getattr(_SCOPE, "cut", None)
    if pending is not None:
        if pending[0] == window:
            return
        pending[1].close()
    _SCOPE.cut = (window, Span("gelly.pane.cut", window=window))


def drop_cut() -> None:
    """A closed pane the merge loop skips (folded before a restore): end
    the cut the cutter opened for it."""
    pending = getattr(_SCOPE, "cut", None)
    if pending is not None:
        _SCOPE.cut = None
        pending[1].close()


def enter_window(window: int, sampled: Optional[WindowSpan] = None) -> None:
    """The merge loop at a pane's receipt: take the cutter's open cut span
    for ``window`` (or open one now), scope the thread's spans to the
    window, and anchor the sampled span at the window's close."""
    pending = getattr(_SCOPE, "cut", None)
    _SCOPE.cut = None
    if pending is not None and pending[0] == window:
        cut = pending[1]
    else:
        if pending is not None:
            pending[1].close()
        cut = Span("gelly.pane.cut", window=window)
    if sampled is not None:
        sampled.t0 = cut.t0
        cut._sampled = sampled
    _SCOPE.window, _SCOPE.sampled = window, sampled
    _SCOPE.open_cut = cut
    _SCOPE.t_close = cut.t0


def end_cut() -> None:
    """The window's host arrays are ready: close its cut (idempotent)."""
    cut = getattr(_SCOPE, "open_cut", None)
    if cut is not None:
        _SCOPE.open_cut = None
        cut.close()


def leave_window() -> None:
    """The window produced no record: drop the thread's window scope."""
    end_cut()
    _SCOPE.window = _SCOPE.sampled = None


class Emission:
    """What the merge loop hands a record's consumer with the record: the
    window id, its close (the start of its ``gelly.pane.cut``, on the
    ``time.perf_counter`` clock), its sampled span, and the yield time."""

    __slots__ = ("window", "t_close", "sampled", "t_emit")

    def __init__(self, window, t_close, sampled, t_emit):
        self.window = window
        self.t_close = t_close
        self.sampled = sampled
        self.t_emit = t_emit


def emit() -> Emission:
    """The merge loop at a record's yield: leave the window's scope and
    hand the emission to whoever pulls the record (:func:`claim`)."""
    end_cut()
    em = Emission(
        _SCOPE.window,
        getattr(_SCOPE, "t_close", None),
        _SCOPE.sampled,
        time.perf_counter(),
    )
    _SCOPE.window = _SCOPE.sampled = None
    _SCOPE.emission = em
    return em


def claim() -> Optional[Emission]:
    """The consumer, right after pulling a record on the same thread: the
    record's emission, or None when its plane hands none over."""
    em = getattr(_SCOPE, "emission", None)
    if em is not None:
        _SCOPE.emission = None
    return em


def settle(em: Emission) -> None:
    """The merge loop, resumed after a yield: a record whose emission no
    consumer claimed ends at its yield."""
    if getattr(_SCOPE, "emission", None) is em:
        _SCOPE.emission = None
        finish(em, em.t_emit)


def finish(em: Emission, t_end: Optional[float] = None) -> None:
    """Record the emission's sampled span, if any, into the recorder."""
    if em.sampled is not None:
        flight_recorder().record(em.sampled, t_end)
