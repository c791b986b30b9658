"""JobManager: many concurrent streaming queries over one device pipeline.

The scheduling model is COOPERATIVE: one scheduler thread round-robins the
runnable jobs in weighted-fair rounds, pulling each job's record iterator
``weight * fair_quantum`` times per round.  One pull advances that job's
query by one emission — which, under the hood, dispatches its next
window(s) through the existing pack/transfer/dispatch/drain pipeline
(core/async_exec.py when the job's ``StreamConfig.async_windows`` > 0, the
synchronous loops otherwise).  Nothing about the per-query execution
changes: the same merge loops, the same checkpoints, and — decisively —
the same process-global ``compile_cache``, so N same-shape jobs share one
set of compiled executables and co-scheduling costs scheduling, not N
compilations (the GraphBLAST kernel-reuse observation applied to tenancy).

Isolation boundaries:

* **Admission** (``submit``): bounded concurrent jobs and bounded
  aggregate summary-state bytes.  Over-capacity submits raise
  ``AdmissionError`` immediately — never a hang.
* **Per-job bounded emission queues**: the scheduler only ever
  ``put_nowait``s; a job whose sink lags until its queue fills is simply
  skipped for the round (``job_queue_full_skips`` counts it) while other
  jobs keep dispatching.  A slow sink slows ITS job, nothing else.
* **Per-job bounded device work**: a job with ``_DEVICE_AHEAD`` emitted
  records still computing on the device is skipped for the round
  (``job_device_wait_skips``), so asynchronous dispatch cannot stack a
  job's emission queue of summaries on the device ahead of their folds.
* **Per-job checkpoints**: each job snapshots its own position/summary
  through the unchanged ``utils/checkpoint.py`` machinery, so jobs
  crash-resume independently.

Failure is per-job too: an exception from one job's iterator marks that
job FAILED (the cause lands on ``job.error``) and the round continues with
the rest.
"""

from __future__ import annotations

import itertools
import queue
import threading
import time
from typing import Callable, Dict, Iterator, List, Optional

# The sanctioned global lock order (pinned by graftcheck pass #7): the
# manager lock is the runtime's root, and the journal/metrics registries
# are LEAF locks — emitting or counting under the manager lock is the
# documented-safe direction, and nothing called under a leaf lock may
# re-enter the manager.
# lock-order: manager._lock < events._JOURNAL_LOCK < events.EventJournal._lock
# lock-order: manager._lock < metrics._JOB_LOCK
# lock-order: manager._lock < metrics._HEALTH_LOCK
# lock-order: manager._lock < metrics._ALERT_LOCK
# lock-order: manager._lock < metrics._SCALE_LOCK
# lock-order: manager._lock < metrics._HIST_LOCK

from gelly_streaming_tpu.core.config import RuntimeConfig
from gelly_streaming_tpu.core.windows import FoldRequest, stack_fold_rows
from gelly_streaming_tpu.runtime.job import (
    _SENTINEL,
    AdmissionError,
    Job,
    JobState,
)
from gelly_streaming_tpu.utils import events, metrics, tracing

# distinguishes "initiate a fresh pull" from "resume a parked FoldRequest
# with this fused partial" (which may legitimately be None — the solo
# fallback) in the scheduler's pull loop
_FRESH = object()

# A job's emitted records whose device work may still be running: while
# this many are unfinished, a fresh pull waits (the job is skipped for the
# round, ``job_device_wait_skips``).  Dispatch is asynchronous, so without
# it a backlogged job stacks a whole emission queue of summaries on the
# device ahead of the folds that compute them, past what admission
# charged it.  Two keeps the cut of window w+1 overlapping fold(w).
_DEVICE_AHEAD = 2


def _device_leaves(rec, depth: int = 2) -> list:
    """The device arrays in ``rec``: its pytree leaves, and those among the
    attributes of host wrapper objects (a summary record such as connected
    components' ``DisjointSet`` is not a pytree), ``depth`` objects deep."""
    import jax

    out = []
    for leaf in jax.tree_util.tree_leaves(rec):
        if isinstance(leaf, jax.Array):
            out.append(leaf)
        elif depth and isinstance(getattr(leaf, "__dict__", None), dict):
            out += _device_leaves(vars(leaf), depth - 1)
    return out


def _computed(rec) -> bool:
    """True once every device array in ``rec`` is computed; never blocks.
    A deleted array holds no buffer (and is_ready() must not see it)."""
    return all(x.is_deleted() or x.is_ready() for x in _device_leaves(rec))


def _device_behind(job: Job) -> bool:  # single-thread: scheduler
    """Drop the job's computed records from its unfinished list; True while
    ``_DEVICE_AHEAD`` of them are still running on the device."""
    unfinished = job._unfinished
    while unfinished and _computed(unfinished[0]):
        unfinished.popleft()
    return len(unfinished) >= _DEVICE_AHEAD


class _Quantum:
    """One job's in-flight weighted-fair round, parkable mid-pull.

    The fused-dispatch continuation: when a job's iterator yields a
    ``FoldRequest`` instead of a record, its quantum parks here — credits
    spent so far, the rolling dispatch clock, and the parked request — so
    the scheduler can collect same-key requests from OTHER jobs' quanta
    into one cohort before resuming each with its row of the mega-fold.
    Touched by the one scheduler thread only; lives for one round.
    """

    __slots__ = ("job", "credits", "pulled", "t_round", "t_prev", "request")

    def __init__(self, job: Job, credits: int, t_round: float):
        self.job = job
        self.credits = credits
        self.pulled = 0
        self.t_round = t_round
        # rolling dispatch clock (one perf_counter read per record, not
        # two: each record's dispatch_s spans from the previous read)
        self.t_prev = t_round
        self.request: Optional[FoldRequest] = None


class JobManager:
    """Submit / pause / resume / cancel / status over a shared scheduler.

    Use as a context manager in tests and drivers: ``__exit__`` cancels
    whatever is still live and joins the scheduler thread.
    """

    def __init__(self, cfg: Optional[RuntimeConfig] = None):
        self.cfg = cfg or RuntimeConfig()
        self._lock = threading.RLock()
        self._jobs: Dict[str, Job] = {}  # guarded-by: _lock
        self._admitted_bytes = 0  # guarded-by: _lock
        # state bytes held OUT of the open pool by in-flight rescale swaps
        # (begin_rescale moves a draining job's budget here, priced at the
        # NEW geometry; submit(reserved_bytes=...) consumes it) — counted
        # against max_state_bytes by every admission check, so a
        # concurrent tenant can never steal a swap's budget mid-drain
        self._reserved_bytes = 0  # guarded-by: _lock
        # job SLOTS held the same way: mid-swap the draining job reads
        # terminal, so without this a concurrent submit could fill
        # max_jobs during the drain and strand the resubmit
        self._reserved_jobs = 0  # guarded-by: _lock
        self._seq = itertools.count()
        self._stop = False  # guarded-by: _lock
        self._thread: Optional[threading.Thread] = None  # guarded-by: _lock
        # scheduler parks on this when no job can make progress; submits,
        # resumes, cancels, and consumer gets wake it
        self._wake = threading.Event()
        # health-plane sampling (ISSUE 10): the scheduler loop samples each
        # live job's keep-up gauges every health_sample_s seconds — all
        # state below is touched by the scheduler thread only
        self._health_every = float(self.cfg.health_sample_s or 0.0)
        self._next_health = 0.0  # single-thread: scheduler
        self._keepup: Dict[str, metrics.KeepUpTracker] = {}  # single-thread: scheduler
        # the last job this round skipped because its source had no
        # closable window: the round's park is that job's source wait
        self._source_waiter: Optional[Job] = None  # single-thread: scheduler
        # SLO burn-rate monitor (runtime/slo.py): started with the
        # scheduler when cfg.slos is non-empty, stopped at shutdown
        self._slo_monitor = None  # guarded-by: _lock
        # elastic control plane (runtime/autoscale.py): started with the
        # scheduler when cfg.autoscale / GELLY_AUTOSCALE resolves on
        self._autoscaler = None  # guarded-by: _lock

    # -- submission ----------------------------------------------------------

    def submit(
        self,
        build: Callable[[], Iterator[tuple]],
        *,
        name: Optional[str] = None,
        sink: Optional[Callable] = None,
        weight: int = 1,
        checkpoint_path: Optional[str] = None,
        state_bytes: int = 0,
        edges_per_record: int = 0,
        edges_hint: Optional[int] = None,
        ready: Optional[Callable[[], bool]] = None,
        progress: Optional[Callable[[], dict]] = None,
        reserved_bytes: Optional[int] = None,
    ) -> Job:
        """Admit a query whose ``build()`` returns a fresh records iterator
        (the ``OutputStream`` contract: ``iter(stream.aggregate(...))``).

        ``state_bytes`` is the job's summary-state footprint charged
        against ``RuntimeConfig.max_state_bytes`` (descriptors compute it
        via ``SummaryAggregation.state_nbytes``; ``submit_aggregation``
        fills it in).  Raises ``AdmissionError`` when either cap would be
        exceeded — the job is NOT enqueued.

        ``ready`` (externally-fed sources, e.g. the network ingest plane's
        ``NetworkEdgeSource.ready``): a thread-safe, non-blocking callable
        the scheduler consults before pulling; False skips the job for the
        round (counted as ``job_source_wait_skips``) so a starved source
        idles its own job, never the scheduler.  Producers should ``poke()``
        the manager after feeding the source.

        ``progress`` (optional, same thread-safety contract as ``ready``):
        a probe returning the source's progress dict (see
        ``NetworkEdgeSource.progress``) for the health plane's keep-up
        gauges; jobs without one still get sink-side gauges.

        ``reserved_bytes`` (None = a normal submit): this is a rescale
        RESUBMIT — consume that many bytes of an in-flight swap
        reservation (``begin_rescale``) plus the job slot it holds,
        instead of fresh budget.  Must not exceed the outstanding
        reservation.
        """
        state_bytes = int(state_bytes)
        swap_submit = reserved_bytes is not None
        reserved_bytes = int(reserved_bytes or 0)
        with self._lock:
            if self._stop:
                raise RuntimeError("JobManager is shut down")
            if reserved_bytes < 0 or reserved_bytes > self._reserved_bytes:
                raise ValueError(
                    f"reserved_bytes ({reserved_bytes}) exceeds the "
                    f"outstanding swap reservation ({self._reserved_bytes})"
                )
            if swap_submit and self._reserved_jobs < 1:
                raise ValueError(
                    "reserved_bytes passed without an outstanding rescale "
                    "slot (begin_rescale reserves one per swap)"
                )
            active = [
                j
                for j in self._jobs.values()
                if not j._state_in(*JobState.TERMINAL)
            ]
            # in-flight swaps hold their job slots; a swap's own resubmit
            # consumes (exactly) the slot its begin_rescale reserved
            slots_held = len(active) + self._reserved_jobs - (
                1 if swap_submit else 0
            )
            if slots_held >= self.cfg.max_jobs:
                self._reject(
                    name,
                    f"job cap reached: {len(active)} active + "
                    f"{self._reserved_jobs} rescaling jobs >= "
                    f"max_jobs={self.cfg.max_jobs}",
                )
            # swap reservations count as committed: the open pool is
            # admitted + reserved, and a rescale submit's own reservation
            # covers (exactly) that much of its price
            committed = self._admitted_bytes + self._reserved_bytes
            if (
                self.cfg.max_state_bytes
                and committed + state_bytes - reserved_bytes
                > self.cfg.max_state_bytes
            ):
                self._reject(
                    name,
                    f"state-byte cap reached: {self._admitted_bytes} admitted"
                    f" + {self._reserved_bytes} reserved"
                    f" + {state_bytes} requested > "
                    f"max_state_bytes={self.cfg.max_state_bytes}",
                )
            if checkpoint_path is not None and any(
                j.checkpoint_path == checkpoint_path
                for j in active
            ):
                # two live jobs interleaving saves into ONE snapshot file
                # would corrupt both resumes; derive per-job files from a
                # shared prefix with utils.checkpoint.per_job_file instead
                self._reject(
                    name,
                    f"checkpoint path {checkpoint_path!r} is already in use "
                    "by an active job (use checkpoint.per_job_file to key a "
                    "shared prefix per job)",
                )
            job_id = name or f"job-{next(self._seq)}"
            if job_id in self._jobs and not self._jobs[job_id]._state_in(
                *JobState.TERMINAL
            ):
                self._reject(job_id, f"job name {job_id!r} is already active")
            self._evict_old_terminal()
            job = Job(
                job_id,
                build,
                manager_lock=self._lock,
                sink=sink,
                weight=weight,
                checkpoint_path=checkpoint_path,
                state_bytes=state_bytes,
                edges_per_record=edges_per_record,
                edges_hint=edges_hint,
                queue_depth=self.cfg.job_queue_depth,
                ready=ready,
                progress=progress,
            )
            job._manager = self
            self._jobs[job_id] = job
            self._admitted_bytes += state_bytes
            self._reserved_bytes -= reserved_bytes
            if swap_submit:
                self._reserved_jobs -= 1
            # journal the submit BEFORE the scheduler can run the job: the
            # scheduler's PENDING->RUNNING transition must get a later seq
            # than job_submitted or replay's lifecycle chain breaks (the
            # journal lock is a leaf lock — emitting under the manager
            # lock is the documented-safe order)
            events.journal().emit(
                "job_submitted",
                job=job_id,
                weight=int(weight),
                state_bytes=state_bytes,
                checkpoint=bool(checkpoint_path),
            )
            self._ensure_scheduler()
        if sink is not None:
            self._start_sink_thread(job)
        self._wake.set()
        return job

    @staticmethod
    def _reject(name: Optional[str], msg: str) -> None:
        """Journal + raise one admission refusal (the journal records WHY
        a submit bounced, not just that a counter moved)."""
        events.journal().emit(
            "admission_reject", job=name or "?", reason=msg
        )
        raise AdmissionError(msg)

    def submit_aggregation(
        self,
        stream,
        descriptor,
        *,
        name: Optional[str] = None,
        sink: Optional[Callable] = None,
        weight: int = 1,
        checkpoint_path: Optional[str] = None,
        ready: Optional[Callable[[], bool]] = None,
    ) -> Job:
        """Submit ``descriptor.run(stream)`` as a job — the entry point that
        turns the aggregation runtime's loops into schedulable work.

        ``ready`` passes through to :meth:`submit` (the source-readiness
        gate); a shared gate also coordinates starts — submit N jobs with
        ``ready=event.is_set`` and flip the event once, and the cohort
        enters the scheduler in the same round with no submission-order
        head start (how the fairness bench isolates scheduling from
        submission stagger).

        State bytes come from ``descriptor.admission_nbytes(stream.cfg)``
        — the persistent summary PLUS the declared emission-time scratch
        (``emission_scratch``: a sketch's top-k heap, gathered register
        view, wedge strips).  Pricing the summary alone would let a
        thousand KB-state sketch jobs OOM on the unpriced residue;
        per-record edge accounting from the stream's ingestion-pane size
        when the source pins one (each emission covers one closed pane);
        the total-edge progress hint from ``stream.num_edges_hint()``.

        With fused dispatch resolved on (``cfg.fused_dispatch`` /
        GELLY_FUSED_DISPATCH) and the job on the plain windowed plane,
        the build produces the descriptor's cohort-member generator
        (``run_fused``) so this job's windows can stack into cross-tenant
        mega-folds; every other plane — and fused-off — keeps the exact
        ``descriptor.run`` path, which stays the equivalence oracle.
        """
        from gelly_streaming_tpu.core import aggregation

        cfg = stream.cfg
        state_bytes = descriptor.admission_nbytes(cfg)
        edges_per_record = cfg.ingest_window_edges or 0
        eligible = getattr(descriptor, "fused_eligible", None)
        if (
            aggregation.resolve_fused_dispatch(cfg)
            and eligible is not None
            and eligible(stream)
        ):
            build = lambda: descriptor.run_fused(
                stream, checkpoint_path=checkpoint_path
            )
        else:
            build = lambda: iter(
                descriptor.run(stream, checkpoint_path=checkpoint_path)
            )
        return self.submit(
            build,
            name=name,
            sink=sink,
            weight=weight,
            checkpoint_path=checkpoint_path,
            state_bytes=state_bytes,
            edges_per_record=edges_per_record,
            edges_hint=stream.num_edges_hint(),
            ready=ready,
        )

    # -- rescale budget swap (the elastic control plane, ISSUE 11) -----------

    def begin_rescale(self, job: Job, new_state_bytes: int) -> int:
        """Atomically move a live job's admitted budget into a swap
        reservation priced at its NEW geometry — step one of a live
        re-shard's re-pricing (runtime/autoscale.py).

        Under the ONE admission lock: the job's held bytes leave the
        admitted pool (its later terminal release returns nothing — the
        budget moved, it was not freed) and ``new_state_bytes`` enter the
        reservation, which every admission check counts as committed.  So
        across the whole drain -> resubmit window there is no instant
        where the old and new footprints are both charged (no 2x
        double-book) and no instant where a concurrent tenant can grab
        the freed budget (no steal).  Growth beyond the held bytes is
        admission-checked here; rejection raises ``AdmissionError`` and
        leaves the job exactly as it was.

        Returns the reserved byte count — pass it to
        ``submit(reserved_bytes=...)`` to consume, or to
        ``abort_rescale`` to return it to the pool if the swap dies.
        """
        new_state_bytes = int(new_state_bytes)
        if new_state_bytes < 0:
            raise ValueError("new_state_bytes must be >= 0")
        with self._lock:
            held = job.state_bytes
            grow = new_state_bytes - held
            if (
                self.cfg.max_state_bytes
                and grow > 0
                and self._admitted_bytes + self._reserved_bytes + grow
                > self.cfg.max_state_bytes
            ):
                self._reject(
                    job.job_id,
                    f"rescale re-pricing needs {grow} more state bytes: "
                    f"{self._admitted_bytes} admitted + "
                    f"{self._reserved_bytes} reserved + {grow} > "
                    f"max_state_bytes={self.cfg.max_state_bytes}",
                )
            self._admitted_bytes -= held
            job.state_bytes = 0  # its release now returns nothing
            self._reserved_bytes += new_state_bytes
            # the job SLOT is reserved too: the drain makes this job
            # terminal mid-swap, and a concurrent submit filling max_jobs
            # during it would strand the resubmit
            self._reserved_jobs += 1
        return new_state_bytes

    def abort_rescale(
        self,
        reserved_bytes: int,
        job: Optional[Job] = None,
        restore_state_bytes: int = 0,
    ) -> None:
        """Return an unconsumed swap reservation (bytes + job slot) to the
        open pool — the drain or resubmit failed and budget must not leak
        out of circulation.

        ``job``/``restore_state_bytes``: when the DRAIN itself failed (the
        cancel timed out and the job is still live), re-charge the job's
        original bytes out of the freed reservation — a running job whose
        ``state_bytes`` stayed zeroed would let admission stack a second
        full job on top of its live summary state.  A job that did reach a
        terminal state restores nothing (its budget is correctly free).
        """
        with self._lock:
            self._reserved_bytes = max(
                0, self._reserved_bytes - int(reserved_bytes)
            )
            self._reserved_jobs = max(0, self._reserved_jobs - 1)
            if (
                job is not None
                and restore_state_bytes
                and not job._state_in(*JobState.TERMINAL)
            ):
                job.state_bytes = int(restore_state_bytes)
                self._admitted_bytes += int(restore_state_bytes)
        self._wake.set()

    @property
    def autoscaler(self):
        """The elastic control plane's policy thread, or None when
        ``RuntimeConfig.autoscale`` / ``GELLY_AUTOSCALE`` left it off (or
        no job has started the scheduler yet).  The serving plane
        registers its rescale handles here."""
        with self._lock:
            return self._autoscaler

    # holds-lock: _lock
    def _evict_old_terminal(self) -> None:
        """Bound the terminal-job history to ``keep_terminal_jobs`` (oldest
        first; dict order is submission order).  Caller holds _lock.  The
        evicted jobs' per-job metrics rows are dropped too — the module
        totals keep their contribution, so a long-lived serving process's
        footprint is bounded while its aggregates stay exact."""
        with self._lock:
            terminal = [
                job_id
                for job_id, j in self._jobs.items()
                if j._state_in(*JobState.TERMINAL)
            ]
            excess = len(terminal) - self.cfg.keep_terminal_jobs
            for job_id in terminal[: max(0, excess)]:
                del self._jobs[job_id]
                metrics.drop_job_stats(job_id)

    # -- lifecycle commands --------------------------------------------------

    def pause(self, job: Job) -> bool:
        """Stop scheduling ``job`` after its in-progress pull completes.

        The iterator stays suspended in place and the job's checkpoint
        keeps its last saved position; ``resume`` continues exactly where
        pulling stopped, so pause/resume is emission-exact in process and
        checkpoint-exact across one (crash-resume replays from the
        snapshot, the merge loops' existing contract).

        Best-effort by design: the scheduler may finish or fail the job
        concurrently with this call, so an un-pausable state (DRAINING /
        terminal) returns False rather than racing the caller into an
        exception — the check and the transition are one atomic step under
        the manager lock.
        """
        with self._lock:
            if not job._state_in(JobState.PENDING, JobState.RUNNING):
                return False
            job._transition(JobState.PAUSED)
            return True

    def resume(self, job: Job) -> bool:
        """PAUSED -> RUNNING; False if the job is not paused (same
        best-effort contract as ``pause``)."""
        with self._lock:
            if not job._state_in(JobState.PAUSED):
                return False
            job._transition(JobState.RUNNING)
        self._wake.set()
        return True

    def cancel(
        self, job: Job, wait: bool = True, timeout: Optional[float] = 30.0
    ) -> bool:
        """Request cancellation; the SCHEDULER performs it (closing the
        job's iterator mid-``next()`` from another thread is illegal), so
        the cancel rides the same thread that owns the generator: close ->
        the merge loop's GeneratorExit drain recycles in-flight arenas ->
        CANCELLED, with already-queued emissions left deliverable (dropping
        them would gap the at-least-once emission contract).  With ``wait``
        (default) blocks until terminal; returns whether the job IS
        terminal on return."""
        with self._lock:
            if job._state_in(*JobState.TERMINAL):
                return True
            job._cancel_requested = True
        self._wake.set()
        if wait:
            return job.wait(timeout)
        return job._state_in(*JobState.TERMINAL)

    def status(self) -> dict:
        """Per-job status snapshot + module totals.

        ``jobs`` maps job id -> {state, weight, queue_depth, checkpoint,
        error, and the per-job counters from utils.metrics.job_stats:
        records, dispatches, edges, dispatch seconds, stall/skip counts,
        queue-depth high-water}.  ``totals`` preserves the module
        aggregates as sums (max for high-water marks).
        """
        with self._lock:
            jobs = dict(self._jobs)
            admitted = self._admitted_bytes
            reserved = self._reserved_bytes
            dumps = {
                job_id: job._trace_dump for job_id, job in jobs.items()
            }
            fused = {
                job_id: job._fused_windows for job_id, job in jobs.items()
            }
        out = {}
        for job_id, job in jobs.items():
            row = {
                "state": job.state,
                "weight": job.weight,
                "queue_depth": job.queue_depth,
                "fused_windows": fused[job_id],
                "state_bytes": job.state_bytes,
                "edges_hint": job.edges_hint,
                "checkpoint_path": job.checkpoint_path,
                "error": repr(job.error) if job.error is not None else None,
                **metrics.job_stats(job_id),
            }
            latency = metrics.job_latency_snapshot(job_id)
            if latency:
                row["latency_ms"] = latency
            health = metrics.job_health(job_id)
            if health:
                row["health"] = health
            scale = metrics.job_scale(job_id)
            if scale:
                row["scale"] = scale
            alerts = metrics.alerts_for("job", job_id)
            if alerts:
                row["alerts"] = alerts
            if dumps[job_id] is not None:
                # the FAILED post-mortem: the flight recorder's last spans
                # at the moment the job died (see _fail)
                row["trace"] = dumps[job_id]
            out[job_id] = row
        return {
            "jobs": out,
            "admitted_state_bytes": admitted,
            "reserved_state_bytes": reserved,
            "totals": metrics.job_totals(),
        }

    def poke(self) -> None:
        """Wake the scheduler to re-check job readiness — producers feeding
        an externally-driven source (``submit(ready=...)``) call this after
        queueing data so the next round starts now rather than at the
        parked loop's 50 ms re-check."""
        self._wake.set()

    def wait_all(self, timeout: Optional[float] = None) -> bool:
        """Block until every submitted job is terminal (True) or the
        timeout elapses (False)."""
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._lock:
            jobs = list(self._jobs.values())
        for job in jobs:
            left = None if deadline is None else deadline - time.monotonic()
            if left is not None and left <= 0:
                return False
            if not job.wait(left):
                return False
        return True

    def shutdown(self, cancel: bool = True, timeout: float = 60.0) -> None:
        """Stop the scheduler.  ``cancel`` (default) cancels live jobs
        first — their in-flight windows drain through the completion-queue
        path; ``cancel=False`` waits for them to finish instead."""
        with self._lock:
            jobs = list(self._jobs.values())
        if cancel:
            for job in jobs:
                self.cancel(job, wait=False)
        self.wait_all(timeout)
        with self._lock:
            self._stop = True
            thread = self._thread
            monitor = self._slo_monitor
            self._slo_monitor = None
            autoscaler = self._autoscaler
            self._autoscaler = None
        self._wake.set()
        if monitor is not None:
            monitor.stop()
        if autoscaler is not None:
            autoscaler.stop()
        if thread is not None:
            thread.join(timeout)

    def __enter__(self) -> "JobManager":
        return self

    def __exit__(self, *exc) -> None:
        self.shutdown(cancel=True)

    # -- scheduler internals -------------------------------------------------

    # holds-lock: _lock
    def _ensure_scheduler(self) -> None:
        """Start the scheduler thread on first submit; caller holds _lock.
        The SLO monitor (when objectives are configured) starts and stops
        with it — a manager that never runs a job never pays a thread."""
        with self._lock:
            if self._thread is None or not self._thread.is_alive():
                self._thread = threading.Thread(
                    target=self._loop, name="gelly-job-scheduler", daemon=True
                )
                self._thread.start()
            if self.cfg.slos and self._slo_monitor is None:
                from gelly_streaming_tpu.runtime.slo import SLOMonitor

                self._slo_monitor = SLOMonitor(
                    self.cfg.slos, interval_s=self.cfg.slo_interval_s
                )
                self._slo_monitor.start()
            if self._autoscaler is None:
                from gelly_streaming_tpu.runtime.autoscale import (
                    Autoscaler,
                    resolve_autoscale,
                )

                if resolve_autoscale(self.cfg):
                    self._autoscaler = Autoscaler(self.cfg.autoscale_policy)
                    self._autoscaler.start()

    def _start_sink_thread(self, job: Job) -> None:
        """Per-job sink pump: drains the bounded queue into the sink on its
        own thread, so sink latency lands on this job alone."""

        def pump():  # single-thread: per-job sink pump
            tracing.scope_job(job.job_id)
            while True:
                item = job._out.get()
                if item is _SENTINEL:
                    break
                # (record, emission): the emission names the record's
                # window, so the sink's spans (tracing.SPANS) share the
                # window's (job, window) with its scheduler-side spans
                rec, em = item
                t0 = time.perf_counter()
                if em is not None:
                    tracing.scope_window(em.window, em.sampled)
                try:
                    job.sink(rec)
                except BaseException as e:
                    self._fail(job, e)
                    break
                finally:
                    tracing.scope_window(None)
                t1 = time.perf_counter()
                metrics.job_add(job.job_id, "job_sink_stall_s", t1 - t0)
                if em is not None:
                    tracing.finish(em, t1)
                    if em.t_close is not None:
                        # the window's close to its record on the host,
                        # scoped explicitly: this thread carries no
                        # set_hist_job tag
                        metrics.hist_record(
                            "window_close_to_emission_ms",
                            (t1 - em.t_close) * 1e3,
                            job=job.job_id,
                        )
                self._wake.set()  # queue space freed: the job is runnable
            self._mark_drained(job)

        job._sink_thread = threading.Thread(
            target=pump, name=f"gelly-sink-{job.job_id}", daemon=True
        )
        job._sink_thread.start()

    def _mark_drained(self, job: Job) -> None:
        """DRAINING -> DONE once the job's sentinel was consumed (sink pump
        or ``results``); no-op for FAILED/CANCELLED drains."""
        with self._lock:
            if job._state_in(JobState.DRAINING):
                job._transition(JobState.DONE)
                self._release(job)

    # holds-lock: _lock
    def _release(self, job: Job) -> None:
        """Return a terminal job's admitted bytes and drop its source
        closure (which may capture the whole input dataset) so a retained
        terminal job costs bookkeeping, not data; caller holds _lock.
        The job's health gauge row goes too — a DONE job's last backlog
        reading must not keep an SLO alert burning (the metrics locks are
        leaf locks, safe under the manager lock)."""
        with self._lock:
            self._admitted_bytes -= job.state_bytes
            job.state_bytes = 0  # idempotent: released exactly once
            job._build = None
        metrics.drop_job_health(job.job_id)

    def _fail(self, job: Job, err: BaseException) -> None:
        """Mark FAILED from ANY thread (scheduler pull errors, sink pump
        errors).  Sentinel delivery is DEFERRED to the scheduler — only the
        scheduler thread ever puts into a job's queue, which is what makes
        its full()-check-then-put_nowait in ``_run_quantum`` race-free.

        The FAILED transition snapshots the flight recorder into the job
        (``status()`` surfaces it as ``trace``): the last N window spans
        at the moment of death are the post-mortem — where each recent
        window's time went — captured before later jobs overwrite the
        ring.  Empty when tracing never ran; the recorder's lock nests
        inside the manager lock here and never the other way around.
        """
        dump = (
            tracing.flight_recorder().last(32) if tracing.active() else []
        )
        with self._lock:
            if job._state_in(*JobState.TERMINAL):
                return
            job._error = err
            job._trace_dump = dump
            job._transition(JobState.FAILED)
            self._release(job)
            job._sentinel_pending = True
        self._wake.set()

    def _enqueue_sentinel(self, job: Job) -> None:  # single-thread: scheduler
        """Best-effort sentinel enqueue; a full queue defers it to the next
        scheduler round (``_sentinel_pending``) rather than blocking."""
        try:
            job._out.put_nowait(_SENTINEL)
            delivered = True
        except queue.Full:
            delivered = False
        with self._lock:
            job._sentinel_pending = not delivered
        if not delivered:
            self._wake.set()

    # The scheduler loop and everything below it runs on the ONE scheduler
    # thread; job lifecycle state is still read/written under the manager
    # lock because API threads mutate it concurrently.

    def _loop(self) -> None:  # single-thread: scheduler
        while True:
            with self._lock:
                if self._stop:
                    return
                jobs = list(self._jobs.values())
            progressed = False
            # quanta parked at a FoldRequest this round, awaiting a cohort
            pending: List[_Quantum] = []  # single-thread: scheduler
            self._source_waiter = None
            for job in jobs:
                try:
                    progressed |= self._run_quantum(job, pending)
                except BaseException as e:  # defensive: never kill the loop
                    self._fail(job, e)
            # cross-tenant fused dispatch: every parked window collected
            # above now folds — same-key cohorts in ONE vmapped dispatch,
            # loners solo — and each quantum resumes its remaining credits
            # (which may park again, so cycles repeat until no job is
            # parked; per-round work stays bounded by the credit budget)
            progressed |= self._dispatch_cohorts(pending)
            if self._health_every:
                # the health plane's sampling point: BETWEEN rounds on the
                # one scheduler thread, reading host-side Python counters
                # only — the dispatch hot path above gains a clock check
                # per round and zero device syncs
                now = time.monotonic()
                if now >= self._next_health:
                    self._next_health = now + self._health_every
                    try:
                        self._sample_health(jobs, now)
                    except Exception:
                        # same invariant as the quantum loop: a malformed
                        # progress dict (user-supplied probe) must degrade
                        # a gauge sweep, never kill the ONE scheduler
                        pass
            if not progressed:
                # nothing runnable: park until a submit/resume/cancel or a
                # consumer freeing queue space wakes us (short cap so a
                # missed wake degrades to polling, never to a wedge).  A
                # job skipped for its source makes the park a source wait
                # for the window it is filling.
                waiter = self._source_waiter
                if waiter is None:
                    self._wake.wait(0.05)
                else:
                    with tracing.span(
                        "gelly.source.wait",
                        job=waiter.job_id,
                        window=waiter._last_window + 1,
                    ):
                        self._wake.wait(0.05)
                self._wake.clear()

    def _run_quantum(
        self, job: Job, collect: "List[_Quantum]"
    ) -> bool:  # single-thread: scheduler
        """One weighted-fair round for one job; True if it made progress.

        A job whose iterator parks at a ``FoldRequest`` lands its quantum
        in ``collect`` for the round's cohort dispatch (``_dispatch_cohorts``)
        instead of completing here — the quantum's credits carry over to
        the resume, so fairness accounting is identical either way (one
        emission = one credit, fused or solo).
        """
        with self._lock:
            cancel_now = job._cancel_requested and not job._state_in(
                *JobState.TERMINAL
            )
            sentinel_owed = job._sentinel_pending
            if not cancel_now:
                if job._state_in(JobState.PENDING):
                    job._transition(JobState.RUNNING)
                elif not job._state_in(JobState.RUNNING):
                    # PAUSED / DRAINING / terminal: only a deferred
                    # sentinel still needs delivering (and the computed
                    # records' references dropping)
                    if sentinel_owed:
                        self._enqueue_sentinel(job)
                    _device_behind(job)
                    return False
        if cancel_now:
            self._cancel_now(job)
            return True
        ready = job._ready
        if ready is not None:
            # the network-source gate: a pull would block the ONE scheduler
            # thread on that job's producer, so an un-ready source skips the
            # round instead (cancel above still wins: a dead client's job
            # stays cancellable forever)
            try:
                if not ready():
                    metrics.job_add(job.job_id, "job_source_wait_skips", 1)
                    self._source_waiter = job
                    return False
            except BaseException as e:
                self._fail(job, e)
                return True
        t_round = time.perf_counter()
        q = _Quantum(job, job.weight * self.cfg.fair_quantum, t_round)
        return self._pull_loop(q, collect, _FRESH)

    def _pull_loop(
        self, q: "_Quantum", collect: "List[_Quantum]", send
    ) -> bool:  # single-thread: scheduler
        """Run (or resume) one quantum's pull loop.

        ``send`` is ``_FRESH`` to initiate new pulls, or the fused partial
        (possibly None — the solo-fallback signal) to resume a parked
        ``FoldRequest`` first.  The per-pull gates (RUNNING state, cancel,
        queue fullness, source readiness) apply only when INITIATING a
        fresh pull: a parked fold always resumes, because its window's
        device work happened in the cohort dispatch and dropping the
        resume would strand the emission.  The queue-full guarantee still
        holds — fullness was checked before the pull that parked, and
        this thread is the queue's sole producer.

        Clocking (profiled: two ``perf_counter`` reads per pull were the
        scheduler's second-hottest line behind the fold itself): ONE read
        per record, rolled through ``q.t_prev``, spans gate overhead into
        ``job_dispatch_s`` — nanoseconds against a device fold — and the
        round-level health/SLO clock stays in ``_loop``, once per round,
        never per pull.
        """
        job = q.job
        ready = job._ready
        # tag this thread with the job id for the duration of its pulls:
        # histograms recorded deep inside the merge loops / network source
        # (close-to-emission, push-to-fold) land in this job's rows too
        prev_scope = metrics.set_hist_job(job.job_id)
        prev_job = tracing.scope_job(job.job_id)
        try:
            while True:
                if send is _FRESH:
                    if q.pulled >= q.credits:
                        break
                    if not job._state_in(JobState.RUNNING):
                        break
                    if job._cancel_pending():
                        break
                    if job._out.full():
                        metrics.job_add(job.job_id, "job_queue_full_skips", 1)
                        break
                    if _device_behind(job):
                        metrics.job_add(
                            job.job_id, "job_device_wait_skips", 1
                        )
                        break
                    if q.pulled and ready is not None and not ready():
                        # re-check between pulls: each pull drains a
                        # window's worth from the source, so readiness
                        # established for the FIRST pull says nothing
                        # about the rest of the quantum — a pull past the
                        # queued data would block the scheduler thread on
                        # that job's producer (the wedge the gate exists
                        # to prevent)
                        break
                    if job._it is None:
                        build = job._build
                        if build is None:
                            break  # raced a concurrent terminal transition
                        # lazy build: first schedule pays the query's setup
                        # (including any cold compile) on the scheduler
                        # thread — cooperative by design, amortized by the
                        # shared cache
                        job._it = iter(build())
                        q.t_prev = time.perf_counter()
                    try:
                        rec, em = self._pull(job, _FRESH)
                    except StopIteration:
                        with self._lock:
                            job._transition(JobState.DRAINING)
                        self._enqueue_sentinel(job)
                        q.pulled += 1
                        break
                    except BaseException as e:
                        self._fail(job, e)
                        q.pulled += 1
                        break
                else:
                    partial, send = send, _FRESH
                    q.t_prev = time.perf_counter()
                    try:
                        rec, em = self._pull(job, partial)
                    except StopIteration:
                        with self._lock:
                            job._transition(JobState.DRAINING)
                        self._enqueue_sentinel(job)
                        q.pulled += 1
                        break
                    except BaseException as e:
                        self._fail(job, e)
                        q.pulled += 1
                        break
                if type(rec) is FoldRequest:
                    # park: the window's fold is offered to this round's
                    # cohort; the quantum resumes from _dispatch_cohorts
                    q.request = rec
                    collect.append(q)
                    return bool(q.pulled)
                t_rec = time.perf_counter()
                metrics.job_add(job.job_id, "job_dispatch_s", t_rec - q.t_prev)
                q.t_prev = t_rec
                metrics.job_add(job.job_id, "job_dispatches", 1)
                metrics.job_add(job.job_id, "job_records", 1)
                if not job._first_emitted:
                    job._first_emitted = True
                    metrics.hist_record(
                        "submit_to_first_emission_ms",
                        (t_rec - job._submit_t) * 1e3,
                        job=job.job_id,
                    )
                if job.edges_per_record:
                    metrics.job_add(
                        job.job_id, "job_edges", job.edges_per_record
                    )
                if em is not None:
                    job._last_window = em.window
                # sole producer is this thread and fullness was checked
                # above, so put_nowait cannot raise.  A sink's pump gets
                # the record's emission with it; a sink-less job's window
                # ends here.
                if job.sink is not None:
                    job._out.put_nowait((rec, em))
                else:
                    if em is not None:
                        tracing.finish(em, em.t_emit)
                    job._out.put_nowait(rec)
                job._unfinished.append(rec)
                metrics.job_high_water(
                    job.job_id, "job_queue_depth_hwm", job._out.qsize()
                )
                q.pulled += 1
        finally:
            metrics.set_hist_job(prev_scope)
            tracing.scope_job(prev_job)
        if q.pulled:
            # scheduler queue wait: the gap from this job's previous
            # PRODUCTIVE quantum to this one's start — what a closed
            # window waits before the shared scheduler gets back to its
            # job.  Recorded only on productive quanta: unproductive
            # visits (full queue, unready source) neither advance the
            # anchor nor record, so consumer backpressure never
            # masquerades as ramping scheduler wait.
            if job._last_quantum_end is not None:
                metrics.hist_record(
                    "sched_queue_wait_ms",
                    (q.t_round - job._last_quantum_end) * 1e3,
                    job=job.job_id,
                )
            metrics.job_add(job.job_id, "job_sched_rounds", 1)
            job._last_quantum_end = time.perf_counter()
        return bool(q.pulled)

    def _pull(self, job: Job, send):  # single-thread: scheduler
        """One pull of the job's records iterator (``send`` resumes a
        parked fold) under a ``gelly.sched.pull`` span, tagged with the
        pulled record's window; returns ``(record, emission or None)``."""
        with tracing.span("gelly.sched.pull") as pull:
            rec = next(job._it) if send is _FRESH else job._it.send(send)
            em = tracing.claim()
            if em is not None:
                pull.tag(window=em.window)
        return rec, em

    def _dispatch_cohorts(
        self, pending: "List[_Quantum]"
    ) -> bool:  # single-thread: scheduler
        """Drain the round's parked quanta through cohort dispatch cycles.

        Each cycle groups parked ``FoldRequest``s by key — (descriptor
        cache token, frozen config, has-val, pow2 pane bucket) — so only
        windows that would compile and trace IDENTICALLY may share a
        dispatch; each cohort folds once and every member resumes with
        its own row.  Resumed quanta may park again at their next window,
        feeding the next cycle; total pulls per round stay bounded by the
        per-job credit budgets, so the cycles terminate.
        """
        progressed = False
        while pending:
            quanta, pending = pending, []
            cohorts: Dict[tuple, List[_Quantum]] = {}
            for q in quanta:
                cohorts.setdefault(q.request.key, []).append(q)
            for qs in cohorts.values():
                try:
                    partials = self._fused_fold(qs)
                except BaseException as e:
                    # a cohort-level dispatch failure fails its MEMBERS
                    # (their windows were in that dispatch), not the round
                    for q in qs:
                        self._fail(q.job, e)
                    continue
                for q, partial in zip(qs, partials):
                    q.request = None
                    if q.job._it is None:
                        continue  # raced a terminal transition mid-round
                    try:
                        progressed |= self._pull_loop(q, pending, partial)
                    except BaseException as e:
                        self._fail(q.job, e)
        return progressed

    def _fused_fold(self, qs: "List[_Quantum]"):  # single-thread: scheduler
        """One cohort's device work: N parked same-key windows stacked into
        the superbatch row layout and folded by ONE call to the shared
        vmapped executable.  Returns one per-row partial per member, in
        member order; a singleton cohort returns ``[None]`` — the member
        solo-folds in its own generator, keeping the oracle path exercised
        even under fused mode.

        The row axis is pow2-bucketed by ``stack_fold_rows``, so tenancy
        varying 1..16 jobs revisits at most log2 bucket shapes and the
        process-wide recompile guard stays at zero.  No host sync happens
        here: the fold and the compiled per-row drain both dispatch
        asynchronously and each member's partial stays a device pytree,
        materialized only where the plain plane would have synced anyway
        (transform at emission).
        """
        if len(qs) == 1:
            metrics.fused_add("fused_solo_fallbacks", 1)
            return [None]
        import jax
        import jax.numpy as jnp

        reqs = [q.request for q in qs]
        src, dst, val, msk, pad_rows = stack_fold_rows(reqs)
        t0 = time.perf_counter()
        states = reqs[0].fold(
            jnp.asarray(src),
            jnp.asarray(dst),
            None if val is None else jax.tree.map(jnp.asarray, val),
            jnp.asarray(msk),
        )
        # drain in ONE dispatch too: the compiled split slices the stacked
        # result into per-row partials (eager per-row a[i] slices cost one
        # device call per job — measured ~2x the fused fold itself at 16
        # rows — and would undo the amortization the mega-fold just bought)
        rows = len(qs) + pad_rows
        parts = reqs[0].split(rows)(states)
        # the one dispatch's wall time attributes evenly: each tenant row
        # cost ~1/N of the fused call, and the per-job histograms/benches
        # read job_dispatch_s exactly as they do for solo dispatch
        share = (time.perf_counter() - t0) / len(qs)
        metrics.fused_add("fused_dispatches", 1)
        metrics.fused_add("fused_jobs_total", len(qs))
        metrics.fused_add("fused_pad_rows_total", pad_rows)
        metrics.fused_high_water("fused_jobs_per_dispatch_hwm", len(qs))
        with self._lock:
            for q in qs:
                q.job._fused_windows += 1
        partials = []
        for i, q in enumerate(qs):
            metrics.job_add(q.job.job_id, "job_dispatch_s", share)
            partials.append(parts[i])
        return partials

    def _sample_health(self, jobs, now: float) -> None:  # single-thread: scheduler
        """One keep-up gauge sweep over the live jobs (ISSUE 10).

        For jobs with a ``progress`` probe (network-fed sources) the full
        vocabulary: watermark lag from the probe's positional accounting,
        backlog depth/age from its queue snapshot, EWMA arrival vs drain
        rates, the keep-up ratio, and a time-to-queue-full estimate.
        Other jobs get sink-side gauges (drain rate, emission-queue
        depth).  Terminal jobs lose their gauge rows — a DONE job's stale
        backlog must not keep an SLO alert burning.

        Each job's sample is individually fault-isolated (a malformed
        user-supplied probe dict degrades THAT job's gauges for the
        sweep, never the rest), and a probe that stops producing REPLACES
        the row with sink-side figures — no frozen backlog/lag values
        driving SLO verdicts after the source is gone.
        """
        for job in jobs:
            try:
                self._sample_job_health(job, now)
            except Exception:
                continue  # one bad probe must not abort the sweep
        # trackers for jobs evicted between sweeps (terminal + evicted
        # before a tick saw them) would otherwise accumulate forever in a
        # long-lived churny server
        live = {job.job_id for job in jobs}
        for job_id in [j for j in self._keepup if j not in live]:
            del self._keepup[job_id]

    def _sample_job_health(self, job: Job, now: float) -> None:  # single-thread: scheduler
        job_id = job.job_id
        if job._state_in(*JobState.TERMINAL):
            if self._keepup.pop(job_id, None) is not None:
                metrics.drop_job_health(job_id)
            return
        gauges = {"out_queue_depth": job._out.qsize()}
        prog = None
        probe = job._progress
        if probe is not None:
            try:
                prog = probe()
            except BaseException:
                prog = None  # a broken probe degrades, never fails a job
        tracker = self._keepup.get(job_id)
        if tracker is None:
            tracker = self._keepup[job_id] = metrics.KeepUpTracker()
        if prog is not None:
            arrival, drain = tracker.sample(
                now, prog["edges_in"], prog["edges_out"]
            )
            lag = max(
                0, prog["closable_windows"] - prog["delivered_windows"]
            )
            backlog_edges = prog["backlog_edges"]
            gauges.update(
                watermark_lag_windows=lag,
                backlog_batches=prog["backlog_batches"],
                backlog_edges=backlog_edges,
                backlog_age_s=round(prog["backlog_age_s"], 4),
                arrival_eps=round(arrival, 2),
                drain_eps=round(drain, 2),
                keepup_ratio=(
                    round(min(drain / arrival, 999.0), 4)
                    if arrival > 1e-9
                    else 1.0
                ),
            )
            net = arrival - drain
            headroom = prog["queue_capacity_edges"] - backlog_edges
            # -1 = not filling (the JSON/Prometheus-safe "infinity")
            gauges["time_to_queue_full_s"] = (
                round(max(headroom, 0) / net, 2) if net > 1e-9 else -1.0
            )
        else:
            # sink-side drain only: the job's attributed edge counter
            # is the best cumulative drain figure available
            edges = metrics.job_stats(job_id)["job_edges"]
            _arrival, drain = tracker.sample(now, edges, edges)
            gauges["drain_eps"] = round(drain, 2)
        metrics.job_health_set(job_id, gauges)

    def _cancel_now(self, job: Job) -> None:  # single-thread: scheduler
        """Perform a requested cancel on the scheduler thread.

        Closing the iterator propagates GeneratorExit into the merge loop,
        whose drain path waits on each in-flight fold and recycles its
        transfer arenas (``# arena-live-until: drain`` — see
        core/async_exec.py); then the job is marked CANCELLED and the
        sentinel appended.  Emissions already in the queue stay DELIVERABLE:
        they were emitted past their windows' checkpoint saves, so dropping
        them would turn a cancel + resubmit-from-checkpoint into an
        at-most-once gap (the runtime keeps the framework's state
        exactly-once / emission at-least-once contract).
        """
        it = job._it
        job._it = None
        if it is not None and hasattr(it, "close"):
            try:
                it.close()
            except BaseException as e:
                # a close-time error must not mask the cancel; record it
                with self._lock:
                    if job._error is None:
                        job._error = e
        with self._lock:
            if not job._state_in(*JobState.TERMINAL):
                job._transition(JobState.CANCELLED)
                self._release(job)
        self._enqueue_sentinel(job)
