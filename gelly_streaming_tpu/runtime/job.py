"""Job: one submitted streaming query under the multi-tenant runtime.

The reference runs one query per Flink job graph, submitted to a cluster
that multiplexes many jobs over shared task slots; everything in THIS repo
before the runtime ran exactly one query per process, run-to-completion.  A
``Job`` is the unit the ``JobManager`` (runtime/manager.py) schedules: a
re-runnable record source (an ``OutputStream``-contract iterator factory —
``aggregate()`` and the property streams already produce these), an
optional emission sink, an optional per-job positional checkpoint (the
existing ``utils/checkpoint.py`` machinery rides along unchanged: the merge
loops save position+summary per window, so pause/resume and crash-resume
replay from the snapshot), and a lifecycle state machine:

    PENDING --> RUNNING <--> PAUSED
                   |  \\
                   |   +--> FAILED / CANCELLED
                   v
               DRAINING --> DONE / CANCELLED

* **PENDING** — admitted, not yet scheduled.
* **RUNNING** — the scheduler pulls the job's iterator in weighted-fair
  rounds; each pull dispatches that job's next window through the shared
  device pipeline.  Under cross-tenant fused dispatch
  (``cfg.fused_dispatch``) a pull may instead PARK at a ``FoldRequest``:
  the scheduler stacks same-shape parked windows from other tenants into
  one vmapped mega-fold and resumes each job with its own row — one
  emission still costs one pull credit, so weighted fairness is
  unchanged (see runtime/manager.py ``_dispatch_cohorts``).
* **PAUSED** — the iterator is left SUSPENDED in place (its in-flight
  windows stay queued, its checkpoint keeps the last saved position);
  ``resume`` continues pulling exactly where it stopped, so in-process
  pause/resume is bit-exact by construction.
* **DRAINING** — the source is exhausted; emissions already in the job's
  bounded queue are still being consumed by the sink.
* **DONE / FAILED / CANCELLED** — terminal; the job's admitted state bytes
  are returned to the manager's budget.

Every lifecycle field is mutated ONLY under the manager's lock (``_lock``
is the manager's RLock, shared by reference): the scheduler thread, the
API threads (pause/resume/cancel), and sink threads all observe the same
transition order, and the lock-discipline analyzer pass pins the guard
statically (tests/analysis_corpus/{good,bad}_jobstate.py).
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Any, Callable, Iterator, List, Optional

from gelly_streaming_tpu.utils import events


class JobState:
    """Lifecycle states (string constants so status() serializes as-is)."""

    PENDING = "PENDING"
    RUNNING = "RUNNING"
    PAUSED = "PAUSED"
    DRAINING = "DRAINING"
    DONE = "DONE"
    FAILED = "FAILED"
    CANCELLED = "CANCELLED"

    TERMINAL = frozenset({DONE, FAILED, CANCELLED})


# legal transitions; anything else is a caller error surfaced loudly (a
# silent illegal transition is how a cancelled job comes back to life)
_ALLOWED = frozenset(
    {
        (JobState.PENDING, JobState.RUNNING),
        (JobState.PENDING, JobState.PAUSED),
        (JobState.PENDING, JobState.CANCELLED),
        (JobState.PENDING, JobState.FAILED),
        (JobState.RUNNING, JobState.PAUSED),
        (JobState.RUNNING, JobState.DRAINING),
        (JobState.RUNNING, JobState.FAILED),
        (JobState.RUNNING, JobState.CANCELLED),
        (JobState.PAUSED, JobState.RUNNING),
        (JobState.PAUSED, JobState.CANCELLED),
        (JobState.PAUSED, JobState.FAILED),
        (JobState.DRAINING, JobState.DONE),
        (JobState.DRAINING, JobState.FAILED),
        (JobState.DRAINING, JobState.CANCELLED),
    }
)


class AdmissionError(RuntimeError):
    """Submission rejected by admission control (job or byte cap).

    Explicit by contract: an over-capacity submit must FAIL the caller,
    never hang waiting for a slot — backpressure on submission is the
    caller's policy decision, not the runtime's.
    """


class JobError(RuntimeError):
    """Raised by consumers of a FAILED job's results; carries the cause."""


# end-of-stream marker on a job's emission queue (identity-compared)
_SENTINEL = object()


class Job:
    """A submitted query.  Constructed by ``JobManager.submit`` only.

    The public surface is read-mostly (``state``, ``results``, ``collect``,
    ``wait``, ``close``); lifecycle commands go through the manager
    (``manager.pause(job)`` etc.) so every transition happens under the one
    manager lock.
    """

    def __init__(
        self,
        job_id: str,
        build: Callable[[], Iterator[tuple]],
        *,
        manager_lock: threading.RLock,
        sink: Optional[Callable[[tuple], Any]] = None,
        weight: int = 1,
        checkpoint_path: Optional[str] = None,
        state_bytes: int = 0,
        edges_per_record: int = 0,
        edges_hint: Optional[int] = None,
        queue_depth: int = 64,
        ready: Optional[Callable[[], bool]] = None,
        progress: Optional[Callable[[], dict]] = None,
    ):
        if weight <= 0:
            raise ValueError("job weight must be positive")
        self.job_id = job_id
        self.weight = int(weight)
        self.sink = sink
        self.checkpoint_path = checkpoint_path
        self.state_bytes = int(state_bytes)
        self.edges_per_record = int(edges_per_record)
        # total edges the source expects to deliver (EdgeStream
        # num_edges_hint); None for opaque sources — status() progress only
        self.edges_hint = edges_hint
        # zero-arg factory of a FRESH records iterator (the OutputStream
        # contract): called lazily on first schedule; a resubmitted job with
        # the same checkpoint path restores position through the merge
        # loop's own machinery, nothing runtime-specific
        self._build = build
        # source-readiness gate for jobs fed by an external producer (the
        # network ingest plane): the scheduler calls it before pulling and
        # SKIPS the job's round on False, so a pull never blocks the shared
        # scheduler thread on a slow or dead producer.  Must be thread-safe
        # and non-blocking; None = always runnable (the historical default).
        self._ready = ready
        # health-plane probe (ISSUE 10): a thread-safe, non-blocking
        # callable returning the source's progress dict (edges in/out,
        # backlog depth/age, closable vs delivered windows — see
        # NetworkEdgeSource.progress).  Sampled by the scheduler loop at
        # the health rate; None = gauge row limited to sink-side figures.
        self._progress = progress
        # the MANAGER's RLock, shared by reference: the analyzer unifies
        # the two identities so edges through either are re-entrant on
        # the other
        self._lock = manager_lock  # lock-alias: manager._lock
        self._state = JobState.PENDING  # guarded-by: _lock
        self._error: Optional[BaseException] = None  # guarded-by: _lock
        self._cancel_requested = False  # guarded-by: _lock
        # the live records iterator; built, pulled, and closed ONLY on the
        # scheduler thread, so generator re-entrancy is impossible
        self._it: Optional[Iterator[tuple]] = None  # single-thread: scheduler
        # a sentinel that could not be enqueued (queue full at finish/fail
        # time) and is owed to the queue; retried by the scheduler rounds
        self._sentinel_pending = False  # guarded-by: _lock
        # bounded emission queue: the isolation boundary between the shared
        # dispatch loop and this job's sink (scheduler = sole producer)
        self._out: "queue.Queue" = queue.Queue(maxsize=max(1, queue_depth))
        self._done_evt = threading.Event()
        self._sink_thread: Optional[threading.Thread] = None
        self._manager = None  # set by JobManager.submit
        # observability (ISSUE 9): admission timestamp for the
        # submit-to-first-emission histogram, the scheduler's per-quantum
        # bookkeeping for the queue-wait histogram, and the flight-recorder
        # dump attached on a FAILED transition for post-mortems
        self._submit_t = time.perf_counter()
        self._first_emitted = False  # single-thread: scheduler
        # windows this job contributed to cross-tenant fused dispatches
        # (runtime/manager.py cohorts over the FoldRequest leg of
        # ``run_fused``): bumped by the scheduler's cohort pass, read by
        # status() from API threads — hence lock-guarded, not
        # scheduler-private like the iterator bookkeeping above
        self._fused_windows = 0  # guarded-by: _lock
        self._last_quantum_end: Optional[float] = None  # single-thread: scheduler
        # the window of the last record pulled (its emission's window id):
        # the window being filled next is this + 1
        self._last_window = -1  # single-thread: scheduler
        self._trace_dump: Optional[List[dict]] = None  # guarded-by: _lock

    # -- read-side API -------------------------------------------------------

    @property
    def state(self) -> str:
        with self._lock:
            return self._state

    @property
    def error(self) -> Optional[BaseException]:
        with self._lock:
            return self._error

    @property
    def queue_depth(self) -> int:
        """Current emission-queue occupancy (approximate, lock-free)."""
        return self._out.qsize()

    def wait(self, timeout: Optional[float] = None) -> bool:
        """Block until the job is terminal; True if it reached a terminal
        state within ``timeout`` seconds (None = wait forever)."""
        return self._done_evt.wait(timeout)

    def results(self) -> Iterator[tuple]:
        """Consume this job's emissions (records in emission order).

        Only for jobs submitted WITHOUT a sink — a sink-driven job's queue
        is owned by its sink thread.  Ends when the job's source is
        exhausted; raises ``JobError`` after delivering the queued records
        if the job failed.  A PAUSED job's consumer simply blocks until
        resume/cancel — the queue is the natural backpressure.
        """
        if self.sink is not None:
            raise RuntimeError(
                f"job {self.job_id!r} delivers to its sink; results() is "
                "for sink-less jobs"
            )
        while True:
            rec = self._out.get()
            if rec is _SENTINEL:
                break
            yield rec
        self._manager._mark_drained(self)
        err = self.error
        if err is not None:
            raise JobError(f"job {self.job_id!r} failed: {err!r}") from err

    def collect(self) -> List[tuple]:
        return list(self.results())

    # -- lifecycle commands (delegate to the manager) ------------------------

    def pause(self) -> bool:
        """Best-effort: True iff the job moved to PAUSED (False when the
        scheduler already finished/failed it — never a race exception)."""
        return self._manager.pause(self)

    def resume(self) -> bool:
        """Best-effort: True iff the job moved PAUSED -> RUNNING."""
        return self._manager.resume(self)

    def cancel(self, wait: bool = True, timeout: Optional[float] = 30.0):
        return self._manager.cancel(self, wait=wait, timeout=timeout)

    def close(self) -> None:
        """Cancel and wait: the job's in-flight windows are drained through
        the completion-queue path (their transfer arenas recycled — see
        async_exec's GeneratorExit drain) before this returns."""
        self._manager.cancel(self, wait=True)

    # -- transitions (manager/scheduler only) --------------------------------

    # holds-lock: _lock
    def _transition(self, new_state: str) -> None:
        """Move the state machine; caller MUST hold the manager lock — the
        ``# holds-lock:`` contract makes every call site checkable (pass
        #6), and the re-entrant acquisition below keeps the guard visible
        locally too.

        Every legal transition lands in the structured event journal
        (utils/events.py) — the journal lock is a leaf lock, so emitting
        under the manager lock cannot deadlock — which is what makes a
        job's full lifecycle replayable post-mortem instead of
        reconstructed from span guesses.
        """
        with self._lock:
            if (self._state, new_state) not in _ALLOWED:
                raise RuntimeError(
                    f"job {self.job_id!r}: illegal transition "
                    f"{self._state} -> {new_state}"
                )
            old = self._state
            self._state = new_state
            if new_state in JobState.TERMINAL:
                self._done_evt.set()
            fields = {"job": self.job_id, "from": old, "to": new_state}
            if new_state == JobState.FAILED and self._error is not None:
                fields["error"] = repr(self._error)
            events.journal().emit("job_transition", **fields)

    def _state_in(self, *states: str) -> bool:
        with self._lock:
            return self._state in states

    def _cancel_pending(self) -> bool:
        with self._lock:
            return self._cancel_requested

    def __repr__(self):
        return f"Job({self.job_id!r}, state={self.state})"
