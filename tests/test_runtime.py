"""Multi-tenant job runtime (ISSUE 5): concurrent queries over one device
pipeline.

The contract under test: N concurrent jobs emit BIT-IDENTICAL record
sequences to the same queries run serially (the scheduler multiplexes
dispatch opportunities, never results) across the wire, windowed, and
owner-sharded planes; pause/resume and crash-resume ride the per-job
positional checkpoints; admission control rejects loudly; same-shape jobs
share executables (0 recompiles); and one slow sink cannot stall the rest.

Every threaded test carries ``timeout_cap`` (tests/conftest.py): a wedged
scheduler or completion queue must FAIL the test, not hang tier-1.
"""

import dataclasses
import os
import signal
import subprocess
import sys
import textwrap
import threading
import time

import numpy as np
import pytest

import jax.numpy as jnp

from gelly_streaming_tpu.core.aggregation import SummaryBulkAggregation
from gelly_streaming_tpu.core.config import RuntimeConfig, StreamConfig
from gelly_streaming_tpu.core.stream import EdgeStream
from gelly_streaming_tpu.library.connected_components import (
    ConnectedComponents,
)
from gelly_streaming_tpu.runtime import (
    AdmissionError,
    JobManager,
    JobState,
)
from gelly_streaming_tpu.utils import metrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.timeout_cap(300)

CAP = 1 << 12
WIN = 1 << 10
N = 8 * WIN
# aligned batch -> the packed-wire fast path with running emission
CFG_WIRE = StreamConfig(
    vertex_capacity=CAP, batch_size=1 << 9, ingest_window_edges=WIN
)
# misaligned batch -> the windowed runtime's ingestion panes
CFG_WINDOWED = StreamConfig(
    vertex_capacity=CAP, batch_size=(1 << 9) + 96, ingest_window_edges=WIN
)


def _graph(seed: int, n: int = N, cap: int = CAP):
    rng = np.random.default_rng(seed)
    return (
        rng.integers(0, cap, n).astype(np.int32),
        rng.integers(0, cap, n).astype(np.int32),
    )


def _cc_serial(cfg, s, d, checkpoint_path=None):
    out = EdgeStream.from_arrays(s, d, cfg).aggregate(
        ConnectedComponents(), checkpoint_path=checkpoint_path
    )
    return [np.asarray(rec[0].parent) for rec in out]


def _materialize_cc(records):
    return [np.asarray(rec[0].parent) for rec in records]


class EdgeCount(SummaryBulkAggregation):
    """NON-idempotent fold: re-folding any pane after a resume overcounts,
    so the final value proves exactly-once state (the async-pipeline
    tests' oracle, reused for the runtime's checkpoints)."""

    order_free = True

    @property
    def cache_token(self):
        return type(self)

    def initial_state(self, cfg):
        return jnp.zeros((), jnp.int32)

    def update(self, state, src, dst, val, mask):
        return state + jnp.sum(mask.astype(jnp.int32))

    def combine(self, a, b):
        return a + b


# ---------------------------------------------------------------------------
# concurrent-vs-serial emission parity
# ---------------------------------------------------------------------------


def _assert_four_jobs_match_serial(cfg):
    datasets = [_graph(seed) for seed in range(4)]
    serial = [_cc_serial(cfg, s, d) for s, d in datasets]
    with JobManager() as jm:
        jobs = [
            jm.submit_aggregation(
                EdgeStream.from_arrays(s, d, cfg),
                ConnectedComponents(),
                name=f"cc-{i}",
            )
            for i, (s, d) in enumerate(datasets)
        ]
        outs = [_materialize_cc(job.results()) for job in jobs]
        states = [job.state for job in jobs]
    assert states == [JobState.DONE] * 4
    for i, (want, got) in enumerate(zip(serial, outs)):
        assert len(want) == len(got), (i, len(want), len(got))
        for w, (a, b) in enumerate(zip(want, got)):
            assert np.array_equal(a, b), f"job {i} window {w} diverged"


def test_four_jobs_wire_plane_match_serial():
    _assert_four_jobs_match_serial(CFG_WIRE)


def test_four_jobs_windowed_plane_match_serial():
    _assert_four_jobs_match_serial(CFG_WINDOWED)


def test_four_jobs_async_windowed_plane_match_serial():
    # each job runs its own async window pipeline (depth 2) under the one
    # scheduler: pack/transfer threads and completion queues per job, all
    # dispatching through the shared executables
    _assert_four_jobs_match_serial(
        dataclasses.replace(CFG_WINDOWED, async_windows=2)
    )


def test_four_jobs_sharded_plane_match_serial():
    # the owner-sharded mesh streaming plane (2 shards of the virtual CPU
    # mesh) — one emission per job at stream end
    cfg = StreamConfig(
        vertex_capacity=CAP, batch_size=1 << 9, num_shards=2
    )
    datasets = [_graph(seed, n=4 * (1 << 9)) for seed in range(4)]
    serial = [_cc_serial(cfg, s, d) for s, d in datasets]
    with JobManager() as jm:
        jobs = [
            jm.submit_aggregation(
                EdgeStream.from_arrays(s, d, cfg),
                ConnectedComponents(),
                name=f"mesh-{i}",
            )
            for i, (s, d) in enumerate(datasets)
        ]
        outs = [_materialize_cc(job.results()) for job in jobs]
    for i, (want, got) in enumerate(zip(serial, outs)):
        assert len(want) == len(got)
        for a, b in zip(want, got):
            assert np.array_equal(a, b), f"mesh job {i} diverged"


# ---------------------------------------------------------------------------
# lifecycle: pause / resume / cancel
# ---------------------------------------------------------------------------


def test_pause_resume_emission_parity():
    s, d = _graph(7)
    serial = _cc_serial(CFG_WIRE, s, d)
    with JobManager() as jm:
        job = jm.submit_aggregation(
            EdgeStream.from_arrays(s, d, CFG_WIRE),
            ConnectedComponents(),
            name="pausable",
        )
        it = job.results()
        got = [next(it), next(it)]
        assert job.pause() is True
        assert job.state == JobState.PAUSED
        # paused: the iterator is suspended in place; nothing else arrives
        job.resume()
        got.extend(it)
    assert len(got) == len(serial)
    for want, rec in zip(serial, got):
        assert np.array_equal(want, np.asarray(rec[0].parent))


def test_pause_checkpoints_then_cancel_resubmit_is_exact(tmp_path):
    """Cancel a checkpointed job mid-stream and resubmit from its
    checkpoint: delivered records overlap at the boundary only
    (at-least-once, never a gap) and the non-idempotent final count is
    exact (state exactly-once)."""
    s, d = _graph(11)
    ck = str(tmp_path / "ck")
    cfg = CFG_WINDOWED
    serial = [
        int(rec[0])
        for rec in EdgeStream.from_arrays(s, d, cfg).aggregate(EdgeCount())
    ]
    with JobManager() as jm:
        job = jm.submit_aggregation(
            EdgeStream.from_arrays(s, d, cfg),
            EdgeCount(),
            name="count",
            checkpoint_path=ck,
        )
        it = job.results()
        first = [int(next(it)[0]), int(next(it)[0])]
        job.cancel(wait=True)
        first.extend(int(rec[0]) for rec in it)  # the queued tail delivers
        assert job.state == JobState.CANCELLED

        job2 = jm.submit_aggregation(
            EdgeStream.from_arrays(s, d, cfg),
            EdgeCount(),
            name="count-resumed",
            checkpoint_path=ck,
        )
        second = [int(rec[0]) for rec in job2.results()]
    assert second, "resumed job emitted nothing"
    overlap = len(first) + len(second) - len(serial)
    assert overlap >= 0, "cancel+resume dropped emissions (a gap)"
    assert first[: len(first) - overlap] + second == serial
    assert second[-1] == serial[-1] == len(s)


def test_cancel_mid_flight_async_job(tmp_path):
    """Cancelling an async-windowed job mid-flight returns promptly and
    terminally — its in-flight windows drain through the completion queue
    (arena recycle) rather than wedging the scheduler."""
    s, d = _graph(13)
    cfg = dataclasses.replace(CFG_WINDOWED, async_windows=3)
    with JobManager() as jm:
        job = jm.submit_aggregation(
            EdgeStream.from_arrays(s, d, cfg),
            ConnectedComponents(),
            name="doomed",
        )
        it = job.results()
        next(it)
        assert job.cancel(wait=True, timeout=60)
        assert job.state == JobState.CANCELLED
        # a second job over the same pipeline still runs clean after the
        # cancel (no leaked arenas / wedged prefetcher threads)
        ok = jm.submit_aggregation(
            EdgeStream.from_arrays(s, d, cfg),
            ConnectedComponents(),
            name="after",
        )
        assert len(_materialize_cc(ok.results())) == N // WIN


def test_pause_resume_on_finished_job_is_refused_not_raced():
    """pause()/resume() race the scheduler by nature, so an un-pausable
    state returns False (check+transition atomic under the manager lock)
    instead of throwing at the caller."""
    s, d = _graph(17, n=WIN)
    with JobManager() as jm:
        job = jm.submit_aggregation(
            EdgeStream.from_arrays(s, d, CFG_WIRE),
            ConnectedComponents(),
            name="one",
        )
        job.collect()
        assert job.wait(30) and job.state == JobState.DONE
        assert job.pause() is False
        assert job.resume() is False
        assert job.state == JobState.DONE


def test_shared_checkpoint_path_is_refused(tmp_path):
    """Two ACTIVE jobs interleaving saves into one snapshot file would
    corrupt both resumes — admission rejects the collision; per_job_file
    is the shared-prefix escape hatch."""
    from gelly_streaming_tpu.utils.checkpoint import per_job_file

    s, d = _graph(67)
    ck = str(tmp_path / "shared")
    with JobManager() as jm:
        gate = threading.Event()

        def held_source():
            gate.wait(60)
            return iter(())

        jm.submit(held_source, name="holder", checkpoint_path=ck)
        with pytest.raises(AdmissionError, match="already in use"):
            jm.submit_aggregation(
                EdgeStream.from_arrays(s, d, CFG_WIRE),
                ConnectedComponents(),
                name="collider",
                checkpoint_path=ck,
            )
        # the derived per-job files do not collide
        a = per_job_file(ck, "job-a")
        b = per_job_file(ck, "job-b")
        assert a != b and a.startswith(ck) and b.startswith(ck)
        jm.submit_aggregation(
            EdgeStream.from_arrays(s, d, CFG_WIRE),
            ConnectedComponents(),
            name="keyed",
            checkpoint_path=a,
        ).collect()
        gate.set()


def test_terminal_jobs_are_evicted_beyond_retention():
    """A long-lived manager must not grow without bound: older terminal
    jobs (and their per-job metrics rows) are evicted at submit, while the
    module totals keep their contribution."""
    metrics.reset_job_stats()
    s, d = _graph(71, n=WIN)
    with JobManager(RuntimeConfig(keep_terminal_jobs=2)) as jm:
        for i in range(5):
            job = jm.submit_aggregation(
                EdgeStream.from_arrays(s, d, CFG_WIRE),
                ConnectedComponents(),
                name=f"gen-{i}",
            )
            job.collect()
            assert job.wait(30)
        status = jm.status()
        # at most keep_terminal_jobs finished jobs + the newest one linger
        assert len(status["jobs"]) <= 3
        assert "gen-0" not in status["jobs"]
        # evicted per-job rows are gone, totals keep every job's records
        assert "gen-0" not in metrics.all_job_stats()
        assert metrics.job_totals()["job_records"] == 5 * 1
        # a terminal job's source closure was dropped at release time
        assert all(j._build is None for j in jm._jobs.values())


# ---------------------------------------------------------------------------
# the GeneratorExit drain (cancel recycles arenas through the drain path)
# ---------------------------------------------------------------------------


def test_async_merge_loop_close_drains_and_releases():
    """Closing the async Merger mid-stream (the cancel path) must run every
    dispatched-but-undrained window through the NORMAL drain — releasing
    its arenas exactly once — before GeneratorExit propagates."""
    from gelly_streaming_tpu.core import async_exec
    from gelly_streaming_tpu.core.windows import WindowPane

    agg = EdgeCount()
    cfg = StreamConfig(vertex_capacity=64, batch_size=32)
    released = []

    def panes():
        for w in range(8):
            pane = WindowPane(
                window_id=w,
                max_timestamp=-1,
                src=np.zeros((4,), np.int32),
                dst=np.zeros((4,), np.int32),
                val=None,
                time=None,
            )
            yield pane, w

    def fold(payload):
        return jnp.zeros((), jnp.int32) + payload

    gen = async_exec.async_merge_loop(
        agg,
        cfg,
        panes(),
        fold,
        checkpoint_path=None,
        restore=False,
        unwrap=True,
        depth=4,
        release=released.append,
    )
    next(gen)
    next(gen)
    gen.close()
    # windows 0..5 dispatched (2 drained by the yields, 4 in flight at
    # close); every one released exactly once, through the drain path
    assert released == [0, 1, 2, 3, 4, 5]


# ---------------------------------------------------------------------------
# admission control
# ---------------------------------------------------------------------------


def test_admission_job_cap_rejects():
    s, d = _graph(19, n=WIN)
    with JobManager(RuntimeConfig(max_jobs=2)) as jm:
        gate = threading.Event()

        def held_source():
            gate.wait(60)  # holds its job slot open until released
            return iter(())

        held = [
            jm.submit(held_source, name=f"hold-{i}") for i in range(2)
        ]
        with pytest.raises(AdmissionError, match="job cap"):
            jm.submit_aggregation(
                EdgeStream.from_arrays(s, d, CFG_WIRE),
                ConnectedComponents(),
            )
        gate.set()
        for job in held:
            job.collect()


def test_admission_byte_cap_rejects_and_releases():
    s, d = _graph(23, n=WIN)
    one_job = ConnectedComponents().state_nbytes(CFG_WIRE)
    assert one_job > 0
    with JobManager(
        RuntimeConfig(max_state_bytes=int(one_job * 1.5))
    ) as jm:
        first = jm.submit_aggregation(
            EdgeStream.from_arrays(s, d, CFG_WIRE),
            ConnectedComponents(),
            name="fits",
        )
        with pytest.raises(AdmissionError, match="state-byte cap"):
            jm.submit_aggregation(
                EdgeStream.from_arrays(s, d, CFG_WIRE),
                ConnectedComponents(),
                name="rejected",
            )
        first.collect()
        assert first.wait(30)
        # terminal jobs return their budget: the next submit is admitted
        again = jm.submit_aggregation(
            EdgeStream.from_arrays(s, d, CFG_WIRE),
            ConnectedComponents(),
            name="admitted-after-release",
        )
        again.collect()


def test_rescale_budget_swap_is_atomic_under_the_admission_lock():
    """The elastic control plane's re-pricing (ISSUE 11): a draining
    job's state bytes move into a swap reservation UNDER the admission
    lock, so across the whole drain -> resubmit window (a) a same-size
    swap never transiently double-books against the cap, and (b) a
    concurrent tenant can never steal the freed budget mid-swap."""
    s, d = _graph(29, n=WIN)
    one_job = ConnectedComponents().state_nbytes(CFG_WIRE)
    with JobManager(RuntimeConfig(max_state_bytes=one_job)) as jm:
        gate = threading.Event()

        def held_source():
            gate.wait(60)
            return iter(())

        job = jm.submit(held_source, name="scaling", state_bytes=one_job)
        # (a) cap == one job's bytes: a same-size re-pricing must fit —
        # if old and new were ever both charged, this would reject
        reserved = jm.begin_rescale(job, one_job)
        assert reserved == one_job
        assert job.state_bytes == 0  # budget moved, not freed
        # (b) the reservation is committed budget: a concurrent tenant
        # cannot grab it while the swap is in flight
        with pytest.raises(AdmissionError, match="reserved"):
            jm.submit_aggregation(
                EdgeStream.from_arrays(s, d, CFG_WIRE),
                ConnectedComponents(),
                name="thief",
            )
        gate.set()
        jm.cancel(job, wait=True)
        status = jm.status()
        # the drained job's release returned NOTHING to the pool (its
        # budget lives in the reservation): admitted 0, reserved one_job
        assert status["admitted_state_bytes"] == 0
        assert status["reserved_state_bytes"] == one_job
        # the resubmit consumes the reservation exactly
        resubmitted = jm.submit(
            lambda: iter(()),
            name="scaling",
            state_bytes=one_job,
            reserved_bytes=reserved,
        )
        status = jm.status()
        assert status["admitted_state_bytes"] == one_job
        assert status["reserved_state_bytes"] == 0
        # once the rescaled job finishes, the budget is free again
        resubmitted.collect()
        assert resubmitted.wait(30)
        after = jm.submit_aggregation(
            EdgeStream.from_arrays(s, d, CFG_WIRE),
            ConnectedComponents(),
            name="after",
        )
        after.collect()


def test_rescale_budget_abort_returns_reservation():
    """A swap that dies mid-flight must return its reservation to the
    open pool — never leak budget out of circulation."""
    one_job = ConnectedComponents().state_nbytes(CFG_WIRE)
    s, d = _graph(31, n=WIN)
    with JobManager(RuntimeConfig(max_state_bytes=one_job)) as jm:
        job = jm.submit(lambda: iter(()), name="dies", state_bytes=one_job)
        reserved = jm.begin_rescale(job, one_job)
        jm.cancel(job, wait=True)
        jm.abort_rescale(reserved)
        assert jm.status()["reserved_state_bytes"] == 0
        ok = jm.submit_aggregation(
            EdgeStream.from_arrays(s, d, CFG_WIRE),
            ConnectedComponents(),
            name="pool-restored",
        )
        ok.collect()


def test_rescale_reserves_the_job_slot_against_concurrent_submits():
    """The swap holds its max_jobs SLOT too: mid-drain the old job reads
    terminal, and without the slot reservation a concurrent submit could
    fill the cap and strand the resubmit (refused 'job cap'), killing
    the rescaled job."""
    s, d = _graph(37, n=WIN)
    with JobManager(RuntimeConfig(max_jobs=1)) as jm:
        job = jm.submit(lambda: iter(()), name="scaling", state_bytes=0)
        reserved = jm.begin_rescale(job, 0)
        jm.cancel(job, wait=True)  # 0 active jobs — but 1 rescaling
        with pytest.raises(AdmissionError, match="rescaling"):
            jm.submit_aggregation(
                EdgeStream.from_arrays(s, d, CFG_WIRE),
                ConnectedComponents(),
                name="slot-thief",
            )
        # the swap's own resubmit consumes exactly the reserved slot
        resub = jm.submit(
            lambda: iter(()),
            name="scaling",
            state_bytes=0,
            reserved_bytes=reserved,
        )
        resub.collect()
        jm.wait_all(30)
        ok = jm.submit_aggregation(
            EdgeStream.from_arrays(s, d, CFG_WIRE),
            ConnectedComponents(),
            name="after-slot",
        )
        ok.collect()


def test_abort_rescale_restores_a_live_jobs_budget():
    """The drain-failed path: a job whose cancel never completed is still
    RUNNING — aborting the swap must re-charge its bytes (a live summary
    with state_bytes=0 would let admission stack a second full job on
    top) and release both reservations."""
    one_job = ConnectedComponents().state_nbytes(CFG_WIRE)
    with JobManager(RuntimeConfig(max_state_bytes=one_job)) as jm:
        gate = threading.Event()

        def held_source():
            gate.wait(60)
            return iter(())

        job = jm.submit(held_source, name="undrainable", state_bytes=one_job)
        reserved = jm.begin_rescale(job, one_job)
        assert job.state_bytes == 0
        # the drain "times out": the job is still live; abort restores
        jm.abort_rescale(reserved, job=job, restore_state_bytes=one_job)
        assert job.state_bytes == one_job
        status = jm.status()
        assert status["admitted_state_bytes"] == one_job
        assert status["reserved_state_bytes"] == 0
        # the cap is exactly honest again: a second job is refused...
        with pytest.raises(AdmissionError, match="state-byte cap"):
            jm.submit(lambda: iter(()), name="over", state_bytes=one_job)
        gate.set()
        jm.cancel(job, wait=True)
        # ...and a TERMINAL job's abort restores nothing (budget is free)
        jm.begin_rescale(job, one_job)  # held is already 0
        jm.abort_rescale(reserved, job=job, restore_state_bytes=one_job)
        assert jm.status()["admitted_state_bytes"] == 0


def test_rescale_growth_beyond_cap_rejects_and_leaves_job_intact():
    """Re-pricing at a BIGGER geometry admission-checks the growth; a
    rejection leaves the job exactly as it was (still admitted)."""
    one_job = ConnectedComponents().state_nbytes(CFG_WIRE)
    with JobManager(RuntimeConfig(max_state_bytes=one_job)) as jm:
        gate = threading.Event()

        def held_source():
            gate.wait(60)
            return iter(())

        job = jm.submit(held_source, name="fixed", state_bytes=one_job)
        with pytest.raises(AdmissionError, match="re-pricing"):
            jm.begin_rescale(job, 2 * one_job)
        assert job.state_bytes == one_job  # untouched
        assert jm.status()["reserved_state_bytes"] == 0
        gate.set()
        jm.cancel(job, wait=True)


# ---------------------------------------------------------------------------
# executable sharing across jobs (the co-scheduling thesis)
# ---------------------------------------------------------------------------


def test_zero_recompiles_across_same_shape_jobs():
    from gelly_streaming_tpu.core import compile_cache

    warm_s, warm_d = _graph(29)
    _cc_serial(CFG_WIRE, warm_s, warm_d)  # first job's warmup compiles
    compile_cache.reset_stats()
    datasets = [_graph(seed) for seed in (31, 37, 41)]
    with JobManager() as jm:
        jobs = [
            jm.submit_aggregation(
                EdgeStream.from_arrays(s, d, CFG_WIRE),
                ConnectedComponents(),
                name=f"warmed-{i}",
            )
            for i, (s, d) in enumerate(datasets)
        ]
        for job in jobs:
            job.collect()
    stats = compile_cache.stats()
    assert stats["recompiles"] == 0, stats
    assert stats["compiles"] == 0, (
        "same-shape jobs should reuse the warm executables outright",
        stats,
    )


# ---------------------------------------------------------------------------
# isolation: one slow sink cannot stall other jobs
# ---------------------------------------------------------------------------


def test_slow_sink_does_not_stall_other_jobs():
    s, d = _graph(43)
    gate = threading.Event()
    slow_records = []

    def slow_sink(rec):
        gate.wait(120)  # wedged until the fast job proves it finished
        slow_records.append(rec)

    with JobManager(RuntimeConfig(job_queue_depth=2)) as jm:
        slow = jm.submit_aggregation(
            EdgeStream.from_arrays(s, d, CFG_WIRE),
            ConnectedComponents(),
            name="slow",
            sink=slow_sink,
        )
        fast = jm.submit_aggregation(
            EdgeStream.from_arrays(*_graph(47), CFG_WIRE),
            ConnectedComponents(),
            name="fast",
        )
        out = _materialize_cc(fast.results())
        assert len(out) == N // WIN
        assert fast.state == JobState.DONE
        assert not slow.wait(0), "slow job should still be in flight"
        status = jm.status()
        assert status["jobs"]["slow"]["job_queue_full_skips"] >= 1
        gate.set()
        assert slow.wait(60)
        assert slow.state == JobState.DONE
    assert len(slow_records) == N // WIN


def test_one_job_failure_is_isolated():
    def boom():
        yield (1,)
        raise ValueError("query exploded")

    s, d = _graph(53)
    with JobManager() as jm:
        bad = jm.submit(boom, name="bad")
        good = jm.submit_aggregation(
            EdgeStream.from_arrays(s, d, CFG_WIRE),
            ConnectedComponents(),
            name="good",
        )
        out = _materialize_cc(good.results())
        assert len(out) == N // WIN
        assert bad.wait(30)
        assert bad.state == JobState.FAILED
        assert isinstance(bad.error, ValueError)
        from gelly_streaming_tpu.runtime import JobError

        with pytest.raises(JobError, match="query exploded"):
            bad.collect()


def _wait_for(cond, what, timeout_s=30.0):
    deadline = time.monotonic() + timeout_s
    while not cond():
        assert time.monotonic() < deadline, f"timed out waiting for {what}"
        time.sleep(0.01)


def test_job_runs_at_most_two_records_ahead_of_the_device(monkeypatch):
    """Dispatch is asynchronous, so the scheduler bounds each job's records
    still computing on the device: with none finished a job emits two and
    is then skipped (``job_device_wait_skips``); each record that finishes
    lets exactly one more pull run, and every record arrives in order."""
    from gelly_streaming_tpu.runtime import manager as mgr

    done = set()
    monkeypatch.setattr(mgr, "_computed", lambda rec: rec[0] in done)

    def records():
        for i in range(8):
            yield (i,)

    with JobManager() as jm:
        job = jm.submit(records, name="ahead")

        def skips():
            return jm.status()["jobs"]["ahead"]["job_device_wait_skips"]

        _wait_for(lambda: skips() >= 3, "the device-wait skips")
        assert job.queue_depth == mgr._DEVICE_AHEAD == 2
        for i in range(3):
            done.add(i)
            _wait_for(lambda: job.queue_depth == 3 + i, f"record {i + 2}")
            before = skips()
            _wait_for(lambda: skips() >= before + 3, "the bound to hold again")
            assert job.queue_depth == 3 + i
        done.update(range(8))
        assert job.collect() == [(i,) for i in range(8)]
        assert job.state == JobState.DONE


def test_computed_reads_device_readiness():
    """The bound's readiness probe finds the device arrays inside pytrees
    and inside host wrapper objects (a summary record such as
    ``DisjointSet`` is not a pytree); computed and deleted arrays and host
    leaves count as finished (a deleted array is never probed)."""
    from gelly_streaming_tpu.core.output import device_leaves
    from gelly_streaming_tpu.runtime.manager import _computed

    class Summary:
        def __init__(self, **attrs):
            self.__dict__.update(attrs)

    parent, seen = jnp.arange(4), jnp.ones(3)
    rec = (7, Summary(parent=parent, extra={"seen": seen, "n": 3}), np.zeros(2), None)
    assert {id(x) for x in device_leaves(rec)} == {id(parent), id(seen)}
    gone = jnp.arange(4)
    gone.delete()
    assert _computed(rec)
    assert _computed({"summary": gone, "wrapped": Summary(parent=parent)})


def _walk_case(kind):
    """(record, its device leaves in wire order) for one record shape."""
    if kind == "cc":
        from gelly_streaming_tpu.summaries.disjoint_set import DisjointSet

        parent, seen = jnp.arange(8), jnp.ones(8, bool)
        return (DisjointSet(8, parent=parent, seen=seen),), [parent, seen]
    if kind == "triangles":
        from gelly_streaming_tpu.library.triangles import WindowTriangles
        from gelly_streaming_tpu.ops import oriented_triangles

        stats = jnp.zeros(len(oriented_triangles.STATS), jnp.int32)
        return (WindowTriangles(stats),), [stats]
    a, b, c = jnp.arange(3), jnp.ones(2), jnp.zeros(4, jnp.int32)
    return ({"b": b, "a": a}, (c, np.zeros(2), 3)), [a, b, c]


@pytest.mark.parametrize("kind", ["cc", "triangles", "tree"])
def test_one_record_walk_for_probe_fetch_and_copy(kind, monkeypatch):
    """The scheduler's in-flight probe, the async plane's host fetch and
    the host copy that makes the wire leaves all see a record's device
    leaves through one walk: the same arrays, in the same (wire) order —
    through a ``DisjointSet`` wrapper and a ``WindowTriangles`` record
    with ``on_host``, as through plain dicts and tuples."""
    import jax

    from gelly_streaming_tpu.core import async_exec, output
    from gelly_streaming_tpu.runtime.manager import _computed

    rec, want = _walk_case(kind)
    seen = {}
    walk, wait = output.device_leaves, jax.block_until_ready

    def spy_walk(r):
        seen.setdefault("walk", []).append([id(x) for x in walk(r)])
        return walk(r)

    def spy_wait(xs):
        seen["copy"] = [id(x) for x in xs]
        return wait(xs)

    monkeypatch.setattr(output, "device_leaves", spy_walk)
    monkeypatch.setattr(jax, "block_until_ready", spy_wait)
    assert _computed(rec)
    async_exec.start_host_fetch(rec)
    host = output.record_leaves(rec)
    ids = [id(x) for x in want]
    assert seen["walk"] == [ids, ids]  # the probe, then the fetch
    assert seen["copy"] == ids
    if kind == "tree":
        assert [np.asarray(x).tolist() for x in host] == [
            np.asarray(x).tolist() for x in (want + [np.zeros(2), 3])
        ]


# ---------------------------------------------------------------------------
# status / metrics scoping
# ---------------------------------------------------------------------------


def test_status_reports_per_job_counters_and_totals():
    metrics.reset_job_stats()
    datasets = [_graph(seed) for seed in (59, 61)]
    with JobManager() as jm:
        jobs = [
            jm.submit_aggregation(
                EdgeStream.from_arrays(s, d, CFG_WIRE),
                ConnectedComponents(),
                name=f"meter-{i}",
            )
            for i, (s, d) in enumerate(datasets)
        ]
        for job in jobs:
            job.collect()
        status = jm.status()
    windows = N // WIN
    for i in range(2):
        row = status["jobs"][f"meter-{i}"]
        assert row["state"] == JobState.DONE
        assert row["job_records"] == windows
        assert row["job_dispatches"] == windows
        assert row["job_edges"] == N
        assert row["edges_hint"] == N  # the source's total-edge hint
        assert row["job_dispatch_s"] > 0
    # module aggregates preserved as sums over the per-job rows
    per_job = metrics.all_job_stats()
    totals = metrics.job_totals()
    for key in ("job_records", "job_dispatches", "job_edges"):
        assert totals[key] == sum(row[key] for row in per_job.values())


# ---------------------------------------------------------------------------
# SIGKILL-mid-stream: two jobs resume from their independent checkpoints
# ---------------------------------------------------------------------------

_CHILD = textwrap.dedent(
    """
    import os, signal, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    sys.path.insert(0, {repo!r})
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import jax.numpy as jnp
    from gelly_streaming_tpu.core.aggregation import SummaryBulkAggregation
    from gelly_streaming_tpu.core.config import StreamConfig
    from gelly_streaming_tpu.core.stream import EdgeStream
    from gelly_streaming_tpu.runtime import JobManager

    class EdgeCount(SummaryBulkAggregation):
        order_free = True
        @property
        def cache_token(self):
            return type(self)
        def initial_state(self, cfg):
            return jnp.zeros((), jnp.int32)
        def update(self, state, src, dst, val, mask):
            return state + jnp.sum(mask.astype(jnp.int32))
        def combine(self, a, b):
            return a + b

    kill_after = int(os.environ.get("KILL_AFTER_SAVES", "0"))
    if kill_after:
        import gelly_streaming_tpu.utils.checkpoint as ckpt
        real = ckpt.save_state
        n = [0]
        def hooked(p, s):
            real(p, s)
            n[0] += 1
            if n[0] >= kill_after:
                os.kill(os.getpid(), signal.SIGKILL)  # no cleanup, no atexit
        ckpt.save_state = hooked

    cfg = StreamConfig(
        vertex_capacity=64,
        batch_size=96,
        # 128 % 96 != 0 -> the WINDOWED runtime (not the wire fast path)
        ingest_window_edges=128,
    )
    finals = {{}}
    with JobManager() as jm:
        jobs = []
        for name, seed, ck in (("a", 5, {ck_a!r}), ("b", 6, {ck_b!r})):
            rng = np.random.default_rng(seed)
            src = rng.integers(0, 64, 1024).astype(np.int32)
            dst = rng.integers(0, 64, 1024).astype(np.int32)
            stream = EdgeStream.from_arrays(src, dst, cfg)
            jobs.append(
                (name, jm.submit_aggregation(
                    stream, EdgeCount(), name=name, checkpoint_path=ck
                ))
            )
        for name, job in jobs:
            out = job.collect()
            finals[name] = int(out[-1][0])
    print("FINAL", finals["a"], finals["b"])
    """
)


def _run_child(script, env_extra):
    env = dict(os.environ, **env_extra)
    return subprocess.run(
        [sys.executable, str(script)],
        env=env,
        capture_output=True,
        timeout=300,
    )


def test_sigkill_two_jobs_resume_from_independent_checkpoints(tmp_path):
    """SIGKILL the manager mid-stream with two checkpointed jobs in flight;
    a fresh process resubmits both against their own checkpoints and each
    completes its non-idempotent count exactly — positions never merge."""
    ck_a = str(tmp_path / "ck_a")
    ck_b = str(tmp_path / "ck_b")
    script = tmp_path / "child.py"
    script.write_text(_CHILD.format(repo=REPO, ck_a=ck_a, ck_b=ck_b))

    first = _run_child(script, {"KILL_AFTER_SAVES": "6"})
    assert first.returncode == -signal.SIGKILL, (
        first.returncode,
        first.stdout,
        first.stderr,
    )
    # both jobs made independent progress before the kill
    assert os.path.exists(ck_a + ".npz") or os.path.exists(ck_b + ".npz")

    second = _run_child(script, {})
    assert second.returncode == 0, second.stderr.decode()
    assert b"FINAL 1024 1024" in second.stdout, (
        second.stdout,
        second.stderr,
    )


# ---------------------------------------------------------------------------
# gelly-serve
# ---------------------------------------------------------------------------


def test_serve_main_runs_jobs_to_done(capsys, monkeypatch):
    from gelly_streaming_tpu.core import output
    from gelly_streaming_tpu.runtime import serve

    copied = []
    copy = output.record_leaves

    def spy(rec):
        copied.append(copy(rec))
        return copied[-1]

    monkeypatch.setattr(output, "record_leaves", spy)
    rc = serve.main(
        [
            "--jobs",
            "2",
            "--query",
            "cc",
            "--edges",
            "8192",
            "--capacity",
            "4096",
            "--window-edges",
            "4096",
            "--status-interval",
            "0",
        ]
    )
    assert rc == 0
    out = capsys.readouterr()
    assert "2 job(s)" in out.out
    assert "DONE" in out.err
    # the synthetic sink copies each cc record's parent and seen arrays
    assert copied and all(
        [leaf.shape for leaf in leaves] == [(), (4096,), (4096,)]
        for leaves in copied
    )
