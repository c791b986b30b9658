"""Streaming RPC serving plane (ISSUE 8): the network frontend on the
multi-tenant job runtime.

The contracts under test:

* EQUIVALENCE — N remote clients streaming wire batches concurrently over
  loopback produce emission leaves BIT-IDENTICAL to the same jobs run
  in-process (windowed / async / owner-sharded planes; fixed-width and
  BDV wire formats), and warmed same-shape remote jobs compile nothing.
* ROBUSTNESS — garbage, truncated, and oversized frames get a clean error
  frame (never a hang or a traceback-closed socket); wire buffers failing
  the ``from_wire`` guards are refused per buffer with the connection kept
  alive.
* RECOVERY — drain replies with checkpoint-derived resume cursors;
  SIGKILL the server mid-stream, restart, reconnect: the client resumes
  from the cursor with exact non-idempotent counts and overlap-only
  emissions (the at-least-once contract checkpoints already pin).
* TENANCY — token auth, per-tenant admission caps and scheduler weights,
  per-tenant observability counters.

Every test carries ``timeout_cap``: a wedged scheduler, a blocking pull
on a starved socket, or a hung drain must FAIL, not wedge tier-1.
"""

import os
import signal
import socket
import struct
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from gelly_streaming_tpu.core.config import (
    RuntimeConfig,
    ServerConfig,
    StreamConfig,
    TenantConfig,
)
from gelly_streaming_tpu.core.stream import EdgeStream
from gelly_streaming_tpu.core.types import EdgeBatch
from gelly_streaming_tpu.io.sources import NetworkEdgeSource, SourceQuiesced
from gelly_streaming_tpu.library.connected_components import (
    ConnectedComponents,
)
from gelly_streaming_tpu.runtime import JobManager, JobState
from gelly_streaming_tpu.runtime import protocol
from gelly_streaming_tpu.runtime.client import (
    ClientError,
    GellyClient,
    ServerRefused,
)
from gelly_streaming_tpu.runtime.server import (
    StreamServer,
    _TokenBucket,
    record_leaves,
)
from gelly_streaming_tpu.utils import metrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.timeout_cap(300)

CAP = 1 << 12
W = 1 << 10
B = 1 << 9
N = 4 * W


def _graph(seed: int, n: int = N, cap: int = CAP):
    rng = np.random.default_rng(seed)
    return (
        rng.integers(0, cap, n).astype(np.int32),
        rng.integers(0, cap, n).astype(np.int32),
    )


def _batches_stream(src, dst, cfg, batch):
    """The in-process twin of a remote push job: the identical decoded
    batch sequence through the identical windowed planes."""

    def factory():
        for i in range(0, len(src), batch):
            yield EdgeBatch.from_arrays(
                src[i : i + batch], dst[i : i + batch], pad_to=batch
            )

    return EdgeStream.from_batches(factory, cfg)


def _oracle_leaves(src, dst, cfg, batch, descriptor=None):
    out = _batches_stream(src, dst, cfg, batch).aggregate(
        descriptor or ConnectedComponents()
    )
    return [record_leaves(rec) for rec in out]


def _assert_leaves_equal(want, got, label=""):
    assert len(want) == len(got), (label, len(want), len(got))
    for w, (a, b) in enumerate(zip(want, got)):
        assert len(a) == len(b), (label, w)
        for x, y in zip(a, b):
            assert np.array_equal(x, y), f"{label} window {w} diverged"


# ---------------------------------------------------------------------------
# equivalence: remote == in-process, bit-identical
# ---------------------------------------------------------------------------


def test_four_remote_clients_stream_bit_identical_concurrently():
    """4 clients, each its own connection/thread/dataset, streaming
    concurrently: every job's emission leaves equal the in-process run of
    the same batches — and (CC on these planes) the from_arrays wire fast
    path too, so the remote plane is anchored to the user-facing oracle."""
    cfg = StreamConfig(
        vertex_capacity=CAP, batch_size=B, ingest_window_edges=W
    )
    datasets = [_graph(seed) for seed in range(4)]
    oracles = [_oracle_leaves(s, d, cfg, B) for s, d in datasets]
    results = [None] * 4
    errors = []
    with JobManager() as jm, StreamServer(jm, ServerConfig()) as server:

        def run_client(i):
            try:
                s, d = datasets[i]
                with GellyClient("127.0.0.1", server.port) as c:
                    c.submit(
                        name=f"cc-{i}",
                        query="cc",
                        capacity=CAP,
                        window_edges=W,
                        batch=B,
                    )
                    c.push_edges(
                        f"cc-{i}", s, d, batch=B, capacity=CAP, bdv=(i % 2 == 1)
                    )
                    results[i] = list(
                        c.iter_results(f"cc-{i}", deadline_s=240)
                    )
            except BaseException as e:  # surfaced on the main thread
                errors.append((i, e))

        threads = [
            threading.Thread(target=run_client, args=(i,)) for i in range(4)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=280)
    assert not errors, errors
    for i in range(4):
        _assert_leaves_equal(oracles[i], results[i], f"client {i}")
    # anchor to the independent from_arrays oracle (the wire fast path):
    # parent arrays must agree value-for-value across planes
    s, d = datasets[0]
    wire = [
        np.asarray(rec[0].parent)
        for rec in EdgeStream.from_arrays(s, d, cfg).aggregate(
            ConnectedComponents()
        )
    ]
    got = [leaves[1] for leaves in results[0]]  # [capacity, parent, seen]
    for a, b in zip(wire, got):
        assert np.array_equal(a, b)


def test_remote_async_and_sharded_planes_match_oracle():
    s, d = _graph(7)
    for name, kwargs in (
        ("async", {"async_windows": 2}),
        ("sharded", {"num_shards": 2}),
    ):
        cfg = StreamConfig(
            vertex_capacity=CAP, batch_size=B, ingest_window_edges=W, **kwargs
        )
        oracle = _oracle_leaves(s, d, cfg, B)
        with JobManager() as jm, StreamServer(jm, ServerConfig()) as server:
            with GellyClient("127.0.0.1", server.port) as c:
                c.submit(
                    name=name,
                    query="cc",
                    capacity=CAP,
                    window_edges=W,
                    batch=B,
                    **kwargs,
                )
                c.push_edges(name, s, d, batch=B, capacity=CAP)
                got = list(c.iter_results(name, deadline_s=240))
        _assert_leaves_equal(oracle, got, name)


def test_warmed_same_shape_remote_jobs_compile_nothing():
    from gelly_streaming_tpu.core import compile_cache

    cfg = StreamConfig(
        vertex_capacity=CAP, batch_size=B, ingest_window_edges=W
    )
    warm_s, warm_d = _graph(29)
    _oracle_leaves(warm_s, warm_d, cfg, B)  # the warmup pays the compiles
    compile_cache.reset_stats()
    datasets = [_graph(seed) for seed in (31, 37)]
    with JobManager() as jm, StreamServer(jm, ServerConfig()) as server:
        with GellyClient("127.0.0.1", server.port) as c:
            for i, (s, d) in enumerate(datasets):
                c.submit(
                    name=f"warm-{i}",
                    query="cc",
                    capacity=CAP,
                    window_edges=W,
                    batch=B,
                )
                c.push_edges(f"warm-{i}", s, d, batch=B, capacity=CAP)
            for i in range(2):
                assert list(c.iter_results(f"warm-{i}", deadline_s=240))
    stats = compile_cache.stats()
    assert stats["recompiles"] == 0, stats
    assert stats["compiles"] == 0, (
        "warmed same-shape remote jobs should reuse executables outright",
        stats,
    )


def test_served_cc_cuts_panes_without_pipeline_step(monkeypatch):
    """A pushed cc job's pane cutter reads the network source's host batches
    as they come: each window's record equals a host union-find over the
    edges pushed so far, no stage-less stream ran a pipeline step, and the
    scheduler's in-flight bound probes the record's device arrays."""
    from fixtures import host_min_labels

    from gelly_streaming_tpu.core.stream import EdgeStream

    from gelly_streaming_tpu.core.output import device_leaves
    from gelly_streaming_tpu.runtime import manager as mgr

    stepped, probed = [], []
    orig_step, orig_computed = EdgeStream._compiled_step, mgr._computed

    def spy_step(self):
        stepped.append(self._stages)
        return orig_step(self)

    def spy_computed(rec):
        probed.append(len(device_leaves(rec)))
        return orig_computed(rec)

    monkeypatch.setattr(EdgeStream, "_compiled_step", spy_step)
    monkeypatch.setattr(mgr, "_computed", spy_computed)
    s, d = _graph(41)
    with JobManager() as jm, StreamServer(jm, ServerConfig()) as server:
        with GellyClient("127.0.0.1", server.port) as c:
            c.submit(name="cc-direct", query="cc", capacity=CAP,
                     window_edges=W, batch=B)
            c.push_edges("cc-direct", s, d, batch=B, capacity=CAP)
            records = list(c.iter_results("cc-direct", deadline_s=240))
    assert len(records) == N // W
    for k, (_cap, parent, seen) in enumerate(records):
        hi = (k + 1) * W
        roots = np.asarray(parent)
        while not np.array_equal(roots[roots], roots):
            roots = roots[roots]
        # union by min root: each root is its component's smallest id
        np.testing.assert_array_equal(
            roots, host_min_labels(CAP, s[:hi], d[:hi]), f"window {k}"
        )
        want_seen = np.zeros(CAP, bool)
        want_seen[s[:hi]] = want_seen[d[:hi]] = True
        np.testing.assert_array_equal(np.asarray(seen, bool), want_seen)
    assert () not in stepped, "a stage-less stream ran the pipeline step"
    # the scheduler's in-flight bound sees each record's parent and seen
    assert probed and set(probed) == {2}, probed


# ---------------------------------------------------------------------------
# protocol robustness: refusal, never a hang or a dirty close
# ---------------------------------------------------------------------------


def _raw_conn(port):
    sock = socket.create_connection(("127.0.0.1", port), timeout=30)
    return sock, sock.makefile("rwb")


def test_garbage_frame_gets_clean_error_frame_then_close():
    with JobManager() as jm, StreamServer(jm, ServerConfig()) as server:
        sock, f = _raw_conn(server.port)
        f.write(b"ZZZZ" + b"\x00" * 64)
        f.flush()
        reply = protocol.read_frame(f)
        assert reply is not None
        head, _ = reply
        assert head["ok"] is False and head["code"] == "bad-frame"
        assert f.read(1) == b""  # server closed its side cleanly
        sock.close()
        # the listener is unharmed: a fresh connection works
        with GellyClient("127.0.0.1", server.port) as c:
            assert c.ping()["ok"]


def test_oversized_payload_refused_with_error_frame():
    srv_cfg = ServerConfig(max_frame_bytes=1 << 14)
    with JobManager() as jm, StreamServer(jm, srv_cfg) as server:
        sock, f = _raw_conn(server.port)
        head = b'{"verb":"push"}'
        f.write(
            protocol.MAGIC
            + struct.pack(">II", len(head), (1 << 14) + 1)[0:8]
        )
        f.write(head)
        f.flush()
        reply = protocol.read_frame(f)
        head_r, _ = reply
        assert head_r["ok"] is False and head_r["code"] == "frame-too-large"
        sock.close()


def test_truncated_frame_and_undecodable_header_are_survivable():
    with JobManager() as jm, StreamServer(jm, ServerConfig()) as server:
        # truncated: half a prefix then hangup — nothing to reply to
        sock, f = _raw_conn(server.port)
        f.write(protocol.MAGIC + b"\x00\x00")
        f.flush()
        sock.close()
        # undecodable JSON header
        sock2, f2 = _raw_conn(server.port)
        bad = b"\xff\xfenot json"
        f2.write(protocol.MAGIC + struct.pack(">II", len(bad), 0) + bad)
        f2.flush()
        head, _ = protocol.read_frame(f2)
        assert head["ok"] is False and head["code"] == "bad-frame"
        sock2.close()
        # server still healthy
        with GellyClient("127.0.0.1", server.port) as c:
            assert c.ping()["ok"]


def test_bad_wire_buffers_refused_per_buffer_connection_survives():
    from gelly_streaming_tpu.io import wire as wire_mod

    metrics.reset_tenant_stats()
    with JobManager() as jm, StreamServer(jm, ServerConfig()) as server:
        with GellyClient("127.0.0.1", server.port) as c:
            c.submit(
                name="j", query="cc", capacity=CAP, window_edges=W, batch=B
            )
            # wrong size for the fixed width
            with pytest.raises(ServerRefused, match="holds") as e:
                c.push_wire("j", np.zeros(7, np.uint8))
            assert e.value.code == "bad-wire"
            # out-of-range ids (width 2 can express ids >= CAP=4096)
            s = np.full(B, CAP + 5, np.int32)
            buf = wire_mod.pack_edges(s, s, 2)
            with pytest.raises(ServerRefused, match="decodes vertex ids"):
                c.push_wire("j", buf)
            # BDV truncated below the per-buffer byte floor
            with pytest.raises(ServerRefused, match="truncated"):
                c.push_wire("j", np.zeros(16, np.uint8), kind="bdv")
            # tail with a count/payload mismatch
            with pytest.raises(ServerRefused, match="tail payload"):
                c.call(
                    {"verb": "push", "job": "j", "kind": "tail", "count": 8},
                    np.zeros(4, "<i4").tobytes(),
                )
            # unknown job / unknown verb are typed refusals
            with pytest.raises(ServerRefused) as e2:
                c.push_wire("nope", np.zeros(4 * B, np.uint8))
            assert e2.value.code == "unknown-job"
            with pytest.raises(ServerRefused) as e3:
                c.call({"verb": "frobnicate"})
            assert e3.value.code == "unknown-verb"
            # the connection survived every refusal; the job still works
            src, dst = _graph(3)
            c.push_edges("j", src, dst, batch=B, capacity=CAP)
            assert len(list(c.iter_results("j", deadline_s=240))) == N // W
    rejects = metrics.tenant_totals()["tenant_ingest_rejects"]
    assert rejects >= 3, rejects


# ---------------------------------------------------------------------------
# isolation: a dead/idle client starves only its own job
# ---------------------------------------------------------------------------


def test_push_to_terminal_job_refused_not_wedged():
    """A cancelled job's generator never drains its ingest queue again;
    a client that keeps pushing must get a typed refusal once the queue
    fills — never a forever-blocked connection thread."""
    from gelly_streaming_tpu.io import wire as wire_mod

    srv_cfg = ServerConfig(ingest_queue_batches=4)
    with JobManager() as jm, StreamServer(jm, srv_cfg) as server:
        with GellyClient("127.0.0.1", server.port) as c:
            # a window the pushes below never close: the job stays PENDING
            c.submit(
                name="t", query="cc", capacity=CAP, window_edges=1 << 20,
                batch=B,
            )
            assert c.cancel("t")["state"] == JobState.CANCELLED
            s = np.zeros(B, np.int32)
            buf = wire_mod.pack_edges(s, s, 2)
            with pytest.raises(ServerRefused) as e:
                for _ in range(8):  # queue cap 4: the 5th+ must refuse
                    c.push_wire("t", buf)
            assert e.value.code == "terminal"


def test_dead_client_starves_only_its_own_job():
    metrics.reset_job_stats()
    with JobManager() as jm, StreamServer(jm, ServerConfig()) as server:
        dead = GellyClient("127.0.0.1", server.port)
        dead.submit(
            name="starved", query="cc", capacity=CAP, window_edges=W, batch=B
        )
        # push HALF a window, then vanish without eos: the job must never
        # block the scheduler round
        s, d = _graph(41, n=W // 2)
        dead.push_edges(
            "starved", s, d, batch=B, capacity=CAP, close=False
        )
        dead.close()
        with GellyClient("127.0.0.1", server.port) as c:
            src, dst = _graph(43)
            c.submit(
                name="live", query="cc", capacity=CAP, window_edges=W, batch=B
            )
            c.push_edges("live", src, dst, batch=B, capacity=CAP)
            got = list(c.iter_results("live", deadline_s=240))
            assert len(got) == N // W
            status = c.status()
        row = status["status"]["jobs"]["default/starved"]
        assert row["state"] in ("PENDING", "RUNNING")
        # the gate skipped the starved job's rounds instead of pulling
        assert (
            metrics.job_stats("default/starved")["job_source_wait_skips"] >= 1
        )


# ---------------------------------------------------------------------------
# drain -> restart -> resume (graceful), and the status verb
# ---------------------------------------------------------------------------


def test_drain_replies_cursors_and_restart_resumes_exactly(tmp_path):
    srv_cfg = ServerConfig(checkpoint_prefix=str(tmp_path / "ck"))
    src, dst = _graph(11)
    serial = [(i + 1) * W for i in range(N // W)]
    first = []
    with JobManager() as jm, StreamServer(jm, srv_cfg) as server:
        with GellyClient("127.0.0.1", server.port) as c:
            c.submit(
                name="cnt",
                query="edges",
                capacity=CAP,
                window_edges=W,
                batch=B,
                checkpoint=True,
            )
            # also an async-windowed job mid-flight: its in-flight windows
            # must flush through the completion-queue path, not wedge drain
            c.submit(
                name="afly",
                query="cc",
                capacity=CAP,
                window_edges=W,
                batch=B,
                async_windows=2,
            )
            c.push_edges(
                "afly", *_graph(13, n=2 * W), batch=B, capacity=CAP,
                close=False,
            )
            half = 2 * W + W // 2
            c.push_edges(
                "cnt", src[:half], dst[:half], batch=B, capacity=CAP,
                close=False,
            )
            deadline = time.monotonic() + 120
            while len(first) < 2 and time.monotonic() < deadline:
                recs, _state, _eos = c.results("cnt", timeout_ms=2000)
                first.extend(int(r[0]) for r in recs)
            assert len(first) >= 2
            t0 = time.monotonic()
            reply = c.drain()
            assert time.monotonic() - t0 < 90  # flush, not wedge
            cur = reply["cursors"]["cnt"]
            assert cur["state"] == "CANCELLED"
            # the cursor is whole saved windows, behind or at the emissions
            assert cur["resume_edges"] is not None
            assert cur["resume_edges"] % W == 0
            assert 0 < cur["resume_edges"] <= len(first) * W
            assert reply["cursors"]["afly"]["state"] == "CANCELLED"
            # a quiesced source refuses further pushes loudly — and a
            # refusal mid-PIPELINE (several frames in flight) must leave
            # the connection in sync: the next verb still works
            with pytest.raises(ServerRefused) as e:
                c.push_edges(
                    "cnt", src, dst, batch=B, capacity=CAP, close=False,
                )
            assert e.value.code == "quiesced"
            assert c.status()["ok"]
    # "restart": a fresh manager + server over the same checkpoint prefix
    with JobManager() as jm, StreamServer(jm, srv_cfg) as server:
        with GellyClient("127.0.0.1", server.port) as c:
            rep = c.submit(
                name="cnt",
                query="edges",
                capacity=CAP,
                window_edges=W,
                batch=B,
                checkpoint=True,
            )
            assert rep["resume_edges"] == cur["resume_edges"]
            c.push_edges(
                "cnt", src, dst, batch=B, capacity=CAP,
                start=rep["resume_edges"],
            )
            second = [
                int(r[0]) for r in c.iter_results("cnt", deadline_s=240)
            ]
    # overlap-only emissions; the non-idempotent final count is exact
    overlap = len(first) + len(second) - len(serial)
    assert overlap >= 0, "drain/resume dropped emissions (a gap)"
    assert first[: len(first) - overlap] + second == serial
    assert second[-1] == N


def test_status_verb_reuses_serve_status_lines_and_tenant_stats():
    from gelly_streaming_tpu.runtime.serve import _status_lines

    metrics.reset_tenant_stats()
    with JobManager() as jm, StreamServer(jm, ServerConfig()) as server:
        with GellyClient("127.0.0.1", server.port) as c:
            c.submit(
                name="st", query="cc", capacity=CAP, window_edges=W, batch=B
            )
            s, d = _graph(17)
            c.push_edges("st", s, d, batch=B, capacity=CAP)
            assert list(c.iter_results("st", deadline_s=240))
            reply = c.status()
    # the verb ships the SAME renderer's lines (no duplicated formatter)
    assert reply["lines"] == _status_lines(reply["status"])
    assert any("default/st" in line for line in reply["lines"])
    ten = reply["tenants"]["default"]
    assert ten["tenant_requests"] > 0
    assert ten["tenant_ingest_edges"] == N
    assert ten["tenant_ingest_wire_bytes"] > 0
    assert ten["tenant_ingest_raw_bytes"] == 8 * N
    assert ten["tenant_jobs_submitted"] == 1
    assert reply["server"]["connections"] >= 1


# ---------------------------------------------------------------------------
# tenancy: auth, quotas, priority
# ---------------------------------------------------------------------------

_TENANTS = (
    TenantConfig(tenant="alpha", token="tok-a", max_jobs=1, weight=3),
    TenantConfig(
        tenant="beta", token="tok-b", max_state_bytes=1, max_ingest_bps=0
    ),
)


def test_tenant_auth_and_quota_enforcement():
    metrics.reset_tenant_stats()
    srv_cfg = ServerConfig(tenants=_TENANTS)
    with JobManager() as jm, StreamServer(jm, srv_cfg) as server:
        # missing/unknown token refused before any verb runs
        with GellyClient("127.0.0.1", server.port, token="wrong") as c:
            with pytest.raises(ServerRefused) as e:
                c.ping()
            assert e.value.code == "auth"
        with GellyClient("127.0.0.1", server.port, token="tok-a") as c:
            rep = c.submit(
                name="one",
                query="cc",
                capacity=CAP,
                window_edges=W,
                batch=B,
                weight=2,
            )
            # tenant weight multiplies job weight in the fair scheduler
            assert rep["weight"] == 6
            with pytest.raises(ServerRefused) as e:
                c.submit(
                    name="two",
                    query="cc",
                    capacity=CAP,
                    window_edges=W,
                    batch=B,
                )
            assert e.value.code == "admission"
            # alpha's namespace is its own: beta can reuse the name, but
            # beta's 1-byte state cap refuses any real summary
            with GellyClient(
                "127.0.0.1", server.port, token="tok-b"
            ) as cb:
                with pytest.raises(ServerRefused) as eb:
                    cb.submit(
                        name="one",
                        query="cc",
                        capacity=CAP,
                        window_edges=W,
                        batch=B,
                    )
                assert eb.value.code == "admission"
            s, d = _graph(19)
            c.push_edges("one", s, d, batch=B, capacity=CAP)
            assert list(c.iter_results("one", deadline_s=240))
            # status is tenant-scoped: alpha sees only alpha's jobs and
            # only alpha's counters — no cross-tenant disclosure
            view = c.status()
            assert all(
                k.startswith("alpha/") for k in view["status"]["jobs"]
            )
            assert set(view["tenants"]) == {"alpha"}
    stats = metrics.all_tenant_stats()
    assert stats["alpha"]["tenant_admission_rejections"] == 1
    assert stats["beta"]["tenant_admission_rejections"] == 1
    assert stats["alpha"]["tenant_ingest_edges"] == N


def test_token_bucket_math():
    bucket = _TokenBucket(1000)
    assert bucket.reserve(500) == 0.0
    assert bucket.reserve(500) == 0.0  # the 1-second burst allowance
    sleep_s = bucket.reserve(1000)
    assert sleep_s > 0.5  # ~1s of debt at 1000 B/s
    assert _TokenBucket(0).reserve(1 << 30) == 0.0  # unlimited


def test_tenant_ingest_rate_limit_throttles_connection():
    metrics.reset_tenant_stats()
    tenants = (
        TenantConfig(tenant="slow", token="tok-s", max_ingest_bps=16384),
    )
    with JobManager() as jm, StreamServer(
        jm, ServerConfig(tenants=tenants)
    ) as server:
        with GellyClient("127.0.0.1", server.port, token="tok-s") as c:
            c.submit(
                name="rl", query="cc", capacity=CAP, window_edges=W, batch=B
            )
            s, d = _graph(23, n=2 * W)
            c.push_edges("rl", s, d, batch=B, capacity=CAP)
            assert list(c.iter_results("rl", deadline_s=240))
    # 2048 edges at 4 B/edge (width 2) = 8 KiB wire > the 16 KiB burst
    # only partially — but the accounting must prove the limiter engaged
    # on the byte ledger even when no sleep happened
    stats = metrics.tenant_stats("slow")
    assert stats["tenant_ingest_wire_bytes"] >= 4 * 2 * W


# ---------------------------------------------------------------------------
# NetworkEdgeSource units: the ready() gate and the push guards
# ---------------------------------------------------------------------------


def test_network_source_ready_accounting_and_resume():
    cfg = StreamConfig(
        vertex_capacity=64, batch_size=16, ingest_window_edges=32
    )
    src = NetworkEdgeSource(cfg, 16)
    assert not src.ready()  # empty
    from gelly_streaming_tpu.io import wire as wire_mod

    buf = wire_mod.pack_edges(
        np.arange(16, dtype=np.int32), np.arange(16, dtype=np.int32), 2
    )
    for _ in range(2):  # one full window queued, boundary edge not yet
        src.push_wire(buf, 2)
    assert not src.ready()
    src.push_wire(buf, 2)  # first edge of window 1 arrives: closable
    assert src.ready()
    # closed: always ready (drain everything, then end-of-stream)
    src.close()
    assert src.ready()
    with pytest.raises(SourceQuiesced):
        src.push_wire(buf, 2)
    # resume: filler windows never make the source ready on their own
    res = NetworkEdgeSource(cfg, 16, resume_edges=64)
    assert not res.ready()
    res.push_wire(buf, 2)  # 16 real edges: window 2 not yet closable
    assert not res.ready()
    for _ in range(2):
        res.push_wire(buf, 2)
    assert res.ready()  # edge 96 arrived: window 2 closable
    # quiesce freezes scheduling and refuses pushes
    res.quiesce()
    assert not res.ready()
    with pytest.raises(SourceQuiesced):
        res.push_wire(buf, 2)
    # misaligned cursors and window-spanning batches are refused loudly
    with pytest.raises(ValueError, match="multiple"):
        NetworkEdgeSource(cfg, 16, resume_edges=48)
    with pytest.raises(ValueError, match="must be <="):
        NetworkEdgeSource(cfg, 64)


def test_network_source_tail_guards():
    cfg = StreamConfig(vertex_capacity=64, batch_size=16)
    src = NetworkEdgeSource(cfg, 16)
    with pytest.raises(ValueError, match="intern ids first"):
        src.push_tail(np.array([99], np.int64), np.array([1], np.int64))
    with pytest.raises(ValueError, match="1..16"):
        src.push_tail(np.zeros(17, np.int32), np.zeros(17, np.int32))
    assert src.push_tail([1, 2], [3, 4]) == 2


# ---------------------------------------------------------------------------
# SIGKILL the server mid-stream; restart; reconnect; resume
# ---------------------------------------------------------------------------


def _spawn_listen_server(tmp_path, extra_env=None):
    env = dict(
        os.environ,
        XLA_FLAGS="--xla_force_host_platform_device_count=1",
        JAX_PLATFORMS="cpu",
        PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""),
        **(extra_env or {}),
    )
    proc = subprocess.Popen(
        [
            sys.executable,
            "-m",
            "gelly_streaming_tpu.runtime.serve",
            "--listen",
            "127.0.0.1:0",
            "--checkpoint-prefix",
            str(tmp_path / "ck"),
            "--status-interval",
            "0",
        ],
        env=env,
        stderr=subprocess.PIPE,
        stdout=subprocess.PIPE,
    )
    port = None
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline:
        line = proc.stderr.readline().decode()
        if "listening on" in line:
            port = int(line.rsplit(":", 1)[1])
            break
        if not line and proc.poll() is not None:
            break
    assert port, "server child never reported its port"
    return proc, port


@pytest.mark.timeout_cap(600)
def test_sigkill_server_restart_client_resumes_from_cursor(tmp_path):
    src, dst = _graph(47)
    serial = [(i + 1) * W for i in range(N // W)]

    proc, port = _spawn_listen_server(tmp_path)
    first = []
    try:
        with GellyClient("127.0.0.1", port) as c:
            c.submit(
                name="kill",
                query="edges",
                capacity=CAP,
                window_edges=W,
                batch=B,
                checkpoint=True,
            )
            half = 3 * W
            c.push_edges(
                "kill", src[:half], dst[:half], batch=B, capacity=CAP,
                close=False,
            )
            deadline = time.monotonic() + 180
            while len(first) < 2 and time.monotonic() < deadline:
                recs, _state, _eos = c.results("kill", timeout_ms=2000)
                first.extend(int(r[0]) for r in recs)
        assert len(first) >= 2
    finally:
        proc.kill()  # SIGKILL: no drain, no cleanup, no atexit
        proc.wait(timeout=30)

    proc2, port2 = _spawn_listen_server(tmp_path)
    try:
        with GellyClient("127.0.0.1", port2) as c:
            rep = c.submit(
                name="kill",
                query="edges",
                capacity=CAP,
                window_edges=W,
                batch=B,
                checkpoint=True,
            )
            # the cursor came from the dead process's checkpoint
            assert rep["resume_edges"] > 0
            assert rep["resume_edges"] % W == 0
            c.push_edges(
                "kill", src, dst, batch=B, capacity=CAP,
                start=rep["resume_edges"],
            )
            second = [
                int(r[0]) for r in c.iter_results("kill", deadline_s=240)
            ]
            # remote shutdown ends the --listen loop cleanly
            c.drain(shutdown=True)
        assert proc2.wait(timeout=60) == 0
    finally:
        if proc2.poll() is None:
            proc2.kill()
            proc2.wait(timeout=30)
    overlap = len(first) + len(second) - len(serial)
    assert overlap >= 0, "SIGKILL/restart dropped emissions (a gap)"
    assert first[: len(first) - overlap] + second == serial
    assert second[-1] == N  # exact non-idempotent count: state exactly-once


# ---------------------------------------------------------------------------
# gelly-client console script against a live server
# ---------------------------------------------------------------------------


def test_gelly_client_console_flow(capsys):
    from gelly_streaming_tpu.runtime import client as client_mod

    with JobManager() as jm, StreamServer(jm, ServerConfig()) as server:
        addr = f"127.0.0.1:{server.port}"
        assert (
            client_mod.main(
                [
                    "--connect",
                    addr,
                    "submit",
                    "--name",
                    "cli",
                    "--query",
                    "edges",
                    "--capacity",
                    str(CAP),
                    "--window-edges",
                    str(W),
                    "--batch",
                    str(B),
                ]
            )
            == 0
        )
        assert (
            client_mod.main(
                [
                    "--connect",
                    addr,
                    "push-edges",
                    "--job",
                    "cli",
                    "--edges",
                    str(N),
                    "--capacity",
                    str(CAP),
                    "--batch",
                    str(B),
                ]
            )
            == 0
        )
        assert client_mod.main(["--connect", addr, "status"]) == 0
        assert client_mod.main(["--connect", addr, "drain"]) == 0
    out = capsys.readouterr().out
    assert "submitted cli" in out
    assert "end of stream" in out
    assert "default/cli" in out


def test_client_deadline_fails_loudly_not_forever():
    with JobManager() as jm, StreamServer(jm, ServerConfig()) as server:
        with GellyClient("127.0.0.1", server.port) as c:
            c.submit(
                name="idle", query="cc", capacity=CAP, window_edges=W, batch=B
            )
            with pytest.raises(ClientError, match="no end-of-stream"):
                for _ in c.iter_results(
                    "idle", poll_timeout_ms=100, deadline_s=1.0
                ):
                    pass


def test_record_leaves_frees_device_leaves_without_the_collector():
    """A record's device leaves are freed as soon as the record and its
    host leaves are dropped: nothing in the walk holds them in a reference
    cycle that only the garbage collector would break."""
    import gc

    import jax
    import jax.numpy as jnp

    gc.disable()
    try:
        rec = (jnp.arange(4093, dtype=jnp.int32) + 7, {"b": jnp.ones((4091,))})
        leaves = record_leaves(rec)
        assert [leaf.shape for leaf in leaves] == [(4093,), (4091,)]
        assert all(isinstance(leaf, np.ndarray) for leaf in leaves)
        del rec, leaves
        shapes = {a.shape for a in jax.live_arrays()}
        assert (4093,) not in shapes and (4091,) not in shapes
    finally:
        gc.enable()


# ---------------------------------------------------------------------------
# observability plane (ISSUE 9): metrics / trace verbs, FAILED post-mortems,
# gelly-top


def _push_one_job(server, name, seed=0, trace_sample=0.0, token=""):
    s, d = _graph(seed)
    with GellyClient("127.0.0.1", server.port, token=token) as c:
        spec = dict(
            name=name, query="cc", capacity=CAP, window_edges=W, batch=B
        )
        if trace_sample:
            spec["trace_sample"] = trace_sample
        c.submit(**spec)
        c.push_edges(name, s, d, batch=B, capacity=CAP)
        return list(c.iter_results(name, deadline_s=240))


def test_metrics_verb_returns_histograms_and_prometheus():
    metrics.reset_histograms()
    with JobManager() as jm, StreamServer(jm, ServerConfig()) as server:
        recs = _push_one_job(server, "obs")
        assert recs
        with GellyClient("127.0.0.1", server.port) as c:
            snap = c.metrics()
            # the four canonical histograms all saw this job
            job_rows = snap["histograms"]["jobs"]["default/obs"]
            for name in (
                "submit_to_first_emission_ms",
                "window_close_to_emission_ms",
                "push_to_fold_ms",
                "sched_queue_wait_ms",
            ):
                assert job_rows[name]["count"] > 0, name
                assert job_rows[name]["p99_ms"] >= job_rows[name]["p50_ms"]
            # per-tenant submit-to-first row (stamped at the server sink)
            t_row = snap["histograms"]["tenants"]["default"]
            assert t_row["submit_to_first_emission_ms"]["count"] == 1
            # process planes ride along
            assert snap["pipeline"]["pipeline_windows_drained"] >= 0
            assert "recompiles" in snap["compile_cache"]
            # prometheus text renders the same registry
            text = c.metrics_prometheus()
            assert 'gelly_job_records{job="default/obs"}' in text
            assert "gelly_submit_to_first_emission_ms_count" in text
            assert 'le="+Inf"' in text


def test_metrics_verb_is_tenant_scoped():
    cfg = ServerConfig(
        tenants=(
            TenantConfig(tenant="a", token="tok-a"),
            TenantConfig(tenant="b", token="tok-b"),
        )
    )
    metrics.reset_histograms()
    with JobManager() as jm, StreamServer(jm, cfg) as server:
        _push_one_job(server, "mine", token="tok-a")
        with GellyClient("127.0.0.1", server.port, token="tok-b") as c:
            snap = c.metrics()
            # tenant b sees none of tenant a's jobs, rows, or histograms
            assert snap["jobs"] == {}
            assert snap["job_totals"] == {}
            assert list(snap["tenants"]) == ["b"]
            assert snap["histograms"]["jobs"] == {}
            assert snap["histograms"]["tenants"] == {}
        with GellyClient("127.0.0.1", server.port, token="tok-a") as c:
            snap = c.metrics()
            assert "a/mine" in snap["jobs"]
            assert "a/mine" in snap["histograms"]["jobs"]


def test_trace_verb_dumps_sampled_spans():
    from gelly_streaming_tpu.utils import tracing

    tracing.reset_tracing()
    with JobManager() as jm, StreamServer(jm, ServerConfig()) as server:
        recs = _push_one_job(server, "traced", trace_sample=1.0)
        with GellyClient("127.0.0.1", server.port) as c:
            reply = c.trace(64)
            assert reply["tracing_active"]
            spans = reply["spans"]
            assert len(spans) >= len(recs)
            stages = {s["stage"] for s in spans[-1]["stages"]}
            assert "dispatch" in stages and "queued" in stages
            # the per-stage aggregates the metrics verb exposes: stage sums
            # equal the total wall clock (the queued residual closes the
            # gap by construction)
            agg = c.metrics()["spans"]["stages"]
            plane = next(iter(agg.values()))
            attributed = sum(
                v["total_ms"] for k, v in plane.items() if k != "total"
            )
            assert attributed == pytest.approx(
                plane["total"]["total_ms"], rel=0.10
            )
    tracing.reset_tracing()


def test_failed_job_status_carries_flight_recorder_dump():
    from gelly_streaming_tpu.utils import tracing

    # tracing must be ACTIVE for the dump (a process that never traced
    # has nothing to dump); activate it and seed one span
    tracing.sampler(StreamConfig(trace_sample=1.0), "seed")
    span = tracing.WindowSpan(999_999, "seed", 7)
    tracing.flight_recorder().record(span)

    def bad_build():
        def it():
            yield (np.zeros(4),)
            raise RuntimeError("kaboom")

        return it()

    with JobManager() as jm:
        job = jm.submit(bad_build, name="doomed")
        job.wait(60)
        assert job.state == JobState.FAILED
        row = jm.status()["jobs"]["doomed"]
        assert row["error"] is not None
        assert isinstance(row["trace"], list) and row["trace"]
        assert any(s["trace_id"] == 999_999 for s in row["trace"])
    tracing.reset_tracing()


def test_gelly_top_once_renders_live_server(capsys):
    from gelly_streaming_tpu.runtime import top as top_mod

    with JobManager() as jm, StreamServer(jm, ServerConfig()) as server:
        _push_one_job(server, "topjob")
        rc = top_mod.main(
            ["--connect", f"127.0.0.1:{server.port}", "--once"]
        )
    assert rc == 0
    out = capsys.readouterr().out
    assert "gelly-top" in out
    assert "default/topjob" in out
    assert "DONE" in out
    assert "TENANT" in out and "default" in out


def test_gelly_top_render_frame_computes_eps():
    from gelly_streaming_tpu.runtime.top import render_frame

    status = {
        "server": {"connections": 1, "served_jobs": 1, "port": 1234},
        "status": {
            "jobs": {
                "t/j": {
                    "state": "RUNNING",
                    "job_records": 10,
                    "job_edges": 20_000,
                    "queue_depth": 2,
                }
            }
        },
    }
    snap = {
        "pipeline": {},
        "spans": {},
        "tenants": {},
        "histograms": {
            "jobs": {
                "t/j": {
                    "window_close_to_emission_ms": {
                        "count": 10,
                        "p50_ms": 1.5,
                        "p99_ms": 9.0,
                    },
                    "submit_to_first_emission_ms": {
                        "count": 1,
                        "p50_ms": 42.0,
                        "p99_ms": 42.0,
                    },
                }
            }
        },
    }
    lines = render_frame(status, snap, {"t/j": 10_000}, 2.0)
    row = next(l for l in lines if l.startswith("t/j"))
    assert "RUNNING" in row
    assert "5.0k" in row  # (20000 - 10000) / 2.0 s
    assert "1.5/9.0" in row
    assert "42.0" in row
