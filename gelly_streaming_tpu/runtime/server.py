"""Streaming RPC serving plane: a long-lived network frontend on the
multi-tenant job runtime (ISSUE 8).

Everything below PR 5's ``JobManager`` spoke an in-process API driven by a
local config; this module is the layer the runtime was built to carry
(ROADMAP open item 1): a socket server exposing the job lifecycle —
``submit`` / ``pause`` / ``resume`` / ``cancel`` / ``status`` / ``drain``
— plus NETWORK EDGE INGESTION: clients push the framework's own wire
buffers (fixed-width or BDV-compressed, ~2.7 B/edge on the socket) into a
running job's ``NetworkEdgeSource`` (io/sources.py), and consume emission
records back with ``results``.

Architecture (pure stdlib: socket + selectors + threading):

* an ACCEPT loop (selectors over the listener) spawns one handler thread
  per connection, bounded by ``ServerConfig.max_connections``;
* each connection speaks length-prefixed JSON+binary frames
  (runtime/protocol.py); malformed/oversized frames get a clean error
  frame — never a hang, never a traceback-closed socket;
* per-tenant AUTH (token per request), QUOTAS (jobs, state bytes, ingest
  bytes/s via a token bucket that throttles the pushing connection), and
  PRIORITY (tenant weight multiplies job weight in the weighted-fair
  scheduler) layer onto the existing admission control;
* isolation is the same story at every layer: a slow/dead client
  backpressures its own socket (bounded ingest queue) and idles its own
  job (``NetworkEdgeSource.ready`` gating the scheduler round), while a
  slow results consumer blocks its own job's sink pump — the scheduler
  round and other tenants never wait;
* DRAIN rides the per-job positional checkpoints: quiesce the sources,
  flush in-flight windows through the normal completion-queue cancel path,
  and reply with resume cursors — a restarted server + reconnecting client
  resumes bit-exactly from the cursor (the replay-skip contract every
  checkpointed plane already pins).
"""

from __future__ import annotations

import io as _io
import selectors
import socket
import threading
import time
from collections import deque
from typing import Dict, Optional, Tuple

import numpy as np

from gelly_streaming_tpu.core.config import (
    ServerConfig,
    StreamConfig,
    TenantConfig,
)
from gelly_streaming_tpu.core.output import record_leaves

# The serving plane's slice of the sanctioned global lock order (pass
# #7): the re-entrant admission serialization is the OUTERMOST lock of
# the whole runtime — it wraps the connection registry and the manager's
# admission RLock (check -> submit -> register is one atomic step), so
# nothing called under the manager or a leaf registry lock may take it.
# lock-order: server.StreamServer._admission < server.StreamServer._lock
# lock-order: server.StreamServer._admission < manager._lock
# lock-order: server.StreamServer._admission < metrics._TENANT_LOCK
from gelly_streaming_tpu.runtime import protocol
from gelly_streaming_tpu.runtime.job import AdmissionError, Job, JobState
from gelly_streaming_tpu.runtime.manager import JobManager
from gelly_streaming_tpu.utils import events, metrics, tracing


# server-side synthetic streams ("generate" submits) materialize host
# arrays outside the summary-state admission pricing; 2^24 edges (~128 MB
# of int32 columns) bounds what one remote spec can allocate
MAX_GENERATE_EDGES = 1 << 24


class _Refused(Exception):
    """A request the server declines with a typed error reply (the
    connection stays open — the frame itself was well-formed)."""

    def __init__(self, code: str, message: str):
        super().__init__(message)
        self.code = code


# the "edges" query's descriptor class, created ONCE per process: its
# cache_token is the class, so every edge-count job shares one set of
# compiled executables (a fresh class per job would recompile per job —
# exactly the N-compilations cost the runtime exists to avoid)
_EDGE_COUNT_CLS = None


def _edge_count_descriptor():
    global _EDGE_COUNT_CLS
    if _EDGE_COUNT_CLS is None:
        import jax.numpy as jnp

        from gelly_streaming_tpu.core.aggregation import (
            SummaryBulkAggregation,
        )

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

        _EDGE_COUNT_CLS = EdgeCount
    return _EDGE_COUNT_CLS()


def descriptor_for(query: str, spec: Optional[dict] = None):
    """The serving plane's query catalog (shared with ``gelly-serve``'s
    synthetic driver): the exact summaries ``cc`` / ``degree`` / ``edges``,
    the exact per-window ``triangles`` count (one record per window, reset
    after each) plus the fixed-tiny-state sketch family
    (``sketch_triangles`` / ``hll_degree`` / ``cm_heavy_hitters``).

    Sketch kinds read their accuracy knobs — ``eps`` / ``delta`` (and
    ``top_k`` for the heavy-hitter sketch) — from ``spec``; malformed
    knobs surface as a typed ``bad-spec`` refusal AT ADMISSION (library
    validation raises ``SketchParamError`` before any state is sized), so
    a bad contract can never hang a submit or fall back to exact."""
    if query == "cc":
        from gelly_streaming_tpu.library.connected_components import (
            ConnectedComponents,
        )

        return ConnectedComponents()
    if query == "degree":
        from gelly_streaming_tpu.library.degree_distribution import (
            DegreeDistributionSummary,
        )

        return DegreeDistributionSummary()
    if query == "edges":
        return _edge_count_descriptor()
    if query == "triangles":
        from gelly_streaming_tpu.library.triangles import WindowTriangleCount

        if int((spec or {}).get("num_shards", 1)) > 1:
            # a window's triangles do not add up across partitions
            raise _Refused("bad-spec", "triangles counts each window on one chip")
        return WindowTriangleCount()
    from gelly_streaming_tpu.library import sketches

    if query in sketches.SKETCH_KINDS:
        spec = spec or {}
        knobs = {}
        try:
            if spec.get("eps") is not None:
                knobs["eps"] = float(spec["eps"])
            if spec.get("delta") is not None:
                knobs["delta"] = float(spec["delta"])
            if spec.get("top_k") is not None:
                knobs["top_k"] = int(spec["top_k"])
        except (TypeError, ValueError) as e:
            raise _Refused("bad-spec", f"bad sketch knob: {e}")
        try:
            return sketches.make_sketch(query, **knobs)
        except sketches.SketchParamError as e:
            raise _Refused("bad-spec", str(e))
    raise _Refused(
        "bad-spec",
        f"unknown query {query!r} (expected cc/degree/edges/triangles or "
        f"a sketch kind: {'/'.join(sketches.SKETCH_KINDS)})",
    )


class _TokenBucket:
    """Per-tenant ingest rate limiter (bytes/second, 1-second burst).

    ``reserve`` COMPUTES the debt-sleep under the lock and returns it; the
    caller sleeps outside — so one throttled connection never holds the
    bucket against the tenant's other connections.
    """

    def __init__(self, bps: int):
        self.bps = int(bps)
        self._lock = threading.Lock()
        self._avail = float(max(self.bps, 1))  # guarded-by: _lock
        self._last = time.monotonic()  # guarded-by: _lock

    def reserve(self, nbytes: int) -> float:
        """Charge ``nbytes``; returns seconds the caller must sleep (0 when
        under the rate).  Debt-based: the charge always succeeds, the sleep
        repays it, so a single frame larger than one second's budget is
        throttled proportionally instead of deadlocking."""
        if not self.bps:
            return 0.0
        with self._lock:
            now = time.monotonic()
            burst = float(max(self.bps, 1))
            self._avail = min(burst, self._avail + (now - self._last) * self.bps)
            self._last = now
            self._avail -= float(nbytes)
            if self._avail >= 0:
                return 0.0
            return -self._avail / self.bps


class _ServedJob:
    """Server-side bookkeeping for one submitted job: the network source
    (push jobs), the spec it was built from, and the bounded emission
    buffer its sink fills for ``results`` fetches."""

    def __init__(
        self,
        name: str,
        tenant: str,
        cfg: StreamConfig,
        descriptor,
        source,
        checkpoint_path: Optional[str],
        buffer_cap: int,
    ):
        self.name = name
        self.tenant = tenant
        self.cfg = cfg
        self.descriptor = descriptor
        self.source = source  # None for server-generated sources
        self.checkpoint_path = checkpoint_path
        self.job: Optional[Job] = None  # set right after manager.submit
        self.accept_bdv = False
        # serving-plane latency: submit time for the per-TENANT
        # submit-to-first-emission histogram (the manager records the
        # per-job row; this one is what the serving bench reads back
        # through the metrics verb)
        self.submit_t = time.perf_counter()
        self._first_emit_done = False  # single-thread: sink pump
        self._cap = max(1, buffer_cap)
        self._cond = threading.Condition()
        # emission records (host leaf-array lists) awaiting a results fetch
        self._records: deque = deque()  # guarded-by: _cond
        self._abandoned = False  # guarded-by: _cond

    def sink(self, rec) -> None:
        """The job's sink (runs on its per-job sink-pump thread):
        materialize the record's leaves to host and buffer them.  A full
        buffer blocks HERE — the pump stalls, the job's bounded emission
        queue fills, and the scheduler skips that one job's rounds: the
        slow-consumer isolation boundary, end to end."""
        leaves = record_leaves(rec)
        window = tracing.current_window()
        if not self._first_emit_done:
            self._first_emit_done = True
            # scoped rows only: the scheduler already recorded this job's
            # sample into the global scope (hist_record's default path)
            metrics.hist_record(
                "submit_to_first_emission_ms",
                (time.perf_counter() - self.submit_t) * 1e3,
                tenant=self.tenant,
                record_global=False,
            )
        with self._cond:
            while len(self._records) >= self._cap and not self._abandoned:
                self._cond.wait(0.1)
            if self._abandoned:
                return
            self._records.append((window, leaves))
            self._cond.notify_all()

    def fetch(self, max_records: int, timeout_s: float, max_bytes: int):
        """Up to ``max_records`` / ``max_bytes`` of buffered records as
        ``(window, leaves)`` pairs (blocking up to ``timeout_s`` for the
        first), plus (state, eos).

        The BYTE bound is the real contract: records are popped
        destructively and the reply must fit the client's frame cap — an
        unbounded reply would be refused by the reader and lose the popped
        records with no redelivery.  At least one record always ships
        (a single record is bounded by the summary's own state size, well
        under any sane frame cap).
        """
        deadline = time.monotonic() + max(0.0, timeout_s)
        out = []
        nbytes = 0
        while True:
            with self._cond:
                while (
                    self._records
                    and len(out) < max_records
                    and nbytes < max_bytes
                ):
                    window, leaves = self._records.popleft()
                    out.append((window, leaves))
                    nbytes += sum(leaf.nbytes for leaf in leaves)
                self._cond.notify_all()  # sink may be waiting on space
                have_more = bool(self._records)
            state = self.job.state if self.job is not None else "PENDING"
            pump = self.job._sink_thread if self.job is not None else None
            pump_done = pump is not None and not pump.is_alive()
            eos = (
                state in JobState.TERMINAL
                and pump_done
                and not have_more
                and not out
            )
            if out or eos:
                return out, state, eos
            left = deadline - time.monotonic()
            if left <= 0:
                return out, state, False
            with self._cond:
                self._cond.wait(min(0.1, left))

    def pending_records(self) -> int:
        with self._cond:
            return len(self._records)

    def abandon(self) -> None:
        """Server shutdown: release a sink blocked on buffer space."""
        with self._cond:
            self._abandoned = True
            self._cond.notify_all()


class _ServedRescaleTarget:
    """One served push-job's actuation handle for the elastic control
    plane (runtime/autoscale.py ``RescaleTarget`` contract): policy lives
    in the autoscaler, the mechanics — quiesce, drain-flush, cursor,
    resubmit at the new geometry — live here, because only the serving
    plane can rebuild this job's source and spec."""

    def __init__(self, server: "StreamServer", sj: "_ServedJob"):
        self._server = server
        self._sj = sj

    def job_state(self) -> str:
        job = self._sj.job
        return job.state if job is not None else JobState.PENDING

    def current_shards(self) -> int:
        return self._sj.cfg.num_shards

    def eligible(self, num_shards: int) -> bool:
        """Geometry feasibility for THIS job: an even vertex split and a
        mesh the process can actually build (more shards than devices
        would silently fall back to single-chip partitioning — legal, but
        not the scale-out the decision meant to buy)."""
        import jax

        sj = self._sj
        return (
            num_shards >= 1
            and sj.source is not None
            and bool(sj.checkpoint_path)
            and bool(sj.cfg.ingest_window_edges)
            and sj.cfg.vertex_capacity % num_shards == 0
            and num_shards <= len(jax.devices())
        )

    def rescale(self, num_shards: int, reason: str) -> dict:
        return self._server._rescale_served(self._sj, num_shards, reason)


class StreamServer:
    """The long-lived network frontend over one ``JobManager``.

    Use as a context manager::

        with JobManager(rt_cfg) as jm, StreamServer(jm, srv_cfg) as server:
            ...  # server.port is bound; clients connect
    """

    _VERBS = (
        "ping",
        "submit",
        "push",
        "eos",
        "results",
        "status",
        "metrics",
        "trace",
        "health",
        "alerts",
        "events",
        "pause",
        "resume",
        "cancel",
        "drain",
        "shutdown",
    )

    def __init__(self, manager: JobManager, cfg: ServerConfig = ServerConfig()):
        self.manager = manager
        self.cfg = cfg
        self._lock = threading.Lock()
        self._conns: set = set()  # guarded-by: _lock
        self._jobs: Dict[str, _ServedJob] = {}  # guarded-by: _lock
        # serializes tenant-cap check -> manager.submit -> registration:
        # two concurrent submits must not both pass a tenant's job/byte cap
        # before either registers (the check-then-act race the corpus pair
        # pins for the connection registry, applied to admission).
        # Re-entrant: the rescale path holds it across helper calls that
        # take it again for their own guarded accesses.
        self._admission = threading.RLock()
        # per-tenant in-flight rescale swaps: while a job drains for a
        # rescale its manager-side bytes live in a reservation and the old
        # job reads terminal/zero-byte, so the tenant-cap arithmetic below
        # would see a vacancy a concurrent submit could steal — these
        # figures keep the swap counted against the TENANT's caps too
        self._tenant_swaps: Dict[str, dict] = {}  # guarded-by: _admission
        self._stop = threading.Event()
        self._shutdown_requested = threading.Event()
        self._sock: Optional[socket.socket] = None
        self._accept_thread: Optional[threading.Thread] = None
        self._port: Optional[int] = None
        # open mode: zero configured tenants = one implicit open tenant
        self._open_mode = not cfg.tenants
        self._by_token = {t.token: t for t in cfg.tenants}
        self._open_tenant = TenantConfig()
        self._buckets = {
            t.tenant: _TokenBucket(t.max_ingest_bps) for t in cfg.tenants
        }
        self._buckets.setdefault(self._open_tenant.tenant, _TokenBucket(0))
        # the GIL-free serving data plane (ISSUE 14): a native decode pool
        # validating + decoding pushed wire buffers into transfer arenas
        # off the interpreter.  0 workers = no pool: pushes ride the
        # pure-Python NetworkEdgeSource.push_wire path, the bit-identical
        # equivalence oracle.
        from gelly_streaming_tpu.runtime.decode_pool import (
            DecodePool,
            resolve_decode_workers,
        )

        workers = resolve_decode_workers(cfg.decode_workers)
        self._decode_pool = DecodePool(workers) if workers > 0 else None

    # -- lifecycle -----------------------------------------------------------

    @property
    def port(self) -> int:
        if self._port is None:
            raise RuntimeError("server not started")
        return self._port

    def start(self) -> "StreamServer":
        self._sock = socket.create_server(
            (self.cfg.host, self.cfg.port), backlog=16, reuse_port=False
        )
        self._port = self._sock.getsockname()[1]
        self._accept_thread = threading.Thread(
            target=self._accept_loop, name="gelly-server-accept", daemon=True
        )
        self._accept_thread.start()
        return self

    def stop(self) -> None:
        """Close the listener and every connection; jobs are the caller's
        (``manager.shutdown`` / the drain verb decide their fate)."""
        self._stop.set()
        if self._sock is not None:
            try:
                self._sock.close()
            except OSError:
                pass
        with self._lock:
            conns = list(self._conns)
            served = list(self._jobs.values())
        for sock in conns:
            try:
                sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                sock.close()
            except OSError:
                pass
        for sj in served:
            sj.abandon()
        if self._decode_pool is not None:
            self._decode_pool.close()
        if self._accept_thread is not None:
            self._accept_thread.join(timeout=5)

    def wait_shutdown(self, timeout: Optional[float] = None) -> bool:
        """Block until a client requested ``shutdown`` (or drain with
        ``shutdown: true``); the ``gelly-serve --listen`` loop's exit."""
        return self._shutdown_requested.wait(timeout)

    def __enter__(self) -> "StreamServer":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # -- connection plumbing -------------------------------------------------

    def _accept_loop(self) -> None:  # single-thread: acceptor
        sel = selectors.DefaultSelector()
        sel.register(self._sock, selectors.EVENT_READ)
        try:
            while not self._stop.is_set():
                if not sel.select(timeout=0.2):
                    continue
                try:
                    sock, _addr = self._sock.accept()
                except OSError:
                    return
                try:
                    # request/reply framing: Nagle + delayed ACK would add
                    # ~40 ms to every small frame round trip
                    sock.setsockopt(
                        socket.IPPROTO_TCP, socket.TCP_NODELAY, 1
                    )
                except OSError:
                    pass
                with self._lock:
                    over = len(self._conns) >= self.cfg.max_connections
                    if not over:
                        self._conns.add(sock)
                if over:
                    self._refuse_connection(sock)
                    continue
                threading.Thread(
                    target=self._serve_conn,
                    args=(sock,),
                    name="gelly-server-conn",
                    daemon=True,
                ).start()
        finally:
            sel.close()

    def _refuse_connection(self, sock: socket.socket) -> None:
        try:
            f = sock.makefile("wb")
            protocol.write_frame(
                f,
                protocol.error_reply(
                    f"connection limit ({self.cfg.max_connections}) reached",
                    code="busy",
                ),
            )
            f.close()
        except OSError:
            pass
        finally:
            try:
                sock.close()
            except OSError:
                pass

    def _serve_conn(self, sock: socket.socket) -> None:
        f = sock.makefile("rwb")
        # per-connection reusable payload arena (native prefix probe +
        # readinto): a push frame's bytes land in the SAME buffer every
        # frame, are decoded into int32 arenas before the reply, and the
        # next read overwrites them — no per-frame payload allocation
        reader = protocol.FrameReader(f, self.cfg.max_frame_bytes)
        try:
            while not self._stop.is_set():
                try:
                    frame = reader.read()
                except protocol.FrameTooLarge as e:
                    # the oversized payload is unread: reply, then close
                    # (the stream cannot be resynced past it)
                    self._best_effort_reply(
                        f, protocol.error_reply(str(e), code="frame-too-large")
                    )
                    break
                except protocol.ProtocolError as e:
                    self._best_effort_reply(
                        f, protocol.error_reply(str(e), code="bad-frame")
                    )
                    break
                except OSError:
                    break
                if frame is None:
                    break  # clean EOF
                header, payload = frame
                reply, pay, close_after, after_reply = self._dispatch(
                    header, payload
                )
                write_failed = False
                try:
                    protocol.write_frame(f, reply, pay)
                except OSError:
                    write_failed = True
                if after_reply is not None:
                    # post-reply effects (the shutdown event): fired only
                    # once the reply is ON THE WIRE, so the --listen
                    # loop's stop() can never close this socket under an
                    # in-flight drain/shutdown acknowledgement.  Fired
                    # even when the write FAILED — a shutdown whose
                    # requester hung up must still shut the server down
                    # (the pre-ISSUE-14 unconditional behavior).
                    after_reply()
                if write_failed or close_after:
                    break
        finally:
            with self._lock:
                self._conns.discard(sock)
            try:
                f.close()
            except OSError:
                pass
            try:
                sock.close()
            except OSError:
                pass

    @staticmethod
    def _best_effort_reply(f, header: dict) -> None:
        try:
            protocol.write_frame(f, header)
        except OSError:
            pass

    # -- request dispatch ----------------------------------------------------

    def _tenant_for(self, header: dict) -> TenantConfig:
        if self._open_mode:
            return self._open_tenant
        token = header.get("token")
        tenant = self._by_token.get(token) if isinstance(token, str) else None
        if tenant is None:
            raise _Refused("auth", "unknown or missing tenant token")
        return tenant

    def _dispatch(self, header: dict, payload: bytes) -> tuple:
        """Route one frame -> ``(reply, payload, close_after, after_reply)``.

        Handlers return 3-tuples, or 4-tuples whose last element is a
        POST-REPLY callback — run by the connection thread only after the
        reply frame is written (the drain/shutdown verbs defer their
        shutdown-event set this way, so the acknowledgement always
        reaches the client before the listener starts tearing down)."""
        verb = header.get("verb")
        try:
            tenant = self._tenant_for(header)
        except _Refused as e:
            return protocol.error_reply(str(e), code=e.code), b"", False, None
        metrics.tenant_add(tenant.tenant, "tenant_requests", 1)
        if verb not in self._VERBS:
            return (
                protocol.error_reply(
                    f"unknown verb {verb!r} (expected one of "
                    f"{'/'.join(self._VERBS)})",
                    code="unknown-verb",
                ),
                b"",
                False,
                None,
            )
        handler = getattr(self, "_h_" + verb)
        try:
            out = handler(tenant, header, payload)
        except _Refused as e:
            return protocol.error_reply(str(e), code=e.code), b"", False, None
        except Exception as e:  # a handler bug must not kill the socket
            return (
                protocol.error_reply(
                    f"{type(e).__name__}: {e}", code="internal"
                ),
                b"",
                False,
                None,
            )
        if len(out) == 3:
            return out[0], out[1], out[2], None
        return out

    def _job_key(self, tenant: TenantConfig, name: str) -> str:
        return f"{tenant.tenant}/{name}"

    def _served(self, tenant: TenantConfig, header: dict) -> _ServedJob:
        name = header.get("job")
        if not isinstance(name, str) or not name:
            raise _Refused("bad-spec", "missing 'job' field")
        with self._lock:
            sj = self._jobs.get(self._job_key(tenant, name))
        if sj is None:
            raise _Refused(
                "unknown-job", f"no job {name!r} for tenant {tenant.tenant!r}"
            )
        return sj

    # -- verbs ---------------------------------------------------------------

    def _h_ping(self, tenant, header, payload):
        return {"ok": True, "tenant": tenant.tenant}, b"", False

    def _h_submit(self, tenant, header, payload):
        spec = header.get("spec")
        if not isinstance(spec, dict):
            raise _Refused("bad-spec", "submit needs a 'spec' object")
        name = spec.get("name")
        if not isinstance(name, str) or not name:
            raise _Refused("bad-spec", "job spec needs a non-empty 'name'")
        key = self._job_key(tenant, name)
        query = spec.get("query", "cc")
        # ``summary`` selects a sketch descriptor by kind — it overrides
        # ``query`` so a spec can keep its exact query name while swapping
        # the summary for the fixed-tiny-state approximate one
        summary_kind = spec.get("summary")
        if summary_kind is not None:
            if not isinstance(summary_kind, str):
                raise _Refused(
                    "bad-spec", "'summary' must be a sketch-kind string"
                )
            query = summary_kind
        weight = int(spec.get("weight", 1))
        if weight <= 0:
            raise _Refused("bad-spec", "job weight must be positive")

        checkpoint_path = None
        if spec.get("checkpoint"):
            if not self.cfg.checkpoint_prefix:
                raise _Refused(
                    "bad-spec",
                    "server has no checkpoint_prefix configured; "
                    "checkpointed jobs are unavailable",
                )
            from gelly_streaming_tpu.utils.checkpoint import per_job_file

            checkpoint_path = per_job_file(
                self.cfg.checkpoint_prefix, f"{tenant.tenant}.{name}"
            )

        source_kind = spec.get("source", "push")
        if source_kind == "push":
            try:
                cfg = StreamConfig(
                    vertex_capacity=int(spec.get("capacity", 1 << 16)),
                    batch_size=int(spec.get("batch", 1 << 10)),
                    ingest_window_edges=int(spec.get("window_edges", 0)),
                    async_windows=int(spec.get("async_windows", 0)),
                    num_shards=int(spec.get("num_shards", 1)),
                    # per-job span tracing opt-in: sampled windows land in
                    # the flight recorder (the trace verb / FAILED
                    # post-mortems); 0 = off, the zero-overhead default
                    trace_sample=float(spec.get("trace_sample", 0.0)),
                )
            except (TypeError, ValueError) as e:
                raise _Refused("bad-spec", f"bad stream config: {e}")
            descriptor = descriptor_for(query, spec)
            stream = None
        elif source_kind == "generate":
            from gelly_streaming_tpu.runtime.serve import _build_query

            # the synthetic stream is materialized host-side OUTSIDE the
            # summary-state admission caps: a client-controlled edge count
            # must not be able to OOM the server process
            n_gen = int(spec.get("edges", 100_000))
            if n_gen > MAX_GENERATE_EDGES:
                raise _Refused(
                    "bad-spec",
                    f"generate source caps at {MAX_GENERATE_EDGES} edges "
                    f"(requested {n_gen}); push the stream instead",
                )
            try:
                stream, descriptor = _build_query(dict(spec))
            except SystemExit as e:
                raise _Refused("bad-spec", str(e))
            cfg = stream.cfg
        else:
            raise _Refused(
                "bad-spec", f"unknown source {source_kind!r} (push/generate)"
            )

        # admission charges the persistent summary PLUS the descriptor's
        # declared emission-time scratch (top-k heaps, gathered register
        # views): a job that fits its steady-state budget but OOMs at its
        # first emit was never actually admissible
        state_bytes = descriptor.admission_nbytes(cfg)
        contract = (
            descriptor.error_contract()
            if hasattr(descriptor, "error_contract")
            else None
        )

        resume_edges = 0
        w = cfg.ingest_window_edges
        if source_kind == "push":
            resume_edges = self._resume_cursor(descriptor, cfg, checkpoint_path)

        from gelly_streaming_tpu.io.wire import BDV_MAX_ID_BITS

        source = None
        if source_kind == "push":
            try:
                source = self._make_push_source(cfg, resume_edges)
            except ValueError as e:
                raise _Refused("bad-spec", str(e))
        sj = _ServedJob(
            name,
            tenant.tenant,
            cfg,
            descriptor,
            source,
            checkpoint_path,
            self.cfg.result_buffer_records,
        )
        sj.accept_bdv = bool(
            getattr(descriptor, "order_free", False)
            and cfg.vertex_capacity <= (1 << BDV_MAX_ID_BITS)
        )
        # check -> submit -> register is one atomic admission step: without
        # the serialization, two concurrent submits could both pass the
        # tenant caps before either registers
        with self._admission:
            self._admit_tenant(tenant, state_bytes)
            try:
                if source is not None:
                    job = self._submit_push_job(
                        key, sj, cfg, source, weight * tenant.weight,
                        state_bytes,
                    )
                else:
                    job = self.manager.submit_aggregation(
                        stream,
                        descriptor,
                        name=key,
                        sink=sj.sink,
                        weight=weight * tenant.weight,
                        checkpoint_path=checkpoint_path,
                    )
            except AdmissionError as e:
                metrics.tenant_add(
                    tenant.tenant, "tenant_admission_rejections", 1
                )
                raise _Refused("admission", str(e))
            sj.job = job
            with self._lock:
                old = self._jobs.get(key)
                self._jobs[key] = sj
        if old is not None:
            old.abandon()  # a terminal predecessor's buffered records go
        # the journal's submit-spec record: the EXACT client spec, so a
        # fleet failover (runtime/fleet.py) can resubmit the job on a
        # standby from journal replay alone — the replicated checkpoint
        # then supplies the resume cursor.  The spec arrived as a JSON
        # frame header, so it journals verbatim.
        events.journal().emit(
            "job_spec", job=key, tenant=tenant.tenant, spec=dict(spec)
        )
        scaler = self.manager.autoscaler
        if scaler is not None and source is not None and checkpoint_path and w:
            # elastic control plane: put the job under management — the
            # policy thread can now drain + resubmit it at a new shard
            # geometry (push-source + checkpoint + ingest windows are the
            # preconditions a cursor-exact rescale needs)
            scaler.register(key, _ServedRescaleTarget(self, sj))
        metrics.tenant_add(tenant.tenant, "tenant_jobs_submitted", 1)
        if contract is not None:
            metrics.sketch_register(
                key,
                contract["kind"],
                contract["eps"],
                contract["delta"],
                descriptor.state_nbytes(cfg),
                state_bytes,
            )
        if resume_edges:
            # the journal's restart-cursor record: a resumed job's replay
            # region is part of the post-mortem story (which edges were
            # checkpoint-covered vs re-pushed)
            events.journal().emit(
                "restart_cursor",
                job=key,
                tenant=tenant.tenant,
                resume_edges=resume_edges,
            )
        return (
            {
                "ok": True,
                "job": name,
                "resume_edges": resume_edges,
                "batch": cfg.batch_size,
                "window_edges": cfg.ingest_window_edges,
                "capacity": cfg.vertex_capacity,
                "accept_bdv": sj.accept_bdv,
                "state_bytes": state_bytes,
                "weight": weight * tenant.weight,
                "checkpoint": bool(checkpoint_path),
                # the declared accuracy contract of an approximate summary
                # (None for the exact catalog): clients see WHAT accuracy
                # they were admitted at, not just that they were admitted
                "error_contract": contract,
            },
            b"",
            False,
        )

    # holds-lock: _admission
    def _admit_tenant(self, tenant: TenantConfig, new_state_bytes: int) -> None:
        """Per-tenant admission on top of the manager's global caps; caller
        holds ``_admission`` and gets a typed refusal, the counters get the
        rejection.  In-flight rescale swaps count as held jobs/bytes: the
        draining job reads terminal/zero-byte mid-swap, but its budget is
        coming right back at the new geometry — a concurrent submit must
        not steal the vacancy (the manager-level reservation's guarantee,
        applied to the tenant caps)."""
        if not (tenant.max_jobs or tenant.max_state_bytes):
            return
        with self._admission:
            row = self._tenant_swaps.get(tenant.tenant)
            swaps = dict(row) if row else {"jobs": 0, "bytes": 0}
        with self._lock:
            live = [
                sj
                for sj in self._jobs.values()
                if sj.tenant == tenant.tenant
                and sj.job is not None
                and not sj.job._state_in(*JobState.TERMINAL)
            ]
        live_count = len(live) + swaps["jobs"]
        if tenant.max_jobs and live_count >= tenant.max_jobs:
            self._reject_tenant(
                tenant,
                f"tenant job cap reached: {live_count} live/rescaling jobs "
                f">= max_jobs={tenant.max_jobs}",
            )
        if tenant.max_state_bytes:
            held = sum(sj.job.state_bytes for sj in live) + swaps["bytes"]
            if held + new_state_bytes > tenant.max_state_bytes:
                self._reject_tenant(
                    tenant,
                    f"tenant state-byte cap reached: {held} held + "
                    f"{new_state_bytes} requested > "
                    f"max_state_bytes={tenant.max_state_bytes}",
                )

    def _tenant_swap_begin(self, tenant_id: str, nbytes: int) -> None:
        """Count one in-flight rescale against the tenant's caps."""
        with self._admission:
            sw = self._tenant_swaps.setdefault(
                tenant_id, {"jobs": 0, "bytes": 0}
            )
            sw["jobs"] += 1
            sw["bytes"] += nbytes

    def _tenant_swap_end(self, tenant_id: str, nbytes: int) -> None:
        """Release one in-flight rescale's tenant-cap figures."""
        with self._admission:
            sw = self._tenant_swaps.get(tenant_id)
            if sw is None:
                return
            sw["jobs"] = max(0, sw["jobs"] - 1)
            sw["bytes"] = max(0, sw["bytes"] - nbytes)
            if sw["jobs"] == 0 and sw["bytes"] == 0:
                del self._tenant_swaps[tenant_id]

    @staticmethod
    def _reject_tenant(tenant: TenantConfig, msg: str) -> None:
        """Counter + journal + typed refusal for one tenant-cap bounce."""
        metrics.tenant_add(tenant.tenant, "tenant_admission_rejections", 1)
        events.journal().emit(
            "admission_reject", tenant=tenant.tenant, reason=msg
        )
        raise _Refused("admission", msg)

    def _h_push(self, tenant, header, payload):
        # the push's spans (tracing.SPANS: the frame, its decode and its
        # enqueue) carry the job from this thread's scope
        prev = tracing.scope_job(self._job_key(tenant, header.get("job")))
        try:
            offset = header.get("offset")
            with tracing.span(
                "gelly.push", offset=offset if isinstance(offset, int) else None
            ):
                return self._push(tenant, header, payload)
        finally:
            tracing.scope_job(prev)

    def _push(self, tenant, header, payload):
        sj = self._served(tenant, header)
        if sj.source is None:
            raise _Refused(
                "bad-spec", f"job {sj.name!r} is not a push-source job"
            )
        kind = header.get("kind", "wire")
        bucket = self._buckets.get(tenant.tenant)
        if bucket is not None:
            sleep_s = bucket.reserve(len(payload))
            if sleep_s > 0:
                # throttle THIS connection's thread: the client's socket
                # backs up, the scheduler never notices
                metrics.tenant_add(tenant.tenant, "tenant_throttle_s", sleep_s)
                time.sleep(sleep_s)
        from gelly_streaming_tpu.io import wire as wire_mod
        from gelly_streaming_tpu.io.sources import (
            PushOutOfSync,
            SourceQuiesced,
        )

        # optional positional declaration: the frame's global edge offset
        # (resume filler included).  Stamped by GellyClient.push_edges;
        # verified against the source's exact accounting so a stale
        # pipelined frame can never land past a live rescale's cursor.
        offset = header.get("offset")
        if offset is not None and not isinstance(offset, int):
            raise _Refused("bad-spec", "push 'offset' must be an integer")
        buf = np.frombuffer(payload, np.uint8)
        try:
            if kind == "wire":
                width = wire_mod.width_for_capacity(sj.cfg.vertex_capacity)
                n = self._push_buffer(sj, buf, width, offset)
            elif kind == "bdv":
                if not sj.accept_bdv:
                    raise _Refused(
                        "bdv-refused",
                        f"job {sj.name!r} does not accept BDV buffers "
                        "(order-sensitive query or capacity > 2^28)",
                    )
                width = (wire_mod.BDV, sj.cfg.vertex_capacity)
                n = self._push_buffer(sj, buf, width, offset)
            elif kind == "tail":
                count = int(header.get("count", -1))
                # copied out of the connection's reusable payload arena:
                # push_tail's int32 cast is a VIEW for aligned input, and
                # the queued batch must outlive the next frame's read
                ids = np.frombuffer(payload, "<i4").copy()
                if count <= 0 or len(ids) != 2 * count:
                    raise ValueError(
                        f"tail payload holds {len(ids)} int32s; 'count': "
                        f"{count} needs exactly {2 * max(count, 0)}"
                    )
                source = sj.source
                n = self._push_with_backpressure(
                    sj,
                    source,
                    lambda timeout: source.push_tail(
                        ids[:count], ids[count:], timeout=timeout,
                        offset=offset,
                    ),
                )
            else:
                raise _Refused(
                    "bad-spec", f"unknown push kind {kind!r} (wire/bdv/tail)"
                )
        except PushOutOfSync as e:
            # positionally stale (raced a rescale/drain, or reattached
            # after a fleet failover): the client re-syncs from the
            # ADVERTISED cursor — ``expected`` is the source's exact
            # position, so a reconnecting pusher re-declares without a
            # second round trip; the connection survives
            metrics.tenant_add(tenant.tenant, "tenant_ingest_rejects", 1)
            return (
                protocol.error_reply(
                    str(e), code="out-of-sync", expected=e.expected
                ),
                b"",
                False,
            )
        except ValueError as e:
            # a well-formed frame carrying a bad wire buffer: refuse the
            # BUFFER, keep the connection (the client can correct and go on)
            metrics.tenant_add(tenant.tenant, "tenant_ingest_rejects", 1)
            return protocol.error_reply(str(e), code="bad-wire"), b"", False
        except SourceQuiesced as e:
            return protocol.error_reply(str(e), code="quiesced"), b"", False
        metrics.tenant_add(tenant.tenant, "tenant_ingest_edges", n)
        metrics.tenant_add(
            tenant.tenant, "tenant_ingest_wire_bytes", len(payload)
        )
        metrics.tenant_add(tenant.tenant, "tenant_ingest_raw_bytes", 8 * n)
        metrics.tenant_high_water(
            tenant.tenant, "tenant_ingest_queue_hwm", sj.source.queued_batches
        )
        return (
            {
                "ok": True,
                "accepted": n,
                "queued_batches": sj.source.queued_batches,
                "edges_accepted": sj.source.edges_accepted,
            },
            b"",
            False,
        )

    def _push_buffer(self, sj: _ServedJob, buf, width, offset) -> int:
        """Route one full wire/BDV buffer: through the decode pool when
        configured (native validate + decode into a transfer arena, GIL
        released — runtime/decode_pool.py), else the pure-Python
        ``push_wire`` path.  Identical refusal surface either way: the
        pool raises the numpy oracle's own typed errors, and the
        open-check precedes the decode so a quiesced source refuses
        ``quiesced`` before any buffer is judged, exactly like
        ``push_wire``'s guard order."""
        # bind the source for the whole push (the rescale-swap rule of
        # _push_with_backpressure, which shares this binding)
        source = sj.source
        pool = self._decode_pool
        if pool is None:
            return self._push_with_backpressure(
                sj,
                source,
                lambda timeout: source.push_wire(
                    buf, width, timeout=timeout, offset=offset
                ),
            )
        from gelly_streaming_tpu.runtime.decode_pool import DecodePoolClosed

        source.check_open()
        try:
            with tracing.span("gelly.push.decode"):
                s, d, release = pool.decode(
                    buf, width, source.batch, sj.cfg.vertex_capacity
                )
        except DecodePoolClosed:
            # same typed refusal the Python path gives a push that races
            # the server's stop
            raise _Refused("shutting-down", "server is stopping")
        try:
            return self._push_with_backpressure(
                sj,
                source,
                lambda timeout: source.push_decoded(
                    s, d, timeout=timeout, offset=offset, release=release
                ),
            )
        except BaseException:
            # the batch never reached the queue: the arena comes back to
            # the pool here instead of leaking with the refused push
            release()
            raise

    def _push_with_backpressure(self, sj: _ServedJob, source, attempt) -> int:
        """Blocking push with bounded waits: a full ingest queue
        backpressures this connection (the client's TCP window fills
        behind us), but a server stop — or the job reaching a terminal
        state, whose dead generator would never drain the queue again —
        still unsticks the thread with a typed refusal instead of a
        forever-wedged connection.

        ``source`` must be the caller's binding of ``sj.source``: a live
        rescale swaps ``sj.source`` mid-flight, and a batch that was
        blocked on the old (quiesced) queue must NOT retry into the new
        source — it would land ahead of the resume cursor and shift every
        replayed pane boundary.  The client re-pushes it from the cursor
        instead.  ``attempt(timeout)`` performs one bounded push against
        that binding.
        """
        import queue as _queue

        while True:
            try:
                # 0.25 s slices re-validate on retry — negligible next to
                # the wait itself, and only paid when the queue is full
                return attempt(0.25)
            except _queue.Full:
                if self._stop.is_set():
                    raise _Refused("shutting-down", "server is stopping")
                if sj.source is not source or source.draining:
                    # a rescale/drain owns this source now: the typed
                    # quiesced refusal (not "terminal — stop pushing") is
                    # what tells the client the job is coming back and
                    # everything past the cursor is its to re-push.  The
                    # swap window makes the old job transiently terminal,
                    # so this check must come first.
                    from gelly_streaming_tpu.io.sources import SourceQuiesced

                    raise SourceQuiesced(
                        f"job {sj.name!r} is draining for a rescale/drain: "
                        "re-push everything past the resume cursor"
                    )
                job = sj.job
                if job is not None and job._state_in(*JobState.TERMINAL):
                    raise _Refused(
                        "terminal",
                        f"job {sj.name!r} is {job.state}: its queue will "
                        "never drain; stop pushing",
                    )

    def _h_eos(self, tenant, header, payload):
        sj = self._served(tenant, header)
        if sj.source is None:
            raise _Refused(
                "bad-spec", f"job {sj.name!r} is not a push-source job"
            )
        sj.source.close()
        return (
            {"ok": True, "edges_accepted": sj.source.edges_accepted},
            b"",
            False,
        )

    def _h_results(self, tenant, header, payload):
        sj = self._served(tenant, header)
        max_records = max(1, min(int(header.get("max", 256)), 4096))
        timeout_s = max(0.0, min(float(header.get("timeout_ms", 1000)), 6e4))
        timeout_s /= 1e3
        # half the smaller frame cap leaves room for npz container
        # overhead: the reply must fit BOTH this server's cap and the
        # client reader's default
        max_bytes = (
            min(self.cfg.max_frame_bytes, protocol.DEFAULT_MAX_PAYLOAD) // 2
        )
        job = self._job_key(tenant, sj.name)
        with tracing.span("gelly.results.wait", job=job):
            records, state, eos = sj.fetch(max_records, timeout_s, max_bytes)
        # raw leaf framing (ISSUE 14): dtype/shape metadata rides the JSON
        # header, the payload is the leaves' raw bytes concatenated in
        # order.  The previous npz container cost ~0.4 ms of zipfile work
        # (GIL-held, both ends) per record — a measurable slice of the
        # serving data plane's fold-phase budget at 4+ fetching clients;
        # the raw frame is a single buffer join, ~15x cheaper, and the
        # byte payload is identical information (same leaves, same order).
        with tracing.span(
            "gelly.results.frame",
            job=job,
            window=records[0][0] if records else None,
            records=len(records),
        ) as frame:
            leafmeta = [
                [[leaf.dtype.str, list(leaf.shape)] for leaf in leaves]
                for _window, leaves in records
            ]
            payload_out = b"".join(
                np.ascontiguousarray(leaf).tobytes()
                for _window, leaves in records
                for leaf in leaves
            )
            frame.tag(bytes=len(payload_out))
        metrics.tenant_add(
            tenant.tenant, "tenant_records_fetched", len(records)
        )
        err = sj.job.error if sj.job is not None else None
        return (
            {
                "ok": True,
                "job": sj.name,
                "count": len(records),
                "leafmeta": leafmeta,
                "state": state,
                "eos": eos,
                "error": repr(err) if err is not None else None,
            },
            payload_out,
            False,
        )

    def _h_status(self, tenant, header, payload):
        from gelly_streaming_tpu.runtime.serve import _status_lines

        status = self.manager.status()
        # tenant-scoped view: every other verb refuses cross-tenant job
        # access (_served), so the observability verb must not leak other
        # tenants' job names, volumes, or rejection counts — the totals
        # and admitted-byte figures are recomputed over the tenant's own
        # rows for the same reason (process-wide aggregates minus your own
        # rows IS the other tenants' volume)
        prefix = f"{tenant.tenant}/"
        rows = {
            k: v for k, v in status["jobs"].items() if k.startswith(prefix)
        }
        totals = self._totals_over(rows.values())
        status = dict(
            status,
            jobs=rows,
            totals=totals,
            admitted_state_bytes=sum(
                row.get("state_bytes", 0) for row in rows.values()
            ),
        )
        # the global swap-reservation figure would disclose other
        # tenants' in-flight rescales — same rule as the recomputed
        # totals above
        status.pop("reserved_state_bytes", None)
        with self._lock:
            n_conns = len(self._conns)
            n_jobs = sum(
                1 for sj in self._jobs.values() if sj.tenant == tenant.tenant
            )
        reply = {
            "ok": True,
            "status": status,
            # this tenant's approximate-summary contracts: which jobs are
            # sketches, at what declared (eps, delta), and the byte price
            # each was admitted at (same disclosure scoping as the rows)
            "sketch_jobs": {
                k: v
                for k, v in metrics.all_sketch_stats().items()
                if k.startswith(prefix)
            },
            "tenants": {tenant.tenant: metrics.tenant_stats(tenant.tenant)},
            "server": {
                "connections": n_conns,
                "served_jobs": n_jobs,
                "port": self._port,
                # the serving data plane's decode story: pool size and
                # native-vs-numpy-twin served counts (0 workers = the
                # pure-Python oracle path)
                "decode_workers": (
                    self._decode_pool.workers
                    if self._decode_pool is not None
                    else 0
                ),
                "decode": (
                    self._decode_pool.stats()
                    if self._decode_pool is not None
                    else None
                ),
            },
            "lines": _status_lines(status),
        }
        return reply, b"", False

    def _h_metrics(self, tenant, header, payload):
        """The exposition verb: the full observability registry
        (utils.metrics.metrics_snapshot) with the per-job and per-tenant
        sections scoped to the REQUESTING tenant — same disclosure rule as
        ``status`` (another tenant's job names/volumes must not leak; the
        process-plane counters — pipeline/wire/comms/compile-cache — and
        the span stage aggregates are infrastructure figures, shared).

        ``format: "prometheus"`` returns the text exposition format as the
        frame payload instead of JSON in the header — point a scraper's
        fetch at ``gelly-client metrics --prometheus`` or GellyClient.
        """
        snap = metrics.metrics_snapshot()
        prefix = f"{tenant.tenant}/"
        snap["jobs"] = {
            k: v for k, v in snap["jobs"].items() if k.startswith(prefix)
        }
        snap["job_totals"] = self._totals_over(snap["jobs"].values())
        snap["tenants"] = {tenant.tenant: metrics.tenant_stats(tenant.tenant)}
        snap["tenant_totals"] = dict(snap["tenants"][tenant.tenant])
        hists = snap.get("histograms", {})
        hists["jobs"] = {
            k: v
            for k, v in hists.get("jobs", {}).items()
            if k.startswith(prefix)
        }
        hists["tenants"] = {
            k: v
            for k, v in hists.get("tenants", {}).items()
            if k == tenant.tenant
        }
        snap["health"] = {
            k: v
            for k, v in snap.get("health", {}).items()
            if k.startswith(prefix)
        }
        snap["scale"] = {
            k: v
            for k, v in snap.get("scale", {}).items()
            if k.startswith(prefix)
        }
        snap["sketch_jobs"] = {
            k: v
            for k, v in snap.get("sketch_jobs", {}).items()
            if k.startswith(prefix)
        }
        snap["alerts"] = [
            a for a in snap.get("alerts", []) if self._alert_visible(a, tenant)
        ]
        if header.get("format") == "prometheus":
            from gelly_streaming_tpu.utils.metrics import render_prometheus

            text = render_prometheus(snap).encode("utf-8")
            return {"ok": True, "format": "prometheus"}, text, False
        return {"ok": True, "metrics": snap}, b"", False

    def _alert_visible(self, alert: dict, tenant: TenantConfig) -> bool:
        """The disclosure rule for alert rows, matching status/metrics:
        your jobs' alerts, your tenant-scope alerts, and global ones."""
        scope = alert.get("scope")
        if scope == "job":
            return str(alert.get("id", "")).startswith(f"{tenant.tenant}/")
        if scope == "tenant":
            return alert.get("id") == tenant.tenant
        return True

    def _event_visible(self, ev: dict, tenant: TenantConfig) -> bool:
        """Journal disclosure: events naming a job belong to its tenant
        (prefix rule); events naming only a tenant likewise; alert events
        follow the alert rule; everything else (process-plane) is shared."""
        job = ev.get("job")
        if isinstance(job, str) and "/" in job:
            return job.startswith(f"{tenant.tenant}/")
        if isinstance(job, str):
            # a non-prefixed job id is a LOCAL (driver-submitted) job:
            # not any remote tenant's to read
            return False
        if ev.get("kind") == "alert":
            return self._alert_visible(
                {"scope": ev.get("scope"), "id": ev.get("id")}, tenant
            )
        t = ev.get("tenant")
        if isinstance(t, str):
            return t == tenant.tenant
        return True

    def _h_health(self, tenant, header, payload):
        """The keep-up verdict verb (ISSUE 10): this tenant's per-job
        health gauges, the alert rows visible to it, the configured SLO
        specs, and the monitor's own liveness figures."""
        import dataclasses as _dc

        prefix = f"{tenant.tenant}/"
        jobs = {
            k: v
            for k, v in metrics.all_job_health().items()
            if k.startswith(prefix)
        }
        alerts = [
            a for a in metrics.all_alerts() if self._alert_visible(a, tenant)
        ]
        with self.manager._lock:
            monitor = self.manager._slo_monitor
        scaler = self.manager.autoscaler
        reply = {
            "ok": True,
            "health": {
                "jobs": jobs,
                "alerts": alerts,
                "slos": [_dc.asdict(s) for s in self.manager.cfg.slos],
                "monitor": monitor.stats() if monitor is not None else None,
                "scale": {
                    k: v
                    for k, v in metrics.all_job_scale().items()
                    if k.startswith(prefix)
                },
                "autoscaler": scaler.stats() if scaler is not None else None,
            },
        }
        return reply, b"", False

    def _h_alerts(self, tenant, header, payload):
        alerts = [
            a for a in metrics.all_alerts() if self._alert_visible(a, tenant)
        ]
        return {"ok": True, "alerts": alerts}, b"", False

    def _h_events(self, tenant, header, payload):
        """Tail the structured event journal (tenant-scoped)."""
        try:
            n = int(header.get("n", 64))
        except (TypeError, ValueError):
            raise _Refused("bad-spec", "events 'n' must be an integer")
        n = max(1, min(n, 4096))
        kind = header.get("kind")
        if kind is not None and not isinstance(kind, str):
            raise _Refused("bad-spec", "events 'kind' must be a string")
        journal = events.journal()
        # over-fetch before the visibility filter so n VISIBLE events come
        # back even when other tenants are chatty (ring is bounded anyway)
        items = [
            ev
            for ev in journal.tail(journal.capacity, kind=kind)
            if self._event_visible(ev, tenant)
        ][-n:]
        return (
            {"ok": True, "events": items, "journal": journal.stats()},
            b"",
            False,
        )

    @staticmethod
    def _totals_over(rows) -> dict:
        """Field-wise totals over a tenant's own job rows (sums; max for
        high-water marks) — the same recompute rule the status verb uses,
        so scoped aggregates never include other tenants' volume."""
        totals: dict = {}
        for row in rows:
            for key, val in row.items():
                if key.startswith("job_") and isinstance(val, (int, float)):
                    if key.endswith("_hwm"):
                        totals[key] = max(totals.get(key, 0), val)
                    else:
                        totals[key] = totals.get(key, 0) + val
        return totals

    def _h_trace(self, tenant, header, payload):
        """Dump the flight recorder's last N window spans.

        An operator/diagnostics surface: spans carry plane names, window
        ids, and stage timings — no tenant payloads, job names, or graph
        data — so the process-wide ring is returned as-is (a per-tenant
        slice would hide exactly the cross-job interference a latency
        post-mortem is looking for).
        """
        from gelly_streaming_tpu.utils import tracing

        try:
            n = int(header.get("n", 32))
        except (TypeError, ValueError):
            raise _Refused("bad-spec", "trace 'n' must be an integer")
        n = max(1, min(n, 4096))
        spans = tracing.flight_recorder().last(n) if tracing.active() else []
        return (
            {
                "ok": True,
                "spans": spans,
                "tracing_active": tracing.active(),
                "stats": tracing.span_stats(),
            },
            b"",
            False,
        )

    def _lifecycle(self, tenant, header, op):
        sj = self._served(tenant, header)
        ok = op(sj.job)
        return (
            {"ok": True, "result": bool(ok), "state": sj.job.state},
            b"",
            False,
        )

    def _h_pause(self, tenant, header, payload):
        return self._lifecycle(tenant, header, self.manager.pause)

    def _h_resume(self, tenant, header, payload):
        return self._lifecycle(tenant, header, self.manager.resume)

    def _h_cancel(self, tenant, header, payload):
        return self._lifecycle(
            tenant, header, lambda job: self.manager.cancel(job, wait=True)
        )

    def _resume_cursor(self, descriptor, cfg, checkpoint_path) -> int:
        """The drain/restart/rescale cursor: how many whole windows the
        job's positional checkpoint already covers (the same snapshot the
        merge loop skips by on replay — consistent by construction)."""
        w = cfg.ingest_window_edges
        if not (checkpoint_path and w):
            return 0
        last_window, _gdone = descriptor.restored_position(cfg, checkpoint_path)
        return (last_window + 1) * w

    def _make_push_source(self, cfg, resume_edges: int):
        from gelly_streaming_tpu.io.sources import NetworkEdgeSource

        return NetworkEdgeSource(
            cfg,
            cfg.batch_size,
            resume_edges=resume_edges,
            max_queued_batches=self.cfg.ingest_queue_batches,
            on_data=self.manager.poke,
        )

    def _submit_push_job(
        self,
        key: str,
        sj: _ServedJob,
        cfg: StreamConfig,
        source,
        weight: int,
        state_bytes: int,
        reserved_bytes: "int | None" = None,
    ) -> Job:
        """The ONE (re)submit recipe for a push-source job — shared by the
        submit verb and the rescale actuator, so the wiring (build
        closure, readiness/progress probes, per-record edge accounting)
        cannot drift between the two paths.  ``cfg``/``source`` are
        explicit because a rescale submits the NEW geometry before
        swapping them into ``sj``."""
        from gelly_streaming_tpu.core import aggregation

        eligible = getattr(sj.descriptor, "fused_eligible", None)

        def build():  # the OutputStream contract: a fresh records iterator
            stream = source.stream()
            # served tenants are the fused plane's home case: N push jobs
            # with shared library descriptors (class-level cache tokens)
            # on the windowed plane stack into cross-tenant mega-folds;
            # anything else keeps descriptor.run — the oracle path
            if (
                aggregation.resolve_fused_dispatch(cfg)
                and eligible is not None
                and eligible(stream)
            ):
                return sj.descriptor.run_fused(
                    stream, checkpoint_path=sj.checkpoint_path
                )
            return iter(
                stream.aggregate(
                    sj.descriptor, checkpoint_path=sj.checkpoint_path
                )
            )
        return self.manager.submit(
            build,
            name=key,
            sink=sj.sink,
            weight=weight,
            checkpoint_path=sj.checkpoint_path,
            state_bytes=state_bytes,
            edges_per_record=cfg.ingest_window_edges or 0,
            ready=source.ready,
            progress=source.progress,
            reserved_bytes=reserved_bytes,
        )

    def _rescale_served(self, sj: _ServedJob, new_shards: int, reason: str) -> dict:
        """Live re-shard one served push job (the autoscaler's actuator).

        Rides the drain verb's exact machinery end to end: quiesce the
        source (further pushes refused ``quiesced`` — the client's
        pipelined-push refusal drain handles the rejection cleanly),
        cancel through the GeneratorExit completion-queue flush, read the
        resume cursor back from the positional checkpoint, then resubmit
        the SAME job name at the new geometry from that cursor — the
        restore re-routes the checkpointed summary into the new owner
        blocks via the spec's ``shard_summary`` at the new shard count
        (core/sharded_state.py), so the resumed fold is bit-exact and
        emissions across the rescale are overlap-only.

        The admitted state bytes are re-priced ATOMICALLY: the old job's
        budget moves into a manager swap reservation BEFORE the drain
        (``begin_rescale``) and the resubmit consumes it
        (``reserved_bytes=``), so no concurrent tenant can steal the
        budget mid-swap and the two geometries are never double-booked.
        Buffered emission records survive (at-least-once: they were
        emitted past their windows' checkpoint saves).
        """
        import dataclasses as _dc

        key = self._job_key_for(sj)
        old_job = sj.job
        if old_job is None:
            raise RuntimeError(f"job {key!r} was never submitted")
        new_cfg = _dc.replace(sj.cfg, num_shards=int(new_shards))
        new_state_bytes = sj.descriptor.state_nbytes(new_cfg)
        old_held = old_job.state_bytes
        # budget swap begins UNDER the admission lock: the manager-side
        # reservation (global cap + job slot) and the tenant-swap figures
        # (per-tenant caps) move together, so no concurrent submit — this
        # tenant's or anyone's — can steal the draining job's slot or
        # bytes mid-swap
        with self._admission:
            reserved = self.manager.begin_rescale(old_job, new_state_bytes)
            self._tenant_swap_begin(sj.tenant, new_state_bytes)
        try:
            # the drain runs OUTSIDE the admission lock (a cancel flush
            # legitimately takes seconds; other tenants keep submitting)
            if sj.source is not None:
                sj.source.quiesce()
            if not old_job._state_in(*JobState.TERMINAL):
                if not self.manager.cancel(old_job, wait=True, timeout=120.0):
                    # the flush outlived the timeout: the job is STILL
                    # LIVE — proceeding would resubmit a duplicate name
                    # against a running job.  Abort; the except path
                    # restores its budget and reopens its source.
                    raise RuntimeError(
                        f"drain of {key!r} did not complete within 120s; "
                        "rescale aborted, job left running"
                    )
            resume_edges = self._resume_cursor(
                sj.descriptor, new_cfg, sj.checkpoint_path
            )
            source = self._make_push_source(new_cfg, resume_edges)
            with self._admission:
                job = self._submit_push_job(
                    key, sj, new_cfg, source, old_job.weight,
                    new_state_bytes, reserved_bytes=reserved,
                )
                # consume the tenant-swap figures in the same hold that
                # makes the new job live (and visible to _admit_tenant)
                self._tenant_swap_end(sj.tenant, new_state_bytes)
                with self._lock:
                    sj.cfg = new_cfg
                    sj.source = source
                    sj.job = job
        except BaseException:
            # the swap died (drain timeout, admission surprise): both
            # reservations go back to their pools, and a job whose drain
            # never completed gets its budget re-charged and its source
            # reopened — it is still running and its clients must not be
            # stranded awaiting a restart that will never come
            self.manager.abort_rescale(
                reserved, job=old_job, restore_state_bytes=old_held
            )
            with self._admission:
                self._tenant_swap_end(sj.tenant, new_state_bytes)
            if sj.source is not None and not old_job._state_in(
                *JobState.TERMINAL
            ):
                sj.source.resume_pushes()
            raise
        events.journal().emit(
            "restart_cursor",
            job=key,
            tenant=sj.tenant,
            resume_edges=resume_edges,
        )
        return {"resume_edges": resume_edges, "state_bytes": new_state_bytes}

    def _job_key_for(self, sj: _ServedJob) -> str:
        return f"{sj.tenant}/{sj.name}"

    def _h_drain(self, tenant, header, payload):
        """Graceful drain: quiesce sources, flush in-flight windows through
        the normal completion-queue cancel path, read back the positional
        checkpoints, reply with resume cursors.

        The cursor is derived from the CHECKPOINT after the flush — the one
        artifact a restart actually reads — so cursor and resumed fold
        cannot disagree.  Edges the client pushed past the cursor were
        never folded into a saved window; re-pushing them from the cursor
        is the at-least-once overlap the checkpoint contract already pins.
        """
        names = header.get("jobs")
        with self._lock:
            targets = [
                sj
                for sj in self._jobs.values()
                if sj.tenant == tenant.tenant
                and (names is None or sj.name in names)
            ]
        cursors = {}
        for sj in targets:
            if sj.source is not None:
                sj.source.quiesce()
            job = sj.job
            if job is not None and not job._state_in(*JobState.TERMINAL):
                self.manager.cancel(job, wait=True, timeout=60.0)
            cursor = None
            if sj.checkpoint_path and sj.cfg.ingest_window_edges:
                cursor = self._resume_cursor(
                    sj.descriptor, sj.cfg, sj.checkpoint_path
                )
            cursors[sj.name] = {
                "resume_edges": cursor,
                "checkpoint": bool(sj.checkpoint_path),
                "state": job.state if job is not None else "PENDING",
                "records_pending": sj.pending_records(),
            }
            events.journal().emit(
                "drain_cursor",
                job=self._job_key(tenant, sj.name),
                tenant=tenant.tenant,
                resume_edges=cursor,
            )
        after = None
        if header.get("shutdown"):
            # deferred to after the reply write (see _dispatch): setting
            # the event here would let the --listen loop's stop() close
            # this socket under the cursors the client is waiting on
            after = self._shutdown_requested.set
        return {"ok": True, "cursors": cursors}, b"", False, after

    def _h_shutdown(self, tenant, header, payload):
        return {"ok": True}, b"", True, self._shutdown_requested.set
