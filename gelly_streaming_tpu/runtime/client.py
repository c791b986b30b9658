"""``gelly-client``: the remote side of the streaming RPC serving plane.

``GellyClient`` is the programmatic API (one socket, synchronous
request/reply frames — runtime/protocol.py); ``main`` is the console
script: submit / status / push-edges / results / drain / cancel against a
``gelly-serve --listen`` server.

Edges cross the socket in the framework's own wire encodings: the client
packs micro-batches with io/wire.py (fixed-width, or BDV delta/varint at
~2.7 B/edge when the server's submit reply advertises ``accept_bdv``), so
the link cost is the PR-6 compressed format, not 8-byte id pairs.
Emission records come back as their flattened array leaves (one ``.npz``
payload per ``results`` reply) — bit-identical to what an in-process
sink's ``jax.tree.leaves`` would see, which is exactly what the
equivalence tests compare.
"""

from __future__ import annotations

import argparse
import io as _io
import socket
import sys
import time
from typing import Iterator, List, Optional, Tuple

import numpy as np

from gelly_streaming_tpu.runtime import protocol


class ClientError(RuntimeError):
    """Transport-level failure (connection closed, bad frame)."""


class ServerRefused(RuntimeError):
    """The server answered with ``ok: false``; carries the typed code
    plus the full reply header as ``details`` — resync fields like the
    ``out-of-sync`` refusal's ``expected`` cursor and the ``rerouted``
    refusal's ``backend`` live there."""

    def __init__(self, code: str, message: str, details: Optional[dict] = None):
        super().__init__(message)
        self.code = code
        self.details = details or {}


class GellyClient:
    """One connection to a StreamServer.  Thread-compatible, not
    thread-safe: use one client per pushing thread (that is also what
    keeps per-connection backpressure per-client)."""

    def __init__(
        self,
        host: str,
        port: int,
        token: str = "",
        timeout: Optional[float] = 120.0,
    ):
        self.token = token
        self._host = host
        self._port = port
        self._timeout = timeout
        self._sock = socket.create_connection((host, port), timeout=timeout)
        try:
            # request/reply framing: Nagle + delayed ACK would add ~40 ms
            # to every small frame round trip
            self._sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        except OSError:
            pass
        self._f = self._sock.makefile("rwb")

    def close(self) -> None:
        try:
            self._f.close()
        except OSError:
            pass
        try:
            self._sock.close()
        except OSError:
            pass

    def reconnect(self) -> None:
        """Drop the (possibly dead) socket and dial the same address
        again.  Behind a ``gelly-router`` this re-resolves placement: the
        router places every frame per-request, so after a failover the
        same address reaches the standby that took the jobs over."""
        self.close()
        self._sock = socket.create_connection(
            (self._host, self._port), timeout=self._timeout
        )
        try:
            self._sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        except OSError:
            pass
        self._f = self._sock.makefile("rwb")

    def __enter__(self) -> "GellyClient":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- frame plumbing ------------------------------------------------------

    def call_raw(
        self, header: dict, payload: bytes = b""
    ) -> Tuple[dict, bytes]:
        """One request/reply round trip; raises ``ClientError`` on
        transport failure, returns the reply even when ``ok`` is false."""
        header = dict(header)
        header.setdefault("token", self.token)
        try:
            protocol.write_frame(self._f, header, payload)
            reply = protocol.read_frame(self._f)
        except (OSError, protocol.ProtocolError) as e:
            raise ClientError(f"transport failure: {e}") from e
        if reply is None:
            raise ClientError("server closed the connection")
        return reply

    def call(self, header: dict, payload: bytes = b"") -> Tuple[dict, bytes]:
        """``call_raw`` + refusal handling: ``ok: false`` raises
        ``ServerRefused(code)``."""
        head, pay = self.call_raw(header, payload)
        if not head.get("ok"):
            raise ServerRefused(
                head.get("code", "error"),
                head.get("error", "refused"),
                details=head,
            )
        return head, pay

    # -- verbs ---------------------------------------------------------------

    def ping(self) -> dict:
        return self.call({"verb": "ping"})[0]

    def submit(self, **spec) -> dict:
        """Submit a job spec; returns the reply (``resume_edges`` is the
        cursor to push from for checkpointed jobs)."""
        return self.call({"verb": "submit", "spec": spec})[0]

    def push_wire(self, job: str, buf, kind: str = "wire") -> dict:
        return self.call(
            {"verb": "push", "job": job, "kind": kind},
            np.ascontiguousarray(buf, np.uint8).tobytes(),
        )[0]

    def push_tail(
        self, job: str, src, dst, offset: Optional[int] = None
    ) -> dict:
        src = np.ascontiguousarray(src, "<i4")
        dst = np.ascontiguousarray(dst, "<i4")
        header = {"verb": "push", "job": job, "kind": "tail", "count": len(src)}
        if offset is not None:
            header["offset"] = int(offset)
        return self.call(header, src.tobytes() + dst.tobytes())[0]

    def eos(self, job: str) -> dict:
        return self.call({"verb": "eos", "job": job})[0]

    def push_edges(
        self,
        job: str,
        src,
        dst,
        batch: int,
        capacity: int,
        bdv: bool = False,
        start: int = 0,
        close: bool = True,
        window: int = 32,
        position: Optional[int] = None,
        declare_position: bool = True,
    ) -> int:
        """Pack ``src/dst[start:]`` into full wire batches (+ raw tail) and
        push them, optionally closing the stream.  Returns edges pushed.

        ``start`` is the resume cursor from ``submit`` — on reconnect the
        client ships only the suffix the server's checkpoint doesn't cover.

        ``position`` is the GLOBAL stream offset of ``src[start]`` when it
        differs from ``start`` itself — the incremental pattern, where each
        call pushes a fresh chunk (``start=0``) of a stream whose earlier
        edges went in previous calls: pass the count pushed so far.
        ``declare_position=False`` drops the offset stamps entirely (the
        server's legacy unchecked behavior) for callers that cannot know
        their position.

        Push frames are PIPELINED: up to ``window`` frames are written
        before their replies are read (replies come back in order — the
        server handles one connection's frames sequentially), so the
        socket round trip is paid once per window, not once per batch,
        while the bounded reply window still surfaces refusals promptly
        and keeps the server's per-connection backpressure effective.

        Every frame is stamped with its global edge ``offset`` (``start +
        batches pushed so far``), which the server verifies against the
        source's exact positional accounting — so a frame still in flight
        when a live rescale/drain swaps the job's source is refused
        ``out-of-sync`` instead of silently landing past the new resume
        cursor.  On a ``quiesced``/``out-of-sync`` refusal, re-push from
        the advertised cursor.
        """
        from gelly_streaming_tpu.io import wire as wire_mod

        src = np.ascontiguousarray(src, np.int32)[start:]
        dst = np.ascontiguousarray(dst, np.int32)[start:]
        base = int(position) if position is not None else start
        width = wire_mod.width_for_capacity(capacity)
        n_full = len(src) // batch
        outstanding = 0
        # a refusal mid-pipeline must not desync the connection: every
        # outstanding reply is still read (in order) before the first
        # refusal is raised, so the next verb on this socket reads ITS
        # reply, not a stale push ack
        refusal: Optional[ServerRefused] = None

        def read_reply():
            nonlocal refusal
            reply = protocol.read_frame(self._f)
            if reply is None:
                raise ClientError("server closed the connection")
            head, _pay = reply
            if not head.get("ok") and refusal is None:
                refusal = ServerRefused(
                    head.get("code", "error"),
                    head.get("error", "refused"),
                    details=head,
                )

        try:
            for i in range(n_full):
                s_b = src[i * batch : (i + 1) * batch]
                d_b = dst[i * batch : (i + 1) * batch]
                if bdv:
                    head = {"verb": "push", "job": job, "kind": "bdv"}
                    buf = wire_mod.pack_edges_bdv(s_b, d_b, capacity)
                else:
                    head = {"verb": "push", "job": job, "kind": "wire"}
                    buf = wire_mod.pack_edges(s_b, d_b, width)
                if declare_position:
                    head["offset"] = base + i * batch
                head["token"] = self.token
                protocol.write_frame(self._f, head, np.ascontiguousarray(buf))
                outstanding += 1
                if outstanding >= max(1, window):
                    read_reply()
                    outstanding -= 1
                if refusal is not None:
                    break  # stop producing; drain what's in flight below
            while outstanding:
                read_reply()
                outstanding -= 1
        except (OSError, protocol.ProtocolError) as e:
            raise ClientError(f"transport failure: {e}") from e
        if refusal is not None:
            raise refusal
        if len(src) % batch:
            self.push_tail(
                job,
                src[n_full * batch :],
                dst[n_full * batch :],
                offset=base + n_full * batch if declare_position else None,
            )
        if close:
            self.eos(job)
        return len(src)

    # refusal codes that mean "the stream will come back: retry through
    # the same address" — rerouted (fleet failover in progress), quiesced
    # (live rescale/drain swapping the source), unavailable
    _RETRY_CODES = frozenset({"rerouted", "quiesced", "unavailable"})

    def push_edges_resilient(
        self,
        job: str,
        src,
        dst,
        batch: int,
        capacity: int,
        bdv: bool = False,
        start: int = 0,
        close: bool = True,
        window: int = 32,
        deadline_s: float = 120.0,
        backoff_s: float = 0.2,
    ) -> int:
        """``push_edges`` with automatic reconnect-with-resync: survives
        connection loss and typed ``rerouted`` refusals (fleet failover
        behind a ``gelly-router``) by re-dialing the same address and
        re-declaring the push position.

        The resync protocol NEVER silently re-pushes acked edges.  The
        client's cursor only moves when the server tells it to: every
        frame is offset-stamped, so a frame the server already counted is
        REFUSED ``out-of-sync`` with the advertised ``expected`` cursor
        (never folded twice), and the cursor jumps there.  The one case
        where edges are re-sent is ``expected`` BELOW the cursor — a
        failover landed the job on a standby whose checkpoint trails the
        acked stream — and that overlap is server-directed: exactly the
        suffix past the resume cursor, the same at-least-once/overlap-
        only contract every restart path in the repo pins.

        Raises the refusal unchanged for non-retryable codes (auth,
        unknown-job, bad-spec: retrying cannot fix those) and
        ``ClientError`` when ``deadline_s`` expires first.
        """
        total = len(src)
        pos = int(start)
        deadline = time.monotonic() + deadline_s
        last_err: Optional[Exception] = None

        def _wait(transport: bool) -> None:
            if time.monotonic() > deadline:
                raise ClientError(
                    f"resilient push of {job!r} did not finish within "
                    f"{deadline_s}s (cursor {pos}/{total}): {last_err}"
                ) from last_err
            time.sleep(backoff_s)
            if transport:
                try:
                    self.reconnect()
                except OSError as e:  # router itself briefly down
                    nonlocal_err(e)

        def nonlocal_err(e: Exception) -> None:
            nonlocal last_err
            last_err = e

        while pos < total:
            try:
                self.push_edges(
                    job,
                    src,
                    dst,
                    batch=batch,
                    capacity=capacity,
                    bdv=bdv,
                    start=pos,
                    close=False,
                    window=window,
                )
                pos = total
            except ClientError as e:
                # connection loss mid-window: frames past the last ack
                # may or may not have landed.  Reconnect and retry from
                # the stale cursor — counted frames are refused
                # out-of-sync (not folded) and the refusal's expected
                # cursor moves us forward.
                nonlocal_err(e)
                _wait(transport=True)
            except ServerRefused as e:
                expected = e.details.get("expected")
                if e.code == "out-of-sync" and isinstance(expected, int):
                    # the server's cursor IS the resync point — jump
                    # there immediately, no backoff (this is the common
                    # post-reconnect/post-failover step, not an error)
                    moved = min(max(expected, 0), total)
                    nonlocal_err(e)
                    if moved == pos:
                        # no progress: something upstream is still
                        # settling (e.g. a resume filler in flight) —
                        # don't spin on refusals
                        _wait(transport=False)
                    pos = moved
                elif e.code in self._RETRY_CODES:
                    nonlocal_err(e)
                    _wait(transport=False)
                else:
                    raise
        if close:
            while True:
                try:
                    self.eos(job)
                    break
                except ClientError as e:
                    nonlocal_err(e)
                    _wait(transport=True)
                except ServerRefused as e:
                    if e.code not in self._RETRY_CODES:
                        raise
                    nonlocal_err(e)
                    _wait(transport=False)
        return total - int(start)

    def results(
        self, job: str, max_records: int = 256, timeout_ms: int = 1000
    ) -> Tuple[List[List[np.ndarray]], str, bool]:
        """Fetch buffered emission records: (records, job state, eos).
        Each record is the list of its flattened host array leaves."""
        head, payload = self.call(
            {
                "verb": "results",
                "job": job,
                "max": max_records,
                "timeout_ms": timeout_ms,
            }
        )
        records: List[List[np.ndarray]] = []
        if head["count"]:
            # raw leaf framing: dtype/shape metadata in the header, the
            # payload is the leaves' bytes concatenated in order (the
            # server's _h_results twin — same leaves the npz container
            # used to carry, without the per-record zipfile cost)
            off = 0
            for meta in head["leafmeta"]:
                leaves = []
                for dtype_str, shape in meta:
                    dt = np.dtype(dtype_str)
                    count = int(np.prod(shape, dtype=np.int64)) if shape else 1
                    nb = dt.itemsize * count
                    arr = np.frombuffer(
                        payload, dt, count=count, offset=off
                    ).reshape(shape)
                    leaves.append(arr)
                    off += nb
                records.append(leaves)
        return records, head["state"], bool(head["eos"])

    def iter_results(
        self, job: str, poll_timeout_ms: int = 1000, deadline_s: float = 300.0
    ) -> Iterator[List[np.ndarray]]:
        """Yield records until end-of-stream (or ``deadline_s``, then
        ``ClientError`` — a remote hang must fail loudly, not forever)."""
        deadline = time.monotonic() + deadline_s
        while True:
            records, state, eos = self.results(
                job, timeout_ms=poll_timeout_ms
            )
            for rec in records:
                yield rec
            if eos:
                return
            if time.monotonic() > deadline:
                raise ClientError(
                    f"job {job!r} produced no end-of-stream within "
                    f"{deadline_s}s (state {state})"
                )

    def status(self) -> dict:
        return self.call({"verb": "status"})[0]

    def metrics(self) -> dict:
        """The full observability registry (tenant-scoped job/tenant rows
        + process-plane counters + histograms + span stage aggregates)."""
        return self.call({"verb": "metrics"})[0]["metrics"]

    def metrics_prometheus(self) -> str:
        """The same registry rendered in the Prometheus text exposition
        format (ships as the frame payload)."""
        _head, payload = self.call(
            {"verb": "metrics", "format": "prometheus"}
        )
        return payload.decode("utf-8")

    def health(self) -> dict:
        """The health plane's keep-up verdicts: per-job gauges (watermark
        lag, backlog depth/age, arrival/drain EWMA rates, keep-up ratio,
        time-to-queue-full), visible alert rows, configured SLO specs, and
        the monitor's liveness stats."""
        return self.call({"verb": "health"})[0]["health"]

    def alerts(self) -> list:
        """Just the visible SLO alert rows (state, burn rates, since)."""
        return self.call({"verb": "alerts"})[0]["alerts"]

    def events(self, n: int = 64, kind: "Optional[str]" = None) -> list:
        """Tail the structured event journal (job transitions, admission
        rejections, drain/restart cursors, alert firings/clears)."""
        header: dict = {"verb": "events", "n": n}
        if kind is not None:
            header["kind"] = kind
        return self.call(header)[0]["events"]

    def trace(self, n: int = 32) -> dict:
        """The flight recorder's last ``n`` window spans plus the span
        stage aggregates: ``{"spans": [...], "tracing_active": bool,
        "stats": {...}}``.  Empty spans until some run enables
        ``trace_sample`` / ``GELLY_TRACE_SAMPLE``."""
        return self.call({"verb": "trace", "n": n})[0]

    def pause(self, job: str) -> dict:
        return self.call({"verb": "pause", "job": job})[0]

    def resume(self, job: str) -> dict:
        return self.call({"verb": "resume", "job": job})[0]

    def cancel(self, job: str) -> dict:
        return self.call({"verb": "cancel", "job": job})[0]

    def drain(
        self, jobs: Optional[List[str]] = None, shutdown: bool = False
    ) -> dict:
        """Graceful drain; the reply's ``cursors`` map job -> resume
        cursor (``resume_edges``) for checkpointed push jobs."""
        header = {"verb": "drain", "shutdown": bool(shutdown)}
        if jobs is not None:
            header["jobs"] = list(jobs)
        return self.call(header)[0]

    def shutdown_server(self) -> dict:
        return self.call({"verb": "shutdown"})[0]


# ---------------------------------------------------------------------------
# console script
# ---------------------------------------------------------------------------


def _parse_addr(addr: str) -> Tuple[str, int]:
    host, _, port = addr.rpartition(":")
    if not host or not port.isdigit():
        raise SystemExit(f"--connect needs host:port, got {addr!r}")
    return host, int(port)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="gelly-client",
        description="remote console for a gelly-serve --listen server",
    )
    parser.add_argument(
        "--connect", required=True, help="server address, host:port"
    )
    parser.add_argument("--token", default="", help="tenant auth token")
    sub = parser.add_subparsers(dest="cmd", required=True)

    sub.add_parser("status", help="server + per-job status lines")

    p_submit = sub.add_parser("submit", help="submit a push-source job")
    p_submit.add_argument("--name", required=True)
    p_submit.add_argument(
        "--query", default="cc", choices=("cc", "degree", "edges", "triangles")
    )
    p_submit.add_argument(
        "--summary",
        default=None,
        choices=("sketch_triangles", "hll_degree", "cm_heavy_hitters"),
        help="swap the job's summary for a fixed-tiny-state sketch "
        "(overrides --query; see --eps/--delta)",
    )
    p_submit.add_argument(
        "--eps",
        type=float,
        default=None,
        help="sketch relative-error target (sketch summaries only)",
    )
    p_submit.add_argument(
        "--delta",
        type=float,
        default=None,
        help="sketch failure probability of the eps bound",
    )
    p_submit.add_argument("--capacity", type=int, default=1 << 16)
    p_submit.add_argument("--window-edges", type=int, default=1 << 13)
    p_submit.add_argument("--batch", type=int, default=1 << 12)
    p_submit.add_argument("--weight", type=int, default=1)
    p_submit.add_argument("--checkpoint", action="store_true")

    p_push = sub.add_parser(
        "push-edges",
        help="push a seeded synthetic edge stream into a submitted job "
        "(geometry flags must match the submit)",
    )
    p_push.add_argument("--job", required=True)
    p_push.add_argument("--edges", type=int, default=100_000)
    p_push.add_argument("--seed", type=int, default=0)
    p_push.add_argument("--capacity", type=int, default=1 << 16)
    p_push.add_argument("--batch", type=int, default=1 << 12)
    p_push.add_argument("--bdv", action="store_true")
    p_push.add_argument(
        "--start", type=int, default=0, help="resume cursor (edges to skip)"
    )
    p_push.add_argument(
        "--no-results",
        action="store_true",
        help="push + eos only; don't consume emissions",
    )

    p_results = sub.add_parser("results", help="stream a job's emissions")
    p_results.add_argument("--job", required=True)

    p_metrics = sub.add_parser(
        "metrics",
        help="dump the server's observability registry (counters, "
        "histogram quantiles, span stage aggregates)",
    )
    p_metrics.add_argument(
        "--prometheus",
        action="store_true",
        help="print the Prometheus text exposition format instead of JSON",
    )

    p_trace = sub.add_parser(
        "trace", help="dump the flight recorder's last N window spans"
    )
    p_trace.add_argument("--last", type=int, default=32)

    sub.add_parser(
        "health",
        help="per-job keep-up gauges (lag, backlog age, keep-up ratio) "
        "and SLO alert states",
    )

    p_events = sub.add_parser(
        "events",
        help="tail the structured event journal (lifecycle transitions, "
        "admission rejections, cursors, alert firings/clears)",
    )
    p_events.add_argument("--last", type=int, default=64)
    p_events.add_argument("--kind", default=None)

    p_cancel = sub.add_parser("cancel", help="cancel a job")
    p_cancel.add_argument("--job", required=True)

    p_drain = sub.add_parser(
        "drain", help="drain this tenant's jobs; print resume cursors"
    )
    p_drain.add_argument("--shutdown", action="store_true")

    args = parser.parse_args(argv)
    host, port = _parse_addr(args.connect)
    with GellyClient(host, port, token=args.token) as client:
        try:
            return _run_cmd(client, args)
        except ServerRefused as e:
            print(f"refused [{e.code}]: {e}", file=sys.stderr)
            return 2


def _run_cmd(client: GellyClient, args) -> int:
    if args.cmd == "status":
        reply = client.status()
        for line in reply["lines"]:
            print(line)
        srv = reply["server"]
        print(
            f"server: {srv['connections']} connection(s), "
            f"{srv['served_jobs']} served job(s)"
        )
        return 0
    if args.cmd == "submit":
        spec = dict(
            name=args.name,
            query=args.query,
            capacity=args.capacity,
            window_edges=args.window_edges,
            batch=args.batch,
            weight=args.weight,
            checkpoint=args.checkpoint,
        )
        # sketch knobs travel only when given: the server validates them
        # at admission and refuses loudly on a bad contract
        if args.summary is not None:
            spec["summary"] = args.summary
        if args.eps is not None:
            spec["eps"] = args.eps
        if args.delta is not None:
            spec["delta"] = args.delta
        reply = client.submit(**spec)
        line = (
            f"submitted {reply['job']}: batch={reply['batch']} "
            f"window={reply['window_edges']} resume_edges="
            f"{reply['resume_edges']} accept_bdv={reply['accept_bdv']}"
        )
        contract = reply.get("error_contract")
        if contract:
            line += (
                f" sketch={contract['kind']} eps={contract['eps']} "
                f"delta={contract['delta']}"
            )
        print(line)
        return 0
    if args.cmd == "push-edges":
        rng = np.random.default_rng(args.seed)
        src = rng.integers(0, args.capacity, args.edges).astype(np.int32)
        dst = rng.integers(0, args.capacity, args.edges).astype(np.int32)
        t0 = time.perf_counter()
        pushed = client.push_edges(
            args.job,
            src,
            dst,
            batch=args.batch,
            capacity=args.capacity,
            bdv=args.bdv,
            start=args.start,
        )
        dt = time.perf_counter() - t0
        print(
            f"pushed {pushed} edges in {dt:.2f}s "
            f"({pushed / max(dt, 1e-9):.0f} eps over the socket)"
        )
        if not args.no_results:
            n = 0
            for _rec in client.iter_results(args.job):
                n += 1
            print(f"{n} record(s), end of stream")
        return 0
    if args.cmd == "results":
        n = 0
        for rec in client.iter_results(args.job):
            n += 1
            shapes = ", ".join(str(leaf.shape) for leaf in rec)
            print(f"record {n}: {len(rec)} leaves [{shapes}]")
        print(f"{n} record(s), end of stream")
        return 0
    if args.cmd == "metrics":
        if args.prometheus:
            sys.stdout.write(client.metrics_prometheus())
            return 0
        import json as _json

        print(_json.dumps(client.metrics(), indent=2, sort_keys=True))
        return 0
    if args.cmd == "trace":
        reply = client.trace(args.last)
        if not reply["tracing_active"]:
            print(
                "tracing is off (enable with trace_sample / "
                "GELLY_TRACE_SAMPLE on the server)"
            )
        for span in reply["spans"]:
            stages = " ".join(
                f"{s['stage']}={s['ms']:.2f}" for s in span["stages"]
            )
            print(
                f"#{span['trace_id']} {span['plane']} w={span['window']} "
                f"total={span['total_ms']:.2f}ms  {stages}"
            )
        return 0
    if args.cmd == "health":
        health = client.health()
        for job_id, row in sorted(health["jobs"].items()):
            gauges = " ".join(
                f"{k}={v}" for k, v in sorted(row.items())
            )
            print(f"{job_id}: {gauges}")
        for a in health["alerts"]:
            print(
                f"alert [{a['state']}] {a['scope']}:{a['id']} {a['slo']} "
                f"burn_fast={a['burn_fast']} burn_slow={a['burn_slow']}"
            )
        mon = health.get("monitor")
        print(
            f"monitor: {mon}" if mon else "monitor: off (no SLOs configured)"
        )
        return 0
    if args.cmd == "events":
        import json as _json

        for ev in client.events(args.last, kind=args.kind):
            print(_json.dumps(ev, sort_keys=True))
        return 0
    if args.cmd == "cancel":
        reply = client.cancel(args.job)
        print(f"cancel {args.job}: state={reply['state']}")
        return 0
    if args.cmd == "drain":
        reply = client.drain(shutdown=args.shutdown)
        for name, cur in sorted(reply["cursors"].items()):
            print(
                f"{name}: state={cur['state']} resume_edges="
                f"{cur['resume_edges']} pending={cur['records_pending']}"
            )
        return 0
    raise SystemExit(f"unknown command {args.cmd!r}")


if __name__ == "__main__":
    sys.exit(main())
