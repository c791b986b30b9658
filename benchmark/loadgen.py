"""The client side of one run: the pusher, the results reader, and the
check of what came back against the plain reference.

The harness starts this as a child process with ``JAX_PLATFORMS=cpu``, so
it never loads the chip's library.  It speaks JSON lines: the harness
writes ``{"setup": ...}``, then ``{"port": n}``, ``{"go": {"t0", "t1"}}``
and ``{"stop": true}`` to its stdin; it answers on stdout with events
``generated``, ``warm``, ``done`` and finally ``result`` (or ``error``).
Every time stamp is ``time.monotonic_ns()``, which both processes share.

Two threads drive the server through ``GellyClient`` over loopback, each
on its own connection:

* the pusher sends the stream in batches of the configuration's ``batch``
  edges, each stamped with its stream position.  A ``closed`` mix keeps
  the server's source queue full (TCP backpressure paces it); an ``open``
  mix makes each batch due at a fixed time after the window opens and
  pushes it then, whatever became of the earlier ones;
* the reader polls ``results`` and stamps each record as it arrives.

Warm-up is the measured job's own first two windows (the first compiles
the fold, the second the combine); the window opens when the harness says
``go``.  Once it has closed, the reader's sample of records is compared
with the reference over exactly the edges each record covers.
"""

from __future__ import annotations

import json
import queue
import sys
import threading
import time

import numpy as np

from benchmark import spec, streams

WARM_RECORDS = 2
# how long past the window's close a due record is waited for
LATE_WAIT_S = 60.0


def _say(**event) -> None:
    sys.stdout.write(json.dumps(event) + "\n")
    sys.stdout.flush()


def _log(msg: str) -> None:
    print(f"loadgen: {msg}", file=sys.stderr, flush=True)


class Run:
    """Shared state of one run's client side."""

    def __init__(self, setup: dict):
        self.cell = setup["cell"]
        self.seed = int(setup["seed"])
        self.control = bool(setup.get("control"))
        cfg = self.cell["config"]
        self.cfg = cfg
        self.traffic = self.cell["traffic"]
        self.open_loop = self.traffic["loop"] == "open"
        self.capacity = int(cfg["capacity"])
        self.window = int(cfg["window_edges"])
        self.batch = int(cfg["batch"])
        if self.window % self.batch:
            raise ValueError("window_edges must be a multiple of batch")
        self.per_window = self.window // self.batch
        # open loop: the warm-up pushes windows 0 and 1 and the first batch
        # of window 2 (window 1 closes when edge 2W arrives)
        self.warm_batches = WARM_RECORDS * self.per_window + 1
        self.job = f"bench-{self.cell['name']}"
        self.warm = threading.Event()
        self.go = threading.Event()
        self.t0 = self.t1 = 0
        self.reader_done = threading.Event()
        self.stopping = threading.Event()
        self.edges = None
        self.port = None
        # pusher: (batch index or first position, due ns or None, start ns,
        # end ns, edges) per push call
        self.pushes: list = []
        self.push_error = None
        # reader: receipt time per record index
        self.t_recv: list = []
        self.kept: dict = {}  # record index -> leaves
        self.first_in = None
        self.last_in = None
        self.reservoir: list = []
        self.candidates = 0
        self.rng = np.random.default_rng([self.seed % (1 << 63), 2])
        self.read_error = None

    # -- schedule -------------------------------------------------------------

    def due_ns(self, j: int) -> int:
        """Due time of scheduled batch ``j`` (open loop, ``j >= warm``)."""
        off = streams.due_offset_s(self.traffic, j - self.warm_batches, self.batch)
        return self.t0 + int(round(off * 1e9))

    def window_due_ns(self, k: int):
        """Due time of the batch holding window ``k``'s last edge, or None
        for a warm-up window."""
        last = (k + 1) * self.per_window - 1
        if last < self.warm_batches:
            return None
        return self.due_ns(last)

    def in_window(self, k: int, t_recv: int) -> bool:
        if self.open_loop:
            due = self.window_due_ns(k)
            return due is not None and self.t0 <= due < self.t1
        return self.t0 <= t_recv <= self.t1

    def due_windows(self) -> list:
        """Open loop: the windows whose last edge falls due in the window."""
        out = []
        k = WARM_RECORDS
        while True:
            due = self.window_due_ns(k)
            if due >= self.t1:
                return out
            if due >= self.t0:
                out.append(k)
            k += 1

    # -- records --------------------------------------------------------------

    def on_record(self, k: int, t: int, leaves: list) -> None:
        self.t_recv.append(t)
        if k == WARM_RECORDS - 1:
            self.warm.set()
        if not self.go.is_set() or not self.in_window(k, t):
            return
        # the first and the latest in-window record are always compared,
        # the rest by a reservoir sample drawn from the seed
        if self.first_in is None:
            self.first_in = k
            self.kept[k] = leaves
            return
        prev = self.last_in
        self.last_in = k
        self.kept[k] = leaves
        if prev is None:
            return
        n = int(self.cfg["check_records"])
        self.candidates += 1
        if len(self.reservoir) < n:
            self.reservoir.append(prev)
            return
        j = int(self.rng.integers(0, self.candidates))
        if j < n:
            self.kept.pop(self.reservoir[j], None)
            self.reservoir[j] = prev
        else:
            self.kept.pop(prev, None)


def _pusher(run: Run) -> None:
    from gelly_streaming_tpu.runtime.client import ClientError, GellyClient

    c = GellyClient("127.0.0.1", run.port)
    try:
        if run.open_loop:
            j = 0
            while not run.reader_done.is_set():
                # the batch is ready before it falls due
                s, d = run.edges.take(j * run.batch, (j + 1) * run.batch)
                due = None
                if j >= run.warm_batches:
                    run.go.wait()
                    due = run.due_ns(j)
                    wait = (due - time.monotonic_ns()) / 1e9
                    if wait > 0:
                        time.sleep(wait)
                    if run.reader_done.is_set():
                        break
                t_a = time.monotonic_ns()
                c.push_edges(
                    run.job, s, d, batch=run.batch, capacity=run.capacity,
                    close=False, window=1, position=j * run.batch,
                )
                run.pushes.append((j, due, t_a, time.monotonic_ns(), run.batch))
                j += 1
        else:
            chunk = 4 * run.batch
            pos = 0
            while not run.stopping.is_set():
                s, d = run.edges.take(pos, pos + chunk)
                t_a = time.monotonic_ns()
                c.push_edges(
                    run.job, s, d, batch=run.batch, capacity=run.capacity,
                    close=False, position=pos,
                )
                run.pushes.append((pos, None, t_a, time.monotonic_ns(), chunk))
                pos += chunk
    except (ClientError, OSError) as e:
        if not run.stopping.is_set():
            run.push_error = repr(e)
            _log(f"pusher stopped: {e!r}")
    finally:
        c.close()


def _reader(run: Run) -> None:
    from gelly_streaming_tpu.runtime.client import GellyClient

    k = 0
    try:
        with GellyClient("127.0.0.1", run.port) as c:
            while True:
                records, state, eos = c.results(run.job, max_records=4, timeout_ms=100)
                t = time.monotonic_ns()
                for leaves in records:
                    run.on_record(k, t, leaves)
                    k += 1
                if eos:
                    run.read_error = f"job ended early in state {state}"
                    return
                if not run.go.is_set():
                    continue
                if t <= run.t1:
                    continue
                if not run.open_loop:
                    return
                # open loop: wait for every record due in the window
                due = run.due_windows()
                if not due or k > due[-1] or t > run.t1 + LATE_WAIT_S * 1e9:
                    return
    except Exception as e:  # reported in the result, never swallowed
        run.read_error = repr(e)
    finally:
        run.reader_done.set()


def _percentile(xs, p: float):
    return float(np.percentile(np.asarray(xs, np.float64), p)) if len(xs) else None


def _slope_per_s(ts, ys):
    """Least-squares slope of ys over ts (ns), per second."""
    if len(ts) < 3:
        return None
    t = (np.asarray(ts, np.float64) - ts[0]) / 1e9
    y = np.asarray(ys, np.float64)
    if np.ptp(t) == 0:
        return None
    return float(np.polyfit(t, y, 1)[0])


def measure(run: Run) -> dict:
    """End-to-end metrics and counts from the client's stamps."""
    w = run.window
    recv_in = [
        (k, t) for k, t in enumerate(run.t_recv) if run.t0 <= t <= run.t1
    ]
    out: dict = {"metrics": {}, "counts": {}}
    counts = out["counts"]
    counts["records_received"] = len(run.t_recv)
    counts["records_received_in_window"] = len(recv_in)
    pushed_in = [p for p in run.pushes if run.t0 <= p[2] <= run.t1]
    counts["pushes_in_window"] = len(pushed_in)
    counts["edges_pushed_in_window"] = sum(p[4] for p in pushed_in)
    counts["push_call_ms_p50"] = _percentile([(p[3] - p[2]) / 1e6 for p in pushed_in], 50)
    counts["push_error"] = run.push_error
    counts["read_error"] = run.read_error
    # closed-but-unemitted windows at each receipt in the window: the
    # windows the pushed edges close, less the records received
    done_pushes = sorted((p[3], p[4]) for p in run.pushes)
    ts, backlog = [], []
    for k, t in recv_in:
        pushed = sum(n for t_end, n in done_pushes if t_end <= t)
        ts.append(t)
        backlog.append(max(0, (pushed - 1) // w) - (k + 1))
    counts["unemitted_windows_slope_per_s"] = _slope_per_s(ts, backlog)
    if run.open_loop:
        due = run.due_windows()
        lat = [
            (run.t_recv[k] - run.window_due_ns(k)) / 1e6
            for k in due
            if k < len(run.t_recv)
        ]
        out["attempted"] = len(due)
        out["missing"] = len(due) - len(lat)
        out["latency_ms"] = lat
        if lat:
            out["metrics"]["emit_latency_p50_ms"] = _percentile(lat, 50)
            counts["emit_latency_p95_ms"] = _percentile(lat, 95)
        late = [(p[2] - p[1]) / 1e6 for p in pushed_in if p[1] is not None]
        counts["latency_samples"] = len(lat)
        counts["generator_late_ms_p50"] = _percentile(late, 50)
        counts["generator_late_ms_max"] = max(late) if late else None
        counts["offered_edges_per_s"] = float(run.traffic["rate_edges_per_s"])
        out["push_call_ms"] = [(p[3] - p[2]) / 1e6 for p in pushed_in]
    else:
        out["attempted"] = len(recv_in)
        out["missing"] = 0
        if len(recv_in) >= 2:
            (k_a, t_a), (k_b, t_b) = recv_in[0], recv_in[-1]
            out["metrics"]["edges_per_s"] = (k_b - k_a) * w / ((t_b - t_a) / 1e9)
        counts["intervals"] = max(0, len(recv_in) - 1)
    if len(recv_in) >= 2:
        (k_a, t_a), (k_b, t_b) = recv_in[0], recv_in[-1]
        out["seconds_per_window"] = (t_b - t_a) / 1e9 / (k_b - k_a)
    return out


def check(run: Run) -> dict:
    """Compare the kept records with the reference over the edges each
    covers.  In a control run the control's states take the records'
    place in that comparison; the records' own count is kept beside it."""
    ref = spec.reference(run.cell["bench_dir"], run.cfg["reference"])
    ks = sorted(run.kept)
    t = time.perf_counter()
    mism = 0
    bad = 0
    served_mism = 0
    want_iter = ref.states(run.edges.covered, ks, run.window, run.capacity)
    ctrl_iter = (
        ref.control_states(run.edges.covered, ks, run.window, run.capacity)
        if run.control
        else None
    )
    for k, want in want_iter:
        served = ref.canon(run.kept.pop(k), run.capacity)
        got = served if ctrl_iter is None else next(ctrl_iter)[1]
        n = ref.mismatches(want, got)
        mism += n
        bad += n > 0
        if ctrl_iter is not None:
            served_mism += ref.mismatches(want, served)
    out = {
        "compared": ks,
        "mismatched_entries": mism,
        "records_wrong": bad,
        "reference_s": time.perf_counter() - t,
    }
    if run.control:
        out["served_mismatched_entries"] = served_mism
    return out


def main() -> int:
    t_child = time.monotonic_ns()
    inbox: queue.Queue = queue.Queue()

    def read_stdin():
        for line in sys.stdin:
            inbox.put(json.loads(line))
        inbox.put(None)  # the harness went away

    threading.Thread(target=read_stdin, daemon=True).start()

    def expect(key: str, timeout: float):
        msg = inbox.get(timeout=timeout)
        if msg is None:
            raise SystemExit("harness closed the control pipe")
        if key not in msg:
            raise RuntimeError(f"expected {key!r}, got {msg}")
        return msg[key]

    run = Run(expect("setup", 60))
    run.edges = streams.generate(run.cell["bench_dir"], run.cfg, run.seed)
    from gelly_streaming_tpu.runtime.client import GellyClient

    t_imported = time.monotonic_ns()
    run.port = int(expect("port", 1200))
    t_port = time.monotonic_ns()
    # the whole list first: the job then starts at the same stream position
    # in every run, and making edges never shares the window's CPUs
    run.edges.wait()
    t_submit_sent = time.monotonic_ns()
    with GellyClient("127.0.0.1", run.port) as c:
        c.submit(
            name=run.job,
            query=run.cfg["query"],
            capacity=run.capacity,
            window_edges=run.window,
            batch=run.batch,
        )
    t_submit = time.monotonic_ns()
    pusher = threading.Thread(target=_pusher, args=(run,), daemon=True)
    reader = threading.Thread(target=_reader, args=(run,), daemon=True)
    reader.start()
    pusher.start()
    while not run.warm.wait(0.01):
        if not reader.is_alive():
            raise RuntimeError(f"reader stopped during warm-up: {run.read_error}")
    _say(
        event="warm",
        t=time.monotonic_ns(),
        t_child=t_child,
        t_imported=t_imported,
        t_port=t_port,
        t_submit_sent=t_submit_sent,
        t_submit=t_submit,
        t_records=run.t_recv[:WARM_RECORDS],
        edges_made_s=run.edges.seconds,
    )

    go = expect("go", 1500)
    run.t0, run.t1 = int(go["t0"]), int(go["t1"])
    run.go.set()
    expect("stop", (run.t1 - time.monotonic_ns()) / 1e9 + 600)
    if not run.open_loop:
        run.stopping.set()
    reader.join(LATE_WAIT_S + 60)
    run.stopping.set()
    _say(event="done")
    # the harness now stops the server, which unblocks a pusher held by
    # backpressure; wait for it so no thread outlives the run
    pusher.join(120)
    result = measure(run)
    result["check"] = check(run)
    _say(event="result", **result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
