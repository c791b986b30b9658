"""The edge stream a run pushes, and the schedule it pushes it on.

The stream is passes over one list of edges made by the configuration's
generator; the run's seed draws the order of the edges inside each
window.  The client
process makes the list once per run, in worker processes, while the
harness brings the chip up.
"""

from __future__ import annotations

import multiprocessing
import os
import threading
import time

import numpy as np

from benchmark import spec


class EdgeList:
    """The stream over one list of ``m`` edges, cut into windows of
    ``window`` edges.  Stream window ``w`` is list window ``w`` (mod the
    windows a pass holds): every seed sees the same edge sets in the same
    windows, so the same work.  Inside a window the seed draws the order:
    offset ``o`` holds the window's entry ``(a * o + b_w) mod window``,
    with ``a`` odd and ``b_w`` drawn per window."""

    def __init__(self, src: np.ndarray, dst: np.ndarray, window: int, order_seed: int):
        if window & (window - 1) or len(src) % window:
            raise ValueError("window must be a power of two dividing the list")
        self.src = src
        self.dst = dst
        self.m = len(src)
        self.window = window
        rng = np.random.default_rng([order_seed % (1 << 63), 3])
        self.a = int(rng.integers(0, window // 2 or 1)) * 2 + 1
        self.c, self.d = (int(x) for x in rng.integers(0, window, 2))
        self.seconds = None  # time to make the whole list
        self.error = None
        self.ready = threading.Event()

    def wait(self) -> None:
        """Block until the whole list exists."""
        self.ready.wait()
        if self.error is not None:
            raise RuntimeError(f"edge generation failed: {self.error!r}")

    def take(self, lo: int, hi: int):
        """Stream positions ``[lo, hi)``."""
        pos = np.arange(lo, hi, dtype=np.int64)
        w = (pos // self.window) % (self.m // self.window)
        b = (self.c * w + self.d) & (self.window - 1)
        idx = w * self.window + ((self.a * pos + b) & (self.window - 1))
        return self.src[idx], self.dst[idx]

    def covered(self, lo: int, hi: int):
        """The edges of the whole windows in stream positions ``[lo, hi)``,
        in the list's own order: the same multiset as ``take``, read as
        contiguous slices (what the order-free references need)."""
        if lo % self.window or hi % self.window:
            raise ValueError("covered() reads whole windows")
        src, dst, a, n = [], [], lo % self.m, hi - lo
        while n > 0:
            k = min(n, self.m - a)
            src.append(self.src[a : a + k])
            dst.append(self.dst[a : a + k])
            a, n = 0, n - k
        if len(src) == 1:
            return src[0], dst[0]
        return np.concatenate(src or [self.src[:0]]), np.concatenate(dst or [self.dst[:0]])


# edges per generation task
TASK_EDGES = 1 << 20
_WORKER: dict = {}


def _worker_init(src_raw, dst_raw, bench_dir, name, config, seed) -> None:
    _WORKER.update(
        src=np.frombuffer(src_raw, np.int32),
        dst=np.frombuffer(dst_raw, np.int32),
        gen=spec.generator(bench_dir, name),
        config=config,
        seed=seed,
    )


def _worker_fill(lo: int) -> int:
    w = _WORKER
    hi = min(len(w["src"]), lo + TASK_EDGES)
    w["src"][lo:hi], w["dst"][lo:hi] = w["gen"].edges(w["config"], w["seed"], lo, hi - lo)
    return hi


def generate(bench_dir: str, config: dict, seed: int) -> EdgeList:
    """Start making the configuration's edge list; returns at once with
    the list filling in the background.  The list and its vertex labels
    come from the configuration's ``graph_seed``: the same graph, cut into
    the same windows, in every run.  The run's ``seed`` draws the order of
    the edges inside each window.  Worker processes write the list in
    place into shared memory."""
    name = config["generator"]
    graph_seed = int(config["graph_seed"])
    m = spec.generator(bench_dir, name).num_edges(config)
    ctx = multiprocessing.get_context("spawn")
    src_raw = ctx.RawArray("i", m)
    dst_raw = ctx.RawArray("i", m)
    edges = EdgeList(
        np.frombuffer(src_raw, np.int32),
        np.frombuffer(dst_raw, np.int32),
        int(config["window_edges"]),
        seed,
    )
    starts = range(0, m, TASK_EDGES)
    workers = min(len(starts), max(1, (os.cpu_count() or 2) - 4), 8)

    def fill():
        t = time.perf_counter()
        try:
            with ctx.Pool(
                workers,
                initializer=_worker_init,
                initargs=(src_raw, dst_raw, bench_dir, name, config, graph_seed),
            ) as pool:
                for _ in pool.imap_unordered(_worker_fill, starts):
                    pass
            edges.seconds = time.perf_counter() - t
        except BaseException as e:  # the pusher and the check re-raise it
            edges.error = e
        finally:
            edges.ready.set()

    threading.Thread(target=fill, name="edge-generation", daemon=True).start()
    return edges


def due_offset_s(traffic: dict, i: int, batch: int) -> float:
    """Seconds after the window opens at which scheduled batch ``i`` is
    due, for an open-loop mix: evenly at ``rate_edges_per_s``."""
    return i * batch / float(traffic["rate_edges_per_s"])
