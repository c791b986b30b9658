#!/usr/bin/env python
"""Chip smoke: the served streaming path, end to end, on the TPU.

Everything runs in ONE process (a chip belongs to one process at a time):

  A. served streaming CC — ``StreamServer`` on 127.0.0.1:0 in this process,
     driven by ``GellyClient`` from a thread: a ``cc`` job at capacity 2^23
     (a soc-LiveJournal-scale id space) takes 2^25 seeded edges in 16
     windows; the final components must equal scipy's
     ``connected_components`` over the same edges;
  B. window triangles on the compiled Pallas kernel — panes whose compacted
     vertex count lies in (4096, 8192] take the dense MXU path; each pane's
     count must equal a scipy ``A . (A @ A)`` count, and the kernel's
     lowered text must hold a ``tpu_custom_call``;
  C. ``--chips 4`` only, instead of A and B: the same served CC job with
     ``num_shards=4`` and with ``num_shards=1``; both must equal the oracle
     and the owner-sharded state must sit on four distinct devices;
  T. ``--trace DIR`` only, instead of A and B: the served CC window's time
     split (fold and combine alone, then served windows under the
     profiler: device busy seconds, idle share, seconds per XLA module).

Each phase prints one JSON line.  The last line is
``{"ok": true, "device": {...}}``.  There is no CPU fallback: a run that
finds no TPU exits nonzero and prints no result.

    python chip_smoke.py [--chips 4 | --trace DIR] [--seed N]
"""

import argparse
import json
import os
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

CAPACITY = 1 << 23
WINDOW_EDGES = 1 << 21
BATCH = 1 << 20
EDGES = 16 * WINDOW_EDGES


def _say(record: dict) -> None:
    print(json.dumps(record), flush=True)


def seeded_edges(seed: int, n: int, capacity: int):
    rng = np.random.default_rng(seed)
    return (
        rng.integers(0, capacity, n, dtype=np.int32),
        rng.integers(0, capacity, n, dtype=np.int32),
    )


def oracle_components(src, dst, capacity: int) -> np.ndarray:
    """Smallest vertex id of each vertex's component (scipy, host)."""
    from scipy.sparse import coo_matrix
    from scipy.sparse.csgraph import connected_components

    adj = coo_matrix(
        (np.ones(len(src), np.int8), (src, dst)), shape=(capacity, capacity)
    ).tocsr()
    _, labels = connected_components(adj, directed=False)
    return _min_member(labels, capacity)


def _min_member(labels: np.ndarray, capacity: int) -> np.ndarray:
    first = np.full(capacity, capacity, np.int64)
    np.minimum.at(first, labels, np.arange(capacity))
    return first[labels]


def served_components(parent: np.ndarray) -> np.ndarray:
    """Smallest vertex id of each vertex's component, from a union-find
    parent array (pointer jumping to the roots)."""
    roots = np.asarray(parent, np.int64)
    while True:
        nxt = roots[roots]
        if np.array_equal(nxt, roots):
            break
        roots = nxt
    return _min_member(roots, len(roots))


def run_served_cc(
    name: str,
    src,
    dst,
    capacity: int,
    window_edges: int,
    batch: int,
    num_shards: int = 1,
) -> dict:
    """Submit one ``cc`` job to an in-process ``StreamServer``, push the
    edges and drain the results from a client thread.  Returns the last
    record's leaves and the client-side timings."""
    from gelly_streaming_tpu.core.config import ServerConfig
    from gelly_streaming_tpu.runtime.client import GellyClient
    from gelly_streaming_tpu.runtime.manager import JobManager
    from gelly_streaming_tpu.runtime.server import StreamServer

    out: dict = {}

    def client(port: int) -> None:
        try:
            with GellyClient("127.0.0.1", port) as c:
                c.submit(
                    name=name,
                    query="cc",
                    capacity=capacity,
                    window_edges=window_edges,
                    batch=batch,
                    num_shards=num_shards,
                )
                t0 = time.perf_counter()
                c.push_edges(name, src, dst, batch=batch, capacity=capacity)
                out["push_s"] = time.perf_counter() - t0
                records = 0
                last = None
                for rec in c.iter_results(name, deadline_s=900):
                    records += 1
                    last = rec
                out["e2e_s"] = time.perf_counter() - t0
                out["records"] = records
                out["last"] = last
        except Exception as e:  # re-raised on the main thread
            out["error"] = e

    with JobManager() as jm, StreamServer(jm, ServerConfig()) as server:
        t = threading.Thread(target=client, args=(server.port,), daemon=True)
        t.start()
        t.join(timeout=1200)
    if t.is_alive():
        raise RuntimeError(f"served job {name!r} did not finish")
    if "error" in out:
        raise out["error"]
    return out


def phase_served_cc(seed: int, capacity=CAPACITY, window_edges=WINDOW_EDGES,
                    batch=BATCH, edges=EDGES) -> dict:
    """Phase A: warm two windows at the served shapes (the second is the
    first to merge into the running summary), then the measured job; the
    final components must equal the scipy oracle."""
    import jax

    from gelly_streaming_tpu.core import compile_cache
    from gelly_streaming_tpu.utils.native import load_ingest_lib

    src, dst = seeded_edges(seed, edges, capacity)
    t0 = time.perf_counter()
    run_served_cc("warm", src[:2 * window_edges], dst[:2 * window_edges],
                  capacity, window_edges, batch)
    warm_s = time.perf_counter() - t0
    warm = compile_cache.stats()
    compile_cache.reset_stats()
    got = run_served_cc("cc", src, dst, capacity, window_edges, batch)
    after = compile_cache.stats()
    _cap, parent, seen = got["last"]
    want = oracle_components(src, dst, capacity)
    seen_want = np.bincount(np.concatenate([src, dst]), minlength=capacity) > 0
    report = {
        "phase": "A",
        "edges": int(edges),
        "capacity": int(capacity),
        "windows": got["records"],
        "client_push_eps": edges / got["push_s"],
        "client_e2e_eps": edges / got["e2e_s"],
        "warmup_s": warm_s,
        "compile_s": warm["compile_time_s"],
        "compiles_warmup": warm["compiles"],
        "compiles_after_warmup": after["compiles"],
        "recompiles_after_warmup": after["recompiles"],
        "native_ingest_loaded": load_ingest_lib() is not None,
        "peak_bytes_in_use": (jax.devices()[0].memory_stats() or {}).get(
            "peak_bytes_in_use"
        ),
        "components_equal_oracle": bool(
            np.array_equal(served_components(parent), want)
        ),
        "seen_equal_oracle": bool(np.array_equal(seen, seen_want)),
        "components": int(np.count_nonzero(want == np.arange(capacity))),
    }
    _say(report)
    ok = (
        report["windows"] == edges // window_edges
        and report["components_equal_oracle"]
        and report["seen_equal_oracle"]
        and report["recompiles_after_warmup"] == 0
    )
    if not ok:
        raise AssertionError("phase A: served CC disagrees with the oracle")
    return report


def _triangle_panes(seed: int, panes: int, window_ms: int):
    """Per pane: edges among 4096 < k <= 8192 vertices drawn from a sparse
    2^20 id space (so ``_pane_prepare`` compacts, then takes the dense
    kernel), stamped inside the pane's window."""
    rng = np.random.default_rng(seed)
    out = []
    for p in range(panes):
        k = int(rng.integers(4097, 8193))
        verts = rng.choice(1 << 20, size=k, replace=False).astype(np.int32)
        n = 1 << 17
        u = verts[rng.integers(0, k, n)]
        v = verts[rng.integers(0, k, n)]
        t = p * window_ms + rng.integers(0, window_ms, n)
        out.append((u, v, np.sort(t).astype(np.int32)))
    return out


def oracle_triangles(u, v) -> int:
    """Triangles of the pane's simple undirected graph: sum(A * (A @ A)) / 6."""
    from scipy.sparse import coo_matrix

    keep = u != v
    u, v = u[keep], v[keep]
    verts, inv = np.unique(np.concatenate([u, v]), return_inverse=True)
    k = len(verts)
    cu, cv = inv[: len(u)], inv[len(u):]
    a = coo_matrix(
        (np.ones(2 * len(cu), np.int64),
         (np.concatenate([cu, cv]), np.concatenate([cv, cu]))),
        shape=(k, k),
    ).tocsr()
    a.data[:] = 1  # duplicates summed on conversion: back to 0/1
    return int(a.multiply(a @ a).sum()) // 6


def phase_window_triangles(seed: int, panes: int = 4) -> dict:
    """Phase B: ``window_triangles`` over dense-kernel panes, each count
    checked against scipy, and the compiled kernel's lowering inspected."""
    from gelly_streaming_tpu.core.config import StreamConfig
    from gelly_streaming_tpu.core.stream import EdgeStream
    from gelly_streaming_tpu.core.types import EdgeBatch
    from gelly_streaming_tpu.library import triangles
    from gelly_streaming_tpu.ops import pallas_triangles

    window_ms = 1000
    data = _triangle_panes(seed, panes, window_ms)
    cfg = StreamConfig(vertex_capacity=1 << 20, batch_size=1 << 15)
    bs = cfg.batch_size

    def batches():
        for u, v, t in data:
            for i in range(0, len(u), bs):
                yield EdgeBatch.from_arrays(
                    u[i:i + bs], v[i:i + bs], time=t[i:i + bs], pad_to=bs
                )

    stream = EdgeStream.from_batches(batches, cfg)
    t0 = time.perf_counter()
    got = [c for c, _ts in triangles.window_triangles(stream, window_ms)]
    run_s = time.perf_counter() - t0
    want = [oracle_triangles(u, v) for u, v, _ in data]
    # the pane shapes the dense path saw, and the kernel it lowered to
    metas = [triangles._pane_prepare((u, v)) for u, v, _ in data]
    kinds = [m[0][0] for m in metas]
    ks = [m[0][1] for m in metas]
    (meta, (w, n)) = metas[0]
    k = max(pallas_triangles.TILE, -(-meta[1] // pallas_triangles.TILE)
            * pallas_triangles.TILE)
    lowered = pallas_triangles._count_from_packed.lower(
        w, n, k=k, interpret=pallas_triangles._use_interpret()
    ).as_text()
    report = {
        "phase": "B",
        "panes": panes,
        "pane_vertices": ks,
        "pane_paths": kinds,
        "counts": got,
        "oracle_counts": want,
        "counts_equal_oracle": got == want,
        "interpret": pallas_triangles._use_interpret(),
        "tpu_custom_call": "tpu_custom_call" in lowered,
        "run_s": run_s,
    }
    _say(report)
    ok = (
        report["counts_equal_oracle"]
        and all(kd == "packed" for kd in kinds)
        and all(4096 < kv <= triangles.DENSE_PANE_MAX_VERTICES for kv in ks)
        and not report["interpret"]
        and report["tpu_custom_call"]
    )
    if not ok:
        raise AssertionError("phase B: window triangles check failed")
    return report


def phase_sharded_cc(seed: int, shards: int = 4, capacity=CAPACITY,
                     window_edges=WINDOW_EDGES, batch=BATCH,
                     edges=EDGES) -> dict:
    """Phase C: the served CC job at ``num_shards=shards`` and at 1 on the
    same edges; both equal the oracle, and every owner-sharded state the
    mesh plane produced sits on ``shards`` distinct devices."""
    import jax

    from gelly_streaming_tpu.core import aggregation

    src, dst = seeded_edges(seed, edges, capacity)
    want = oracle_components(src, dst, capacity)

    # observe the sharded step's output state (the owner blocks) as the
    # served job produces it
    placements = set()
    runner_cls = aggregation.MeshAggregationRunner
    orig = runner_cls._pane_step_sharded

    def observed(self, *a, **kw):
        step = orig(self, *a, **kw)

        def run(blocks, *dev):
            out = step(blocks, *dev)
            for x in jax.tree.leaves(out[0]):
                placements.add(
                    frozenset(s.device.id for s in x.addressable_shards)
                )
            return out

        return run

    runner_cls._pane_step_sharded = observed
    try:
        results = {}
        for s in (shards, 1):
            t0 = time.perf_counter()
            got = run_served_cc(f"cc-s{s}", src, dst, capacity,
                                window_edges, batch, num_shards=s)
            _cap, parent, _seen = got["last"]
            results[s] = {
                "client_e2e_eps": edges / (time.perf_counter() - t0),
                "windows": got["records"],
                "equal_oracle": bool(
                    np.array_equal(served_components(parent), want)
                ),
            }
    finally:
        runner_cls._pane_step_sharded = orig
    report = {
        "phase": "C",
        "edges": int(edges),
        "capacity": int(capacity),
        f"num_shards_{shards}": results[shards],
        "num_shards_1": results[1],
        "sharded_state_device_sets": sorted(sorted(p) for p in placements),
    }
    _say(report)
    ok = (
        results[shards]["equal_oracle"]
        and results[1]["equal_oracle"]
        and placements
        and all(len(p) == shards for p in placements)
    )
    if not ok:
        raise AssertionError("phase C: sharded served CC check failed")
    return report


def _busy_seconds(events) -> float:
    """Wall time covered by the union of the events' [start, end) spans."""
    spans = sorted((e.start_ns, e.start_ns + e.duration_ns) for e in events)
    busy = 0
    cur_s, cur_e = spans[0]
    for s, e in spans[1:]:
        if s > cur_e:
            busy += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    return (busy + cur_e - cur_s) / 1e9


def _median_s(fn, reps: int = 5) -> float:
    import jax

    jax.block_until_ready(fn())
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def phase_trace(seed: int, trace_dir: str, capacity=CAPACITY,
                window_edges=WINDOW_EDGES, batch=BATCH,
                windows: int = 4) -> dict:
    """Phase T, ``--trace DIR`` only: where a served CC window's time goes.

    First the window's two device steps alone (median of 5): the fold of
    one window into an empty summary and into an already merged one, the
    combine of two fresh summaries and of a merged one with a fresh one,
    and the device->host copy of one record.  Then ``windows`` served
    windows under the profiler (trace in ``trace_dir``): the device plane's
    busy seconds against the served wall give the idle share, and its XLA
    modules the split.  The traced job's components must equal the oracle.
    """
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    from gelly_streaming_tpu.core.config import StreamConfig
    from gelly_streaming_tpu.library.connected_components import (
        ConnectedComponents,
    )

    src, dst = seeded_edges(seed, windows * window_edges, capacity)
    agg = ConnectedComponents()
    update = jax.jit(lambda s, a, b, m: agg.update(s, a, b, None, m))
    combine = jax.jit(agg.combine)
    empty = agg.initial_state(StreamConfig(vertex_capacity=capacity))
    mask = jnp.ones(window_edges, bool)
    w1 = (jnp.asarray(src[:window_edges]), jnp.asarray(dst[:window_edges]))
    w2 = (jnp.asarray(src[window_edges:2 * window_edges]),
          jnp.asarray(dst[window_edges:2 * window_edges]))
    s1, s2 = update(empty, *w1, mask), update(empty, *w2, mask)
    merged = combine(s1, s2)

    def record_to_host() -> float:
        fresh = jax.block_until_ready((merged.parent + 0, merged.seen | False))
        t0 = time.perf_counter()
        for x in fresh:
            np.asarray(x)
        return time.perf_counter() - t0

    record_to_host()
    alone = {
        "fold_into_empty_s": _median_s(lambda: update(empty, *w1, mask)),
        "fold_into_merged_s": _median_s(lambda: update(merged, *w2, mask)),
        "combine_fresh_s": _median_s(lambda: combine(s1, s2)),
        "combine_into_merged_s": _median_s(lambda: combine(merged, s2)),
        "record_to_host_s": float(np.median([record_to_host()
                                             for _ in range(5)])),
    }

    run_served_cc("trace-warm", src[:2 * window_edges],
                  dst[:2 * window_edges], capacity, window_edges, batch)
    jax.profiler.start_trace(trace_dir)
    t0 = time.perf_counter()
    try:
        got = run_served_cc("trace", src, dst, capacity, window_edges, batch)
    finally:
        jax.profiler.stop_trace()
    wall_s = time.perf_counter() - t0
    _cap, parent, _seen = got["last"]
    equal = bool(np.array_equal(served_components(parent),
                                oracle_components(src, dst, capacity)))

    paths = []
    for root, _dirs, files in os.walk(trace_dir):
        paths += [os.path.join(root, f) for f in files
                  if f.endswith(".xplane.pb")]
    profile = ProfileData.from_file(max(paths, key=os.path.getmtime))
    modules = []
    for plane in profile.planes:
        if plane.name == "/device:TPU:0":
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules += list(line.events)
    by_module: dict = {}
    for e in modules:
        name = e.name.split("(")[0]
        by_module[name] = by_module.get(name, 0.0) + e.duration_ns / 1e9
    busy_s = _busy_seconds(modules) if modules else 0.0
    report = {
        "phase": "T",
        "capacity": int(capacity),
        "window_edges": int(window_edges),
        **alone,
        "served_windows": got["records"],
        "served_wall_s": wall_s,
        "device_busy_s": busy_s,
        "device_idle_share": 1.0 - busy_s / wall_s,
        "device_module_s": dict(sorted(by_module.items(),
                                       key=lambda kv: -kv[1])),
        "equal_oracle": equal,
        "trace_dir": trace_dir,
    }
    _say(report)
    if not (equal and modules and got["records"] == windows):
        raise AssertionError("phase T: traced served CC check failed")
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded phase C on four chips")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace", metavar="DIR",
                    help="run only phase T: the served window's time split "
                    "on one chip, with a profiler trace written to DIR")
    args = ap.parse_args(argv)

    from gelly_streaming_tpu.core import compile_cache

    compile_cache.use_persistent_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (found {devices[0].platform})",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} "
              "device(s)", file=sys.stderr)
        return 2
    if args.chips == 4:
        phase_sharded_cc(args.seed)
    elif args.trace:
        phase_trace(args.seed, args.trace)
    else:
        phase_served_cc(args.seed)
        phase_window_triangles(args.seed)
    _say({
        "ok": True,
        "device": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        },
    })
    return 0


if __name__ == "__main__":
    sys.exit(main())
