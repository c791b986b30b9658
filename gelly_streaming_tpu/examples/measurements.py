"""Measurement programs: degree / bipartiteness / triangle throughput+latency.

The reference's pom.xml declares three measurement jars —
``example.degrees.DegreeMeasurement``, ``example.bipartiteness.
BipartiteMeasurement``, ``example.triangles.TriangleMeasurements``
(pom.xml:144-188) — whose classes do not exist in its source tree (an
out-of-tree benchmarking branch, SURVEY.md §6).  This module supplies working
equivalents: each subcommand drives the framework's real ingest path (wire
pack -> prefetched transfer -> jitted fold, as in bench.py) for one workload
and prints ONE JSON line of metrics.

  python -m gelly_streaming_tpu.examples.measurements degrees       [options]
  python -m gelly_streaming_tpu.examples.measurements bipartiteness [options]
  python -m gelly_streaming_tpu.examples.measurements triangles     [options]
  python -m gelly_streaming_tpu.examples.measurements spanner       [options]
  python -m gelly_streaming_tpu.examples.measurements matching      [options]
  python -m gelly_streaming_tpu.examples.measurements sage          [options]
  python -m gelly_streaming_tpu.examples.measurements pagerank      [options]
  python -m gelly_streaming_tpu.examples.measurements sssp          [options]
  python -m gelly_streaming_tpu.examples.measurements kcore         [options]

Options: --edges N --vertices C --batch B --seed S; triangles also takes
--windows W --pane-vertices K (panes are K-vertex random graphs counted with
the MXU kernel; reports p50/p95 per-window latency); spanner adds
--max-degree D --k K (two-phase batch admission, reports edges/s and the
admitted spanner size); matching reports the reference's net-runtime metric
(CentralizedWeightedMatching.java:62-64) plus edges/s; sage adds
--features F --out-features G --max-degree D --train-steps N (windowed
GraphSAGE embedding throughput; N>0 also times jitted unsupervised training
steps); pagerank adds --windows W --tol T (windowed PageRank edges/s,
windows/s, device ms/iteration); replay drives the
wire-replay CC headline (EdgeStream.from_wire) and reports replay/pack
rates plus the encoding's bytes per edge.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import List, Optional

import numpy as np


def _stream_fold(num_edges, capacity, batch, seed, make_fold, init_state):
    """Synthetic edge stream through the shared wire-ingest harness."""
    from gelly_streaming_tpu.utils.ingest_bench import wire_stream_fold

    if num_edges < 2:
        raise SystemExit("--edges must be at least 2")
    rng = np.random.default_rng(seed)
    src = rng.integers(0, capacity, num_edges).astype(np.int32)
    dst = rng.integers(0, capacity, num_edges).astype(np.int32)
    return wire_stream_fold(src, dst, capacity, batch, make_fold, init_state)


def measure_degrees(args) -> dict:
    """Continuous degree stream fold (getDegrees hot path,
    SimpleEdgeStream.java:461-478 as a dense segment add)."""
    import jax.numpy as jnp

    from gelly_streaming_tpu.io import wire
    from gelly_streaming_tpu.ops import segments

    def make_fold(batch, width):
        def fold(counts, buf):
            s, d = wire.unpack_edges(buf, batch, width)
            v = jnp.concatenate([s, d])
            return counts + segments.segment_sum(
                jnp.ones_like(v), v, counts.shape[0], None
            )

        return fold

    eps, folded, counts = _stream_fold(
        args.edges,
        args.vertices,
        args.batch,
        args.seed,
        make_fold,
        lambda: jnp.zeros((args.vertices,), jnp.int32),
    )
    total = int(np.asarray(counts).sum())
    out = {
        "workload": "degrees",
        "edges_per_sec": round(eps, 1),
        "edges_folded": folded,
        "degree_total": total,
    }
    proxy = _degree_flink_proxy(args, folded, np.asarray(counts))
    if proxy:
        out.update(proxy)
    if getattr(args, "trace", False):
        out.update(_measure_degree_trace(args))
    return out


def _degree_flink_proxy(args, folded, device_counts) -> dict:
    """Measured Flink-shaped denominator for BASELINE row 1 (Continuous
    Degree Aggregate): the same record-at-a-time stack as the CC proxy —
    Tuple2 serialize + keyBy hash + socketpair shuffle — folding per-key
    HashMap degree counts (SimpleEdgeStream.java:461-478's DegreeMapFunction
    state), in optimized C++ (native/edge_parser.cpp flink_proxy_degrees).
    The proxy folds exactly the ``folded`` prefix the device harness folded
    (wire_stream_fold folds full batches only), so counts cross-check."""
    import ctypes
    import statistics

    from gelly_streaming_tpu.utils.native import load_ingest_lib

    lib = load_ingest_lib()
    if lib is None or not hasattr(lib, "flink_proxy_degrees"):
        return {}
    rng = np.random.default_rng(args.seed)
    src = rng.integers(0, args.vertices, args.edges).astype(np.int32)
    dst = rng.integers(0, args.vertices, args.edges).astype(np.int32)
    cnt = np.empty(args.vertices, np.int64)
    trials = []
    for _ in range(3):
        ns = lib.flink_proxy_degrees(
            src.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            dst.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            folded,
            cnt.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            args.vertices,
        )
        if ns <= 0:
            return {}
        trials.append(folded / (ns / 1e9))
    return {
        "flink_proxy_eps": round(statistics.median(trials), 1),
        # the harness folds the same seeded stream, so totals must agree
        "flink_proxy_counts_ok": bool(
            np.array_equal(cnt, device_counts.astype(np.int64))
        ),
    }


def _measure_degree_trace(args) -> dict:
    """Running-trace EMISSION plane (VERDICT r4 item 6): the full
    (vertex, degree) record trace — 2 records per edge — through
    ``get_degrees()`` with the pipelined device->host download path
    (io/wire.prefetch_to_host overlapping ``copy_to_host_async`` with later
    batches' compute).  Reports records/s and the downloaded GB/s; on a
    narrow link the steady state should sit at min(downlink, host decode),
    not the serialized per-batch round-trip sum the pre-pipelined path paid
    (SimpleEdgeStream.java:461-478 is the running-trace contract)."""
    import time

    from gelly_streaming_tpu.core.config import StreamConfig
    from gelly_streaming_tpu.core.stream import EdgeStream
    from gelly_streaming_tpu.io import wire

    rng = np.random.default_rng(args.seed)
    n = args.edges - args.edges % args.batch
    src = rng.integers(0, args.vertices, n).astype(np.int32)
    dst = rng.integers(0, args.vertices, n).astype(np.int32)
    cfg = StreamConfig(vertex_capacity=args.vertices, batch_size=args.batch)
    width = wire.width_for_capacity(args.vertices)
    bufs, _ = wire.pack_stream(src, dst, args.batch, width)

    def drain():
        records = nbytes = 0
        stream = EdgeStream.from_wire(bufs, args.batch, width, cfg)
        for block in stream.get_degrees().blocks():
            records += len(block.columns[0])
            nbytes += sum(
                c.nbytes if hasattr(c, "nbytes") else 0
                for c in block.columns
            )
        return records, nbytes

    drain()  # compile + warm the transfer path
    t0 = time.perf_counter()
    records, nbytes = drain()
    dt = time.perf_counter() - t0
    return {
        "trace_records": records,
        "trace_records_per_sec": round(records / dt, 1),
        "trace_host_gbps": round(nbytes / dt / 1e9, 5),
    }


def measure_bipartiteness(args) -> dict:
    """Streaming 2-coloring fold (BipartitenessCheck hot path as the
    doubled-vertex parity union-find, ops/unionfind.py)."""
    import jax.numpy as jnp

    from gelly_streaming_tpu.io import wire
    from gelly_streaming_tpu.ops import unionfind as uf

    def make_fold(batch, width):
        def fold(state, buf):
            parent2, seen = state
            s, d = wire.unpack_edges(buf, batch, width)
            parent2 = uf.parity_union_edges(parent2, s, d, None)
            seen = seen.at[s].max(True).at[d].max(True)
            return parent2, seen

        return fold

    eps, folded, (parent2, seen) = _stream_fold(
        args.edges,
        args.vertices,
        args.batch,
        args.seed,
        make_fold,
        lambda: (
            uf.init_parity_parent(args.vertices),
            jnp.zeros((args.vertices,), bool),
        ),
    )
    ok = bool(uf.is_bipartite(parent2, seen))
    return {
        "workload": "bipartiteness",
        "edges_per_sec": round(eps, 1),
        "edges_folded": folded,
        "bipartite": ok,
    }


def measure_triangles(args) -> dict:
    """Per-window exact triangle count latency (WindowTriangles hot path via
    the Pallas MXU kernel, ops/pallas_triangles.py)."""
    from gelly_streaming_tpu.library.triangles import _pane_triangle_count
    from gelly_streaming_tpu.utils.metrics import WindowLatencyRecorder

    rng = np.random.default_rng(args.seed)
    rec = WindowLatencyRecorder()
    k = args.pane_vertices
    per_pane = max(1, args.edges // max(1, args.windows))
    # unmetered warmup pane: the first call compiles the kernel (hundreds of
    # ms), which would otherwise dominate the latency percentiles
    _pane_triangle_count(
        rng.integers(0, k, per_pane).astype(np.int32),
        rng.integers(0, k, per_pane).astype(np.int32),
    )
    total = 0
    for _ in range(args.windows):
        src = rng.integers(0, k, per_pane).astype(np.int32)
        dst = rng.integers(0, k, per_pane).astype(np.int32)
        rec.window_closed()
        total += _pane_triangle_count(src, dst)
        rec.result_emitted()
    return {
        "workload": "triangles",
        "windows": args.windows,
        "edges_per_window": per_pane,
        "pane_vertices": k,
        "triangles_total": int(total),
        "p50_window_ms": round(rec.percentile(50), 2),
        "p95_window_ms": round(rec.percentile(95), 2),
    }


def measure_spanner(args) -> dict:
    """Streaming k-spanner admission throughput (Spanner.java:71-77 hot path
    through the two-phase batch admission — vectorized meet-in-the-middle
    pre-filter + while_loop over surviving candidates)."""
    import time

    import jax

    from gelly_streaming_tpu.core.config import StreamConfig
    from gelly_streaming_tpu.core.stream import EdgeStream
    from gelly_streaming_tpu.library.spanner import Spanner

    from gelly_streaming_tpu.summaries import adjacency

    rng = np.random.default_rng(args.seed)
    src = rng.integers(0, args.vertices, args.edges).astype(np.int32)
    dst = rng.integers(0, args.vertices, args.edges).astype(np.int32)
    cfg = StreamConfig(
        vertex_capacity=args.vertices,
        max_degree=args.max_degree,
        batch_size=args.batch,
    )

    def timed(body):
        agg = Spanner(window_ms=1000, k=args.k, body=body)

        def run():
            out = (
                EdgeStream.from_arrays(src, dst, cfg).aggregate(agg).collect()
            )
            final = out[-1][0]
            jax.block_until_ready((final.nbrs, final.deg))
            return final

        run()  # compile warmup (first pane compiles filter + admission loop)
        t0 = time.perf_counter()
        final = run()
        dt = time.perf_counter() - t0
        return final, args.edges / dt

    from gelly_streaming_tpu.library.spanner import auto_body

    # the analytical crossover's pick for this (k, C, D) — the SAME helper
    # body="auto" executes (library/spanner.py), so calibration cannot
    # drift from production
    analytical_pick = auto_body(args.vertices, args.max_degree, args.k)
    if args.body != "both":
        final, eps = timed(args.body)
        out = {
            "workload": "spanner",
            "k": args.k,
            "body": args.body,
            "edges_per_sec": round(eps, 1),
            "edges_streamed": args.edges,
            "spanner_edges": int((np.asarray(final.nbrs) >= 0).sum()) // 2,
        }
        if args.body == "auto":
            out["auto_picked"] = analytical_pick
        return out
    # calibration mode (VERDICT r4 item 7): run BOTH exact bodies on the
    # same stream, verify they admit the identical spanner, and check the
    # ball_cost crossover picks the winner.  At k=2 auto runs within_two,
    # not either calibrated body — the crossover is not consulted there, so
    # crossover_correct is null rather than judging a pick auto never makes.
    final_balls, eps_balls = timed("balls")
    final_bfs, eps_bfs = timed("bfs")
    edges_balls = int((np.asarray(final_balls.nbrs) >= 0).sum()) // 2
    edges_bfs = int((np.asarray(final_bfs.nbrs) >= 0).sum()) // 2
    measured_winner = "balls" if eps_balls >= eps_bfs else "bfs"
    return {
        "workload": "spanner_body_calibration",
        "k": args.k,
        "vertices": args.vertices,
        "max_degree": args.max_degree,
        "edges_streamed": args.edges,
        "balls_eps": round(eps_balls, 1),
        "bfs_eps": round(eps_bfs, 1),
        "spanner_edges": edges_balls,
        "bodies_agree": edges_balls == edges_bfs
        and bool(
            np.array_equal(
                np.asarray(final_balls.deg), np.asarray(final_bfs.deg)
            )
        ),
        "measured_winner": measured_winner,
        "analytical_pick": analytical_pick,
        "crossover_correct": (
            measured_winner == analytical_pick
            if analytical_pick in ("balls", "bfs")
            else None
        ),
        "ball_cost": adjacency.ball_cost(args.max_degree, args.k),
        "bfs_cost": args.k * args.vertices * args.max_degree,
    }


def measure_replay(args) -> dict:
    """Wire-replay connected components: the bench.py headline through the
    product API (EdgeStream.from_wire -> aggregate(CC)), sized by argv.

    Reports the replay fold rate (transfer + device unpack + union-find),
    the producer-side pack rate, and the encoding's bytes/edge — the three
    numbers that characterize the ingest plane on any host.
    """
    import time

    import jax

    from gelly_streaming_tpu.core.config import StreamConfig
    from gelly_streaming_tpu.core.stream import EdgeStream
    from gelly_streaming_tpu.io import wire
    from gelly_streaming_tpu.library.connected_components import (
        ConnectedComponents,
    )

    rng = np.random.default_rng(args.seed)
    n = args.edges - args.edges % args.batch  # full batches: all-wire stream
    if n == 0:
        raise SystemExit("--edges must be at least one full --batch")
    src = rng.integers(0, args.vertices, n).astype(np.int32)
    dst = rng.integers(0, args.vertices, n).astype(np.int32)
    width = wire.replay_width(args.vertices, args.batch)  # CC is order-free
    t0 = time.perf_counter()
    bufs, _ = wire.pack_stream(src, dst, args.batch, width)
    pack_eps = n / (time.perf_counter() - t0)
    cfg = StreamConfig(vertex_capacity=args.vertices, batch_size=args.batch)
    agg = ConnectedComponents()
    out = EdgeStream.from_wire(bufs, args.batch, width, cfg).aggregate(agg)
    # one-buffer prefix compiles the identical fused step without replaying
    # (and re-transferring) the whole stream
    EdgeStream.from_wire(bufs[:1], args.batch, width, cfg).aggregate(
        agg
    ).collect()
    t0 = time.perf_counter()
    r = out.collect()
    jax.block_until_ready((r[-1][0].parent, r[-1][0].seen))
    dt = time.perf_counter() - t0
    nbytes = sum(b.nbytes for b in bufs)
    return {
        "workload": "wire_replay_cc",
        "edges": int(n),
        "replay_eps": round(n / dt, 1),
        "pack_eps": round(pack_eps, 1),
        "bytes_per_edge": round(nbytes / n, 2),
        "wire_gbps": round(nbytes / dt / 1e9, 3),
    }


def measure_matching(args) -> dict:
    """Centralized greedy weighted-matching net runtime — the single
    measurement the reference itself ships (CentralizedWeightedMatching.java:
    62-64 prints getNetRuntime over its input), generalized to a synthetic
    weighted stream with a reported edges/s."""
    import time

    import jax

    from gelly_streaming_tpu.core.config import StreamConfig
    from gelly_streaming_tpu.core.stream import EdgeStream
    from gelly_streaming_tpu.library.matching import CentralizedWeightedMatching

    rng = np.random.default_rng(args.seed)
    src = rng.integers(0, args.vertices, args.edges)
    dst = rng.integers(0, args.vertices, args.edges)
    w = rng.random(args.edges).astype(np.float32)
    edges = list(zip(src.tolist(), dst.tolist(), w.tolist()))
    cfg = StreamConfig(vertex_capacity=args.vertices, batch_size=args.batch)

    def run():
        algo = CentralizedWeightedMatching()
        events = algo.run(
            EdgeStream.from_collection(edges, cfg, batch_size=args.batch)
        ).collect()
        jax.block_until_ready(algo.final_state.partner)
        return algo, events

    run()  # compile warmup
    t0 = time.perf_counter()
    algo, events = run()
    net_runtime_s = time.perf_counter() - t0
    matched = int((np.asarray(algo.final_state.partner) >= 0).sum()) // 2
    return {
        "workload": "matching",
        "net_runtime_s": round(net_runtime_s, 3),
        "edges_per_sec": round(args.edges / net_runtime_s, 1),
        "edges_streamed": args.edges,
        "matched_edges": matched,
        "events": len(events),
    }


def measure_sage(args) -> dict:
    """1-layer GraphSAGE windowed message passing (BASELINE.md config row 5:
    "applyOnNeighbors over sliced windows").  Per closed window the framework
    builds degree-bucketed padded [K, D] neighborhoods, gathers [K, D, F]
    feature rows, takes the masked mean and projects through two bf16 MXU
    matmuls (library/graphsage.py sage_kernel).  Reports the end-to-end
    window rate (edges/s and embeddings/s through the product API) and the
    device-only pane latency + feature-gather bandwidth — the number
    BASELINE.md row 5 lacked (VERDICT r4 item 4).
    """
    import time

    import jax
    import jax.numpy as jnp

    from gelly_streaming_tpu.core.config import StreamConfig
    from gelly_streaming_tpu.core.stream import EdgeStream
    from gelly_streaming_tpu.core.types import EdgeDirection
    from gelly_streaming_tpu.library.graphsage import (
        GraphSAGEWindows,
        init_params,
        sage_kernel_jit,
    )

    rng = np.random.default_rng(args.seed)
    window_ms = 1000
    per_w = max(1, args.edges // max(1, args.windows))
    n = per_w * args.windows
    src = rng.integers(0, args.vertices, n)
    dst = rng.integers(0, args.vertices, n)
    ts = np.repeat(np.arange(args.windows) * window_ms, per_w)
    edges = [
        (int(s), int(d), 0.0, int(t)) for s, d, t in zip(src, dst, ts)
    ]
    features = rng.normal(size=(args.vertices, args.features)).astype(
        np.float32
    )
    params = init_params(
        jax.random.PRNGKey(args.seed), args.features, args.out_features
    )
    cfg = StreamConfig(
        vertex_capacity=args.vertices,
        max_degree=args.max_degree,
        batch_size=per_w,
    )
    sage = GraphSAGEWindows(params, features)

    def run():
        snapshot = EdgeStream.from_collection(
            edges, cfg, batch_size=per_w, with_time=True
        ).slice(window_ms, EdgeDirection.ALL)
        total_keys = windows = 0
        for keys, _ in sage.run(snapshot):
            total_keys += len(keys)
            windows += 1
        return total_keys, windows

    run()  # compile warmup (one compile per degree-bucket shape)
    t0 = time.perf_counter()
    total_keys, windows = run()
    wall = time.perf_counter() - t0

    # device-only pane latency + feature-gather volume on the same panes
    snapshot = EdgeStream.from_collection(
        edges, cfg, batch_size=per_w, with_time=True
    ).slice(window_ms, EdgeDirection.ALL)
    pane_ms: List[float] = []
    feat_rows = 0
    for hood in snapshot._neighborhood_panes():
        k = jnp.asarray(hood.keys)
        nb = jnp.asarray(hood.nbrs)
        va = jnp.asarray(hood.valid)
        jax.block_until_ready(
            sage_kernel_jit(params, sage.features, k, nb, va)
        )  # warm this shape
        t1 = time.perf_counter()
        jax.block_until_ready(
            sage_kernel_jit(params, sage.features, k, nb, va)
        )
        pane_ms.append((time.perf_counter() - t1) * 1e3)
        feat_rows += hood.keys.shape[0] * (1 + hood.nbrs.shape[1])
    device_s = sum(pane_ms) / 1e3
    train = {}
    if args.train_steps > 0:
        # training throughput: jitted unsupervised steps (optax adam) on a
        # fixed [K, D] neighborhood batch of the measured shape
        import optax

        from gelly_streaming_tpu.library import graphsage as gs

        k_rows = min(4096, args.vertices)
        keys_t = jnp.asarray(rng.integers(0, args.vertices, k_rows).astype(np.int32))
        nbrs_t = jnp.asarray(
            rng.integers(0, args.vertices, (k_rows, args.max_degree)).astype(np.int32)
        )
        valid_t = jnp.asarray(rng.random((k_rows, args.max_degree)) < 0.7)
        tx = optax.adam(1e-2)
        state = gs.sage_init_train(
            jax.random.PRNGKey(args.seed), args.features, args.out_features, tx
        )
        pos, has, neg = gs.sample_pairs(
            jax.random.PRNGKey(args.seed + 1), nbrs_t, valid_t, args.vertices
        )
        feats_j = jnp.asarray(features)
        step = jax.jit(  # graft: disable=RAWJIT — one-shot measurement closure over per-run arrays; no stable process-global cache key
            lambda st: gs.sage_train_step(
                tx, st, feats_j, keys_t, nbrs_t, valid_t, pos, has, neg
            )
        )
        state, loss0 = step(state)  # compile + first step
        jax.block_until_ready(loss0)
        t2 = time.perf_counter()
        for _ in range(args.train_steps):
            state, loss = step(state)
        jax.block_until_ready(loss)
        dt = time.perf_counter() - t2
        train = {
            "train_steps_per_sec": round(args.train_steps / dt, 2),
            "train_pairs_per_sec": round(args.train_steps * k_rows / dt, 1),
            "train_loss_first": round(float(loss0), 4),
            "train_loss_last": round(float(loss), 4),
        }
    return {
        "workload": "graphsage",
        **train,
        "edges_per_sec": round(n / wall, 1),
        "embeddings_per_sec": round(total_keys / wall, 1),
        "windows": windows,
        "features_in": args.features,
        "features_out": args.out_features,
        "device_p50_pane_ms": round(float(np.percentile(pane_ms, 50)), 3),
        "device_p95_pane_ms": round(float(np.percentile(pane_ms, 95)), 3),
        # gathered [K,(1+D),F] float32 rows per device-second: a lower bound
        # on achieved HBM read bandwidth for the gather+mean stage
        "feature_gather_gbps": round(
            feat_rows * args.features * 4 / max(device_s, 1e-9) / 1e9, 3
        ),
        "feature_elements_per_sec": round(
            feat_rows * args.features / max(device_s, 1e-9), 1
        ),
    }


def measure_pagerank(args) -> dict:
    """Windowed PageRank throughput: edges/s and windows/s through the
    product path (pane assembly -> padded scatter-add power iteration under
    while_loop), plus per-window device iteration latency."""
    import time

    import jax
    import jax.numpy as jnp

    from gelly_streaming_tpu.core.config import StreamConfig
    from gelly_streaming_tpu.core.stream import EdgeStream
    from gelly_streaming_tpu.library.pagerank import pagerank_windows
    from gelly_streaming_tpu.ops import spmv

    rng = np.random.default_rng(args.seed)
    window_ms = 1000
    per_w = max(1, args.edges // max(1, args.windows))
    n = per_w * args.windows
    src = rng.integers(0, args.vertices, n)
    dst = rng.integers(0, args.vertices, n)
    ts = np.repeat(np.arange(args.windows) * window_ms, per_w)
    edges = [(int(s), int(d), 0.0, int(t)) for s, d, t in zip(src, dst, ts)]
    cfg = StreamConfig(vertex_capacity=args.vertices, batch_size=per_w)

    def run():
        stream = EdgeStream.from_collection(
            edges, cfg, batch_size=per_w, with_time=True
        )
        return sum(
            1 for _ in pagerank_windows(stream, window_ms, tol=args.tol)
        )

    run()  # compile warmup
    t0 = time.perf_counter()
    windows = run()
    wall = time.perf_counter() - t0

    # device-only iteration latency on one resident pane
    e_pad = max(1, 1 << (per_w - 1).bit_length())
    s_a = jnp.asarray(np.resize(src[:per_w], e_pad).astype(np.int32))
    d_a = jnp.asarray(np.resize(dst[:per_w], e_pad).astype(np.int32))
    m_a = jnp.asarray(np.arange(e_pad) < per_w)
    op = spmv.prepare_pane(s_a, d_a, None, m_a, args.vertices)

    def one_pane():
        return spmv.pagerank_fixpoint(
            op, damping=0.85, tol=args.tol, max_iters=100
        )

    r, _, iters = one_pane()
    jax.block_until_ready(r)
    t1 = time.perf_counter()
    r, _, iters = one_pane()
    jax.block_until_ready(r)
    dev_ms = (time.perf_counter() - t1) * 1e3
    return {
        "workload": "pagerank",
        "edges_per_sec": round(n / wall, 1),
        "windows_per_sec": round(windows / wall, 2),
        "windows": windows,
        "device_pane_ms": round(dev_ms, 3),
        "device_iters": int(iters),
        "device_ms_per_iter": round(dev_ms / max(int(iters), 1), 4),
    }


def _measure_windowed_algo(args, name: str, run_windows, weighted: bool) -> dict:
    """Shared harness for the per-window fixed-point algorithms (sssp,
    kcore): vectorized timed-edge generation, compile warmup, one timed
    pass; ``run_windows(stream, window_ms)`` yields once per window."""
    import time

    from gelly_streaming_tpu.core.config import StreamConfig
    from gelly_streaming_tpu.core.stream import EdgeStream

    rng = np.random.default_rng(args.seed)
    window_ms = 1000
    per_w = max(1, args.edges // max(1, args.windows))
    n = per_w * args.windows
    src = rng.integers(0, args.vertices, n)
    dst = rng.integers(0, args.vertices, n)
    w = rng.integers(1, 10, n) if weighted else np.zeros(n, np.int64)
    ts = np.repeat(np.arange(args.windows) * window_ms, per_w)
    edges = [
        (int(a), int(b), float(c) if weighted else 0, int(t))
        for a, b, c, t in zip(src, dst, w, ts)
    ]
    cfg = StreamConfig(vertex_capacity=args.vertices, batch_size=per_w)

    def run():
        stream = EdgeStream.from_collection(
            edges, cfg, batch_size=per_w, with_time=True
        )
        return sum(1 for _ in run_windows(stream, window_ms))

    run()  # compile warmup
    t0 = time.perf_counter()
    windows = run()
    wall = time.perf_counter() - t0
    return {
        "workload": name,
        "edges_per_sec": round(n / wall, 1),
        "windows_per_sec": round(windows / wall, 2),
        "windows": windows,
    }


def measure_sssp(args) -> dict:
    """Windowed SSSP throughput: edges/s and windows/s through the product
    path (pane assembly -> scatter-min Bellman-Ford under while_loop)."""
    from gelly_streaming_tpu.library.sssp import sssp_windows

    return _measure_windowed_algo(
        args, "sssp", lambda st, wm: sssp_windows(st, 0, wm), weighted=True
    )


def measure_kcore(args) -> dict:
    """Windowed k-core throughput: edges/s and windows/s through the
    product path (dedupe -> bucketed neighborhoods -> h-index fixpoint)."""
    from gelly_streaming_tpu.library.kcore import core_numbers_windows

    return _measure_windowed_algo(
        args, "kcore", core_numbers_windows, weighted=False
    )


def measure_routing(args) -> dict:
    """Skew robustness of the device keyBy plane (SURVEY §7 "skewed keys"):
    route a zipf-keyed batch over the mesh with plain ``device_route`` vs
    ``device_route_salted`` and report the drop counts and per-shard
    receive imbalance.  The reference's keyBy has no answer to hot keys
    (every record of a key lands on one subtask); the salted router spreads
    each key's occurrences across shards for associative aggregation.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from gelly_streaming_tpu.parallel.mesh import (
        SHARD_AXIS,
        make_mesh,
        shard_map,
    )
    from gelly_streaming_tpu.parallel.routing import (
        device_route,
        device_route_salted,
    )

    s_n = args.shards
    if len(jax.devices()) < s_n:
        return {"skipped": f"need {s_n} devices, have {len(jax.devices())}"}
    per_shard = args.batch
    # the routers pow2-bucket their capacity (cache-stable shapes); report
    # the EFFECTIVE per-pair capacity so drops describe the real experiment
    from gelly_streaming_tpu.parallel.routing import pow2_bucket

    cap = pow2_bucket(args.capacity)
    rng = np.random.default_rng(args.seed)
    # zipf keys clipped into the vertex space: a heavy head (hub vertices)
    # plus a long tail — the power-law shape that breaks plain keyBy
    keys = np.minimum(
        rng.zipf(args.alpha, size=(s_n, per_shard)) - 1, args.vertices - 1
    ).astype(np.int32)
    dst = rng.integers(0, args.vertices, (s_n, per_shard)).astype(np.int32)
    mask = np.ones((s_n, per_shard), bool)
    mesh = make_mesh(s_n)
    spec = P(SHARD_AXIS)

    def run(router):
        def step(src, dst, m):
            r_src, r_dst, r_mask, dropped = router(
                src[0], dst[0], m[0], s_n, cap
            )
            recv = jnp.sum(r_mask.astype(jnp.int32))
            total_drop = jax.lax.psum(dropped, SHARD_AXIS)
            return recv[None], total_drop[None]

        fn = jax.jit(  # graft: disable=RAWJIT — per-mesh measurement step; a Mesh is not a stable process-global cache key
            shard_map(
                step,
                mesh=mesh,
                in_specs=(spec, spec, spec),
                out_specs=(spec, spec),
            )
        )
        recv, drop = fn(
            jnp.asarray(keys), jnp.asarray(dst), jnp.asarray(mask)
        )
        recv = np.asarray(recv)
        return int(np.asarray(drop)[0]), recv

    plain_drop, plain_recv = run(device_route)
    salt_drop, salt_recv = run(device_route_salted)

    def imbalance(recv):
        mean = recv.mean()
        return float(recv.max() / mean) if mean else 0.0

    return {
        "metric": "zipf_routed_drops",
        "shards": s_n,
        "edges": int(s_n * per_shard),
        "capacity_per_pair": cap,
        "zipf_alpha": args.alpha,
        "plain_dropped": plain_drop,
        "salted_dropped": salt_drop,
        "plain_recv_imbalance": round(imbalance(plain_recv), 2),
        "salted_recv_imbalance": round(imbalance(salt_recv), 2),
    }


def main(argv: Optional[List[str]] = None) -> None:
    from gelly_streaming_tpu.core import compile_cache

    compile_cache.use_persistent_cache()
    p = argparse.ArgumentParser(prog="measurements", description=__doc__)
    sub = p.add_subparsers(dest="workload", required=True)
    for name in ("degrees", "bipartiteness"):
        sp = sub.add_parser(name)
        sp.add_argument("--edges", type=int, default=1 << 20)
        sp.add_argument("--vertices", type=int, default=1 << 17)
        sp.add_argument("--batch", type=int, default=1 << 16)
        sp.add_argument("--seed", type=int, default=0)
        if name == "degrees":
            sp.add_argument(
                "--trace", action="store_true",
                help="also drain the full (vertex, degree) record trace "
                "through the pipelined emission plane and report records/s",
            )
    sp = sub.add_parser("triangles")
    sp.add_argument("--edges", type=int, default=1 << 17)
    sp.add_argument("--seed", type=int, default=0)
    sp.add_argument("--windows", type=int, default=8)
    sp.add_argument("--pane-vertices", type=int, default=1024)
    sp = sub.add_parser("spanner")
    sp.add_argument("--edges", type=int, default=1 << 17)
    # a saturating id space: the k=2 spanner caps near C^1.5 edges, so most
    # of the stream dies in the vectorized pre-filter — the regime the
    # two-phase admission is built for
    sp.add_argument("--vertices", type=int, default=512)
    sp.add_argument("--batch", type=int, default=1 << 14)
    sp.add_argument("--max-degree", type=int, default=64)
    sp.add_argument("--k", type=int, default=2)
    sp.add_argument(
        "--body", choices=("auto", "balls", "bfs", "both"), default="auto",
        help="per-candidate distance test; 'both' runs the calibration "
        "(balls vs bfs on the same stream, crossover check)",
    )
    sp.add_argument("--seed", type=int, default=0)
    sp = sub.add_parser("matching")
    sp.add_argument("--edges", type=int, default=1 << 16)
    sp.add_argument("--vertices", type=int, default=1 << 12)
    sp.add_argument("--batch", type=int, default=1 << 13)
    sp.add_argument("--seed", type=int, default=0)
    sp = sub.add_parser("replay")
    sp.add_argument("--edges", type=int, default=1 << 22)
    sp.add_argument("--vertices", type=int, default=1 << 20)
    sp.add_argument("--batch", type=int, default=1 << 20)
    sp.add_argument("--seed", type=int, default=0)
    sp = sub.add_parser("sage")
    sp.add_argument("--edges", type=int, default=1 << 16)
    sp.add_argument("--vertices", type=int, default=1 << 12)
    sp.add_argument("--windows", type=int, default=8)
    sp.add_argument("--features", type=int, default=128)
    sp.add_argument("--out-features", type=int, default=128)
    sp.add_argument("--max-degree", type=int, default=32)
    sp.add_argument(
        "--train-steps", type=int, default=0,
        help="also measure N jitted unsupervised training steps",
    )
    sp.add_argument("--seed", type=int, default=0)
    sp = sub.add_parser("pagerank")
    sp.add_argument("--edges", type=int, default=1 << 18)
    sp.add_argument("--vertices", type=int, default=1 << 14)
    sp.add_argument("--windows", type=int, default=8)
    sp.add_argument("--tol", type=float, default=1e-8)
    sp.add_argument("--seed", type=int, default=0)
    for name in ("sssp", "kcore"):
        sp = sub.add_parser(name)
        sp.add_argument("--edges", type=int, default=1 << 16)
        sp.add_argument("--vertices", type=int, default=1 << 12)
        sp.add_argument("--windows", type=int, default=8)
        sp.add_argument("--seed", type=int, default=0)
    sp = sub.add_parser("routing")
    sp.add_argument("--shards", type=int, default=8)
    sp.add_argument("--batch", type=int, default=256, help="edges per shard")
    sp.add_argument(
        "--capacity", type=int, default=64,
        help="per-(sender,receiver) bucket capacity",
    )
    sp.add_argument("--vertices", type=int, default=1 << 12)
    sp.add_argument("--alpha", type=float, default=1.3, help="zipf exponent")
    sp.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)
    fn = {
        "degrees": measure_degrees,
        "bipartiteness": measure_bipartiteness,
        "triangles": measure_triangles,
        "spanner": measure_spanner,
        "matching": measure_matching,
        "replay": measure_replay,
        "pagerank": measure_pagerank,
        "sssp": measure_sssp,
        "kcore": measure_kcore,
        "routing": measure_routing,
        "sage": measure_sage,
    }[args.workload]
    print(json.dumps(fn(args)))


if __name__ == "__main__":
    main(sys.argv[1:])
