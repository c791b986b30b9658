"""``gelly-serve``: drive N concurrent streaming queries from a config.

The smallest end-to-end serving loop over the job runtime: build jobs from
a JSON config (or synthesize same-shape ones from flags), submit them all,
and print one status line per job as they progress — the console analog of
a Flink cluster dashboard's job list.

Config file shape (every field optional; flags fill a synthetic default)::

    {
      "max_jobs": 8,
      "max_state_bytes": 0,
      "checkpoint_prefix": "/ckpt/serve",   # one file per job name
      "jobs": [
        {"name": "cc-a", "query": "cc", "edges": 100000,
         "capacity": 65536, "window_edges": 8192, "weight": 1,
         "seed": 0, "checkpoint": "/tmp/ck-cc-a"},
        {"name": "deg-b", "query": "degree", "edges": 100000}
      ]
    }

Queries: ``cc`` (streaming connected components), ``degree`` (degree
distribution summary), ``edges`` (running edge count), plus the
fixed-tiny-state sketch summaries ``sketch_triangles`` / ``hll_degree`` /
``cm_heavy_hitters`` (``eps``/``delta`` knobs per job, or a ``summary``
field that swaps the sketch into any spec).  Sources are synthetic
uniform random graphs (seeded per job), streamed over the wire fast path
with running per-window emission.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

from gelly_streaming_tpu.core.config import (
    RuntimeConfig,
    ServerConfig,
    StreamConfig,
    TenantConfig,
)
from gelly_streaming_tpu.runtime.manager import JobManager


def _build_query(spec: dict):
    """(stream, descriptor) for one job spec (imports deferred: jax-heavy).

    The query catalog itself lives in runtime/server.py
    (``descriptor_for``) — ONE switch serves both the local synthetic
    driver and the serving plane's remote submits.
    """
    from gelly_streaming_tpu.core.stream import EdgeStream
    from gelly_streaming_tpu.runtime import server as server_mod

    query = spec.get("query", "cc")
    # "summary" swaps in a fixed-tiny-state sketch descriptor by kind,
    # keeping the rest of the spec unchanged — same override rule as the
    # server's submit verb
    if spec.get("summary") is not None:
        query = spec["summary"]
    n = int(spec.get("edges", 100_000))
    capacity = int(spec.get("capacity", 1 << 16))
    window_edges = int(spec.get("window_edges", 1 << 13))
    batch = min(window_edges, int(spec.get("batch", 1 << 12)))
    if window_edges % batch:
        raise SystemExit(
            f"job {spec.get('name')}: window_edges ({window_edges}) must be "
            f"a multiple of batch ({batch}) for the wire fast path"
        )
    rng = np.random.default_rng(int(spec.get("seed", 0)))
    src = rng.integers(0, capacity, n).astype(np.int32)
    dst = rng.integers(0, capacity, n).astype(np.int32)
    cfg = StreamConfig(
        vertex_capacity=capacity,
        batch_size=batch,
        ingest_window_edges=window_edges,
    )
    stream = EdgeStream.from_arrays(src, dst, cfg)
    try:
        return stream, server_mod.descriptor_for(query, spec)
    except server_mod._Refused as e:
        raise SystemExit(str(e))


def _status_lines(status: dict) -> list:
    """Render one console line per job from a ``JobManager.status()``
    mapping.  Takes the STATUS DICT (not the manager) so the server's
    ``status`` verb reuses the exact same renderer over the wire — the
    remote console and the local driver cannot drift apart."""
    lines = []
    for job_id in sorted(status["jobs"]):
        s = status["jobs"][job_id]
        lines.append(
            f"{job_id:>12s}  {s['state']:<9s} records={s['job_records']:<6d}"
            f" edges={s['job_edges']:<9d} queue={s['queue_depth']:<3d}"
            f" dispatch_s={s['job_dispatch_s']:.3f}"
            + (f" error={s['error']}" if s["error"] else "")
        )
    return lines


def main(argv=None) -> int:
    from gelly_streaming_tpu.core import compile_cache

    compile_cache.use_persistent_cache()
    parser = argparse.ArgumentParser(
        prog="gelly-serve",
        description="run N concurrent streaming-graph queries over one "
        "device pipeline (the multi-tenant job runtime)",
    )
    parser.add_argument("--config", help="JSON job config (see module doc)")
    parser.add_argument(
        "--listen",
        metavar="HOST:PORT",
        help="start the streaming RPC serving plane on this address "
        "(runtime/server.py) instead of exiting when the config jobs "
        "finish; PORT 0 binds an ephemeral port (printed on stderr). "
        "Remote clients (gelly-client / GellyClient) can then submit "
        "jobs, push edge batches, and drain.",
    )
    parser.add_argument(
        "--checkpoint-prefix",
        help="per-(tenant, job) snapshot prefix for remote jobs submitted "
        "with checkpoint: true (defaults to the config's "
        "checkpoint_prefix)",
    )
    parser.add_argument(
        "--events-path",
        help="JSONL event-journal path (overrides the config's "
        "events_path) — fleet deployments point every backend at its own "
        "journal so the standby can replay it (runtime/fleet.py)",
    )
    parser.add_argument(
        "--decode-workers",
        type=int,
        default=-1,
        help="GIL-free native decode pool size for pushed wire buffers "
        "(runtime/decode_pool.py); -1 defers to GELLY_DECODE_WORKERS, "
        "0 disables the pool (the pure-Python equivalence-oracle path)",
    )
    parser.add_argument(
        "--jobs", type=int, default=2, help="synthetic same-shape job count"
    )
    parser.add_argument(
        "--query",
        default="cc",
        choices=(
            "cc",
            "degree",
            "edges",
            "triangles",
            "sketch_triangles",
            "hll_degree",
            "cm_heavy_hitters",
        ),
        help="synthetic jobs' query (sketch_* / hll_* / cm_* kinds are "
        "the fixed-tiny-state approximate summaries)",
    )
    parser.add_argument(
        "--eps",
        type=float,
        default=None,
        help="sketch accuracy knob: relative-error target (sketch "
        "queries only; each kind has a calibrated default)",
    )
    parser.add_argument(
        "--delta",
        type=float,
        default=None,
        help="sketch accuracy knob: failure probability of the eps bound",
    )
    parser.add_argument("--edges", type=int, default=100_000)
    parser.add_argument("--capacity", type=int, default=1 << 16)
    parser.add_argument("--window-edges", type=int, default=1 << 13)
    parser.add_argument(
        "--status-interval",
        type=float,
        default=1.0,
        help="seconds between status prints (0 = only the final summary)",
    )
    args = parser.parse_args(argv)

    if args.config:
        with open(args.config) as f:
            conf = json.load(f)
    elif args.listen:
        # a bare listener starts EMPTY: remote clients submit the jobs
        conf = {"jobs": []}
    else:
        conf = {
            "jobs": [
                {
                    "name": f"{args.query}-{i}",
                    "query": args.query,
                    "edges": args.edges,
                    "capacity": args.capacity,
                    "window_edges": args.window_edges,
                    "seed": i,
                    **(
                        {"eps": args.eps} if args.eps is not None else {}
                    ),
                    **(
                        {"delta": args.delta}
                        if args.delta is not None
                        else {}
                    ),
                }
                for i in range(args.jobs)
            ]
        }
    specs = conf.get("jobs") or []
    if not specs and not args.listen:
        print("no jobs in config", file=sys.stderr)
        return 2

    # health plane (ISSUE 10): declarative SLO specs, the gauge-sampling
    # rate, and an optional JSONL event-journal path all ride the config
    from gelly_streaming_tpu.core.config import SLOSpec
    from gelly_streaming_tpu.utils import events

    try:
        slos = tuple(SLOSpec(**s) for s in conf.get("slos", []))
    except (TypeError, ValueError) as e:
        print(f"bad slos config: {e}", file=sys.stderr)
        return 2
    if args.events_path or conf.get("events_path"):
        events.configure(
            path=args.events_path or conf["events_path"],
            max_bytes=int(conf.get("events_max_bytes", 4 << 20)),
        )
    # elastic control plane (ISSUE 11): "autoscale": 1 starts the scaling
    # policy thread (or leave -1 and set GELLY_AUTOSCALE); the optional
    # "autoscale_policy" object carries AutoscalePolicy knob overrides
    from gelly_streaming_tpu.core.config import AutoscalePolicy

    try:
        policy = AutoscalePolicy(**conf.get("autoscale_policy", {}))
    except (TypeError, ValueError) as e:
        print(f"bad autoscale_policy config: {e}", file=sys.stderr)
        return 2
    rt_cfg = RuntimeConfig(
        max_jobs=int(conf.get("max_jobs", max(8, len(specs)))),
        max_state_bytes=int(conf.get("max_state_bytes", 0)),
        health_sample_s=float(conf.get("health_sample_s", 1.0)),
        slos=slos,
        slo_interval_s=float(conf.get("slo_interval_s", 0.5)),
        autoscale=int(conf.get("autoscale", -1)),
        autoscale_policy=policy,
    )

    # the serving sink: copy every record to its host leaves, as the
    # served path's results do (a real frontend would serialize them out)
    from gelly_streaming_tpu.core.output import record_leaves as sink

    # per-job checkpoints: an explicit per-job "checkpoint" wins; otherwise
    # a top-level "checkpoint_prefix" keys one file per job name (the
    # shared-prefix model, utils.checkpoint.per_job_file)
    prefix = conf.get("checkpoint_prefix")

    if args.listen:
        return _serve_listen(args, conf, specs, rt_cfg, sink, prefix)

    t0 = time.perf_counter()
    with JobManager(rt_cfg) as manager:
        for spec in specs:
            stream, descriptor = _build_query(spec)
            name = spec.get("name") or f"{spec.get('query', 'cc')}-job"
            ck = spec.get("checkpoint")
            if ck is None and prefix:
                from gelly_streaming_tpu.utils.checkpoint import per_job_file

                ck = per_job_file(prefix, name)
            manager.submit_aggregation(
                stream,
                descriptor,
                name=name,
                sink=sink,
                weight=int(spec.get("weight", 1)),
                checkpoint_path=ck,
            )
        while not manager.wait_all(timeout=args.status_interval or 0.25):
            if args.status_interval:
                for line in _status_lines(manager.status()):
                    print(line, file=sys.stderr)
                print("---", file=sys.stderr)
        elapsed = time.perf_counter() - t0
        print("final:", file=sys.stderr)
        for line in _status_lines(manager.status()):
            print(line, file=sys.stderr)
        status = manager.status()
        failed = [
            j
            for j, s in status["jobs"].items()
            if s["state"] not in ("DONE",)
        ]
        totals = status["totals"]
        print(
            f"{len(specs)} job(s) in {elapsed:.2f}s — "
            f"{totals['job_records']} records, {totals['job_edges']} edges "
            f"({totals['job_edges'] / max(elapsed, 1e-9):.0f} eps aggregate)"
        )
    return 1 if failed else 0


def _serve_listen(args, conf, specs, rt_cfg, sink, prefix) -> int:
    """``--listen`` mode: the long-lived serving plane.  Config jobs (if
    any) run as local jobs alongside remote submissions; the process stays
    up until a client's ``shutdown`` (or ``drain --shutdown``) verb."""
    from gelly_streaming_tpu.runtime.server import StreamServer

    host, _, port_s = args.listen.rpartition(":")
    if not host or not port_s.isdigit():
        print(f"--listen needs HOST:PORT, got {args.listen!r}", file=sys.stderr)
        return 2
    tenants = tuple(
        TenantConfig(
            tenant=t["tenant"],
            token=t["token"],
            max_jobs=int(t.get("max_jobs", 0)),
            max_state_bytes=int(t.get("max_state_bytes", 0)),
            max_ingest_bps=int(t.get("max_ingest_bps", 0)),
            weight=int(t.get("weight", 1)),
        )
        for t in conf.get("tenants", [])
    )
    srv_cfg = ServerConfig(
        host=host,
        port=int(port_s),
        tenants=tenants,
        checkpoint_prefix=args.checkpoint_prefix or prefix,
        decode_workers=args.decode_workers,
    )
    with JobManager(rt_cfg) as manager:
        with StreamServer(manager, srv_cfg) as server:
            # machine-readable so drivers/tests can find an ephemeral port
            print(
                f"gelly-serve: listening on {srv_cfg.host}:{server.port}",
                file=sys.stderr,
                flush=True,
            )
            for spec in specs:
                stream, descriptor = _build_query(spec)
                name = spec.get("name") or f"{spec.get('query', 'cc')}-job"
                manager.submit_aggregation(
                    stream,
                    descriptor,
                    name=name,
                    sink=sink,
                    weight=int(spec.get("weight", 1)),
                )
            while not server.wait_shutdown(args.status_interval or 5.0):
                if args.status_interval:
                    for line in _status_lines(manager.status()):
                        print(line, file=sys.stderr)
                    print("---", file=sys.stderr)
            print("gelly-serve: shutdown requested", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
