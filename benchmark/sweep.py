#!/usr/bin/env python3
"""Find the knee of an open-loop cell: the highest offered rate the served
path sustains.

    python benchmark/sweep.py --workload <cell> --rates 6e6,8e6,10e6 --seconds 20 --seed <n>

Runs the cell once per rate, in one process, with the rate put in place of
the traffic file's.  A rate is sustained when, over the window, neither the
server's source queue nor the count of closed-but-unemitted windows grows
(least-squares slopes under ``QUEUE_SLOPE`` batches/s and
``WINDOW_SLOPE`` windows/s) and the pusher kept to its schedule (median
lateness under one batch interval).  Prints one JSON line per rate and a
last line naming the knee and 0.8 of it.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

QUEUE_SLOPE = 0.1
WINDOW_SLOPE = 0.05


def sustained(window: dict, rate: float, batch: int) -> bool:
    late = window.get("generator_late_ms_p50")
    return (
        window["source_queue_batches_slope_per_s"] is not None
        and window["source_queue_batches_slope_per_s"] < QUEUE_SLOPE
        and window["unemitted_windows_slope_per_s"] is not None
        and window["unemitted_windows_slope_per_s"] < WINDOW_SLOPE
        and late is not None
        and late < batch / rate * 1e3
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated edges/s")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)

    from benchmark import harness, spec
    from benchmark.run import use_checkout_cache

    bench = spec.Benchmark(ROOT)
    use_checkout_cache()
    knee = None
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        cell = bench.cell(args.workload, traffic_override={"rate_edges_per_s": rate})
        out = harness.run_cell(bench, cell, args.seed + i, args.seconds, False)
        w = out.window
        ok = sustained(w, rate, int(cell.config["batch"]))
        print(json.dumps({
            "rate_edges_per_s": rate,
            "sustained": ok,
            "correct": out.line["correct"],
            "metrics": {k: v["value"] for k, v in out.line["metrics"].items()},
            "source_queue_batches": w["source_queue_batches"],
            "source_queue_batches_slope_per_s": w["source_queue_batches_slope_per_s"],
            "unemitted_windows_slope_per_s": w["unemitted_windows_slope_per_s"],
            "generator_late_ms_p50": w.get("generator_late_ms_p50"),
            "generator_late_ms_max": w.get("generator_late_ms_max"),
            "latency_samples": w.get("latency_samples"),
            "push_call_ms_p50": w.get("push_call_ms_p50"),
        }), flush=True)
        if ok and (knee is None or rate > knee):
            knee = rate
    print(json.dumps({"knee_edges_per_s": knee,
                      "rate_at_0.8": None if knee is None else 0.8 * knee}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
