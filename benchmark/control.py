#!/usr/bin/env python3
"""Read the comparison's two sides for a cell, on several seeds.

    python benchmark/control.py --workload <cell> --seeds 1,2,3 --seconds 10

For each seed, one run of the cell as the benchmark makes it, with the
control in the program's place in the check: the reference's own
lower-precision twin (``control_states`` in the cell's reference) stands
for each sampled record, and goes through the same comparison, limit and
``correct``.  The control has to come out not correct.  Beside it is the
served records' own count on the same windows (the program's reading).
The benchmark's own runs never compute the control.  Prints one JSON line
per seed.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)

    from benchmark import harness, spec
    from benchmark.run import use_checkout_cache

    bench = spec.Benchmark(ROOT)
    cell = bench.cell(args.workload)
    use_checkout_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        out = harness.run_cell(bench, cell, seed, args.seconds, False, control=True)
        print(json.dumps({
            "seed": seed,
            "correct": out.line["correct"],
            "control_mismatched_entries": out.line["checks"]["mismatched_entries"]["value"],
            "program_mismatched_entries": out.window["served_mismatched_entries"],
            "records_compared": out.window["records_compared"],
            "reference_s": out.window["reference_s"],
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
