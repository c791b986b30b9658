#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once and print its result line.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared with the plain
reference beside its limit.  Earlier lines give the parts of set-up and
the window's counts.  The checks are also the last lines of standard
error.  Without a TPU, or with fewer chips than the cell asks for, the run
exits 3 and prints no result.

JAX's persistent compilation cache lives at ``<checkout>/.jax_cache``, so
only the first run in a checkout compiles.
"""

import time

T_START_NS = time.monotonic_ns()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def use_checkout_cache() -> None:
    """Every compiled program goes to ``<checkout>/.jax_cache``; the
    program's own cache set-up honours ``JAX_COMPILATION_CACHE_DIR``."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    import jax

    jax.config.update("jax_compilation_cache_dir", os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def report(line: dict) -> None:
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)


def main(argv=None) -> int:
    args = parse(argv)
    from benchmark import harness, spec

    bench = spec.Benchmark(ROOT)
    cell = bench.cell(args.workload)
    use_checkout_cache()
    try:
        out = harness.run_cell(
            bench, cell, args.seed, args.seconds, bool(args.trace),
            t_start_ns=T_START_NS,
        )
    except harness.NoChip as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 3
    print(json.dumps({"setup_parts_s": out.setup_parts}))
    print(json.dumps({"window": out.window}))
    report(out.line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
