"""The served-path benchmark: one cell of ``BENCHMARK.json`` per run.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that defines the yardstick lives here and nowhere in the
program: traffic generation, the plain references that decide
``correct``, the reduction from profiler traces to device metrics, the
table of peaks and the per-layer metric readers.  Each configuration,
traffic mix, generator, reference and metric reader is a file of its own,
found by the name ``BENCHMARK.json`` gives it (``spec.py``).
"""
