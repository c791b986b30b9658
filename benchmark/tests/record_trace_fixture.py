#!/usr/bin/env python3
"""Record the small TPU profiler trace that ``test_tracereduce.py`` reads.

    python benchmark/tests/record_trace_fixture.py

Runs on the chip: five scatter-adds with short host sleeps between them
under the profiler, so the trace has device work and idle gaps, and copies
the ``.xplane.pb`` to ``fixtures/tpu_small.xplane.pb``.
"""

import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))


def main() -> int:
    import jax
    import jax.numpy as jnp

    from benchmark import tracereduce

    if jax.devices()[0].platform != "tpu":
        print("record_trace_fixture: no TPU", file=sys.stderr)
        return 3
    step = jax.jit(lambda x, i: x.at[i].add(1))
    x = jnp.zeros(1 << 22, jnp.int32)
    idx = (jnp.arange(1 << 20) * 7919) % (1 << 22)
    x = step(x, idx).block_until_ready()
    out = tempfile.mkdtemp(dir=os.path.join(os.path.dirname(HERE), "out"))
    jax.profiler.start_trace(out)
    for _ in range(5):
        x = step(x, idx).block_until_ready()
        time.sleep(0.003)
    jax.profiler.stop_trace()
    dst = os.path.join(HERE, "fixtures", "tpu_small.xplane.pb")
    shutil.copy(tracereduce.find_xplane(out), dst)
    shutil.rmtree(out)
    print(dst, os.path.getsize(dst))
    return 0


if __name__ == "__main__":
    sys.exit(main())
