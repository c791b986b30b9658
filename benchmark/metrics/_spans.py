"""The program's spans and the device's events in a cell's traced run,
shared by the span readers beside it.

The harness writes a cell's profile under ``<bench_dir>/out/trace/<cell>/``;
``load`` parses the newest ``.xplane.pb`` there once per run (cached by
path) into:

* ``spans[name]``: the program's ``gelly.*`` annotations from the
  profiler's host planes (``utils/tracing.py`` ``SPANS``), as
  ``(start_ns, end_ns, stats)`` with stats such as ``job`` and ``window``;
* ``modules[name]``: the ``/device:TPU:0`` "XLA Modules" events by short
  name (``jit_gelly_fold``), as ``(start_ns, end_ns)``;
* ``gaps``: the device's idle gaps, the holes in the busy union over
  Modules and Ops that ``tracereduce`` takes.

Only events wholly inside the traced window count, the window running from
the device's first event to its last: a span the profile cut at either end
is left out.  A run with no trace, or a trace with no device work, loads as
None, and a span or module that is absent reads as an empty list, so a
program without the spans gives every reader None.
"""

import os
import statistics
from collections import defaultdict

from benchmark import tracereduce

_CACHE: dict = {}


def load(ctx):
    cell = ctx["cell"]
    try:
        path = tracereduce.find_xplane(
            os.path.join(cell.bench_dir, "out", "trace", cell.name)
        )
    except FileNotFoundError:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _CACHE:
        _CACHE.clear()
        _CACHE[key] = _parse(path)
    return _CACHE[key]


def _parse(path):
    from jax.profiler import ProfileData

    profile = ProfileData.from_file(path)
    planes = [p for p in profile.planes if p.name == tracereduce.DEVICE]
    if not planes:
        return None
    lines = {line.name: list(line.events) for line in planes[0].lines}
    modules = defaultdict(list)
    busy = []
    for e in lines.get("XLA Modules", []):
        s, t = e.start_ns, e.start_ns + e.duration_ns
        modules[tracereduce._short(e.name)].append((s, t))
        busy.append((s, t))
    busy += [(e.start_ns, e.start_ns + e.duration_ns) for e in lines.get("XLA Ops", [])]
    if not busy:
        return None
    merged = tracereduce.union(busy)
    lo, hi = merged[0][0], merged[-1][1]
    spans = defaultdict(list)
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if not e.name.startswith("gelly."):
                    continue
                s, t = e.start_ns, e.start_ns + e.duration_ns
                if lo <= s and t <= hi:
                    spans[e.name].append((s, t, dict(e.stats)))
    return {
        "spans": spans,
        "modules": modules,
        "gaps": [(merged[j][1], merged[j + 1][0]) for j in range(len(merged) - 1)],
    }


def median_ms(intervals):
    """Median duration of ``(start_ns, end_ns, ...)`` intervals, in ms."""
    if not intervals:
        return None
    return statistics.median(i[1] - i[0] for i in intervals) / 1e6


def idle_share(trace, name):
    """Percent of the device's idle time that spans ``name`` overlap."""
    gaps = trace["gaps"]
    idle = sum(t - s for s, t in gaps)
    covered = tracereduce.union((s, t) for s, t, _ in trace["spans"].get(name, []))
    if idle <= 0 or not covered:
        return None
    over, j = 0, 0
    for s, t in gaps:
        while j < len(covered) and covered[j][1] <= s:
            j += 1
        k = j
        while k < len(covered) and covered[k][0] < t:
            over += min(t, covered[k][1]) - max(s, covered[k][0])
            k += 1
    return over / idle * 100.0
