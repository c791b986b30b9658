"""Reduce a JAX profiler trace to the device numbers the benchmark reports.

Busy time is the union of the intervals in which the device ran an XLA
module or op (the busy-union of ``chip_smoke._busy_seconds``, over the
"XLA Modules" line it read, and the "XLA Ops" line beside it): a union
counts overlapping events once and does not depend on module names.  Idle
gaps are the holes in that union; each is labelled by the host event that
overlaps it most in the profiler's host plane.
"""

from __future__ import annotations

import bisect
import os
from collections import defaultdict

DEVICE = "/device:TPU:0"
TOP = 10


def find_xplane(trace_dir: str) -> str:
    """The newest ``.xplane.pb`` under ``trace_dir``."""
    paths = []
    for root, _dirs, files in os.walk(trace_dir):
        paths += [os.path.join(root, f) for f in files if f.endswith(".xplane.pb")]
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def union(spans) -> list:
    """Merge ``(start, end)`` spans into disjoint sorted intervals."""
    merged: list = []
    for s, e in sorted(spans):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def _short(name: str) -> str:
    """``jit_combine(123)`` -> ``jit_combine``; ``%while.2 = (...) ...`` ->
    ``while.2``."""
    return name.split(" = ")[0].lstrip("%").split("(")[0]


def reduce_profile(profile) -> dict:
    """Busy seconds of the one device the cells use, the device ops that
    took most time (named ``module/op``), and the longest idle gaps, each
    with the host event that overlaps it most."""
    planes = [p for p in profile.planes if p.name == DEVICE]
    if not planes:
        return {"busy_s": 0.0, "device_ops": [], "idle_gaps": []}
    lines = {line.name: list(line.events) for line in planes[0].lines}
    modules = sorted(
        (e.start_ns, e.start_ns + e.duration_ns, _short(e.name))
        for e in lines.get("XLA Modules", [])
    )
    starts = [m[0] for m in modules]
    spans = [m[:2] for m in modules]
    op_s: dict = defaultdict(float)
    for e in lines.get("XLA Ops", []):
        spans.append((e.start_ns, e.start_ns + e.duration_ns))
        j = bisect.bisect_right(starts, e.start_ns) - 1
        module = modules[j][2] if j >= 0 and e.start_ns < modules[j][1] else "?"
        op_s[f"{module}/{_short(e.name)}"] += e.duration_ns / 1e9
    merged = union(spans)
    gaps = [(merged[j][1], merged[j + 1][0]) for j in range(len(merged) - 1)]
    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "busy_s": sum(e - s for s, e in merged) / 1e9,
        "device_ops": sorted(op_s.items(), key=lambda kv: -kv[1])[:TOP],
        "idle_gaps": [
            [_host_label(profile, g), (g[1] - g[0]) / 1e9] for g in gaps[:TOP]
        ],
    }


def _host_label(profile, gap) -> str:
    """``thread:event`` of the host event with the most overlap with
    ``gap``, leaving out events over a second long and over fifty times
    the gap (session-long scopes say nothing about one gap)."""
    g0, g1 = gap
    overlap: dict = defaultdict(int)
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                s, t = e.start_ns, e.start_ns + e.duration_ns
                if t <= g0 or s >= g1:
                    continue
                if e.duration_ns > 50 * (g1 - g0) and e.duration_ns > 1e9:
                    continue
                overlap[f"{line.name}:{_short(e.name)}"] += min(t, g1) - max(s, g0)
    if not overlap:
        return "no host event"
    return max(overlap.items(), key=lambda kv: kv[1])[0]


def reduce_dir(trace_dir: str) -> dict:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(find_xplane(trace_dir)))
