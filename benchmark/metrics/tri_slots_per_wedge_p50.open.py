"""Median, over the windows in the traced window, of the triangle count's
compare slots evaluated (padding included) per oriented wedge: the
``tri_slots`` and ``tri_wedges`` counters that tag each window's
``gelly.sink.copy`` span.  1 would be no padding and a one-step search;
the rest is the binary search's depth and the tiles' padding."""

import os
import statistics

from benchmark import spec

_spans = spec.load_module(os.path.join(os.path.dirname(__file__), "_spans.py"))


def read(ctx):
    trace = _spans.load(ctx)
    if trace is None:
        return None
    ratios = [
        stats["tri_slots"] / stats["tri_wedges"]
        for _s, _t, stats in trace["spans"].get("gelly.sink.copy", [])
        if stats.get("tri_wedges")
    ]
    return statistics.median(ratios) if ratios else None
