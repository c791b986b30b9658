"""Median time from a window's close to its record on the host, in ms:
per window id, the end of its ``gelly.sink.copy`` span less the start of
its ``gelly.pane.cut`` span, joined by the ``window`` stat the two carry
(not by time); windows with either span outside the traced window are
left out."""

import os
import statistics

from benchmark import spec

_spans = spec.load_module(os.path.join(os.path.dirname(__file__), "_spans.py"))


def close_to_host_ns(trace):
    """``{window: ns}`` over the windows with both spans in the trace."""
    cut = {}
    for s, _t, stats in trace["spans"].get("gelly.pane.cut", []):
        w = stats.get("window")
        if w is not None:
            cut[w] = max(s, cut.get(w, s))
    out = {}
    for _s, t, stats in trace["spans"].get("gelly.sink.copy", []):
        w = stats.get("window")
        if w in cut and t > cut[w]:
            out[w] = t - cut[w]
    return out


def read(ctx):
    trace = _spans.load(ctx)
    if trace is None:
        return None
    per_window = close_to_host_ns(trace)
    return statistics.median(per_window.values()) / 1e6 if per_window else None
