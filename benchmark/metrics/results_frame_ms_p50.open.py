"""Median time to frame one results reply that carries records, in ms:
the program's ``gelly.results.frame`` spans (leaf metadata and the
payload join) with at least one record."""

import os

from benchmark import spec

_spans = spec.load_module(os.path.join(os.path.dirname(__file__), "_spans.py"))


def read(ctx):
    trace = _spans.load(ctx)
    if trace is None:
        return None
    frames = [f for f in trace["spans"].get("gelly.results.frame", []) if f[2].get("records", 0) >= 1]
    return _spans.median_ms(frames)
