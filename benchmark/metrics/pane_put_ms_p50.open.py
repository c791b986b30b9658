"""Median time of one pane's host-to-device put, in ms: the program's
``gelly.pane.put`` spans."""

import os

from benchmark import spec

_spans = spec.load_module(os.path.join(os.path.dirname(__file__), "_spans.py"))


def read(ctx):
    trace = _spans.load(ctx)
    return None if trace is None else _spans.median_ms(trace["spans"].get("gelly.pane.put"))
