"""Median time of one record's device-to-host copy in the sink, in ms:
the program's ``gelly.sink.copy`` spans."""

import os

from benchmark import spec

_spans = spec.load_module(os.path.join(os.path.dirname(__file__), "_spans.py"))


def read(ctx):
    trace = _spans.load(ctx)
    return None if trace is None else _spans.median_ms(trace["spans"].get("gelly.sink.copy"))
