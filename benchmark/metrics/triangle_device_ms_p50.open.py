"""Median device time of one window's triangle count in the traced window,
in ms: the ``jit_gelly_fold`` "XLA Modules" events on the TPU (the served
``triangles`` query's fold is the whole count)."""

import os

from benchmark import spec

_spans = spec.load_module(os.path.join(os.path.dirname(__file__), "_spans.py"))


def read(ctx):
    trace = _spans.load(ctx)
    return None if trace is None else _spans.median_ms(trace["modules"].get("jit_gelly_fold"))
