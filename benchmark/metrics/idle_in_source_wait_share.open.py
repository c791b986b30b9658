"""Share of the device's idle time, in percent, in which the scheduler
waited on the job's source for the next push: the idle gaps of the busy
union against the program's ``gelly.source.wait`` spans."""

import os

from benchmark import spec

_spans = spec.load_module(os.path.join(os.path.dirname(__file__), "_spans.py"))


def read(ctx):
    trace = _spans.load(ctx)
    return None if trace is None else _spans.idle_share(trace, "gelly.source.wait")
