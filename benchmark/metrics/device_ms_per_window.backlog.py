"""Device ms per window under a backlog: the union of device-op intervals
over the traced window, divided by the records emitted in it."""

import os

from benchmark import spec

_device = spec.load_module(os.path.join(os.path.dirname(__file__), "_device.py"))


def read(ctx):
    return _device.ms_per_window(ctx)
