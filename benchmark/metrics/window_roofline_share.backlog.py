"""A window's share of its HBM roofline, in percent: the least bytes a
window needs (work.window_min_bytes) at the chip's HBM bandwidth, over
the device time a window took."""

import os

from benchmark import spec, work

_device = spec.load_module(os.path.join(os.path.dirname(__file__), "_device.py"))


def read(ctx):
    ms = _device.ms_per_window(ctx)
    if ms is None or ctx["peaks"] is None:
        return None
    least_s = work.window_min_bytes(ctx["cell"].config) / (ctx["peaks"]["hbm_gbps"] * 1e9)
    return least_s / (ms / 1e3) * 100.0
