"""A window's triangle count as a share of its HBM roofline, in percent:
the least bytes the count needs (``_triangle_work.window_min_bytes``) at
the chip's HBM bandwidth, over ``triangle_device_ms_p50.open``."""

import os

from benchmark import spec

_here = os.path.dirname(__file__)
_work = spec.load_module(os.path.join(_here, "_triangle_work.py"))
_device_ms = spec.load_module(os.path.join(_here, "triangle_device_ms_p50.open.py"))


def read(ctx):
    ms = _device_ms.read(ctx)
    if not ms or ctx.get("peaks") is None:
        return None
    least_s = _work.window_min_bytes(ctx["cell"].config) / (ctx["peaks"]["hbm_gbps"] * 1e9)
    return least_s / (ms / 1e3) * 100.0
