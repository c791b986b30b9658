"""Share of the traced window, in percent, in which the device ran no
XLA module or op."""


def read(ctx):
    busy_s = ctx["trace"]["busy_s"]
    if busy_s <= 0 or ctx["window_s"] <= 0:
        return None
    return (1.0 - busy_s / ctx["window_s"]) * 100.0
