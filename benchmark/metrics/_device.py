"""Device time per window, shared by the per-cell readers beside it."""


def ms_per_window(ctx):
    """The device's busy share of the traced window times the time
    between records there (from the client's receipt stamps); None where
    the trace holds no device work or fewer than two records came."""
    busy_s = ctx["trace"]["busy_s"]
    per_window_s = ctx["seconds_per_window"]
    if busy_s <= 0 or not per_window_s or ctx["window_s"] <= 0:
        return None
    return busy_s / ctx["window_s"] * per_window_s * 1e3
