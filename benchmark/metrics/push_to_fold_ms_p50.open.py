"""Median push-to-fold time over the window, in ms: the server's own
``push_to_fold_ms`` histogram (how long a pushed batch sat in the job's
source queue), the buckets recorded inside the window, read as the
program reads its quantiles (the nearest-rank bucket's lower bound)."""

import math


def read(ctx):
    buckets = ctx["hist_window"].get("push_to_fold_ms") or {}
    total = sum(buckets.values())
    if total <= 0:
        return None
    rank = max(1, math.ceil(0.5 * total))
    seen = 0
    for lower in sorted(buckets):
        seen += buckets[lower]
        if seen >= rank:
            return float(lower)
    return None
