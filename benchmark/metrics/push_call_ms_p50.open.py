"""Median time of one ``GellyClient.push_edges`` call of one batch, in ms:
the benchmark's own host-clock span around the call, which returns once
the server has acknowledged the batch (client pack, socket, server
decode and enqueue)."""

import statistics


def read(ctx):
    calls = ctx["push_call_ms"]
    if not calls:
        return None
    return statistics.median(calls)
