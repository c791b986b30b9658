"""Longest ``GellyClient.push_edges`` call of one batch in the window, in
ms: the same host-clock span as ``push_call_ms_p50.open``.  A stall of the
server's acknowledgements shows here first; the open-loop pusher starts
every later batch late until the stall ends."""


def read(ctx):
    calls = ctx["push_call_ms"]
    if not calls:
        return None
    return max(calls)
