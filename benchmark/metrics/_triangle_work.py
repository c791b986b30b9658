"""The least HBM traffic a window's triangle count needs, whatever
implements it: the window's edges read once, two int32 ids each."""


def window_min_bytes(config: dict) -> int:
    return 8 * int(config["window_edges"])
