"""The least work a window needs, whatever implements it.

A window has to read its edges once (two int32 ids each) and write the
record it emits once (the configuration's ``record_bytes_per_vertex`` for
every vertex of the capacity): the bytes below which no implementation
can go, so their time at the chip's HBM bandwidth is the window's roofline.
"""


def window_min_bytes(config: dict) -> int:
    return 8 * int(config["window_edges"]) + int(config["record_bytes_per_vertex"]) * int(
        config["capacity"]
    )
