"""Shared test fixtures: the reference's sample graph and golden helpers.

The 7-edge / 5-vertex fixture mirrors GraphStreamTestUtils.getLongLongEdges
(test/GraphStreamTestUtils.java:55-68); golden comparisons are order-insensitive
like Flink's compareResultsByLinesInMemory.
"""

from gelly_streaming_tpu.core.config import StreamConfig
from gelly_streaming_tpu.core.stream import EdgeStream

LONG_LONG_EDGES = [
    (1, 2, 12),
    (1, 3, 13),
    (2, 3, 23),
    (3, 4, 34),
    (3, 5, 35),
    (4, 5, 45),
    (5, 1, 51),
]

CFG = StreamConfig(vertex_capacity=16, max_degree=16, batch_size=4)


def long_long_stream(batch_size=None, cfg=CFG):
    return EdgeStream.from_collection(
        LONG_LONG_EDGES, cfg, batch_size=batch_size
    )


def assert_lines(output_lines, expected: str):
    """Order-insensitive golden compare (compareResultsByLinesInMemory analog)."""
    got = sorted(output_lines)
    want = sorted(l for l in expected.strip().split("\n") if l)
    assert got == want, f"\n got: {got}\nwant: {want}"


def host_min_labels(capacity, src, dst):
    """Reference union-find (min-root labels) for cross-checking CC kernels."""
    import numpy as np

    parent = np.arange(capacity)

    def find(v):
        while parent[v] != v:
            parent[v] = parent[parent[v]]
            v = parent[v]
        return v

    for a, b in zip(src, dst):
        ra, rb = find(int(a)), find(int(b))
        if ra != rb:
            parent[max(ra, rb)] = min(ra, rb)
    return np.array([find(v) for v in range(capacity)])


def kronecker(scale: int, edges: int, seed: int):
    """Graph 500 Kronecker edges (A/B/C = 0.57/0.19/0.19), labels permuted."""
    import numpy as np

    rng = np.random.default_rng(seed)
    src = np.zeros(edges, np.int64)
    dst = np.zeros(edges, np.int64)
    for level in range(scale):
        u = rng.random(edges)
        src |= (u >= 0.76).astype(np.int64) << level  # quadrants C and D
        dst |= (((u >= 0.57) & (u < 0.76)) | (u >= 0.95)).astype(np.int64) << level
    perm = rng.permutation(1 << scale)
    return perm[src].astype(np.int32), perm[dst].astype(np.int32)
