"""Streaming Connected Components tests.

Mirrors example/test/ConnectedComponentsTest.java (expected components at :41)
and adds: tree-combine equivalence, multi-window running merge, and the
sharded mesh data plane on the virtual 8-device CPU mesh (the MiniCluster
analog)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from gelly_streaming_tpu.core.config import StreamConfig
from gelly_streaming_tpu.core.stream import EdgeStream
from gelly_streaming_tpu.library.connected_components import (
    ConnectedComponents,
    ConnectedComponentsTree,
    sharded_cc_fixpoint,
)
from gelly_streaming_tpu.ops import unionfind as uf
from gelly_streaming_tpu.parallel.mesh import make_mesh, shard_map
from gelly_streaming_tpu.parallel.routing import host_route

CC_EDGES = [
    (1, 2),
    (1, 3),
    (2, 3),
    (1, 5),
    (6, 7),
    (8, 9),
]  # ConnectedComponentsTest.java:55-63

CFG = StreamConfig(vertex_capacity=16, max_degree=16)


def test_connected_components_golden():
    stream = EdgeStream.from_collection(CC_EDGES, CFG)
    results = stream.aggregate(ConnectedComponents(window_ms=5)).collect()
    ds = results[-1][0]
    # expected components (ConnectedComponentsTest.java:41)
    assert str(ds) == "{1=[1, 2, 3, 5], 6=[6, 7], 8=[8, 9]}"
    comps = sorted(
        ", ".join(str(v) for v in members)
        for members in ds.components().values()
    )
    assert comps == ["1, 2, 3, 5", "6, 7", "8, 9"]


def test_connected_components_tree_equivalent():
    stream = EdgeStream.from_collection(CC_EDGES, CFG)
    results = stream.aggregate(ConnectedComponentsTree(window_ms=5)).collect()
    assert str(results[-1][0]) == "{1=[1, 2, 3, 5], 6=[6, 7], 8=[8, 9]}"


def test_connected_components_multi_window_merge():
    # Event-time stream spanning three windows: the running summary merges
    # across windows (Merger semantics, SummaryAggregation.java:107-119).
    edges = [
        (1, 2, 0, 10),
        (3, 4, 0, 20),  # window 0: {1,2} {3,4}
        (2, 3, 0, 110),  # window 1 bridges -> {1,2,3,4}
        (5, 6, 0, 210),  # window 2 adds {5,6}
    ]
    stream = EdgeStream.from_collection(edges, CFG, batch_size=1, with_time=True)
    results = stream.aggregate(ConnectedComponents(window_ms=100)).collect()
    assert len(results) == 3
    assert str(results[0][0]) == "{1=[1, 2], 3=[3, 4]}"
    assert str(results[1][0]) == "{1=[1, 2, 3, 4]}"
    assert str(results[2][0]) == "{1=[1, 2, 3, 4], 5=[5, 6]}"


def test_sharded_cc_matches_single_device():
    rng = np.random.default_rng(3)
    c = 256
    m = 400
    src = rng.integers(0, c, m).astype(np.int32)
    dst = rng.integers(0, c, m).astype(np.int32)

    single = np.asarray(
        uf.union_edges(uf.init_parent(c), jnp.asarray(src), jnp.asarray(dst))
    )

    mesh = make_mesh(8)
    routed = host_route(src, dst, 8, key="src")
    fixpoint = jax.jit(
        shard_map(
            lambda p, s, d, k: sharded_cc_fixpoint(
                p, s.reshape(-1), d.reshape(-1), k.reshape(-1)
            ),
            mesh=mesh,
            in_specs=(P(), P("shards"), P("shards"), P("shards")),
            out_specs=P(),
        )
    )
    parent = fixpoint(
        uf.init_parent(c),
        jnp.asarray(routed.src),
        jnp.asarray(routed.dst),
        jnp.asarray(routed.mask),
    )
    np.testing.assert_array_equal(np.asarray(parent), single)


# ---- every producer of a CC summary hands the fold a compressed parent -------

_PRE_CAP = 64
_PRE_CFG = StreamConfig(vertex_capacity=_PRE_CAP, batch_size=64)


def _pre_edges(seed):
    rng = np.random.default_rng(seed)
    return (
        jnp.asarray(rng.integers(0, _PRE_CAP, 48), jnp.int32),
        jnp.asarray(rng.integers(0, _PRE_CAP, 48), jnp.int32),
    )


def _pre_folded(agg, seed):
    state = agg.initial_state(_PRE_CFG)
    for k in range(2):
        src, dst = _pre_edges(seed + k)
        state = agg.update(state, src, dst, None, jnp.ones((48,), bool))
    return state


def _pre_snapshot(agg, tmp_path):
    from gelly_streaming_tpu.core.aggregation import MergerState

    path = str(tmp_path / "cc.npz")
    MergerState(agg, _PRE_CFG, path, restore=False).save(_pre_folded(agg, 1))
    return MergerState(agg, _PRE_CFG, path).running


def _pre_mesh(sharded_state, seed=3):
    import dataclasses

    rng = np.random.default_rng(seed)
    src = rng.integers(0, _PRE_CAP, 300).astype(np.int32)
    dst = rng.integers(0, _PRE_CAP, 300).astype(np.int32)
    cfg = dataclasses.replace(_PRE_CFG, num_shards=8, sharded_state=sharded_state)
    recs = EdgeStream.from_arrays(src, dst, cfg).aggregate(ConnectedComponents()).collect()
    return recs[-1][0]


_PRODUCERS = {
    "initial_state": lambda agg, tmp: agg.initial_state(_PRE_CFG),
    "update": lambda agg, tmp: _pre_folded(agg, 1),
    "combine": lambda agg, tmp: agg.combine(_pre_folded(agg, 1), _pre_folded(agg, 5)),
    "snapshot_restore": _pre_snapshot,
    "sharded_emission": lambda agg, tmp: _pre_mesh(sharded_state=1),
    "mesh_combine": lambda agg, tmp: _pre_mesh(sharded_state=0),
}


@pytest.mark.parametrize("producer", sorted(_PRODUCERS))
def test_cc_summary_producers_return_a_compressed_parent(producer, tmp_path):
    """The window fold (``uf.fold_roots``) takes the running summary's
    parent as fully compressed, with no pass to make it so: every producer
    of a CC summary or record returns ``parent[parent] == parent``."""
    out = _PRODUCERS[producer](ConnectedComponents(), tmp_path)
    p = np.asarray(out.parent)
    np.testing.assert_array_equal(p[p], p)
    assert (p <= np.arange(_PRE_CAP)).all()  # each entry its component's minimum


def test_cc_snapshot_layout_is_unchanged(tmp_path):
    """A CC snapshot holds (parent, seen) under the layout it always had:
    the fold's counters stay out, a snapshot in that layout restores, and
    the counters restore as no fold's."""
    from typing import NamedTuple

    from gelly_streaming_tpu.utils.checkpoint import load_state, save_state

    class CCState(NamedTuple):  # the summary as every earlier snapshot holds it
        parent: jax.Array
        seen: jax.Array

    agg = ConnectedComponents()
    state = _pre_folded(agg, 1)
    assert int(np.asarray(state.uf_stats)[0]) > 0  # the fold hooked
    new, old = tmp_path / "new.npz", tmp_path / "old.npz"
    save_state(str(new), {"summary": state})
    save_state(str(old), {"summary": CCState(state.parent, state.seen)})
    with np.load(new) as a, np.load(old) as b:
        assert sorted(a.files) == sorted(b.files)
        for key in a.files:
            np.testing.assert_array_equal(a[key], b[key])
    got = load_state(str(old), {"summary": agg.initial_state(_PRE_CFG)})["summary"]
    np.testing.assert_array_equal(np.asarray(got.parent), np.asarray(state.parent))
    np.testing.assert_array_equal(np.asarray(got.seen), np.asarray(state.seen))
    assert np.asarray(got.uf_stats).tolist() == [0] * len(uf.UF_STATS)
