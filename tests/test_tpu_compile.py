"""Compiles for a described TPU v5e (no chip attached).

The chip's compiler is installed here and compiles for a topology that is
described, not attached, so what the chip would refuse — a kernel over its
VMEM, a program over its HBM, a mesh step that cannot be partitioned — fails
here at no chip time.  Nothing runs: these pin that the main path's kernels
compile at the sizes ``chip_smoke.py`` drives, not their results.

The topology is described inside a module-scoped fixture (never at import:
only one process may load the TPU library, and every xdist worker imports
this file), with the persistent compile cache off around the compiles.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gelly_streaming_tpu.ops import pallas_triangles as pt

pytestmark = pytest.mark.timeout_cap(240)

CAPACITY = 1 << 23  # chip_smoke phase A's id space
WINDOW = 1 << 21  # ... and its served window
HBM_BYTES = 16 << 30  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_pallas_kernel_compiles_at_max_k(one_chip):
    lowered = pt._count_halves.lower(
        _shape((pt.MAX_K, pt.MAX_K), jnp.bool_, one_chip), interpret=False
    )
    assert "tpu_custom_call" in lowered.as_text()
    lowered.compile()


def test_pallas_kernel_above_max_k_overflows_vmem(one_chip):
    """MAX_K is the largest K the chip compiles: the next power of two
    runs out of VMEM in Mosaic."""
    k = 2 * pt.MAX_K
    with pytest.raises(Exception, match="(?i)vmem"):
        pt._count_halves.lower(
            _shape((k, k), jnp.bool_, one_chip), interpret=False
        ).compile()


def test_check_k_refuses_above_max_k():
    pt._check_k(pt.MAX_K)
    with pytest.raises(ValueError, match="exceeds"):
        pt._check_k(pt.MAX_K + pt.TILE)
    k = pt.MAX_K + pt.TILE
    with pytest.raises(ValueError, match="exceeds"):
        pt.triangle_count_dense(np.zeros((k, k), bool), interpret=True)


def test_cc_fold_compiles_at_capacity_2_23(one_chip):
    """The served CC window fold (descriptor update, donated state) at
    chip_smoke's capacity fits one chip."""
    from gelly_streaming_tpu.library.connected_components import (
        CCState,
        ConnectedComponents,
    )

    agg = ConnectedComponents()
    state = CCState(
        parent=_shape((CAPACITY,), jnp.int32, one_chip),
        seen=_shape((CAPACITY,), jnp.bool_, one_chip),
    )
    edges = _shape((WINDOW,), jnp.int32, one_chip)
    mask = _shape((WINDOW,), jnp.bool_, one_chip)
    compiled = (
        jax.jit(lambda s, a, b, m: agg.update(s, a, b, None, m), donate_argnums=0)
        .lower(state, edges, edges, mask)
        .compile()
    )
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES


def test_sharded_cc_step_compiles_on_four_chips(topo):
    """The owner-sharded served pane step (route -> fold -> delta exchange
    -> gather in one shard_map) at capacity 2^23 over a 2x2 mesh."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from gelly_streaming_tpu.core.aggregation import MeshAggregationRunner
    from gelly_streaming_tpu.core.config import StreamConfig
    from gelly_streaming_tpu.io import wire
    from gelly_streaming_tpu.library.connected_components import (
        ConnectedComponents,
    )
    from gelly_streaming_tpu.parallel.mesh import SHARD_AXIS

    shards = 4
    mesh = Mesh(np.array(topo.devices[:shards]), (SHARD_AXIS,))
    agg = ConnectedComponents()
    cfg = StreamConfig(
        vertex_capacity=CAPACITY,
        batch_size=WINDOW // 2,
        ingest_window_edges=WINDOW,
        num_shards=shards,
    )
    runner = MeshAggregationRunner(agg, mesh=mesh)
    spec = agg.sharded_state_spec(cfg)
    cap = runner._pane_cap(WINDOW)
    width = agg._wire_width(cfg)
    ctx = runner._shard_ctx(cfg, spec, shards * cap)
    step = runner._pane_step_sharded(cfg, spec, cap, ("wire", width), ctx)
    split = NamedSharding(mesh, P(SHARD_AXIS))
    blocks = jax.tree.map(
        lambda a: _shape(a.shape, a.dtype, split),
        jax.eval_shape(lambda: spec.initial_shard_state(cfg, shards)),
    )
    rows = _shape((shards, wire.wire_nbytes(cap, width)), jnp.uint8, split)
    counts = _shape((shards,), jnp.int32, split)
    compiled = step.lower(blocks, rows, counts).compile()
    text = compiled.as_text()
    assert "all-to-all" in text  # the delta exchange crosses chips
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES


def test_triangle_count_compiles_as_the_served_fold(one_chip):
    """The served ``triangles`` fold (ops/oriented_triangles.py through
    ``WindowTriangleCount``'s ``jit_gelly_fold``): sorts, scatters, gathers
    and the wedge loop's dynamic trip counts lower for the chip.  Compiled
    at 2^12 entries: at the served 2^21 the TPU's sorts take ~2 min of host
    compile time (PERF.md §7), too long for this suite."""
    from gelly_streaming_tpu.library.triangles import WindowTriangleCount
    from gelly_streaming_tpu.ops import oriented_triangles as ot

    desc = WindowTriangleCount()
    n = 1 << 12
    state = _shape((len(ot.STATS),), jnp.int32, one_chip)
    ids = _shape((n,), jnp.int32, one_chip)
    mask = _shape((n,), jnp.bool_, one_chip)
    lowered = jax.jit(desc.update).lower(state, ids, ids, None, mask)
    assert "while" in lowered.as_text()
    compiled = lowered.compile()
    assert compiled.memory_analysis().temp_size_in_bytes < HBM_BYTES
