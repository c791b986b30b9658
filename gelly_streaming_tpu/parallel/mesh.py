"""Device mesh and vertex-space partitioning.

This package is the TPU-native stand-in for the Flink runtime services the
reference consumes (network shuffle via keyBy, broadcast, all-window gather,
iteration feedback — SURVEY.md §2.3/§5.8, pom.xml:38-63): a 1-D
``jax.sharding.Mesh`` over a ``shards`` axis carries the data plane; vertex
ownership is ``vertex_id % num_shards`` over the dense interned id space
(the analog of Flink's key-group hashing).
"""

from __future__ import annotations

import time
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from gelly_streaming_tpu.utils import tracing

SHARD_AXIS = "shards"


def make_mesh(num_shards: Optional[int] = None, devices: Optional[Sequence] = None) -> Mesh:
    """1-D mesh over the first ``num_shards`` devices (default: all)."""
    t0 = time.perf_counter()
    devs = list(devices if devices is not None else jax.devices())
    n = num_shards or len(devs)
    if n > len(devs):
        raise ValueError(f"requested {n} shards but only {len(devs)} devices")
    mesh = Mesh(np.array(devs[:n]), (SHARD_AXIS,))
    # setup-time observability: when tracing is on, the topology a run
    # built (and what it cost) lands in the flight recorder next to the
    # window spans — the first thing a mesh-plane post-mortem checks
    tracing.record_event(
        "mesh",
        "build",
        t0,
        shards=n,
        platform=devs[0].platform if devs else "none",
    )
    return mesh


def owner_of(vertex_ids: np.ndarray, num_shards: int) -> np.ndarray:
    """Owning shard of each vertex (dense interned ids: modulo spreads load)."""
    return vertex_ids % num_shards


def mesh_cache_key(mesh: Mesh):
    """A process-stable hashable identity for a mesh, for executable-cache keys.

    A ``Mesh`` object itself hashes by identity semantics that are not
    guaranteed stable across re-created meshes on every jax version, so
    kernels compiled per mesh key on the raw object could silently retrace
    when a runner is rebuilt.  Device ids + platform + axis names ARE stable
    for the same topology within a process, so two ``make_mesh(n)`` calls
    resolve to the same executables (core/compile_cache.py keys the
    mesh-runner sharded steps on this).
    """
    return (
        tuple((d.platform, d.id) for d in mesh.devices.flat),
        tuple(mesh.axis_names),
    )


def block_rows(capacity: int, num_shards: int) -> int:
    """Rows of one owner block of a [capacity] modulo-sharded state."""
    if capacity % num_shards:
        raise ValueError(
            f"vertex capacity {capacity} must divide over {num_shards} shards"
        )
    return capacity // num_shards


def shard_map(fn, mesh: Mesh, in_specs, out_specs):
    """jax.shard_map with the replication (vma) check disabled.

    The framework's kernels run data-dependent ``while_loop``s whose carries
    change mesh-variance mid-loop (invariant labels become shard-varying after
    hooking local edges, then invariant again after pmin) — valid SPMD that the
    static vma checker rejects.
    """
    return jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )


def sharded(mesh: Mesh):
    """Sharding for arrays split on their leading axis."""
    return NamedSharding(mesh, P(SHARD_AXIS))


def replicated(mesh: Mesh):
    return NamedSharding(mesh, P())
