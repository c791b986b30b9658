"""Pallas TPU kernel: dense-adjacency triangle counting on the MXU.

The reference's windowed triangle count shuffles O(d^2) candidate wedges per
vertex through the network and joins them against real edges
(example/WindowTriangles.java:82-139).  The TPU-first formulation is algebraic:
for a pane's undirected simple adjacency matrix A (zero diagonal),

    triangles = sum(A * (A @ A)) / 6

since (A @ A)[u, v] counts common neighbors of u and v, and each triangle is
seen once per ordered adjacent pair.  The FLOPs live in A @ A — exactly what
the MXU's systolic array is for — and the elementwise mask-and-reduce fuses on
top.  This kernel tiles the computation so A^2 is never materialized in HBM:
for each (i, j) output tile it accumulates A[i,:] @ A[:,j] in VMEM, masks by
the A[i,j] tile, and adds the tile's (exact, int32) partial count into an SMEM
scalar across the sequential grid.

Inputs are bfloat16 0/1 values: exact in the MXU with float32 accumulation
(products are 0/1, sums < 2^24), so the count is exact.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE = 128  # MXU-native tile edge


_LO_BITS = 15  # running totals are split into low/high halves (see _kernel)


def _kernel(a_row_ref, a_col_ref, a_tile_ref, out_ref):
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when((i == 0) & (j == 0))
    def _():
        out_ref[0, 0] = jnp.int32(0)
        out_ref[0, 1] = jnp.int32(0)

    # Common-neighbor counts for this output tile: [TILE, K] @ [K, TILE].
    acc = jnp.dot(
        a_row_ref[:], a_col_ref[:], preferred_element_type=jnp.float32
    )
    # Mask by adjacency and reduce exactly.  Each float32 entry is an integer
    # < K <= MAX_K, hence exact; the per-tile sum c is < TILE*TILE*K < 2^31,
    # so converting entries to int32 before the reduce keeps c exact too.  A
    # single running int32 total would wrap beyond ~3.6e8 triangles, and
    # per-tile outputs (the obvious fix) stall the Mosaic pipeline ~8x, so the
    # total is accumulated as a low/high pair: lo += c mod 2^15, hi += c >> 15,
    # recombined on the host in int64.  Both stay < 2^31 for K <= MAX_K.
    masked = acc * a_tile_ref[:].astype(jnp.float32)
    c = jnp.sum(masked.astype(jnp.int32))
    out_ref[0, 0] += c & ((1 << _LO_BITS) - 1)
    out_ref[0, 1] += c >> _LO_BITS


# The largest K the chip compiles: at 2^14 the double-buffered [TILE, K] and
# [K, TILE] bf16 blocks overflow a v5e core's VMEM and Mosaic refuses the
# kernel (tests/test_tpu_compile.py pins both sides).  Exactness holds with
# room: lo <= ntiles * 2^15 and hi <= ntiles * (TILE*TILE*K >> 15) stay far
# below 2^31 at ntiles = (K / TILE)^2 = 2^12.
MAX_K = 1 << 13


@functools.partial(jax.jit, static_argnames=("interpret",))  # graft: disable=RAWJIT — module-scope decorator: one process-global jit per import, no per-call closure to key a cache entry on
def _count_halves(adj: jax.Array, *, interpret: bool = False) -> jax.Array:
    k = adj.shape[0]
    a = adj.astype(jnp.bfloat16)
    grid = (k // TILE, k // TILE)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((TILE, k), lambda i, j: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((k, TILE), lambda i, j: (0, j), memory_space=pltpu.VMEM),
            pl.BlockSpec((TILE, TILE), lambda i, j: (i, j), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(
            (1, 2), lambda i, j: (0, 0), memory_space=pltpu.SMEM
        ),
        out_shape=jax.ShapeDtypeStruct((1, 2), jnp.int32),
        interpret=interpret,
    )(a, a, a)


def _triangles_from_halves(halves) -> int:
    """Recombine the kernel's low/high running totals into the count."""
    halves = np.asarray(halves).astype(np.int64)
    return int((halves[0, 0] + (halves[0, 1] << _LO_BITS)) // 6)


def _check_k(k: int) -> None:
    if k > MAX_K:
        raise ValueError(f"K={k} exceeds the kernel's bound {MAX_K}")


def triangle_count_dense(adj, *, interpret: bool = False) -> int:
    """Exact triangle count of a dense 0/1 adjacency matrix (zero diagonal).

    ``adj`` is [K, K] with K a multiple of TILE (pad with zeros — isolated
    padding vertices contribute nothing) and K <= MAX_K.
    """
    k = adj.shape[0]
    if adj.shape != (k, k) or k % TILE != 0:
        raise ValueError(f"adjacency must be square with K % {TILE} == 0, got {adj.shape}")
    _check_k(k)
    return _triangles_from_halves(_count_halves(adj, interpret=interpret))


def _use_interpret() -> bool:
    """Compiled Mosaic kernel on the TPU; the Pallas interpreter on the CPU
    (tests).  Any other backend is an error, never a silent slow path."""
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(f"no triangle kernel for backend {backend!r}")
    return backend == "cpu"


def _adjacency_count(u, v, ok, k: int, interpret: bool):
    """Scatter a (possibly duplicated, uncanonical) edge list into a dense
    [k, k] adjacency and run the MXU kernel; the scatter dedups for free."""
    uu = jnp.where(ok, u, 0)
    vv = jnp.where(ok, v, 0)
    adj = jnp.zeros((k, k), jnp.bool_)
    adj = adj.at[uu, vv].max(ok)
    adj = adj.at[vv, uu].max(ok)
    return _count_halves(adj, interpret=interpret)


_ID_BITS = (MAX_K - 1).bit_length()  # a (u, v) pair packs into one uint32


@functools.partial(jax.jit, static_argnames=("k", "interpret"))  # graft: disable=RAWJIT — module-scope decorator: one process-global jit per import, no per-call closure to key a cache entry on
def _count_from_packed(w, n, k: int, interpret: bool):
    """Device-side pane count from the 4 B/edge packed pane wire format.

    ``w``: uint32[cap] edge words (u | v << _ID_BITS), ``n``: traced edge
    count (entries past n are padding — masked on device, so varying pane
    sizes share one compiled kernel per pow2 capacity).
    """
    u = (w & ((1 << _ID_BITS) - 1)).astype(jnp.int32)
    v = (w >> _ID_BITS).astype(jnp.int32)
    ok = (jnp.arange(w.shape[0], dtype=jnp.int32) < n) & (u != v)
    return _adjacency_count(u, v, ok, k, interpret)


def pack_pane(u: np.ndarray, v: np.ndarray, mask=None):
    """Host-side pane pack: (u, v) -> (uint32[cap] edge words, n) at
    4 B/edge, capacity padded to the next power of two so varying pane sizes
    reuse a bounded set of compiled kernels.  Masked-out edges are dropped
    here (the wire ships only live edges)."""
    if mask is not None:
        u, v = np.asarray(u)[mask], np.asarray(v)[mask]
    n = len(u)
    if n:
        u = np.asarray(u)
        v = np.asarray(v)
        # u packs into the low _ID_BITS; a larger id would silently bleed
        # into v's bits (corrupted edges, no error) — current callers bound
        # ids by the dense-pane cap, but guard future callers loudly
        if int(min(u.min(), v.min())) < 0 or int(max(u.max(), v.max())) >= (
            1 << _ID_BITS
        ):
            raise ValueError(
                f"pack_pane ids must be in [0, 2^{_ID_BITS}); got "
                f"[{int(min(u.min(), v.min()))}, "
                f"{int(max(u.max(), v.max()))}]"
            )
    n_cap = max(1, 1 << (n - 1).bit_length()) if n else 1
    w = np.zeros((n_cap,), np.uint32)
    w[:n] = u.astype(np.uint32) | (v.astype(np.uint32) << _ID_BITS)
    return w, np.int32(n)


def pane_triangles_submit_packed(w, n, num_vertices: int):
    """Dispatch a packed pane (from ``pack_pane``; host OR device-resident
    arrays) without waiting.  Device-resident inputs let a prefetching
    caller overlap the pane upload with the previous pane's compute."""
    k = max(TILE, ((num_vertices + TILE - 1) // TILE) * TILE)
    _check_k(k)
    halves = _count_from_packed(w, n, k, _use_interpret())
    try:
        halves.copy_to_host_async()  # start the readback behind the compute
    except AttributeError:
        pass
    return halves


def pane_triangles_submit(u: np.ndarray, v: np.ndarray, num_vertices: int, mask=None):
    """Upload + dispatch the dense pane count WITHOUT waiting for the result.

    Returns the kernel's device-resident running-total halves (or None for an
    empty pane); recombine with ``triangles_from_halves`` when the value is
    needed.  Splitting submit from fetch lets a pipelined caller overlap the
    next pane's transfer/compute with this pane's readback.

    ``u``/``v`` may contain duplicates and both orientations (the device
    scatter canonicalizes); self-loops are dropped.  ``num_vertices`` bounds
    the ids.  The pane ships in the packed 4 B/edge wire form (pack_pane).
    """
    if len(u) == 0:
        return None
    w, n = pack_pane(u, v, mask)
    return pane_triangles_submit_packed(w, n, num_vertices)


def triangles_from_halves(halves) -> int:
    """Blocking fetch: device halves (from pane_triangles_submit) -> count."""
    return 0 if halves is None else _triangles_from_halves(halves)


def pane_triangles_dense(
    u: np.ndarray, v: np.ndarray, num_vertices: int, mask=None
) -> int:
    """Synchronous pane count (submit + fetch in one call)."""
    return triangles_from_halves(pane_triangles_submit(u, v, num_vertices, mask))
