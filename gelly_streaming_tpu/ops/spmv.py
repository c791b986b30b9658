"""GraphBLAST-style masked semiring SpMV kernel core with push/pull
direction optimization.

The iterative vertex programs in library/ (pagerank, sssp, k-core,
iterative CC) each used to carry a private jitted kernel around the same
two device idioms: "combine a candidate per masked edge into a dense [C]
summary" (scatter-reduce) and "iterate that under ``lax.while_loop`` to a
fixed point".  This module is the shared home for that linear-algebra
core, in the masked-semiring formulation of GraphBLAST (Yang et al.,
arXiv:1908.01407): a graph pane is a sparse matrix, one propagation round
is y = A^T x over an (add, mul) semiring restricted by an edge mask, and
an algorithm is a semiring + an initial vector + a fixpoint policy.

Two lowerings serve every product:

* **pull (SpMV, dense mask)** — one gather over the pane's dst-STABLE-
  sorted edge copy plus a sorted segment reduction.  Cost is O(e_pad) with
  segment-local writes; the right regime when many vertices are active.
* **push (SpMSpV, sparse frontier)** — expand the active rows of the
  src-sorted CSR into a pow2-bucketed candidate buffer (masked-degree
  cumsum + searchsorted), then scatter-reduce the candidates.  Cost is
  O(f_cap): a frontier touching few edges pays the small bucket, not the
  whole pane.

Direction optimization (Beamer-style, via GraphBLAST's mask-density rule):
inside one cached while_loop executable the per-iteration direction is a
branchless ``lax.cond`` on frontier density vs a TRACED threshold — one
executable serves push, pull, and auto (force modes fold into the
threshold scalar: 2.0 is never exceeded -> always push; -1.0 always is ->
always pull), so flipping GELLY_SPMV_DIRECTION never recompiles.  Real
shape savings come from the host driver escalating through pow2 frontier
capacity buckets (``frontier_caps``): sparse phases run the small-f_cap
executable, dense phases the flat pull — every bucket cached through
core/compile_cache, zero recompiles across panes and direction changes
(pinned by tests/test_spmv.py).

Bit-exactness contract: for idempotent semirings every lowering produces
per-iteration-identical states (a dominated candidate stays dominated,
so relaxing only frontier rows equals relaxing all rows); for plus-times
the pull lowering's dst-STABLE sort preserves each destination's addend
arrival order, so the sorted segment sum accumulates the same sequence
the arrival-order scatter-add does.  The rebuilt library algorithms emit
byte-identical records in every direction mode (tests/test_spmv.py).
"""

from __future__ import annotations

import os
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from gelly_streaming_tpu.core import compile_cache
from gelly_streaming_tpu.ops import unionfind as uf
from gelly_streaming_tpu.utils import metrics
from gelly_streaming_tpu.utils.envswitch import resolve_choice

# Frontier density (|frontier| / |active vertices|) above which "auto"
# switches from the sparse push to the dense pull lowering.  Tuned on the
# skewed-community bench graph (bench.py _spmv_bench): push's expansion
# machinery beats the flat pull only while the frontier touches a small
# fraction of the pane.
DEFAULT_DIRECTION_THRESHOLD = 0.05

DIRECTIONS = ("auto", "push", "pull")

_HIST_BINS = metrics.SPMV_DENSITY_BINS


# ---------------------------------------------------------------------------
# semiring descriptors


def _segment_min(vals, seg, num_segments):
    return jax.ops.segment_min(
        vals, seg, num_segments=num_segments, indices_are_sorted=True
    )


def _segment_sum(vals, seg, num_segments):
    return jax.ops.segment_sum(
        vals, seg, num_segments=num_segments, indices_are_sorted=True
    )


def _scatter_min(target, idx, vals):
    return target.at[idx].min(vals, mode="drop")


def _scatter_add(target, idx, vals):
    return target.at[idx].add(vals, mode="drop")


class Semiring(NamedTuple):
    """An (add, mul) pair with the three reduction lowerings it admits.

    ``identity`` is add's neutral element (the empty-row value);
    ``idempotent`` marks add(a, a) == a — the property that makes
    frontier-restricted (push) iteration state-identical to full
    relaxation, and hence which semirings ``fixpoint`` accepts.
    ``scatter`` combines candidates into an existing [C] target at given
    rows (out-of-range rows drop — the padding sentinel); ``segment``
    reduces a dst-sorted candidate vector segment-wise.
    """

    name: str
    identity: float
    idempotent: bool
    mul: Callable
    combine: Callable
    scatter: Callable
    segment: Callable


#: min-plus: shortest-path relaxation (sssp).
MIN_PLUS = Semiring(
    "min_plus", 1e30, True,
    lambda x, w: x + w, jnp.minimum, _scatter_min, _segment_min,
)
#: plus-times: mass spreading (pagerank's damped transition).
PLUS_TIMES = Semiring(
    "plus_times", 0.0, False,
    lambda x, w: x * w, lambda a, b: a + b, _scatter_add, _segment_sum,
)
#: min-min: label propagation (iterative CC's hooking step).
MIN_MIN = Semiring(
    "min_min", 2**31 - 1, True,
    lambda x, w: jnp.minimum(x, w.astype(x.dtype)),
    jnp.minimum, _scatter_min, _segment_min,
)
#: plus-one: degree / incidence counting (k-core's estimate init).
PLUS_ONE = Semiring(
    "plus_one", 0, False,
    lambda x, w: jnp.ones_like(x), lambda a, b: a + b,
    _scatter_add, _segment_sum,
)


# ---------------------------------------------------------------------------
# pane operator: one pane's edges in the layouts the lowerings need


class PaneOperator(NamedTuple):
    """One pane's (padded) edge list as a masked sparse matrix, in the
    three layouts the lowerings need: arrival order (bit-exact plus-times
    scatter), src-sorted CSR (push expansion), and dst-STABLE-sorted
    (pull segment reduce).  ``n_active`` counts the vertices incident to
    any masked edge — the density denominator."""

    capacity: int
    e_pad: int
    src: jax.Array
    dst: jax.Array
    w: jax.Array
    msk: jax.Array
    s_dst: jax.Array
    s_w: jax.Array
    s_msk: jax.Array
    off: jax.Array
    d_src: jax.Array
    d_dst: jax.Array
    d_w: jax.Array
    d_msk: jax.Array
    n_active: jax.Array


def prepare_pane(src, dst, w, msk, capacity: int) -> PaneOperator:
    """Sort one padded pane into a :class:`PaneOperator` (on device, one
    cached executable per (capacity, e_pad); ``w=None`` means unit
    weights).  Masked-out rows sort past every real key so the CSR offsets
    and segment ids never see them."""
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)
    msk = jnp.asarray(msk, bool)
    e_pad = int(src.shape[0])
    w = (
        jnp.ones((e_pad,), jnp.float32)
        if w is None
        else jnp.asarray(w, jnp.float32)
    )

    def build():
        def kernel(src, dst, w, msk):
            key_s = jnp.where(msk, src, capacity)
            o = jnp.argsort(key_s)  # stable
            off = jnp.searchsorted(
                key_s[o], jnp.arange(capacity + 1)
            ).astype(jnp.int32)
            key_d = jnp.where(msk, dst, capacity)
            o2 = jnp.argsort(key_d)  # stable: arrival order kept per dst
            act = jnp.zeros((capacity,), bool)
            act = act.at[jnp.where(msk, src, 0)].max(msk)
            act = act.at[jnp.where(msk, dst, 0)].max(msk)
            return (
                dst[o], w[o], msk[o], off,
                src[o2], dst[o2], w[o2], msk[o2],
                jnp.sum(act.astype(jnp.int32)),
            )

        return kernel

    fn = compile_cache.cached_jit(
        ("spmv_prep", capacity, e_pad), build, label="spmv"
    )
    return PaneOperator(capacity, e_pad, src, dst, w, msk, *fn(src, dst, w, msk))


def frontier_caps(e_pad: int) -> tuple:
    """The pow2 frontier-capacity buckets the driver escalates through."""
    return tuple(
        sorted({
            min(e_pad, max(256, e_pad >> 4)),
            min(e_pad, max(256, e_pad >> 2)),
            e_pad,
        })
    )


# ---------------------------------------------------------------------------
# the two lowerings (traced helpers shared by one-shots and fixpoint runs)


def _push_product(sem, capacity, f_cap, off, deg, s_dst, s_w, s_msk, x, fm):
    """SpMSpV: expand the frontier's CSR rows into f_cap candidate slots
    and scatter-reduce.  Slot j belongs to the j-th frontier edge (masked-
    degree exclusive cumsum + searchsorted); slots past the frontier's
    edge total target the out-of-range sentinel row and drop.  The caller
    guarantees the frontier's edge count fits f_cap."""
    ident = jnp.asarray(sem.identity, x.dtype)
    deg_f = jnp.where(fm, deg, 0)
    starts = jnp.cumsum(deg_f) - deg_f
    j = jnp.arange(f_cap)
    v = jnp.searchsorted(starts, j, side="right") - 1
    total = starts[-1] + deg_f[-1]
    ok = j < total
    e_idx = jnp.where(ok, off[v] + (j - starts[v]), 0)
    live = ok & s_msk[e_idx]
    rows = jnp.where(live, s_dst[e_idx], capacity)
    cand = jnp.where(live, sem.mul(x[v], s_w[e_idx]), ident)
    return sem.scatter(jnp.full((capacity,), ident, x.dtype), rows, cand)


def _pull_product(sem, capacity, d_src, d_w, d_msk, seg, x):
    """SpMV: gather over the dst-sorted edge copy, sorted segment reduce.
    Combining with an identity-filled vector normalizes empty segments to
    the semiring identity (segment_min's empty value is the dtype max)."""
    ident = jnp.asarray(sem.identity, x.dtype)
    cand = jnp.where(d_msk, sem.mul(x[d_src], d_w), ident)
    y = sem.segment(cand, seg, capacity + 1)[:capacity]
    return sem.combine(jnp.full((capacity,), ident, x.dtype), y)


def spmv_dense(sem: Semiring, op: PaneOperator, x) -> jax.Array:
    """One masked semiring SpMV (dense-mask pull lowering):
    ``y[d] = add over masked edges (s, d, w) of mul(x[s], w)``, identity
    where no edge lands."""
    capacity, e_pad = op.capacity, op.e_pad

    def build():
        def kernel(d_src, d_dst, d_w, d_msk, x):
            seg = jnp.where(d_msk, d_dst, capacity)
            return _pull_product(sem, capacity, d_src, d_w, d_msk, seg, x)

        return kernel

    fn = compile_cache.cached_jit(
        ("spmv_dense", sem.name, capacity, e_pad), build, label="spmv"
    )
    return fn(op.d_src, op.d_dst, op.d_w, op.d_msk, jnp.asarray(x))


def spmsv_frontier(
    sem: Semiring, op: PaneOperator, x, frontier, f_cap: Optional[int] = None
) -> jax.Array:
    """One masked semiring SpMSpV (sparse-frontier push lowering): the
    same product restricted to edges whose source is in ``frontier``.
    Refuses loudly when the frontier's edge count exceeds ``f_cap``
    (silent truncation would be a wrong answer, not a slow one)."""
    capacity, e_pad = op.capacity, op.e_pad
    if f_cap is None:
        f_cap = e_pad
    if not 1 <= f_cap <= e_pad:
        raise ValueError(f"f_cap {f_cap} outside [1, {e_pad}]")
    fm = jnp.asarray(frontier, bool)
    deg = op.off[1:] - op.off[:-1]
    fe = int(jnp.sum(jnp.where(fm, deg, 0)))
    if fe > f_cap:
        raise ValueError(
            f"frontier touches {fe} edges > f_cap {f_cap}; use a bigger "
            "bucket (frontier_caps) or the dense lowering"
        )

    def build():
        def kernel(off, s_dst, s_w, s_msk, x, fm):
            deg = off[1:] - off[:-1]
            return _push_product(
                sem, capacity, f_cap, off, deg, s_dst, s_w, s_msk, x, fm
            )

        return kernel

    fn = compile_cache.cached_jit(
        ("spmsv_frontier", sem.name, capacity, e_pad, f_cap),
        build,
        label="spmv",
    )
    return fn(op.off, op.s_dst, op.s_w, op.s_msk, jnp.asarray(x), fm)


def scatter_into(sem: Semiring, capacity: int, idx, vals, msk) -> jax.Array:
    """One-shot masked scatter-combine into an identity-filled [capacity]
    vector — the degenerate SpMV every degree/count init is (k-core seeds
    estimates with a PLUS_ONE scatter over the pane's src column)."""
    idx = jnp.asarray(idx, jnp.int32)
    vals = jnp.asarray(vals)
    msk = jnp.asarray(msk, bool)
    e_pad = int(idx.shape[0])

    def build():
        def kernel(idx, vals, msk):
            ident = jnp.asarray(sem.identity, vals.dtype)
            return sem.scatter(
                jnp.full((capacity,), ident, vals.dtype),
                jnp.where(msk, idx, capacity),
                jnp.where(msk, vals, ident),
            )

        return kernel

    fn = compile_cache.cached_jit(
        ("spmv_scatter", sem.name, capacity, e_pad, str(vals.dtype)),
        build,
        label="spmv",
    )
    return fn(idx, vals, msk)


# ---------------------------------------------------------------------------
# direction-optimized fixpoint


def _build_run(sem, capacity, e_pad, f_cap):
    """One while_loop executable that serves BOTH directions: each
    iteration picks push or pull by ``lax.cond`` on frontier density vs
    the traced threshold.  The loop exits early (for the host driver to
    escalate buckets) only when push is wanted but the frontier's edge
    count outgrew this bucket's f_cap."""

    def kernel(
        off, s_dst, s_w, s_msk, d_src, d_dst, d_w, d_msk, n_act,
        x, fm, thr, it, max_iters, last_dir,
        push_iters, pull_iters, switches, hist,
    ):
        deg = off[1:] - off[:-1]
        seg = jnp.where(d_msk, d_dst, capacity)
        denom = jnp.maximum(n_act, 1).astype(jnp.float32)

        def fstats(fm):
            fe = jnp.sum(jnp.where(fm, deg, 0))
            dens = jnp.sum(fm).astype(jnp.float32) / denom
            return fe, dens

        def push(args):
            x, fm = args
            return _push_product(
                sem, capacity, f_cap, off, deg, s_dst, s_w, s_msk, x, fm
            )

        def pull(args):
            x, fm = args
            return _pull_product(sem, capacity, d_src, d_w, d_msk, seg, x)

        def cond(c):
            x, fm, it = c[0], c[1], c[2]
            fe, dens = fstats(fm)
            return (
                jnp.any(fm)
                & (it < max_iters)
                & ((dens > thr) | (fe <= f_cap))
            )

        def body(c):
            (x, fm, it, last_dir, push_iters, pull_iters, switches, hist) = c
            _, dens = fstats(fm)
            use_pull = dens > thr
            y = jax.lax.cond(use_pull, pull, push, (x, fm))
            xn = sem.combine(x, y)
            d = use_pull.astype(jnp.int32)
            switched = ((last_dir >= 0) & (d != last_dir)).astype(jnp.int32)
            b = jnp.clip(
                (dens * _HIST_BINS).astype(jnp.int32), 0, _HIST_BINS - 1
            )
            return (
                xn, xn != x, it + 1, d,
                push_iters + (1 - d), pull_iters + d,
                switches + switched, hist.at[b].add(1),
            )

        c = jax.lax.while_loop(
            cond, body,
            (x, fm, it, last_dir, push_iters, pull_iters, switches, hist),
        )
        fe, _ = fstats(c[1])
        return c + (fe,)

    return kernel


class FixpointResult(NamedTuple):
    x: jax.Array
    frontier: jax.Array
    iters: int
    push_iters: int
    pull_iters: int
    switches: int


def _bucket_index(caps, fe: int) -> int:
    for i, cap in enumerate(caps):
        if fe <= cap:
            return i
    return len(caps) - 1


def fixpoint(
    sem: Semiring,
    op: PaneOperator,
    x0,
    *,
    max_iters: int,
    direction: str = "auto",
    threshold: Optional[float] = None,
    frontier=None,
) -> FixpointResult:
    """Iterate ``x = combine(x, A^T x)`` to a fixed point (or the
    iteration bound) with per-iteration push/pull direction optimization.

    Idempotent semirings only: frontier-restricted push relaxation equals
    full relaxation per iteration exactly when a dominated candidate stays
    dominated.  ``direction`` forces one lowering by folding into the
    traced threshold (no recompile); ``threshold`` is the auto-mode
    density cut, defaulting to :data:`DEFAULT_DIRECTION_THRESHOLD`.  The
    initial frontier defaults to the non-identity entries of ``x0``.
    """
    if not sem.idempotent:
        raise ValueError(
            f"fixpoint needs an idempotent semiring (frontier relaxation "
            f"must be dominance-stable); {sem.name} is not"
        )
    if direction not in DIRECTIONS:
        raise ValueError(
            f"direction {direction!r} is not one of {'/'.join(DIRECTIONS)}"
        )
    if threshold is None:
        threshold = DEFAULT_DIRECTION_THRESHOLD
    thr = {"push": 2.0, "pull": -1.0}.get(direction, float(threshold))
    x = jnp.asarray(x0)
    fm = (
        x != jnp.asarray(sem.identity, x.dtype)
        if frontier is None
        else jnp.asarray(frontier, bool)
    )
    caps = frontier_caps(op.e_pad)
    runs = [
        compile_cache.cached_jit(
            ("spmv_run", sem.name, op.capacity, op.e_pad, fc),
            lambda fc=fc: _build_run(sem, op.capacity, op.e_pad, fc),
            label="spmv",
        )
        for fc in caps
    ]
    it = jnp.int32(0)
    last_dir = jnp.int32(-1)
    push_i = pull_i = sw = jnp.int32(0)
    hist = jnp.zeros((_HIST_BINS,), jnp.int32)
    thr_j = jnp.float32(thr)
    mi = jnp.int32(max_iters)
    deg = op.off[1:] - op.off[:-1]
    k = _bucket_index(caps, int(jnp.sum(jnp.where(fm, deg, 0))))
    # every dispatch advances >= 1 iteration or strictly escalates the
    # bucket, so the dispatch count is bounded by the iteration budget
    for _ in range(int(max_iters) + len(caps) + 2):
        (x, fm, it, last_dir, push_i, pull_i, sw, hist, fe) = runs[k](
            op.off, op.s_dst, op.s_w, op.s_msk,
            op.d_src, op.d_dst, op.d_w, op.d_msk, op.n_active,
            x, fm, thr_j, it, mi, last_dir, push_i, pull_i, sw, hist,
        )
        if int(it) >= int(max_iters) or not bool(jnp.any(fm)):
            break
        # live frontier inside the budget: push is wanted (density under
        # threshold) but its edge count outgrew this bucket — escalate
        k = _bucket_index(caps, int(fe))
    else:
        raise RuntimeError("spmv fixpoint made no progress (driver bug)")
    metrics.spmv_add("spmv_fixpoints", 1)
    metrics.spmv_add("spmv_push_iters", int(push_i))
    metrics.spmv_add("spmv_pull_iters", int(pull_i))
    metrics.spmv_add("spmv_direction_switches", int(sw))
    h = np.asarray(hist)
    for b in range(_HIST_BINS):
        if int(h[b]):
            metrics.spmv_add(f"spmv_density_hist_{b}", int(h[b]))
    return FixpointResult(x, fm, int(it), int(push_i), int(pull_i), int(sw))


# ---------------------------------------------------------------------------
# algorithm kernels built on the core (hosted here so library/ modules
# keep only validation + emission)


def pagerank_fixpoint(
    op: PaneOperator, *, damping: float, tol: float, max_iters: int,
    use_pull: bool = False,
):
    """The damped power iteration over one pane (library/pagerank.py's
    kernel on the plus-times semiring).  There is no frontier — every
    iteration spreads all mass — so direction is a whole-run choice:
    push scatter-adds in arrival order (the historical lowering, and the
    auto default: both lowerings measure within noise here), pull
    segment-sums the dst-STABLE-sorted copy — the same per-destination
    addend order, hence push and pull agree bit for bit (pinned by
    tests/test_spmv.py; the pre-refactor kernel agrees within a few ulp,
    since XLA may fuse its float arithmetic differently).  ``use_pull``
    is traced: flipping it reuses the executable."""
    capacity, e_pad = op.capacity, op.e_pad

    def build():
        def kernel(src, dst, mask, d_src, d_dst, d_msk,
                   use_pull, damping, tol, max_iters):
            zeros = jnp.zeros((capacity,), jnp.float32)
            ones = jnp.ones_like(zeros)
            m = mask.astype(jnp.float32)
            in_window = zeros.at[src].max(m).at[dst].max(m) > 0
            out_deg = zeros.at[src].add(m)
            n = jnp.maximum(jnp.sum(in_window.astype(jnp.float32)), 1.0)
            dangling = in_window & (out_deg == 0)
            base = jnp.where(in_window, (1.0 - damping) / n, 0.0)
            safe_deg = jnp.maximum(out_deg, 1.0)
            seg = jnp.where(d_msk, d_dst, capacity)

            def spread_push(r):
                contrib = jnp.where(mask, r[src] / safe_deg[src], 0.0)
                return PLUS_TIMES.scatter(zeros, dst, contrib)

            def spread_pull(r):
                cand = jnp.where(d_msk, r[d_src] / safe_deg[d_src], 0.0)
                return PLUS_TIMES.segment(cand, seg, capacity + 1)[:capacity]

            def body(state):
                r, _, it = state
                spread = jax.lax.cond(use_pull, spread_pull, spread_push, r)
                dangling_mass = jnp.sum(jnp.where(dangling, r, 0.0)) / n
                r_new = base + damping * (
                    spread + jnp.where(in_window, dangling_mass, 0.0)
                )
                delta = jnp.sum(jnp.abs(r_new - r))
                return r_new, delta, it + 1

            def cond(state):
                _, delta, it = state
                return (delta > tol) & (it < max_iters)

            r0 = jnp.where(in_window, ones / n, 0.0)
            r, _, iters = jax.lax.while_loop(cond, body, (r0, jnp.inf, 0))
            return r, in_window, iters

        return kernel

    fn = compile_cache.cached_jit(
        ("spmv_pagerank", capacity, e_pad), build, label="spmv"
    )
    r, in_w, iters = fn(
        op.src, op.dst, op.msk, op.d_src, op.d_dst, op.d_msk,
        jnp.bool_(use_pull), jnp.float32(damping), jnp.float32(tol),
        jnp.int32(max_iters),
    )
    metrics.spmv_add("spmv_fixpoints", 1)
    metrics.spmv_add(
        "spmv_pull_iters" if use_pull else "spmv_push_iters", int(iters)
    )
    return r, in_w, iters


def _build_cc():
    def kernel(parent, seen, src, dst, mask):
        src_ = jnp.where(mask, src, 0)
        dst_ = jnp.where(mask, dst, 0)

        def cond(p):
            return jnp.any(p[src_] != p[dst_])

        def body(p):
            rs = p[src_]
            rd = p[dst_]
            lo = jnp.minimum(rs, rd)
            hi = jnp.maximum(rs, rd)
            return uf.compress(MIN_MIN.scatter(p, hi, lo))

        parent = jax.lax.while_loop(cond, body, uf.compress(parent))
        seen = seen.at[src_].max(mask).at[dst_].max(mask)
        return parent, seen

    return kernel


def cc_fixpoint(parent, seen, src, dst, mask):
    """Connected-components hooking on the min-min semiring: each round
    scatter-mins the lower endpoint label onto the higher (the kernel
    core's scatter primitive — candidates ARE labels), then pointer-
    doubles (ops/unionfind.compress) until every edge's endpoints agree.
    The identical array fixed point to unionfind.union_edges_with_seen —
    parent[v] = min vertex id of v's component, fully compressed — via
    one shared process-global executable."""
    fn = compile_cache.cached_jit(("spmv_cc_fixpoint",), _build_cc, label="spmv")
    return fn(parent, seen, src, dst, mask)


# ---------------------------------------------------------------------------
# config/env resolution (the shared tri-state contract, utils/envswitch.py)


def resolve_direction(cfg) -> str:
    """cfg.spmv_direction ("" defers) > GELLY_SPMV_DIRECTION > auto;
    unrecognized spellings refuse loudly."""
    return resolve_choice(
        cfg.spmv_direction, "GELLY_SPMV_DIRECTION", DIRECTIONS, "auto"
    )


def resolve_threshold(cfg) -> float:
    """cfg.direction_threshold (-1 defers) > GELLY_DIRECTION_THRESHOLD >
    :data:`DEFAULT_DIRECTION_THRESHOLD`; non-density env values refuse
    loudly."""
    if cfg.direction_threshold != -1.0:
        return float(cfg.direction_threshold)
    env = os.environ.get("GELLY_DIRECTION_THRESHOLD")
    if env is None:
        return DEFAULT_DIRECTION_THRESHOLD
    try:
        val = float(env.strip())
    except ValueError:
        raise ValueError(
            f"GELLY_DIRECTION_THRESHOLD={env!r} is not a float density"
        ) from None
    if not 0.0 <= val <= 1.0:
        raise ValueError(
            f"GELLY_DIRECTION_THRESHOLD={env!r} must be in [0, 1]"
        )
    return val
