"""The masked-semiring SpMV kernel core (ISSUE 17): semiring lowerings
fuzzed against numpy oracles, the push/pull direction-optimized fixpoint
against the pre-refactor per-algorithm kernels (embedded here as oracles)
in every direction mode — bit-identical, except PageRank's float ranks,
which push and pull share bit for bit and the oracle matches within a few
ulp — the retrace guard (zero recompiles
across frontier-density drift and force-push/force-pull/auto flips — the
traced threshold is the only thing that changes), the spmv_stats
registry, and the loud-refusal contracts on the direction knobs."""

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gelly_streaming_tpu.core import compile_cache
from gelly_streaming_tpu.core.config import StreamConfig
from gelly_streaming_tpu.core.stream import EdgeStream
from gelly_streaming_tpu.library.pagerank import windowed_pagerank
from gelly_streaming_tpu.library.sssp import windowed_sssp
from gelly_streaming_tpu.ops import spmv
from gelly_streaming_tpu.ops import unionfind as uf
from gelly_streaming_tpu.utils import metrics
from gelly_streaming_tpu.utils.envswitch import env_choice

C = 64
CFG = StreamConfig(vertex_capacity=32, max_degree=16, batch_size=8)


def _rand_pane(rng, e_pad, capacity=C, skew=False, self_loops=False,
               mask_frac=0.8):
    """One padded pane: (src, dst, w, msk) with the fuzz dimensions the
    kernel must survive — skewed hubs, masked padding, self-loops, and
    the max vertex id capacity-1."""
    if skew:
        src = ((rng.zipf(1.3, e_pad) - 1) % capacity).astype(np.int32)
    else:
        src = rng.integers(0, capacity, e_pad).astype(np.int32)
    dst = rng.integers(0, capacity, e_pad).astype(np.int32)
    if self_loops:
        src[: e_pad // 8] = dst[: e_pad // 8]
    src[0], dst[0] = capacity - 1, capacity - 1  # max-id edge always present
    w = (rng.integers(1, 8, e_pad)).astype(np.float32)  # int-valued: exact
    msk = rng.random(e_pad) < mask_frac
    return src, dst, w, msk


def _oracle_dense(sem, src, dst, w, msk, x, capacity):
    """Sequential per-edge reference for one masked semiring SpMV."""
    ident = sem.identity
    if sem.name == "min_plus":
        ident = float(np.float32(ident))  # the f32 the kernel really holds
    y = np.full((capacity,), ident, np.float64)
    for s, d, wt, m in zip(src, dst, w, msk):
        if not m:
            continue
        if sem.name == "min_plus":
            y[d] = min(y[d], float(x[s]) + float(wt))
        elif sem.name == "plus_times":
            y[d] += float(x[s]) * float(wt)
        elif sem.name == "min_min":
            y[d] = min(y[d], min(float(x[s]), float(wt)))
        elif sem.name == "plus_one":
            y[d] += 1
    return y


@pytest.mark.parametrize("case", ["uniform", "skew", "selfloop", "allmask",
                                  "nomask"])
@pytest.mark.parametrize(
    "sem", [spmv.MIN_PLUS, spmv.PLUS_TIMES, spmv.MIN_MIN, spmv.PLUS_ONE],
    ids=lambda s: s.name,
)
def test_spmv_dense_matches_numpy_oracle(sem, case):
    rng = np.random.default_rng(hash((sem.name, case)) % (1 << 31))
    src, dst, w, msk = _rand_pane(
        rng, 128,
        skew=case == "skew",
        self_loops=case == "selfloop",
        mask_frac={"allmask": 0.0, "nomask": 1.0}.get(case, 0.8),
    )
    op = spmv.prepare_pane(src, dst, w, msk, C)
    if sem.name in ("min_min", "plus_one"):
        x = rng.integers(0, 100, C).astype(np.int32)
    else:
        x = rng.integers(0, 10, C).astype(np.float32)
    got = np.asarray(spmv.spmv_dense(sem, op, jnp.asarray(x)))
    want = _oracle_dense(sem, src, dst, w, msk, x, C)
    if sem.name == "plus_times":
        np.testing.assert_allclose(got, want, rtol=1e-5)
    else:
        np.testing.assert_array_equal(got.astype(np.float64), want)


def test_spmsv_frontier_matches_dense_restricted():
    rng = np.random.default_rng(7)
    src, dst, w, msk = _rand_pane(rng, 128, skew=True)
    op = spmv.prepare_pane(src, dst, w, msk, C)
    x = rng.integers(0, 10, C).astype(np.float32)
    fm = rng.random(C) < 0.25
    got = np.asarray(
        spmv.spmsv_frontier(spmv.MIN_PLUS, op, jnp.asarray(x), jnp.asarray(fm))
    )
    # the push lowering only reads frontier rows: mask down to them
    want = _oracle_dense(
        spmv.MIN_PLUS, src, dst, w, msk & fm[src], x, C
    )
    np.testing.assert_array_equal(got.astype(np.float64), want)


def test_spmsv_frontier_overflow_refuses_loudly():
    rng = np.random.default_rng(8)
    src, dst, w, msk = _rand_pane(rng, 128, mask_frac=1.0)
    op = spmv.prepare_pane(src, dst, w, msk, C)
    x = np.zeros((C,), np.float32)
    with pytest.raises(ValueError, match="f_cap"):
        spmv.spmsv_frontier(
            spmv.MIN_PLUS, op, jnp.asarray(x),
            jnp.ones((C,), bool), f_cap=4,
        )


def test_scatter_into_counts_degrees():
    rng = np.random.default_rng(9)
    src, dst, w, msk = _rand_pane(rng, 128)
    got = np.asarray(
        spmv.scatter_into(
            spmv.PLUS_ONE, C, jnp.asarray(src),
            jnp.ones((128,), jnp.int32), jnp.asarray(msk),
        )
    )
    want = np.bincount(src[msk], minlength=C)
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# bit-identity vs the pre-refactor per-algorithm kernels (embedded oracles:
# these ARE the deleted library kernels, verbatim)

_BIG = jnp.float32(1e30)


@partial(jax.jit, static_argnames=("capacity",))
def _pane_sssp_oracle(src, dst, w, mask, source, capacity, max_iters):
    dist0 = jnp.full((capacity,), _BIG).at[source].set(0.0)

    def body(state):
        dist, _, it = state
        cand = jnp.where(mask, dist[src] + w, _BIG)
        relaxed = jnp.full((capacity,), _BIG).at[dst].min(cand)
        new = jnp.minimum(dist, relaxed)
        return new, jnp.any(new < dist), it + 1

    def cond(state):
        _, changed, it = state
        return changed & (it < max_iters)

    dist, _, iters = jax.lax.while_loop(
        cond, body, (dist0, jnp.bool_(True), 0)
    )
    return dist, iters


@partial(jax.jit, static_argnames=("capacity",))
def _pane_pagerank_oracle(src, dst, mask, capacity, damping, tol, max_iters):
    zeros = jnp.zeros((capacity,), jnp.float32)
    ones = jnp.ones_like(zeros)
    m = mask.astype(jnp.float32)
    in_window = zeros.at[src].max(m).at[dst].max(m) > 0
    out_deg = zeros.at[src].add(m)
    n = jnp.maximum(jnp.sum(in_window.astype(jnp.float32)), 1.0)
    dangling = in_window & (out_deg == 0)
    base = jnp.where(in_window, (1.0 - damping) / n, 0.0)
    safe_deg = jnp.maximum(out_deg, 1.0)

    def body(state):
        r, _, it = state
        contrib = jnp.where(mask, r[src] / safe_deg[src], 0.0)
        spread = zeros.at[dst].add(contrib)
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


@pytest.mark.timeout_cap(120)
@pytest.mark.parametrize("mode", ["auto", "push", "pull"])
def test_fixpoint_bit_identical_to_pre_refactor_sssp(mode):
    rng = np.random.default_rng(11)
    src, dst, w, msk = _rand_pane(rng, 256, skew=True)
    want, want_iters = _pane_sssp_oracle(
        jnp.asarray(src), jnp.asarray(dst), jnp.asarray(w),
        jnp.asarray(msk), jnp.int32(0), C, jnp.int32(C - 1),
    )
    op = spmv.prepare_pane(src, dst, w, msk, C)
    x0 = jnp.full((C,), spmv.MIN_PLUS.identity, jnp.float32).at[0].set(0.0)
    res = spmv.fixpoint(
        spmv.MIN_PLUS, op, x0, max_iters=C - 1, direction=mode
    )
    np.testing.assert_array_equal(np.asarray(res.x), np.asarray(want))
    assert res.iters == int(want_iters)
    if mode == "push":
        assert res.pull_iters == 0
    if mode == "pull":
        assert res.push_iters == 0


@pytest.mark.timeout_cap(120)
@pytest.mark.parametrize("threshold", [0.0, 0.03, 0.5, 1.0])
def test_fixpoint_threshold_sweep_keeps_answers(threshold):
    # the density cut changes WHICH lowering runs each iteration, never
    # what it computes
    rng = np.random.default_rng(12)
    src, dst, w, msk = _rand_pane(rng, 256, skew=True)
    want, _ = _pane_sssp_oracle(
        jnp.asarray(src), jnp.asarray(dst), jnp.asarray(w),
        jnp.asarray(msk), jnp.int32(3), C, jnp.int32(C - 1),
    )
    op = spmv.prepare_pane(src, dst, w, msk, C)
    x0 = jnp.full((C,), spmv.MIN_PLUS.identity, jnp.float32).at[3].set(0.0)
    res = spmv.fixpoint(
        spmv.MIN_PLUS, op, x0, max_iters=C - 1, threshold=threshold
    )
    np.testing.assert_array_equal(np.asarray(res.x), np.asarray(want))


@pytest.mark.timeout_cap(120)
def test_fixpoint_rejects_non_idempotent_semirings():
    rng = np.random.default_rng(13)
    src, dst, w, msk = _rand_pane(rng, 64)
    op = spmv.prepare_pane(src, dst, w, msk, C)
    with pytest.raises(ValueError, match="idempotent"):
        spmv.fixpoint(
            spmv.PLUS_TIMES, op, jnp.zeros((C,), jnp.float32), max_iters=4
        )
    with pytest.raises(ValueError, match="direction"):
        spmv.fixpoint(
            spmv.MIN_PLUS, op, jnp.zeros((C,), jnp.float32),
            max_iters=4, direction="sideways",
        )


@pytest.mark.timeout_cap(120)
def test_pagerank_fixpoint_push_pull_bit_identical():
    rng = np.random.default_rng(14)
    src, dst, _, msk = _rand_pane(rng, 256, skew=True)
    want_r, want_in, want_it = _pane_pagerank_oracle(
        jnp.asarray(src), jnp.asarray(dst), jnp.asarray(msk),
        C, jnp.float32(0.85), jnp.float32(1e-6), jnp.int32(100),
    )
    op = spmv.prepare_pane(src, dst, None, msk, C)
    (r_push, in_push, it_push), (r_pull, in_pull, it_pull) = (
        spmv.pagerank_fixpoint(
            op, damping=0.85, tol=1e-6, max_iters=100, use_pull=use_pull
        )
        for use_pull in (False, True)
    )
    # push and pull sum each destination's addends in the same order
    np.testing.assert_array_equal(np.asarray(r_push), np.asarray(r_pull))
    np.testing.assert_array_equal(np.asarray(in_push), np.asarray(in_pull))
    assert int(it_push) == int(it_pull) == int(want_it)
    # the pre-refactor kernel: same in-weights, ranks within a few ulp (the
    # installed XLA compiles its float arithmetic differently)
    np.testing.assert_array_equal(np.asarray(in_push), np.asarray(want_in))
    np.testing.assert_array_max_ulp(
        np.asarray(r_push), np.asarray(want_r), maxulp=4
    )


@pytest.mark.timeout_cap(120)
def test_cc_fixpoint_matches_unionfind():
    rng = np.random.default_rng(15)
    for _ in range(5):
        src = rng.integers(0, C, 64).astype(np.int32)
        dst = rng.integers(0, C, 64).astype(np.int32)
        msk = rng.random(64) < 0.7
        p0, s0 = uf.init_parent(C), jnp.zeros((C,), bool)
        p_want, s_want = uf.union_edges_with_seen(
            p0, s0, jnp.asarray(src), jnp.asarray(dst), jnp.asarray(msk)
        )
        p_got, s_got = spmv.cc_fixpoint(
            p0, s0, jnp.asarray(src), jnp.asarray(dst), jnp.asarray(msk)
        )
        np.testing.assert_array_equal(np.asarray(p_got), np.asarray(p_want))
        np.testing.assert_array_equal(np.asarray(s_got), np.asarray(s_want))


# ---------------------------------------------------------------------------
# emission parity: the rebuilt library algorithms emit the same records in
# every direction mode

def _collect(out):
    return [(int(v), float(d)) for v, d in out.collect()]


@pytest.mark.timeout_cap(120)
def test_windowed_sssp_emissions_identical_across_modes():
    edges = [(0, 1, 4.0), (0, 2, 1.0), (2, 1, 2.0), (1, 3, 1.0), (2, 3, 5.0),
             (3, 4, 0.5), (4, 5, 0.5), (0, 5, 9.0)]
    base = _collect(
        windowed_sssp(EdgeStream.from_collection(edges, CFG), 0, 1000)
    )
    for mode in ("push", "pull", "auto"):
        cfg = dataclasses.replace(CFG, spmv_direction=mode)
        got = _collect(
            windowed_sssp(EdgeStream.from_collection(edges, cfg), 0, 1000)
        )
        assert got == base, mode
    # an explicit threshold changes scheduling, not answers
    cfg = dataclasses.replace(CFG, direction_threshold=0.5)
    got = _collect(
        windowed_sssp(EdgeStream.from_collection(edges, cfg), 0, 1000)
    )
    assert got == base


@pytest.mark.timeout_cap(120)
def test_windowed_pagerank_emissions_identical_across_modes():
    edges = [(0, 1), (1, 2), (2, 0), (2, 3), (3, 0), (1, 3)]
    base = _collect(
        windowed_pagerank(EdgeStream.from_collection(edges, CFG), 1000)
    )
    for mode in ("push", "pull", "auto"):
        cfg = dataclasses.replace(CFG, spmv_direction=mode)
        got = _collect(
            windowed_pagerank(EdgeStream.from_collection(edges, cfg), 1000)
        )
        assert got == base, mode


# ---------------------------------------------------------------------------
# retrace guard: one executable serves both directions — density drift,
# threshold changes, and force-mode flips land zero recompiles

@pytest.mark.timeout_cap(120)
def test_zero_recompiles_across_density_drift_and_mode_flips():
    rng = np.random.default_rng(16)
    src, dst, w, msk = _rand_pane(rng, 256, skew=True)
    op = spmv.prepare_pane(src, dst, w, msk, C)

    def run(source, mode, threshold=None):
        x0 = (
            jnp.full((C,), spmv.MIN_PLUS.identity, jnp.float32)
            .at[source].set(0.0)
        )
        return spmv.fixpoint(
            spmv.MIN_PLUS, op, x0, max_iters=C - 1,
            direction=mode, threshold=threshold,
        )

    run(0, "auto")  # warm the (single-bucket) executable
    compile_cache.reset_stats()
    for source, mode, thr in [
        (0, "push", None), (0, "pull", None), (0, "auto", 0.5),
        (1, "auto", None), (7, "push", None), (C - 1, "pull", None),
        (3, "auto", 0.01),
    ]:
        run(source, mode, thr)
    assert compile_cache.recompiles() == 0
    assert compile_cache.stats()["compiles"] == 0  # not even new buckets


@pytest.mark.timeout_cap(120)
def test_spmv_stats_registry_counts_direction_split():
    rng = np.random.default_rng(17)
    src, dst, w, msk = _rand_pane(rng, 256, skew=True)
    op = spmv.prepare_pane(src, dst, w, msk, C)
    x0 = jnp.full((C,), spmv.MIN_PLUS.identity, jnp.float32).at[0].set(0.0)
    metrics.reset_spmv_stats()
    res = spmv.fixpoint(spmv.MIN_PLUS, op, x0, max_iters=C - 1)
    stats = metrics.spmv_stats()
    assert stats["spmv_fixpoints"] == 1
    assert stats["spmv_push_iters"] == res.push_iters
    assert stats["spmv_pull_iters"] == res.pull_iters
    assert stats["spmv_direction_switches"] == res.switches
    assert stats["spmv_iters_total"] == res.iters
    hist = sum(
        stats[f"spmv_density_hist_{b}"]
        for b in range(metrics.SPMV_DENSITY_BINS)
    )
    assert hist == res.iters  # every iteration lands in exactly one bin
    metrics.reset_spmv_stats()
    assert metrics.spmv_stats()["spmv_fixpoints"] == 0
    # the registry rides into the shared snapshot beside the other planes
    assert "spmv" in metrics.metrics_snapshot()


# ---------------------------------------------------------------------------
# config/env knobs refuse loudly

def test_resolve_direction_env_knob(monkeypatch):
    assert spmv.resolve_direction(CFG) == "auto"
    monkeypatch.setenv("GELLY_SPMV_DIRECTION", "pull")
    assert spmv.resolve_direction(CFG) == "pull"
    monkeypatch.setenv("GELLY_SPMV_DIRECTION", " Push ")
    assert spmv.resolve_direction(CFG) == "push"
    cfg = dataclasses.replace(CFG, spmv_direction="auto")
    assert spmv.resolve_direction(cfg) == "auto"  # cfg beats env
    monkeypatch.setenv("GELLY_SPMV_DIRECTION", "sideways")
    with pytest.raises(ValueError, match="GELLY_SPMV_DIRECTION"):
        spmv.resolve_direction(CFG)


def test_resolve_threshold_env_knob(monkeypatch):
    assert spmv.resolve_threshold(CFG) == spmv.DEFAULT_DIRECTION_THRESHOLD
    monkeypatch.setenv("GELLY_DIRECTION_THRESHOLD", "0.25")
    assert spmv.resolve_threshold(CFG) == 0.25
    cfg = dataclasses.replace(CFG, direction_threshold=0.75)
    assert spmv.resolve_threshold(cfg) == 0.75  # cfg beats env
    for bad in ("lots", "1.5", "-0.1"):
        monkeypatch.setenv("GELLY_DIRECTION_THRESHOLD", bad)
        with pytest.raises(ValueError, match="GELLY_DIRECTION_THRESHOLD"):
            spmv.resolve_threshold(CFG)


def test_env_choice_refuses_unrecognized_spellings(monkeypatch):
    monkeypatch.delenv("GELLY_SPMV_DIRECTION", raising=False)
    assert env_choice("GELLY_SPMV_DIRECTION", spmv.DIRECTIONS, "auto") == "auto"
    monkeypatch.setenv("GELLY_SPMV_DIRECTION", "maybe")
    with pytest.raises(ValueError, match="auto/push/pull"):
        env_choice("GELLY_SPMV_DIRECTION", spmv.DIRECTIONS, "auto")


def test_config_rejects_bad_direction_fields():
    with pytest.raises(ValueError, match="spmv_direction"):
        StreamConfig(vertex_capacity=32, spmv_direction="sideways")
    with pytest.raises(ValueError, match="direction_threshold"):
        StreamConfig(vertex_capacity=32, direction_threshold=1.5)
