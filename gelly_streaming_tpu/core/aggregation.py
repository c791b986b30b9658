"""The aggregation runtime: windowed partial-fold + combine + running merge.

Reference: SummaryAggregation.java (descriptor: updateFun :31, combineFun :36,
transform :41, initialValue :43, transientState :48; the singleton Merger
final-combiner :93-119 with ListCheckpointed state :127-135) and its two
execution strategies SummaryBulkAggregation.java:68-90 (per-partition windowed
fold -> flat all-window combine) and SummaryTreeReduce.java:95-123 (log-depth
pairwise combine tree).

TPU-native form: a "partition" is a shard of the window pane; the per-partition
fold is a batched state-update kernel; the flat combine is a left fold over
partials; the tree combine is pairwise rounds (halving, mirroring enhance()'s
``partition/2`` re-keying).  The running summary (Merger state) is a pytree of
arrays — checkpointable by construction, closing the reference's gap where most
operator state is not checkpointed (SURVEY.md §5.3-4).
"""

from __future__ import annotations

import functools
import os
import time
from types import GeneratorType
from typing import Any, Callable, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from gelly_streaming_tpu.core import compile_cache
from gelly_streaming_tpu.core.config import StreamConfig
from gelly_streaming_tpu.core.output import OutputStream
from gelly_streaming_tpu.core.windows import (
    FoldRequest,
    WindowPane,
    pad_pane_edges,
    stream_panes,
)
from gelly_streaming_tpu.utils import metrics, tracing


def _tree_copy_impl(tree):
    """On-device clone of a pytree.

    Outputs of a jit call never alias its (non-donated) inputs, so the clone
    stays valid after the caller donates the source to the next fold step —
    the invariant async snapshots rely on.
    """
    return jax.tree.map(jnp.copy, tree)


# one executable per pytree structure, shared process-wide and metered by
# the retrace guard (the structure is part of jit's own signature; the key
# names the kernel family)
_tree_copy = compile_cache.cached_jit(("tree_copy",), lambda: _tree_copy_impl)


def resolve_fused_dispatch(cfg: StreamConfig) -> bool:
    """Cross-tenant fused dispatch on/off: ``cfg.fused_dispatch`` forces,
    -1 defers to GELLY_FUSED_DISPATCH (default OFF — solo dispatch is the
    equivalence oracle, and fusing adds one superpane-executable compile
    that cold single-tenant paths should not pay)."""
    from gelly_streaming_tpu.utils.envswitch import resolve_switch

    return resolve_switch(cfg.fused_dispatch, "GELLY_FUSED_DISPATCH", False)


class MergerState:
    """The Merger's state (SummaryAggregation.java:93-135): the running
    summary and the stream position — the last folded window id, and
    whether the untimed global pane (id -1, which has no orderable id) was
    folded.

    The one owner of the windowed snapshot layout
    (``SummaryAggregation._checkpoint_like``) and of the pane route: the
    constructor is the layout's only reader, :meth:`save` its only writer,
    and :meth:`merge` the only place a window's fold is routed (and
    counted) into the running summary.  Both Merger loops
    (``SummaryAggregation._merge_loop`` and its async form,
    ``async_exec.async_merge_loop``) and every reader of a windowed
    snapshot's position go through it.
    """

    __slots__ = ("agg", "path", "running", "start_after", "global_done")

    def __init__(self, agg, cfg, checkpoint_path=None, restore=True):
        self.agg = agg
        self.path = checkpoint_path
        self.running = None
        self.start_after = -1
        self.global_done = False
        if not (checkpoint_path and restore):
            return
        from gelly_streaming_tpu.utils.checkpoint import (
            checkpoint_exists,
            load_state,
        )

        if not checkpoint_exists(checkpoint_path):
            return
        try:
            snap = load_state(checkpoint_path, agg._checkpoint_like(cfg))
        except ValueError:
            # legacy snapshot layout: a bare summary pytree with no stream
            # position (pre-position checkpoints)
            self.running = load_state(checkpoint_path, agg.initial_state(cfg))
            return
        if bool(snap["has_summary"]):
            self.running = snap["summary"]
        self.start_after = int(snap["last_window"])
        self.global_done = bool(snap["global_done"])

    def folded(self, window_id: int) -> bool:
        """Folded before the snapshot: a replayed pane to skip."""
        return 0 <= window_id <= self.start_after or (
            window_id == -1 and self.global_done
        )

    def merge(self, pane_summary, into_running: bool = False):
        """Route one window's fold into the running summary and return it:
        ``into_running`` folds already hold the running summary; a
        transient descriptor's pane alone is its window's summary; any
        other pane's partial combines into the running summary."""
        if into_running:
            self.running = pane_summary
            metrics.pipeline_add("merge_running_folds", 1)
        elif self.agg.transient_state:
            self.running = pane_summary
            metrics.pipeline_add("merge_transient_folds", 1)
        else:
            if self.running is not None:
                pane_summary = self.agg._combine_j(self.running, pane_summary)
            self.running = pane_summary
            metrics.pipeline_add("merge_combines", 1)
        return self.running

    def advance(self, window_id: int) -> None:
        """The window is emitted: the position moves past it."""
        self.start_after = max(window_id, self.start_after)
        self.global_done = self.global_done or window_id == -1

    def save(self, summary) -> None:
        """Snapshot ``summary`` at this position (no-op without a path).
        Transient aggregations reset after emission, so their snapshot
        restores with no running summary."""
        if not self.path:
            return
        from gelly_streaming_tpu.utils.checkpoint import save_state

        save_state(
            self.path,
            {
                "summary": summary,
                "has_summary": np.full((), not self.agg.transient_state, bool),
                "last_window": np.full((), self.start_after, np.int64),
                "global_done": np.full((), self.global_done, bool),
            },
        )

    def reset(self) -> None:
        """After emission: a transient descriptor starts each window anew."""
        if self.agg.transient_state:
            self.running = None


class SummaryAggregation:
    """Abstract aggregation descriptor (SummaryAggregation.java:22-48).

    Subclasses define:
      initial_state(cfg) -> S          (initialValue :43; pytree of arrays)
      update(state, src, dst, val, mask) -> S   (updateFun :31 — folds an edge
                                        micro-batch into the partial state)
      combine(a, b) -> S               (combineFun :36 — merge partials)
      transform(state) -> T            (transform :41 — S to emitted record)
    ``transient_state`` resets the running summary after each emission
    (SummaryAggregation.java:113-115).
    """

    transient_state: bool = False
    # Executable-cache identity.  The streaming kernels (update / combine /
    # the fused wire steps) are traced from bound methods, so by default
    # each descriptor INSTANCE owns its executables (``cache_token`` is the
    # instance).  Descriptors whose update/combine/initial_state are pure
    # functions of (class, cfg) — most library descriptors — override this
    # to the class, so re-created descriptors (a fresh
    # ``ConnectedComponents()`` per stream, window, or bench chunk) share
    # compiled executables instead of retracing.
    @property
    def cache_token(self):
        return self
    # True when transform(fold(edges)) is invariant under reordering edges
    # within (and across) micro-batches — e.g. union-find CC, parity
    # union-find bipartiteness.  Order-free descriptors may legally ride the
    # sorted EF40 multiset wire encoding (io/wire.py), which ships ~2x fewer
    # bytes per edge than the plain arrival-order pack.
    order_free: bool = False

    def __init__(self, window_ms: Optional[int] = None):
        self.window_ms = window_ms

    # -- descriptor hooks -----------------------------------------------------

    def initial_state(self, cfg: StreamConfig):
        raise NotImplementedError

    def update(self, state, src, dst, val, mask):
        raise NotImplementedError

    def combine(self, a, b):
        raise NotImplementedError

    def transform(self, state):
        return state

    def mesh_combine_states(self, cfg: StreamConfig, axis_name: str):
        """Optional COLLECTIVE cross-shard combine for the mesh data plane.

        Return a function ``(state, has_data) -> state`` that runs INSIDE
        shard_map over the mesh axis and reduces every shard's partial into
        the same (replicated-identical) combined state using XLA collectives
        (pmin/pmax/psum/ppermute riding ICI), or None to use the generic
        all_gather + sequential-combine fold.  ``has_data`` is this shard's
        "my bucket was non-empty" flag; descriptors whose initial state is a
        combine identity may ignore it.

        This is the TPU-native replacement for the reference's all-to-one
        ``timeWindowAll.reduce`` (SummaryBulkAggregation.java:81-83): instead
        of funneling S partials to one task and merging S-1 times
        sequentially, the combine is a logarithmic-depth collective over the
        mesh — the asymptotic win the sharded plane exists for.
        """
        return None

    def sharded_state_spec(self, cfg: StreamConfig):
        """Optional owner-sharded summary state protocol (ISSUE 4).

        Return a ``core.sharded_state.ShardedStateSpec`` to make O(C/S)
        owner blocks + delta-exchange reconciliation the descriptor's mesh
        streaming plane (the default path when supported and enabled —
        ``cfg.sharded_state`` / GELLY_SHARDED_STATE); None keeps the
        replicated combine above, which remains the equivalence oracle.
        """
        return None

    def state_nbytes(self, cfg: StreamConfig) -> int:
        """Summary-state footprint of one instance of this query (bytes).

        The admission-accounting entry point for the job runtime
        (runtime/manager.py): ``JobManager`` sums this over admitted jobs
        against ``RuntimeConfig.max_state_bytes``.  Computed via
        ``jax.eval_shape`` — abstract shapes only, nothing is allocated, so
        admission control itself cannot blow the budget it polices.
        """
        shapes = jax.eval_shape(lambda: self.initial_state(cfg))
        return int(
            sum(
                int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
                for leaf in jax.tree.leaves(shapes)
            )
        )

    def emission_scratch(self, cfg: StreamConfig):
        """Pytree of ``jax.ShapeDtypeStruct`` leaves describing the transient
        device buffers ``transform`` materializes at emission time beyond the
        summary itself (e.g. a sketch's gathered register view, a top-k
        heap, wedge-closure matrices).  Purely declarative — nothing is
        allocated; the default (no scratch) is right for descriptors whose
        transform is a view or O(1) reduction of the state.
        """
        return ()

    def aux_emission_nbytes(self, cfg: StreamConfig) -> int:
        """Bytes of ``emission_scratch`` — the emission-time residue that
        ``state_nbytes`` alone does not see."""
        return int(
            sum(
                int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
                for leaf in jax.tree.leaves(self.emission_scratch(cfg))
            )
        )

    def admission_nbytes(self, cfg: StreamConfig) -> int:
        """What admission control must charge for one instance of this query:
        the persistent summary PLUS the peak transient emission-time scratch.

        ``state_nbytes`` assumed the summary IS the job's whole device state;
        for sketch descriptors the emission-time buffers (top-k heap,
        gathered register view, wedge matrices) can dominate the KB-scale
        registers, so a thousand admitted jobs priced by registers alone
        could OOM on the unpriced residue.  runtime/manager.py and
        runtime/server.py charge THIS value against ``max_state_bytes``.
        """
        return self.state_nbytes(cfg) + self.aux_emission_nbytes(cfg)

    # -- execution ------------------------------------------------------------

    def _num_partitions(self, cfg: StreamConfig) -> int:
        return cfg.num_shards

    def _fold_partials(self, items, combine2, fanin: int = 2):
        """Combine-strategy hook over opaque items: flat left fold
        (timeWindowAll.reduce analog, SummaryBulkAggregation.java:81-83).
        Overridden by the tree strategy (which consumes ``fanin``).  Shared by
        the simulated runtime and the mesh runner so the strategies cannot
        diverge."""
        acc = items[0]
        for it in items[1:]:
            acc = combine2(acc, it)
        return acc

    def _tree_fanin(self, cfg: StreamConfig) -> int:
        """Combine-tree fan-in (SummaryTreeReduce's ``degree``, :53-64)."""
        return max(2, cfg.tree_degree)

    def _combine_partials(self, partials, cfg: StreamConfig):
        return self._fold_partials(partials, self._combine_j, self._tree_fanin(cfg))

    # The two executables jit functions of stable names, so the device
    # trace reads ``jit_gelly_fold`` / ``jit_gelly_combine`` whatever the
    # descriptor's methods are called.

    @property
    def _update_j(self):
        def build_fold():
            update = self.update

            def gelly_fold(state, src, dst, val, mask):
                return update(state, src, dst, val, mask)

            return gelly_fold

        return compile_cache.cached_jit(
            ("agg_update", self.cache_token), build_fold
        )

    @property
    def _combine_j(self):
        def build_combine():
            combine = self.combine

            def gelly_combine(a, b):
                return combine(a, b)

            return gelly_combine

        return compile_cache.cached_jit(
            ("agg_combine", self.cache_token), build_combine
        )

    # -- packed-wire fast path ------------------------------------------------
    #
    # The reference's aggregation pipeline runs *inside* the Flink runtime —
    # serialization, shuffle and windowing are the framework's own data plane
    # (SummaryBulkAggregation.java:76-83 over pom.xml:38-63 services).  The
    # equivalent here: when the source exposes packed-wire arrays (value-less,
    # untimed — EdgeStream.from_arrays / file_stream), `run()` streams packed
    # buffers through WirePrefetcher into ONE jitted fused step per micro-batch
    # (device-side unpack -> the stream's stages -> updateFun) with the whole
    # carry donated.  Untimed streams form a single global pane, and updateFun
    # is a fold over edges, so folding batch-by-batch into one running state is
    # exactly the single-partition pane fold of the simulated path.

    def _mesh_wire_eligible(self, stream) -> bool:
        """Wire-backed stream + a real mesh: the sharded STREAMING fold
        (MeshAggregationRunner.wire_records) — per micro-batch, packed
        per-shard rows fold into donated per-shard carries; one collective
        merge at stream end (VERDICT r3 weak #3: no per-pane re-fold)."""
        cfg = stream.cfg
        return (
            (
                getattr(stream, "_wire_arrays", None) is not None
                or getattr(stream, "_wire_packed", None) is not None
            )
            and cfg.num_shards > 1
            and cfg.num_shards <= len(jax.devices())
            # ingestion-time panes demand per-window emission; the streaming
            # fold emits once at stream end, so route to the windowed paths
            and not (cfg.ingest_window_edges or cfg.ingest_window_ms)
        )

    def _wire_emit_every(self, cfg: StreamConfig, batch: int) -> int:
        """Full batches per running emission on the wire fast path (0 = emit
        only at stream end).

        ``ingest_window_edges`` that divides the batch boundary keeps the
        stream ON the fast path: the donated fold carry IS the running
        merged summary (Merger semantics for non-transient descriptors), so
        emitting ``transform(carry)`` every K/batch batches reproduces the
        windowed path's running emission at full wire speed.  Non-aligned
        or transient configurations fall back to the windowed runtime.
        """
        k = cfg.ingest_window_edges
        if not k:
            return 0
        if k % batch or self.transient_state:
            return -1  # not fast-path representable
        return k // batch

    def _wire_eligible(self, stream) -> bool:
        cfg = stream.cfg
        if (
            getattr(stream, "_wire_arrays", None) is None
            and getattr(stream, "_wire_packed", None) is None
        ) or self._num_partitions(cfg) != 1:
            return False
        if cfg.ingest_window_ms:
            return False  # wall-clock panes need the windowed time plane
        packed = getattr(stream, "_wire_packed", None)
        batch = (
            packed[1] if packed is not None else stream._wire_arrays[2]
        )
        return self._wire_emit_every(cfg, batch) >= 0

    def _make_wire_tail(self, stages):
        """The shared (carry, src, dst, mask) -> carry fold tail: stream
        stages then updateFun, traced identically by the per-batch fused
        step, the padded-tail step, and the superbatch scan body."""
        from gelly_streaming_tpu.core.types import EdgeBatch

        def tail(carry, src, dst, mask):
            states, summary = carry
            b = EdgeBatch(src=src, dst=dst, mask=mask)
            out_states = []
            for stage, st in zip(stages, states):
                st, b = stage.apply(st, b)
                out_states.append(st)
            summary = self.update(summary, b.src, b.dst, b.val, b.mask)
            return (tuple(out_states), summary)

        return tail

    def _wire_fused_step(self, stream, batch: int, width):
        """Jitted (stage-states, summary), wire-buffer -> carry step.

        Executables live in the process-global compile cache keyed on
        (descriptor cache token, stages, cfg, batch, width) — so repeated
        runs, re-created streams, AND re-created descriptors with a
        class-level ``cache_token`` all share one compiled kernel.  Keys use
        the stages tuple itself (strong ref), not id(): an id can be reused
        after GC, silently resurrecting a kernel compiled for a DIFFERENT
        stream's stages (e.g. another filter predicate).
        """
        from gelly_streaming_tpu.io import wire

        token = self.cache_token
        stages = stream._stages
        key_tail = (stream._stages, stream.cfg, batch, str(width))

        def make_fused():
            tail = self._make_wire_tail(stages)

            def fused(carry, buf):
                s, d = wire.unpack_edges(buf, batch, width)
                return tail(carry, s, d, jnp.ones((batch,), bool))

            return fused

        return (
            compile_cache.cached_jit(
                ("wire_fused", token) + key_tail, make_fused, donate_argnums=0
            ),
            compile_cache.cached_jit(
                ("wire_tail", token, stages),
                lambda: self._make_wire_tail(stages),
                donate_argnums=0,
            ),
        )

    def _wire_scan_step(self, stream, batch: int, width, group: int):
        """Superbatch step: fold ``group`` stacked wire buffers in ONE
        device call via ``lax.scan`` over the same per-batch tail the fused
        step traces — bit-identical to ``group`` sequential dispatches, at
        1/group of the dispatch overhead.  Compiled once per bucketed group
        size (power-of-two sizes only, see plan_superbatch_groups)."""
        from gelly_streaming_tpu.io import wire

        token = self.cache_token
        stages = stream._stages
        key = (
            "wire_scan",
            token,
            stages,
            stream.cfg,
            batch,
            str(width),
            group,
        )

        def make_scan():
            tail = self._make_wire_tail(stages)

            def scan_fused(carry, bufs):  # bufs: uint8[group, nbytes]
                def body(c, buf):
                    s, d = wire.unpack_edges(buf, batch, width)
                    return tail(c, s, d, jnp.ones((batch,), bool)), None

                carry, _ = jax.lax.scan(body, carry, bufs)
                return carry

            return scan_fused

        return compile_cache.cached_jit(key, make_scan, donate_argnums=0)

    def _wire_width(self, cfg: StreamConfig, batch: Optional[int] = None):
        """Resolve the wire encoding for this descriptor + config.

        "auto" picks EF40 (sorted multiset, ~2x fewer bytes) only when the
        descriptor is order-free, ids fit in 20 bits, the host has spare
        cores to sort on — on a single-core host the per-batch radix sort
        competes with the transfer path for the same CPU and measures slower
        than shipping the plain 40-bit pack (BASELINE.md round 3) — AND it
        actually ships fewer bytes at the EFFECTIVE batch size (``batch``,
        defaulting to cfg.batch_size): its per-batch unary bitvector
        dominates when capacity >> batch, e.g. a short stream whose single
        batch shrank to the stream length.
        """
        from gelly_streaming_tpu.io import wire

        enc = cfg.wire_encoding
        if enc == "auto":
            try:
                # the process's USABLE cores (cgroup/affinity-aware), not the
                # machine's physical count — a container pinned to one core
                # of a 64-core host is still a single-core host here
                cores = len(os.sched_getaffinity(0))
            except AttributeError:
                cores = os.cpu_count() or 1
            # one shared cost policy with the replay producer
            width = wire.replay_width(
                cfg.vertex_capacity,
                batch if batch is not None else cfg.batch_size,
                self.order_free,
            )
            enc = "ef40" if (cores >= 2 and isinstance(width, tuple)) else "plain"
        if enc == "ef40":
            if not self.order_free:
                raise ValueError(
                    "wire_encoding='ef40' ships a sorted multiset; this "
                    "aggregation is not order-free"
                )
            if cfg.vertex_capacity > 1 << 20:
                raise ValueError("ef40 wire encoding needs vertex_capacity <= 2^20")
            return (wire.EF40, cfg.vertex_capacity)
        return wire.width_for_capacity(cfg.vertex_capacity)

    def _binned_modes(self, cfg: StreamConfig):
        """Resolve the propagation-blocking ingest switches for this
        descriptor: ``(binned, compress)``.

        Binning/compression reorder each batch into a (dst, src)-sorted
        multiset, so they are legal only for ORDER-FREE folds: an explicit
        ``cfg.binned_ingest=1`` / ``cfg.wire_compress=1`` on an
        order-sensitive descriptor refuses loudly (the EF40 rule), while
        the ambient env switches quietly stay on the arrival-order oracle —
        a process-wide GELLY_WIRE_COMPRESS=1 must not break the one
        order-sensitive query in a mixed pipeline.  Compression further
        needs ids in 2^28 (BDV varint bound) and yields to an explicit
        ``wire_encoding='ef40'`` (two compressed encodings cannot both win).
        """
        from gelly_streaming_tpu.io import wire

        compress = wire.resolve_wire_compress(cfg)
        binned = wire.resolve_binned_ingest(cfg)
        if not (binned or compress):
            return False, False
        forced = cfg.binned_ingest == 1 or cfg.wire_compress == 1
        if not self.order_free:
            if forced:
                raise ValueError(
                    "binned/compressed ingest ships a (dst, src)-sorted "
                    "multiset; this aggregation is not order-free"
                )
            return False, False
        if compress and cfg.vertex_capacity > 1 << wire.BDV_MAX_ID_BITS:
            if cfg.wire_compress == 1:
                raise ValueError(
                    "wire_compress needs vertex_capacity <= 2^28 (BDV varints)"
                )
            compress = False
        if compress and cfg.wire_encoding == "ef40":
            if cfg.wire_compress == 1:
                raise ValueError(
                    "wire_compress and wire_encoding='ef40' are mutually "
                    "exclusive wire formats; pick one"
                )
            compress = False
        return binned, compress

    def _maybe_bin_pane(
        self, cfg: StreamConfig, pane: WindowPane, width=None
    ) -> WindowPane:
        """Destination-bin a closed pane when binned ingest resolves on.

        Returns the pane with its edges (dst, src)-sorted — the same
        multiset, so order-free folds emit identically while their scatters
        walk the summary arrays segment-locally (the cache half of
        propagation blocking).  Valued/timed panes pass through untouched
        (their payload alignment is not worth permuting on the pack
        thread), as do non-order-free descriptors (loudly when forced —
        see ``_binned_modes``).  Callers that pack the pane at a known wire
        ``width`` pass it: tuple encodings (EF40) regroup each row by src
        themselves, so the pre-sort would be pure wasted pack-thread work
        (the same skip the wire fast path and the mesh row packer apply).
        """
        if pane.val is not None or pane.time is not None or pane.num_edges <= 1:
            return pane
        if width is not None and isinstance(width, tuple):
            return pane
        binned, _compress = self._binned_modes(cfg)
        if not binned:
            return pane
        from gelly_streaming_tpu.io import wire

        s, d = wire.sort_edges_binned(
            pane.src, pane.dst, cfg.vertex_capacity, record_stats=True
        )
        return pane._replace(src=s, dst=d)

    def _wire_checkpoint_like(self, stream):
        """Wire-path snapshot layout: the FULL fold carry (stage states +
        summary — closing the reference's unsaved-operator-state gap,
        SURVEY.md §5.3) plus the stream position in full batches."""
        cfg = stream.cfg
        return {
            "summary": self.initial_state(cfg),
            "stages": tuple(stage.init(cfg) for stage in stream._stages),
            "next_batch": np.zeros((), np.int64),
            # position is in units of full batches, so a resume under a
            # different batch_size would skip/refold the wrong edges — the
            # stored size makes that a hard error instead of silent corruption
            "batch": np.zeros((), np.int64),
            "done": np.zeros((), bool),
        }

    def _wire_restore(self, stream, checkpoint_path: Optional[str], batch: int):
        """Resolve a wire-path snapshot into a resume plan.

        Returns ``(start_batch, carry_host, done_summary)``: the batch to
        resume folding from, the restored carry (or None for a fresh start),
        and — when the snapshot says the stream already finished — the
        summary to re-emit instead of folding.  Legacy layouts degrade
        gracefully: a windowed-loop snapshot whose global pane finished
        re-emits; any other legacy form (windowed not-done, or the oldest
        bare-summary pytree) re-folds from the start — window positions
        don't map to wire batch positions, and exactly-once state holds
        either way.
        """
        cfg = stream.cfg
        if not checkpoint_path:
            return 0, None, None
        from gelly_streaming_tpu.utils.checkpoint import (
            checkpoint_exists,
            load_state,
        )

        if not checkpoint_exists(checkpoint_path):
            return 0, None, None
        try:
            snap = load_state(checkpoint_path, self._wire_checkpoint_like(stream))
        except ValueError:  # a pre-wire-path (windowed-layout) snapshot
            try:
                legacy = MergerState(self, cfg, checkpoint_path)
            except ValueError:
                return 0, None, None  # no readable layout: no position
            if legacy.global_done and legacy.running is not None:
                return 0, None, legacy.running
            return 0, None, None
        if int(snap["batch"]) != batch:
            raise ValueError(
                f"wire checkpoint was written with batch_size "
                f"{int(snap['batch'])}; resuming with {batch} would "
                "misalign the stream position"
            )
        if bool(snap["done"]):
            return 0, None, snap["summary"]
        return int(snap["next_batch"]), (snap["stages"], snap["summary"]), None

    def _wire_records(
        self,
        stream,
        checkpoint_path: Optional[str] = None,
        restore: bool = True,
    ) -> Iterator[tuple]:
        """The packed-wire fast path, with optional positional checkpoints.

        Unlike the reference — whose Merger checkpoints inside the full-speed
        pipeline (SummaryAggregation.java:127-135) but loses all other
        operator state — the snapshot here is the WHOLE fold carry plus the
        batch position, taken every ``cfg.wire_checkpoint_batches`` full
        batches and at stream end.  On restore the source replays from the
        start and already-folded batches are skipped by position (the same
        replay contract as the windowed `_merge_loop`); state is exactly-once,
        the final emission is at-least-once.  A snapshot downloads the carry
        (device->host), so the interval trades recovery granularity against
        sustained ingest rate — at the default every-64-batches the cost is
        amortized to well under a percent of stream time on a PCIe host.
        """
        from gelly_streaming_tpu.io import wire
        from gelly_streaming_tpu.utils import metrics

        cfg = stream.cfg
        packed = getattr(stream, "_wire_packed", None)
        if packed is not None:
            # replay source: buffers are already wire-format (the producer
            # chose the encoding, BDV included); the loop's only host cost
            # is the transfer itself
            bufs, batch, width, tail_pair = packed
            # (EF40/BDV x order-sensitive refusal happens in run(), which
            # guards every consumption path, not just this one)
            src = dst = None
            binned = compress = False
            n_full = len(bufs)
            total_edges = n_full * batch + (len(tail_pair[0]) if tail_pair else 0)
        else:
            src, dst, batch = stream._wire_arrays
            batch = min(batch, max(len(src), 1))
            binned, compress = self._binned_modes(cfg)
            if compress:
                # the compressed wire format: (dst, src)-binned batches ship
                # delta/varint-packed and decode on device inside the same
                # cached fold executable (ops/wire_decode.py)
                width = (wire.BDV, cfg.vertex_capacity)
            else:
                width = self._wire_width(cfg, batch)
                if binned and isinstance(width, tuple):
                    # EF40 regroups each batch by src itself — pre-sorting
                    # by dst would be re-shuffled away; skip the wasted pass
                    binned = False
            n_full = len(src) // batch
            rem = len(src) - n_full * batch
            tail_pair = (
                (src[n_full * batch :], dst[n_full * batch :]) if rem else None
            )
            total_edges = len(src)
        fused, tail = self._wire_fused_step(stream, batch, width)
        start_batch, carry_host, done_summary = self._wire_restore(
            stream, checkpoint_path if restore else None, batch
        )
        if done_summary is not None:
            # stream fully folded before the crash: re-emit (the
            # at-least-once contract) without re-folding
            out = self.transform(done_summary)
            yield out if isinstance(out, tuple) else (out,)
            return
        # committed placement so the first and later calls share one jit entry
        carry = jax.device_put(
            carry_host
            if carry_host is not None
            else (
                tuple(stage.init(cfg) for stage in stream._stages),
                self.initial_state(cfg),
            ),
            jax.devices()[0],
        )

        # -- asynchronous snapshots (the reference's Merger checkpoints are
        # also async: Flink's barrier snapshots copy state off the hot path).
        # A snapshot (a) clones the carry ON DEVICE (a jitted tree copy whose
        # output cannot alias the non-donated input, so the next fused call's
        # donation can't corrupt it), (b) starts the device->host copy in the
        # background, and (c) hands the clone to a writer thread that blocks
        # on the download and does the atomic save — the fold never waits on
        # the downlink.  maxsize=1 bounds in-flight clones (backpressure: a
        # slow disk delays the NEXT snapshot, not the stream).
        import queue as _queue
        import threading as _threading

        snap_q: Optional["_queue.Queue"] = None
        snap_writer: Optional["_threading.Thread"] = None
        snap_err: list = []

        def _write_snapshots():
            from gelly_streaming_tpu.utils.checkpoint import save_state

            while True:
                item = snap_q.get()
                if item is None:
                    return
                pos, done_flag, carry_dev = item
                try:
                    host = jax.tree.map(np.asarray, carry_dev)
                    save_state(
                        checkpoint_path,
                        {
                            "summary": host[1],
                            "stages": host[0],
                            "next_batch": np.full((), pos, np.int64),
                            "batch": np.full((), batch, np.int64),
                            "done": np.full((), done_flag, bool),
                        },
                    )
                except BaseException as e:  # surfaced on the fold thread
                    snap_err.append(e)
                    return

        def _put_snap(item) -> bool:
            """Bounded put that cannot deadlock against a crashed writer:
            re-checks the error slot between attempts (the writer may die
            while this thread is blocked on a full queue)."""
            while not snap_err:
                try:
                    snap_q.put(item, timeout=0.05)
                    return True
                except _queue.Full:
                    continue
            return False

        def snapshot(pos: int, done: bool, carry_now):
            nonlocal snap_q, snap_writer
            if snap_err:
                raise snap_err[0]
            copy = _tree_copy(carry_now)
            for leaf in jax.tree.leaves(copy):
                try:
                    leaf.copy_to_host_async()
                except AttributeError:
                    pass
            if snap_q is None:
                snap_q = _queue.Queue(maxsize=1)
                snap_writer = _threading.Thread(target=_write_snapshots, daemon=True)
                snap_writer.start()
            if not _put_snap((pos, done, copy)):
                raise snap_err[0]

        def finish_snapshots(raise_err: bool = True):
            if snap_q is not None:
                if _put_snap(None):
                    snap_writer.join()
                else:
                    # dead writer never drains the queue — drop the leftovers
                    while True:
                        try:
                            snap_q.get_nowait()
                        except _queue.Empty:
                            break
            if raise_err and snap_err:
                raise snap_err[0]

        every = cfg.wire_checkpoint_batches
        since_snap = 0
        # running emission at batch boundaries (ingestion-time panes that
        # stay on the fast path — see _wire_emit_every); recomputed against
        # the EFFECTIVE batch: a stream shorter than one batch collapses to
        # a single end-of-stream pane, which the final emission covers
        emit_every = max(0, self._wire_emit_every(cfg, batch))

        # superbatch coalescing: dispatch groups of consecutive full batches
        # in ONE device call each.  Group sizes are powers of two <= K and
        # never cross an emission or snapshot boundary, so the observable
        # record/recovery sequence is identical to per-batch dispatch.
        from gelly_streaming_tpu.core.stream import plan_superbatch_groups

        boundaries = []
        if emit_every:
            boundaries.append((emit_every, start_batch))
        if checkpoint_path and every:
            boundaries.append((every, 0))
        groups = plan_superbatch_groups(
            n_full - start_batch, max(1, cfg.superbatch), boundaries
        )

        def device_buffers():
            """(group size, device buffer) pairs: ``uint8[nbytes]`` for
            size-1 groups (the historical per-batch path), ``uint8[g,
            nbytes]`` stacked groups otherwise.  Packing/stacking runs on
            the Prefetcher's background thread; the transfer on its second
            — one transfer per GROUP, so superbatching also amortizes
            per-transfer overhead."""
            offsets = []
            o = 0
            for g in groups:
                offsets.append((o, g))
                o += g
            if packed is not None:

                def prep(item):
                    o, g = item
                    if g == 1:
                        buf = bufs[start_batch + o]
                        metrics.wire_record_batch(1, batch, buf.nbytes)
                        return 1, buf
                    group_bufs = bufs[start_batch + o : start_batch + o + g]
                    widest = max(b.nbytes for b in group_bufs)
                    if all(b.nbytes == widest for b in group_bufs):
                        arena = np.stack(group_bufs)
                    else:
                        # variable-size (BDV) replay buffers: pad to the
                        # group max — trailing zeros decode as dropped
                        # empty varint groups
                        arena = np.zeros((g, widest), np.uint8)
                        for j, b in enumerate(group_bufs):
                            arena[j, : b.nbytes] = b
                    metrics.wire_record_batch(g, g * batch, arena.nbytes)
                    return g, arena

            else:
                from gelly_streaming_tpu.io import ingest as ingest_mod

                workers = ingest_mod.resolve_workers(cfg.ingest_workers)
                nbytes = wire.wire_nbytes(batch, width) if not compress else 0

                def prep(item):
                    o, g = item
                    i0 = start_batch + o
                    if compress:
                        # bin + delta/varint pack (sort on this pack thread,
                        # group rows across the ingest pool); buffers bucket
                        # to stable shapes, so same-regime batches reuse one
                        # compiled decode+fold executable
                        if g == 1:
                            buf = wire.pack_edges_bdv(
                                src[i0 * batch : (i0 + 1) * batch],
                                dst[i0 * batch : (i0 + 1) * batch],
                                cfg.vertex_capacity,
                                record_stats=True,
                            )
                        else:
                            buf = ingest_mod.pack_bdv_group(
                                src,
                                dst,
                                i0,
                                g,
                                batch,
                                cfg.vertex_capacity,
                                workers,
                            )
                        metrics.wire_record_batch(g, g * batch, buf.nbytes)
                        return g, buf
                    if g == 1:
                        s_b = src[i0 * batch : (i0 + 1) * batch]
                        d_b = dst[i0 * batch : (i0 + 1) * batch]
                        if binned:
                            s_b, d_b = wire.sort_edges_binned(
                                s_b, d_b, cfg.vertex_capacity, record_stats=True
                            )
                        buf = wire.pack_edges(s_b, d_b, width)
                        metrics.wire_record_batch(1, batch, buf.nbytes)
                        return 1, buf
                    # pack straight into the group arena (the transfer
                    # layout): no re-copy between pack and device_put
                    arena = np.empty((g, nbytes), np.uint8)
                    if binned:
                        ingest_mod.pack_binned_rows_into(
                            src,
                            dst,
                            i0,
                            g,
                            batch,
                            width,
                            cfg.vertex_capacity,
                            arena,
                            workers,
                        )
                    else:
                        ingest_mod.pack_rows_into(
                            src, dst, i0, g, batch, width, arena, workers
                        )
                    metrics.wire_record_batch(g, g * batch, arena.nbytes)
                    return g, arena

            with wire.Prefetcher(offsets, prep, depth=cfg.prefetch_depth) as pf:
                yield from pf

        pending_final = True
        try:
            pos = start_batch
            # hot-loop: wire fast-path fold (no per-batch host syncs)
            for g, dev in device_buffers():
                if g == 1:
                    carry = fused(carry, dev)
                else:
                    carry = self._wire_scan_step(stream, batch, width, g)(
                        carry, dev
                    )
                pos += g
                if emit_every and pos % emit_every == 0:
                    # the donated carry IS the running merged summary
                    # (Merger semantics): emit the pane's running record
                    # without leaving the fast path.  CLONE first — the next
                    # fused call donates the carry's buffers, which would
                    # delete them out from under the emitted record
                    out = self.transform(_tree_copy(carry[1]))
                    yield out if isinstance(out, tuple) else (out,)
                    # a stream ending exactly on a pane boundary with no
                    # tail has nothing further to emit
                    pending_final = pos != n_full or tail_pair is not None
                since_snap += g
                if checkpoint_path and every and since_snap >= every:
                    # the snapshot clones the carry on device BEFORE the next
                    # fused call donates it away
                    snapshot(pos, False, carry)
                    since_snap = 0
            # hot-loop-end
            if tail_pair is not None:
                rem = len(tail_pair[0])
                mask = np.zeros((batch,), bool)
                mask[:rem] = True
                pad_s = np.zeros((batch,), np.int32)
                pad_d = np.zeros((batch,), np.int32)
                pad_s[:rem] = tail_pair[0]
                pad_d[:rem] = tail_pair[1]
                carry = tail(
                    carry,
                    jnp.asarray(pad_s),
                    jnp.asarray(pad_d),
                    jnp.asarray(mask),
                )
            if total_edges == 0:
                return
            if pending_final:
                out = self.transform(carry[1])
                # emit BEFORE the final snapshot: a crash between the two
                # re-emits on recovery (at-least-once) instead of dropping
                # the record
                yield out if isinstance(out, tuple) else (out,)
            if checkpoint_path:
                snapshot(n_full, True, carry)
        except BaseException:
            # includes GeneratorExit from an abandoning consumer: shut the
            # writer down without masking the in-flight exception
            finish_snapshots(raise_err=False)
            raise
        finish_snapshots()

    def _checkpoint_like(self, cfg):
        """Checkpoint structure: summary + presence flag + stream position.

        ``global_done`` marks the untimed single global pane as folded —
        it has no orderable id (-1), so it needs its own done flag for
        replay-safe skipping.
        """
        return {
            "summary": self.initial_state(cfg),
            "has_summary": np.zeros((), bool),
            "last_window": np.full((), -1, np.int64),
            "global_done": np.zeros((), bool),
        }

    def run(
        self,
        stream,
        checkpoint_path: Optional[str] = None,
        restore: bool = True,
    ) -> OutputStream:
        """Execute over an EdgeStream (entered via GraphStream.aggregate,
        GraphStream.java:139-140 / SimpleEdgeStream.java:100-102).

        With ``checkpoint_path``, the running summary AND the stream position
        (last closed window id) are snapshot after every window close and
        restored on start — the Merger's ListCheckpointed behavior
        (SummaryAggregation.java:127-135), generalized to the whole summary
        pytree plus position (closing the reference's unsaved-state gap).  On
        restore, panes already folded before the snapshot are skipped, so the
        source may simply replay from the beginning.  State is exactly-once;
        emissions after the last snapshot are re-emitted (at-least-once), as
        in the reference's Merger.  The untimed single global pane resumes
        only for an unchanged replay (it has no sub-pane position — a longer
        replayed stream's extra untimed edges would be skipped with it).

        Execution strategy by config (the reference picks its pipeline at
        graph-build time the same way): wire-backed single-shard streams ride
        the packed-wire fast path; ``cfg.num_shards > 1`` with enough devices
        runs the real sharded data plane (MeshAggregationRunner); otherwise
        partitions are simulated sequentially (the MiniCluster shape).  All
        paths share the Merger/checkpoint loop (`_merge_loop`)."""
        if checkpoint_path and stream.cfg.ingest_window_ms:
            raise ValueError(
                "wall-clock ingestion panes (ingest_window_ms) are not "
                "replay-deterministic: a resume would skip panes by id that "
                "cover different edges than the crashed run's; use "
                "ingest_window_edges for checkpointed runs"
            )
        packed = getattr(stream, "_wire_packed", None)
        if packed is not None and isinstance(packed[2], tuple) and not self.order_free:
            # EF40/BDV replay buffers carry per-batch sorted multisets;
            # EVERY consumption path (fast, mesh, simulated) would see
            # reordered edges, so refuse up front rather than only on the
            # fast path
            raise ValueError(
                f"{packed[2][0]} replay buffers carry a sorted multiset; "
                "this aggregation is not order-free"
            )
        if self._wire_eligible(stream):
            return OutputStream(
                lambda: self._wire_records(stream, checkpoint_path, restore)
            )
        if self._mesh_wire_eligible(stream):
            runner = self._mesh_runner(stream.cfg)
            return OutputStream(
                lambda: runner.wire_records(stream, checkpoint_path, restore)
            )
        cfg = stream.cfg
        if cfg.num_shards > 1 and cfg.num_shards <= len(jax.devices()):
            return self._mesh_runner(cfg).run(
                stream, checkpoint_path=checkpoint_path, restore=restore
            )
        window_ms = self.window_ms or cfg.window_ms
        n_parts = self._num_partitions(cfg)

        if cfg.superbatch > 1 and n_parts == 1:
            # superbatch the TIME plane: up to K closed panes
            # (core/windows.group_panes) fold in ONE vmapped device call
            # over a row-per-window layout; the shared Merger loop still
            # merges/emits/checkpoints per window, so the record sequence
            # and recovery semantics are identical to per-pane dispatch.
            def records_sb() -> Iterator[tuple]:
                skip_through, skip_global = self.restored_position(
                    cfg, checkpoint_path, restore
                )
                return self._merge_loop(
                    cfg,
                    self._superpane_folds(
                        stream, window_ms, skip_through, skip_global
                    ),
                    lambda summary: summary,
                    checkpoint_path,
                    restore,
                    unwrap=True,
                )

            return OutputStream(records_sb)

        from gelly_streaming_tpu.core import async_exec

        if async_exec.resolve_depth(cfg) > 0 and n_parts == 1:
            # asynchronous window pipeline: pane padding on the pack thread
            # into reusable arenas, transfers on the second thread, folds
            # dispatched without waiting, emissions drained in window order
            # (core/async_exec.py) — bit-identical record sequence to the
            # synchronous fold_pane path below
            return OutputStream(
                lambda: self._async_pane_records(
                    stream, window_ms, checkpoint_path, restore
                )
            )

        # An order-free fold obeys the combine contract the partitioned
        # folds rely on, so fold(S, E) == combine(S, fold(initial, E)): with
        # one partition each pane folds straight into the running summary
        # and the Merger skips the combine (for CC a second union-find over
        # all C vertices).  The summary is not donated: the previous
        # window's record may still be on its way to the host.
        into_running = (
            self.order_free and not self.transient_state and n_parts == 1
        )

        def fold_pane(pane: WindowPane, running=None):
            # destination-bin the pane first (order-free folds only; no-op
            # otherwise): the round-robin strided slices of a sorted pane
            # stay sorted, so each partition's scatter is segment-local
            pane = self._maybe_bin_pane(cfg, pane)
            partials = []
            for part in range(n_parts):
                # Round-robin partitioning of the pane stands in for the
                # reference's source-subtask tagging (PartitionMapper,
                # SummaryBulkAggregation.java:93-106).
                sel = np.arange(len(pane.src)) % n_parts == part
                if not sel.any():
                    continue
                # Pad to the next power of two so varying pane sizes hit a
                # small, bounded set of compiled kernel shapes.
                n = int(sel.sum())
                padded = max(1, 1 << (n - 1).bit_length())
                mask = np.zeros((padded,), bool)
                mask[:n] = True

                def pad(a, fill=0):
                    out = np.full((padded,) + a.shape[1:], fill, a.dtype)
                    out[:n] = a[sel]
                    return out

                src, dst = pad(pane.src), pad(pane.dst)
                val = None if pane.val is None else jax.tree.map(pad, pane.val)
                tracing.end_cut()  # the window's host arrays are ready
                with tracing.span("gelly.pane.put"):
                    src = jnp.asarray(src, jnp.int32)
                    dst = jnp.asarray(dst, jnp.int32)
                    val = None if val is None else jax.tree.map(jnp.asarray, val)
                    mask = jnp.asarray(mask)
                with tracing.span("gelly.fold.dispatch"):
                    base = self.initial_state(cfg) if running is None else running
                    state = self._update_j(base, src, dst, val, mask)
                partials.append(state)
            if not partials:
                return None
            return self._combine_partials(partials, cfg)

        def records() -> Iterator[tuple]:
            return self._merge_loop(
                cfg,
                stream_panes(stream, window_ms, mark_close=True),
                fold_pane,
                checkpoint_path,
                restore,
                into_running=into_running,
            )

        return OutputStream(records)

    # -- cross-tenant fused dispatch (runtime/manager.py cohorts) -------------

    def fused_eligible(self, stream) -> bool:
        """True when this descriptor/stream pair would ride the plain
        single-partition synchronous windowed plane — the only plane the
        cross-tenant fused protocol replaces.  Wire, mesh-wire, sharded,
        superbatch, and async jobs keep their own (already-batched or
        already-pipelined) planes and simply dispatch solo under a fused
        manager."""
        cfg = stream.cfg
        if self._wire_eligible(stream) or self._mesh_wire_eligible(stream):
            return False
        if cfg.num_shards > 1 and cfg.num_shards <= len(jax.devices()):
            return False
        if self._num_partitions(cfg) != 1:
            return False
        if cfg.superbatch > 1:
            return False
        from gelly_streaming_tpu.core import async_exec

        return async_exec.resolve_depth(cfg) == 0

    def run_fused(
        self,
        stream,
        checkpoint_path: Optional[str] = None,
        restore: bool = True,
    ) -> Iterator[tuple]:
        """The windowed plane as a fused-dispatch COHORT MEMBER: a
        bidirectional generator that parks each window's padded fold at a
        ``FoldRequest`` yield instead of dispatching it.

        The consumer (the manager's scheduler) ``send()``s back either a
        fused per-row partial — its row of one vmapped mega-fold over N
        tenant jobs' same-key requests — or ``None``, which makes the
        generator fold the SAME padded arrays itself through the same
        executable chain as the plain plane (the bit-exact solo oracle).
        A consumer that does not understand the protocol resumes with
        plain ``next()`` — Python defines that as ``send(None)`` — so a
        dropped/parked quantum, a paused-then-resumed job, or a naive
        iterator consumer all degrade to correct solo dispatch rather
        than losing the window.  The plane is ``_merge_loop`` with
        ``_fused_fold`` as its fold step, so everything downstream of the
        fold (running merge order, transform, at-least-once emission,
        positional checkpoints, transient resets) is the sync plane's, and
        fused and solo record sequences are bit-identical (pinned by
        tests/test_fused_dispatch.py).  Fused-eligible streams only
        (``fused_eligible``: the synchronous single-partition plane).
        """
        cfg = stream.cfg
        if checkpoint_path and cfg.ingest_window_ms:
            raise ValueError(
                "wall-clock ingestion panes (ingest_window_ms) are not "
                "replay-deterministic: a resume would skip panes by id that "
                "cover different edges than the crashed run's; use "
                "ingest_window_edges for checkpointed runs"
            )
        return self._merge_loop(
            cfg,
            stream_panes(stream, self.window_ms or cfg.window_ms, mark_close=True),
            functools.partial(self._fused_fold, cfg),
            checkpoint_path,
            restore,
        )

    def _fused_fold(self, cfg: StreamConfig, pane: WindowPane):
        """The fused cohort plane's fold step (see ``run_fused``): a
        generator that parks the pane's padded fold at a ``FoldRequest``
        and returns its partial from the initial state — a cohort row
        cannot read its job's running summary, so the Merger combines it
        (for an order-free fold those records equal the sync plane's
        fold-into-running ones)."""
        pane = self._maybe_bin_pane(cfg, pane)
        n = pane.num_edges
        if n == 0:
            return None  # empty pane: the sync fold returns None too
        # the sync plane's pow2 pad, materialized as the offered row
        src, dst, msk = pad_pane_edges(pane)
        e_pad = len(src)
        val = None
        if pane.val is not None:

            def pad(a):
                out = np.zeros((e_pad,) + a.shape[1:], a.dtype)
                out[:n] = a
                return out

            val = jax.tree.map(pad, pane.val)
        has_val = val is not None
        tracing.end_cut()
        partial = yield FoldRequest(
            key=(self.cache_token, cfg, has_val, e_pad),
            fold=self._superpane_fold_fn(cfg, has_val),
            split=functools.partial(self._superpane_split_fn, cfg),
            src=src,
            dst=dst,
            val=val,
            mask=msk,
            window_id=pane.window_id,
            edges=n,
        )
        if partial is None:
            # solo fallback: no same-key peers this round (or a
            # protocol-naive resume) — fold the identical padded arrays
            # through the plain plane's executable
            with tracing.span("gelly.pane.put"):
                put = (
                    jnp.asarray(src),
                    jnp.asarray(dst),
                    None if val is None else jax.tree.map(jnp.asarray, val),
                    jnp.asarray(msk),
                )
            with tracing.span("gelly.fold.dispatch"):
                partial = self._update_j(self.initial_state(cfg), *put)
        return partial

    def restored_position(self, cfg, checkpoint_path, restore=True):
        """(last folded window id, global pane done) of the windowed
        snapshot at ``checkpoint_path`` — for gating pane work ahead of the
        Merger loop, which restores its own state and stays the source of
        truth, and for the served path's resume cursors.  (-1, False) when
        there is nothing to restore, or the snapshot is another plane's."""
        try:
            state = MergerState(self, cfg, checkpoint_path, restore)
        except ValueError:
            return -1, False
        return state.start_after, state.global_done

    def _async_pane_records(
        self,
        stream,
        window_ms: int,
        checkpoint_path: Optional[str],
        restore: bool,
    ) -> Iterator[tuple]:
        """Single-partition windowed plane on the async pipeline.

        Per pane: padding to the pow2 fold bucket happens on the
        prefetcher's pack thread, writing into reusable transfer arenas
        (async_exec.ArenaPool — recycled only once the consuming fold is
        known complete, since a CPU device_put may alias the host buffer);
        the device_put rides the transfer thread; the fold dispatches here
        through the SAME cached ``_update_j`` executable the synchronous
        ``fold_pane`` traces, so the per-window partials — and therefore
        the merged emission sequence — are bit-identical to the sync path
        (pinned by tests/test_async_windows.py).  Emission/checkpoint
        ordering rides the async Merger (`_merge_loop` -> async_merge_loop).
        """
        from gelly_streaming_tpu.core import async_exec
        from gelly_streaming_tpu.io import wire as wire_mod

        cfg = stream.cfg
        depth = async_exec.resolve_depth(cfg)
        skip_through, skip_global = self.restored_position(
            cfg, checkpoint_path, restore
        )
        # retention cap sized to the pipeline's own in-flight bound (two
        # int32 arenas per pane x panes across the prefetch + completion
        # queues), so steady state recycles instead of reallocating
        pool = async_exec.ArenaPool(per_shape=2 * depth + 6)
        # window spans originate HERE, on the prefetcher's pack thread:
        # each sampled pane gets its trace id before packing and carries
        # the span through transfer/dispatch/drain in its meta tuple
        # (sampling off = one branch per pane, nothing else)
        span_sampler = tracing.sampler(cfg, "windowed")

        def prepare(pane: WindowPane):
            already = (0 <= pane.window_id <= skip_through) or (
                pane.window_id == -1 and skip_global
            )
            n = pane.num_edges
            if already or n == 0:
                return (pane, None, None), None
            span = (
                span_sampler.begin(pane.window_id)
                if span_sampler is not None
                else None
            )
            t_pack = time.perf_counter()
            # destination binning rides this pack thread too (order-free
            # folds only; no-op otherwise) — the dispatch loop never sorts
            pane = self._maybe_bin_pane(cfg, pane)
            padded = max(1, 1 << (n - 1).bit_length())
            src = pool.acquire((padded,), np.int32)
            dst = pool.acquire((padded,), np.int32)
            mask = pool.acquire((padded,), bool)
            src[:n] = pane.src
            dst[:n] = pane.dst
            mask[:n] = True
            val = None
            if pane.val is not None:

                def pad(a):
                    out = np.zeros((padded,) + a.shape[1:], a.dtype)
                    out[:n] = a
                    return out

                val = jax.tree.map(pad, pane.val)
            if span is not None:
                span.mark("pack", t_pack)
                span.annotate(edges=n)
            return (pane, (src, dst, mask), span), (src, dst, val, mask)

        def fold_prepared(item):
            (pane, arenas, _span), dev = item
            if arenas is None:
                return None
            src_d, dst_d, val_d, mask_d = dev
            return self._update_j(
                self.initial_state(cfg), src_d, dst_d, val_d, mask_d
            )

        def release(item):
            (pane, arenas, _span), _dev = item
            if arenas is not None:
                pool.release(*arenas)  # arena-live-until: drain

        with wire_mod.Prefetcher(
            stream_panes(stream, window_ms), prepare, depth=depth + 1
        ) as pf:
            yield from self._merge_loop(
                cfg,
                ((meta[0], (meta, dev)) for meta, dev in pf),
                fold_prepared,
                checkpoint_path,
                restore,
                unwrap=True,
                release=release,
            )

    def _superpane_fold_fn(self, cfg: StreamConfig, has_val: bool):
        """Compiled K-window fold: ONE dispatch produces every coalesced
        window's partial summary via a vmap over per-window edge rows.

        The row layout ([K, E_max]: one padded row per window) keeps the
        dispatch's total work at K * E_max ~= the sum of the pane sizes for
        balanced windows — NOT K times the concatenated run, which a
        mask-per-window fold over the flat [E_total] layout would cost."""
        token = self.cache_token

        def make():
            def fold(src_k, dst_k, val_k, mask_k):
                def one(s, d, v, m):
                    return self.update(self.initial_state(cfg), s, d, v, m)

                if val_k is None:
                    return jax.vmap(lambda s, d, m: one(s, d, None, m))(
                        src_k, dst_k, mask_k
                    )
                return jax.vmap(one)(src_k, dst_k, val_k, mask_k)

            return fold

        return compile_cache.cached_jit(
            ("superpane_fold", token, cfg, has_val), make
        )

    def _superpane_split_fn(self, cfg: StreamConfig, rows: int):
        """Compiled cohort drain: ONE dispatch slices a ``[rows, ...]``
        stacked mega-fold result into per-row partial pytrees (row i =
        job i's window partial, still on device).

        Draining with an eager per-row ``a[i]`` slice instead costs one
        device dispatch per job per cohort — measured ~2x the fused fold
        itself at 16 rows — which would hand back most of the dispatch
        amortization the mega-fold just bought.  Keyed per pow2 row
        bucket, so 1..16-job tenancy reuses at most four traces."""
        token = self.cache_token

        def make():
            def split(states):
                return tuple(
                    jax.tree.map(lambda a, i=i: a[i], states)
                    for i in range(rows)
                )

            return split

        return compile_cache.cached_jit(
            ("superpane_split", token, cfg, rows), make
        )

    def _superpane_folds(
        self, stream, window_ms: int, skip_through: int = -1, skip_global: bool = False
    ):
        """(pane, partial summary) pairs with up to ``cfg.superbatch``
        consecutive panes folded per device dispatch.

        The per-window partial equals the per-pane path's fold exactly: the
        update kernel sees that window's edges (arrival order preserved)
        with padding masked out.  Both row count and row length bucket to
        powers of two (at most log2(K)+1 x shape-bucket compiled variants);
        rows past the group's real panes are fully masked and their
        initial-state outputs discarded.

        ``skip_through``/``skip_global`` gate RESTORED positions: panes a
        checkpoint already folded are dropped here without any device work
        (the merge loop would discard them unfolded anyway — the per-pane
        path never folds them either, and recovery must not pay a full
        re-fold of the pre-crash stream).
        """
        from gelly_streaming_tpu.core import async_exec
        from gelly_streaming_tpu.core.windows import group_panes

        cfg = stream.cfg
        live = (
            self._maybe_bin_pane(cfg, p)
            for p in stream_panes(stream, window_ms)
            if not (
                (0 <= p.window_id <= skip_through)
                or (p.window_id == -1 and skip_global)
            )
        )
        groups = group_panes(live, cfg.superbatch)
        depth = async_exec.resolve_depth(cfg)
        if depth > 0:
            # async pipeline: row assembly on the prefetcher's pack thread
            # (ingest-pool parallel row fill), transfer on its second,
            # folds dispatched here without waiting — same executables and
            # per-window partials as the inline path below
            from gelly_streaming_tpu.io import wire as wire_mod

            def prep(panes):
                return tuple(panes), self._assemble_superpane_rows(panes)

            with wire_mod.Prefetcher(groups, prep, depth=depth + 1) as pf:
                # hot-loop: superpane dispatch (no per-group host syncs)
                for panes, dev in pf:
                    src_d, dst_d, val_d, mask_d = dev
                    fold = self._superpane_fold_fn(cfg, val_d is not None)
                    states = fold(src_d, dst_d, val_d, mask_d)
                    for i, pane in enumerate(panes):
                        yield pane, jax.tree.map(lambda a, i=i: a[i], states)
                # hot-loop-end
            return
        for panes in groups:
            src_k, dst_k, val_k, mask_k = self._assemble_superpane_rows(panes)
            fold = self._superpane_fold_fn(cfg, val_k is not None)
            states = fold(
                jnp.asarray(src_k),
                jnp.asarray(dst_k),
                None if val_k is None else jax.tree.map(jnp.asarray, val_k),
                jnp.asarray(mask_k),
            )
            for i, pane in enumerate(panes):
                yield pane, jax.tree.map(lambda a, i=i: a[i], states)

    def _assemble_superpane_rows(self, panes):
        """Host assembly of a pane group's [rows, E_pad] fold layout (the
        transfer layout `_superpane_fold_fn` consumes): numpy
        (src_k, dst_k, val_k | None, mask_k).  Row filling shards across the
        ingest worker pool (io/ingest.fill_pane_rows_into) — one row per
        pane, each worker writing its slice in place."""
        from gelly_streaming_tpu.io import ingest as ingest_mod

        k = len(panes)
        rows = max(1, 1 << (k - 1).bit_length())  # pow2 bucket, <= K
        e_max = max(p.num_edges for p in panes)
        e_pad = max(1, 1 << (e_max - 1).bit_length())
        src_k = np.zeros((rows, e_pad), np.int32)
        dst_k = np.zeros((rows, e_pad), np.int32)
        mask_k = np.zeros((rows, e_pad), bool)
        ingest_mod.fill_pane_rows_into(panes, src_k, dst_k, mask_k)
        val_k = None
        if any(p.val is not None for p in panes):
            proto = next(p.val for p in panes if p.val is not None)
            val_k = jax.tree.map(
                lambda a: np.zeros((rows, e_pad) + a.shape[1:], a.dtype),
                proto,
            )
            for i, pane in enumerate(panes):
                if pane.val is not None:

                    def fill(buf, a):
                        buf[i, : len(a)] = a
                        return buf

                    val_k = jax.tree.map(fill, val_k, pane.val)
        return src_k, dst_k, val_k, mask_k

    def _mesh_runner(self, cfg: StreamConfig) -> "MeshAggregationRunner":
        """Cached sharded runner for cfg.num_shards (compiled steps persist)."""
        runner = getattr(self, "_mesh_runner_cache", None)
        if runner is None or runner.num_shards != cfg.num_shards:
            from gelly_streaming_tpu.parallel.mesh import make_mesh

            runner = MeshAggregationRunner(self, mesh=make_mesh(cfg.num_shards))
            self._mesh_runner_cache = runner
        return runner

    def _merge_loop(
        self,
        cfg: StreamConfig,
        panes: Iterator,
        fold_pane: Callable,
        checkpoint_path: Optional[str],
        restore: bool,
        unwrap: bool = False,
        release: Optional[Callable] = None,
        into_running: bool = False,
    ) -> Iterator[tuple]:
        """The Merger: running merge + emission + positional checkpointing
        (SummaryAggregation.java:93-135), shared by every windowed plane
        (simulated, fused, superbatch, async, mesh) so their recovery
        semantics cannot diverge; ``MergerState`` holds its state, snapshot
        layout and pane route.

        ``fold_pane(payload) -> summary | None`` supplies the per-pane
        partial fold+combine; everything downstream (merge order,
        transient reset, at-least-once emission, snapshot layout) is
        common.  With ``into_running`` the loop hands it the running
        summary, ``fold_pane(payload, running)`` (None before the first
        window; the restored one after a restore), and takes the result as
        the new running summary with no combine: order-free
        single-partition folds fold straight into it, and the
        owner-sharded plane folds into persistent cross-window blocks and
        returns the running summary itself.  A fold step may also be a
        generator that parks at a ``FoldRequest`` (the fused cohort
        plane, ``run_fused``): the loop yields the request to its consumer
        and takes the generator's return value as the pane's summary.
        With ``unwrap`` the iterator yields (pane, payload) pairs —
        position/window logic reads the pane, the payload goes to
        ``fold_pane`` (the mesh runner attaches prefetched device buffers
        this way).

        With ``cfg.async_windows`` > 0 the loop runs in its asynchronous
        form (core/async_exec.async_merge_loop): folds dispatch without
        waiting and emissions/checkpoints resolve through a completion
        queue in window order — same record sequence and recovery
        semantics, minus the per-window host round trip.  ``release``
        (async only) recycles a window's transfer arenas once its fold is
        known complete.
        """
        from gelly_streaming_tpu.core import async_exec

        depth = async_exec.resolve_depth(cfg)
        if depth > 0:
            yield from async_exec.async_merge_loop(
                self,
                cfg,
                panes,
                fold_pane,
                checkpoint_path,
                restore,
                unwrap=unwrap,
                depth=depth,
                release=release,
                into_running=into_running,
            )
            return
        state = MergerState(self, cfg, checkpoint_path, restore)
        # span sampling resolved ONCE: when off (the default) the loop
        # below pays a single `is not None` branch per window
        span_sampler = tracing.sampler(cfg, "merge")
        for item in panes:
            pane, payload = item if unwrap else (item, item)
            if state.folded(pane.window_id):
                tracing.drop_cut()
                continue  # folded before the snapshot: replay-safe
            span = (
                span_sampler.begin(pane.window_id)
                if span_sampler is not None
                else None
            )
            # the window's spans (cut, put, fold, combine here; sink wait
            # and copy on the consumer's side) share its (job, window)
            tracing.enter_window(pane.window_id, span)
            if into_running:
                pane_summary = fold_pane(payload, state.running)
            else:
                pane_summary = fold_pane(payload)
            if isinstance(pane_summary, GeneratorType):
                # a cohort member's fold parks at its FoldRequest; the
                # round runs other jobs' windows on this thread
                pane_summary = yield from pane_summary
                tracing.scope_window(pane.window_id, span)
            tracing.end_cut()
            if pane_summary is None:
                tracing.leave_window()
                continue
            # Merger: non-blocking running merge, one emission per window
            # close (SummaryAggregation.java:107-119).
            with tracing.span("gelly.combine.dispatch"):
                out = self.transform(state.merge(pane_summary, into_running))
            # Emit BEFORE snapshotting: a crash between the two re-emits
            # this window on recovery (at-least-once emission) instead of
            # dropping it (at-most-once would lose sink data).  The
            # emission (window, close, sampled span) goes to the record's
            # consumer, which ends the window once the record is on host.
            emission = tracing.emit()
            yield out if isinstance(out, tuple) else (out,)
            tracing.settle(emission)
            state.advance(pane.window_id)
            state.save(state.running)
            state.reset()


class SummaryBulkAggregation(SummaryAggregation):
    """Flat combine strategy (SummaryBulkAggregation.java:51-90)."""


class SummaryTreeAggregation(SummaryAggregation):
    """Log-depth combine tree (SummaryTreeReduce.java:47-123): partials merge
    in rounds of ``degree``-ary groups (the reference re-keys partitions by
    ``partition/2`` per level and exposes a configurable ``degree`` :53-64,
    defaulting to the stream parallelism :75) — same fixed point as the flat
    fold for associative combines, fewer sequential merge steps.

    ``degree`` here defaults to ``cfg.tree_degree``; pass it explicitly to
    mirror the reference's constructor knob.
    """

    def __init__(self, window_ms: Optional[int] = None, degree: Optional[int] = None):
        super().__init__(window_ms)
        self.degree = degree

    def _tree_fanin(self, cfg: StreamConfig) -> int:
        return max(2, self.degree or cfg.tree_degree)

    def _fold_partials(self, items, combine2, fanin: int = 2):
        level = list(items)
        while len(level) > 1:
            nxt = []
            for i in range(0, len(level), fanin):
                group = level[i : i + fanin]
                acc = group[0]
                for it in group[1:]:
                    acc = combine2(acc, it)
                nxt.append(acc)
            level = nxt
        return level[0]


class MeshAggregationRunner:
    """Execute a SummaryAggregation's window fold+combine over a device mesh.

    The single-device ``run`` above *simulates* partitions sequentially (the
    MiniCluster shape); this runner is the real multi-chip data plane: each
    window pane is bucketed round-robin across shards on the host, and ONE
    jitted ``shard_map`` step does the per-shard fold (updateFun over the
    shard's bucket), an ``all_gather`` of the partial summaries over the mesh
    axis (riding ICI), and the combine fold — replacing the reference's
    keyBy -> per-partition windowed fold -> timeWindowAll network pipeline
    (SummaryBulkAggregation.java:76-83) with collectives.

    The combine strategy (flat vs tree) comes from the descriptor class
    itself (``_fold_partials``), exactly as in the simulated runtime; with
    one all_gather the communication is identical either way (ICI collectives
    are already ring/tree structured), only the local combine order changes.
    Shards whose bucket is empty are excluded from the combine by masking —
    matching the simulated runtime, which skips empty partitions, so
    descriptors whose initial state is not a combine identity still agree.

    The running cross-window merge stays on device, replicated over the mesh.
    """

    def __init__(self, agg: SummaryAggregation, mesh=None):
        from gelly_streaming_tpu.parallel import mesh as mesh_mod

        self.agg = agg
        self.mesh = mesh if mesh is not None else mesh_mod.make_mesh()
        self._axis = mesh_mod.SHARD_AXIS
        self._step_cache = {}

    @property
    def num_shards(self) -> int:
        return self.mesh.devices.size

    def _combine_over_mesh(self, cfg: StreamConfig):
        """``(state, has_data) -> state``: reduce every shard's partial into
        the same replicated combined state, inside shard_map.

        Uses the descriptor's collective combine (``mesh_combine_states`` —
        log-depth XLA collectives over ICI) when it supplies one, else
        all_gather + the descriptor's combine strategy with empty shards
        masked out (descriptors whose initial state is not a combine
        identity must not see initial_state partials — the simulated runtime
        skips empty partitions the same way)."""
        agg, axis, n = self.agg, self._axis, self.num_shards
        collective = agg.mesh_combine_states(cfg, axis)
        if collective is not None:
            return collective

        def masked_combine(a, b):
            """Combine (state, valid) pairs, ignoring empty-shard partials."""
            sa, va = a
            sb, vb = b
            merged = agg.combine(sa, sb)
            both = va & vb
            state = jax.tree.map(
                lambda m, x, y: jnp.where(both, m, jnp.where(va, x, y)),
                merged,
                sa,
                sb,
            )
            return state, va | vb

        def gather_combine(state, has_data):
            gathered = jax.tree.map(
                lambda a: jax.lax.all_gather(a, axis), state  # gather-ok: replicated fallback combine — the equivalence oracle for the sharded plane
            )
            has = jax.lax.all_gather(has_data, axis)  # gather-ok: replicated fallback combine — the equivalence oracle for the sharded plane
            parts = [
                (jax.tree.map(lambda g: g[i], gathered), has[i])
                for i in range(n)
            ]
            acc, _ = agg._fold_partials(
                parts, masked_combine, agg._tree_fanin(cfg)
            )
            return acc

        return gather_combine

    def _shard_fold_combine(self, cfg: StreamConfig):
        """The shared in-shard_map tail: fold this shard's bucket with
        updateFun, then reduce the partials over the mesh axis."""
        agg = self.agg
        combine = self._combine_over_mesh(cfg)

        def fold_combine(src, dst, val, mask):
            state = agg.initial_state(cfg)
            state = agg.update(state, src, dst, val, mask)
            return combine(state, jnp.any(mask))

        return fold_combine

    def _pane_step(self, cfg: StreamConfig, cap: int, has_val: bool):
        """Compiled sharded fold+combine for panes bucketed at capacity cap
        (raw-array ingest: panes with edge values)."""
        # fan-in is baked into the compiled combine tree -> part of the key
        key = (cfg, cap, has_val, self.agg._tree_fanin(cfg))
        if key in self._step_cache:
            return self._step_cache[key]
        from jax.sharding import PartitionSpec as P

        from gelly_streaming_tpu.parallel.mesh import shard_map

        fold_combine = self._shard_fold_combine(cfg)

        def step(src, dst, val, mask):
            # [1, cap] per shard inside shard_map: fold this shard's bucket
            return fold_combine(
                src[0],
                dst[0],
                None if val is None else jax.tree.map(lambda a: a[0], val),
                mask[0],
            )

        spec = P(self._axis)
        val_spec = spec if has_val else None
        fn = jax.jit(  # graft: disable=RAWJIT — keyed per-mesh in self._step_cache; a Mesh is not a stable process-global cache key
            shard_map(
                step,
                mesh=self.mesh,
                in_specs=(spec, spec, val_spec, spec),
                out_specs=P(),
            )
        )
        self._step_cache[key] = fn
        return fn

    def _pane_step_wire(self, cfg: StreamConfig, cap: int, width):
        """Compiled sharded fold+combine consuming PACKED per-shard wire rows.

        The value-less fast form (VERDICT r2 missing #3): each shard receives
        its bucket as a wire-format byte row + a fill count, unpacks on
        device (the byte combines fuse into the fold), and runs the same
        gather+combine tail as the raw path — the sharded analog of the
        single-chip `_wire_fused_step`, so the mesh plane rides the same
        optimized ingest the single-device path does.
        """
        key = (cfg, cap, str(width), self.agg._tree_fanin(cfg), "wire")
        if key in self._step_cache:
            return self._step_cache[key]
        from jax.sharding import PartitionSpec as P

        from gelly_streaming_tpu.io import wire
        from gelly_streaming_tpu.parallel.mesh import shard_map

        fold_combine = self._shard_fold_combine(cfg)

        def step(rows, counts):
            src, dst = wire.unpack_edges(rows[0], cap, width)
            mask = jnp.arange(cap, dtype=jnp.int32) < counts[0]
            return fold_combine(src, dst, None, mask)

        spec = P(self._axis)
        fn = jax.jit(  # graft: disable=RAWJIT — keyed per-mesh in self._step_cache; a Mesh is not a stable process-global cache key
            shard_map(
                step,
                mesh=self.mesh,
                in_specs=(spec, spec),
                out_specs=P(),
            )
        )
        self._step_cache[key] = fn
        return fn

    # -- sharded streaming wire fold (the mesh form of the single-chip
    # packed-wire fast path, VERDICT r3 weak #3) ------------------------------

    def _wire_stream_fns(self, cfg: StreamConfig, stages, row_len: int, width):
        """Compiled (step, finish) pair for the sharded streaming fold.

        ``step``: donated per-shard carry (stage states, summary, touched) +
        one [S, nbytes] group of packed wire rows with [S] fill counts ->
        next carry.  Each shard unpacks ITS row on device and folds it into
        its local partial — no collectives per micro-batch.  ``finish``: one
        collective merge of the per-shard partials into the replicated
        combined state (the descriptor's mesh_combine_states when supplied,
        else all_gather + masked combine fold).  This is the sharded analog
        of `_wire_fused_step`: streaming donated-carry fold per micro-batch,
        cross-shard communication only at window close
        (SummaryBulkAggregation.java:76-83's per-partition fold, with the
        timeWindowAll funnel replaced by a collective).
        """
        key = (stages, cfg, row_len, str(width), "stream-wire")
        if key in self._step_cache:
            return self._step_cache[key]
        from jax.sharding import PartitionSpec as P

        from gelly_streaming_tpu.core.types import EdgeBatch
        from gelly_streaming_tpu.io import wire
        from gelly_streaming_tpu.parallel.mesh import shard_map

        agg = self.agg
        combine = self._combine_over_mesh(cfg)

        def strip(t):
            return jax.tree.map(lambda a: a[0], t)

        def lift(t):
            return jax.tree.map(lambda a: a[None], t)

        def step(carry, rows, counts):
            states, summary, touched = carry
            s, d = wire.unpack_edges(rows[0], row_len, width)
            mask = jnp.arange(row_len, dtype=jnp.int32) < counts[0]
            b = EdgeBatch(src=s, dst=d, mask=mask)
            out_states = []
            for stage, st in zip(stages, strip(states)):
                st, b = stage.apply(st, b)
                out_states.append(st)
            summary2 = agg.update(strip(summary), b.src, b.dst, b.val, b.mask)
            return (
                lift(tuple(out_states)),
                lift(summary2),
                touched | jnp.any(b.mask)[None],
            )

        def finish(carry):
            _, summary, touched = carry
            return combine(strip(summary), touched[0])

        spec = P(self._axis)
        entry = (
            jax.jit(  # graft: disable=RAWJIT — keyed per-mesh in self._step_cache; a Mesh is not a stable process-global cache key
                shard_map(
                    step,
                    mesh=self.mesh,
                    in_specs=(spec, spec, spec),
                    out_specs=spec,
                ),
                donate_argnums=0,
            ),
            jax.jit(  # graft: disable=RAWJIT — keyed per-mesh in self._step_cache; a Mesh is not a stable process-global cache key
                shard_map(
                    finish, mesh=self.mesh, in_specs=(spec,), out_specs=P()
                )
            ),
        )
        self._step_cache[key] = entry
        return entry

    @staticmethod
    def _pack_padded_row(s, d, row_len: int, width):
        """Pack a (possibly short) edge row to ``row_len``, returning
        (buffer, fill count).  Fixed-width pads keep position, so a count
        prefix selects the real edges; EF40 sorts, so pads carry the maximal
        id pair and sort to the END (same invariant as `_pack_pane_wire`)."""
        from gelly_streaming_tpu.io import wire

        k = len(s)
        if k == row_len:
            return wire.pack_edges(s, d, width), k
        pad_id = width[1] - 1 if isinstance(width, tuple) else 0
        ps = np.full((row_len,), pad_id, np.int32)
        pd = np.full((row_len,), pad_id, np.int32)
        ps[:k] = s
        pd[:k] = d
        return wire.pack_edges(ps, pd, width), k

    def _wire_mesh_plan(self, stream):
        """Resolve a wire-backed stream into (row(i), n_rows, row_len, width,
        total_edges): a linearized sequence of per-shard rows, grouped S at a
        time by the caller.  Replay buffers (already packed at the stream's
        batch) round-robin whole rows; raw arrays split contiguously at
        batch/S so a group folds one batch."""
        cfg = stream.cfg
        S = self.num_shards
        packed = getattr(stream, "_wire_packed", None)
        if packed is not None:
            bufs, batch, width, tail_pair = packed
            row_len = batch
            n_rows = len(bufs) + (1 if tail_pair else 0)
            total = len(bufs) * batch + (len(tail_pair[0]) if tail_pair else 0)

            def row(i):
                if i < len(bufs):
                    return bufs[i], batch
                return self._pack_padded_row(
                    np.ascontiguousarray(tail_pair[0], np.int32),
                    np.ascontiguousarray(tail_pair[1], np.int32),
                    row_len,
                    width,
                )

            return row, n_rows, row_len, width, total
        src, dst, batch = stream._wire_arrays
        total = len(src)
        row_len = max(1, min(batch, max(total, 1)) // S)
        width = self.agg._wire_width(cfg, row_len)
        n_rows = -(-total // row_len) if total else 0
        binned, _compress = self.agg._binned_modes(cfg)
        if binned and isinstance(width, tuple):
            binned = False  # EF40 regroups by src itself; skip the dst sort

        def row(i):
            s_b = src[i * row_len : (i + 1) * row_len]
            d_b = dst[i * row_len : (i + 1) * row_len]
            if binned:
                # destination-binned mesh rows: each shard's streaming fold
                # scatters a sorted segment (order-free folds only — the
                # multiset per row is unchanged, so the stream-end collective
                # merge is bit-identical)
                from gelly_streaming_tpu.io import wire as wire_mod

                s_b, d_b = wire_mod.sort_edges_binned(
                    s_b, d_b, cfg.vertex_capacity, record_stats=True
                )
            return self._pack_padded_row(s_b, d_b, row_len, width)

        return row, n_rows, row_len, width, total

    def _wire_mesh_checkpoint_like(
        self, stream, row_len: int, rows: Optional[int] = None
    ):
        """Snapshot layout; ``rows`` overrides the leading axis (the number
        of shard rows held: S for single-process saves, this process's
        addressable count for per-process saves)."""
        cfg = stream.cfg
        n = self.num_shards if rows is None else rows

        def stack(tree):
            return jax.tree.map(
                lambda a: np.broadcast_to(
                    np.asarray(a), (n,) + np.shape(np.asarray(a))
                ).copy(),
                tree,
            )

        like = {
            "summary": stack(self.agg.initial_state(cfg)),
            "stages": stack(tuple(st.init(cfg) for st in stream._stages)),
            "touched": np.zeros((n,), bool),
            "next_group": np.zeros((), np.int64),
            "row_len": np.zeros((), np.int64),
            "shards": np.zeros((), np.int64),
            "done": np.zeros((), bool),
        }
        if rows is not None:
            like["rows"] = np.zeros((n,), np.int64)
        return like

    def _local_rows(self):
        """Shard rows this process addresses (row r lives on device r)."""
        return sorted(
            r
            for r, d in enumerate(self.mesh.devices.flat)
            if d.process_index == jax.process_index()
        )

    def _wire_mesh_restore_per_process(
        self, stream, checkpoint_path: str, row_len: int, sharding
    ):
        """Per-process restore for multi-process meshes.

        Each process loads only its own file; validity, stream position, and
        row ownership must AGREE across processes (one process_allgather
        round) or all start fresh together — a split restore would deadlock
        the collective finish.  Returns (carry | None, start_group, done).
        """
        from jax.experimental import multihost_utils

        from gelly_streaming_tpu.utils.checkpoint import (
            checkpoint_exists,
            load_state,
            per_process_file,
        )

        rows = self._local_rows()
        k = len(rows)
        path = per_process_file(checkpoint_path)
        snap = None
        if checkpoint_exists(path):
            like = self._wire_mesh_checkpoint_like(stream, row_len, rows=k)
            try:
                snap = load_state(path, like)
            except ValueError:
                snap = None
        if snap is not None and (
            int(snap["row_len"]) != row_len
            or int(snap["shards"]) != self.num_shards
        ):
            # same loud failure as the single-process branch: a changed
            # batch/shard geometry would misalign the stream position, and
            # silently re-folding from group 0 would discard the
            # checkpointed progress with no signal.  Every process computes
            # this from its own file + static config, so all raise together.
            raise ValueError(
                f"mesh wire checkpoint was written at row_len "
                f"{int(snap['row_len'])} x {int(snap['shards'])} shards; "
                f"resuming with {row_len} x {self.num_shards} would "
                "misalign the stream position"
            )
        ok = (
            snap is not None
            and [int(r) for r in snap["rows"]] == rows
        )
        pos = int(snap["next_group"]) if ok else -1
        done = bool(snap["done"]) if ok else False
        agree = multihost_utils.process_allgather(
            np.array([int(ok), pos, int(done)], np.int64)
        )
        if not (
            agree[:, 0].all()
            and (agree[:, 1] == agree[0, 1]).all()
            and (agree[:, 2] == agree[0, 2]).all()
        ):
            return None, 0, False
        row_to_i = {r: i for i, r in enumerate(rows)}
        S = self.num_shards

        def build(local):
            def cb(index):
                r = int(index[0].start or 0)
                return local[row_to_i[r]][None]

            return jax.make_array_from_callback(
                (S,) + local.shape[1:], sharding, cb
            )

        carry = jax.tree.map(
            build, (snap["stages"], snap["summary"], snap["touched"])
        )
        return carry, int(agree[0, 1]), bool(agree[0, 2])

    def _wire_mesh_save_per_process(
        self, checkpoint_path: str, carry, pos: int, done: bool, row_len: int
    ) -> None:
        """Each process saves ONLY its addressable shard rows of the carry."""
        from gelly_streaming_tpu.utils.checkpoint import (
            per_process_file,
            save_state,
        )

        rows = self._local_rows()

        def local(leaf):
            shards = sorted(
                leaf.addressable_shards, key=lambda s: s.index[0].start
            )
            return np.concatenate([np.asarray(s.data) for s in shards], axis=0)

        host = jax.tree.map(local, carry)
        save_state(
            per_process_file(checkpoint_path),
            {
                "summary": host[1],
                "stages": host[0],
                "touched": host[2],
                "rows": np.array(rows, np.int64),
                "next_group": np.full((), pos, np.int64),
                "row_len": np.full((), row_len, np.int64),
                "shards": np.full((), self.num_shards, np.int64),
                "done": np.full((), done, bool),
            },
        )

    # -- owner-sharded summary plane (core/sharded_state.py, ISSUE 4) --------

    def _sharded_spec(self, cfg: StreamConfig):
        """The descriptor's ShardedStateSpec when the owner-sharded plane is
        enabled and usable here.  Multi-process meshes stay on the
        replicated plane (their per-process snapshot machinery predates the
        block layout), as does anything with ``cfg.sharded_state`` off."""
        from gelly_streaming_tpu.core.sharded_state import resolve_sharded_state

        if jax.process_count() > 1 or not resolve_sharded_state(cfg):
            return None
        return self.agg.sharded_state_spec(cfg)

    def _shard_ctx(self, cfg: StreamConfig, spec, interval_edges: int):
        """Static per-step context; the delta capacity pow2-buckets the
        spec's changed-row bound for one exchange interval."""
        from gelly_streaming_tpu.core.sharded_state import ShardContext
        from gelly_streaming_tpu.parallel import routing

        cap = routing.delta_capacity(
            cfg.vertex_capacity,
            self.num_shards,
            spec.delta_bound(cfg, interval_edges),
        )
        return ShardContext(
            cfg=cfg,
            num_shards=self.num_shards,
            axis_name=self._axis,
            delta_cap=cap,
        )

    def _sharded_key(self, spec, cfg: StreamConfig, *extra):
        """Process-stable executable-cache key for a sharded mesh kernel.

        Unlike the legacy ``_step_cache`` (per-runner, raw jax.jit — invisible
        to the retrace guard), sharded kernels live in the process-global
        compile cache: ``mesh_cache_key`` makes re-created runners over the
        same devices resolve to the same executables, and the bench's
        ``cache_recompiles`` attestation covers this plane too.
        """
        from gelly_streaming_tpu.parallel.mesh import mesh_cache_key

        return (
            type(spec),
            self.agg.cache_token,
            mesh_cache_key(self.mesh),
            cfg,
        ) + extra

    def _record_exchange_stats(self, profile: dict, stats_host) -> None:
        """Fold one exchange's [S, 3] device-counter download into the
        process comms metrics (called at exchange boundaries only)."""
        from gelly_streaming_tpu.utils import metrics

        stats = np.asarray(stats_host)
        rounds = int(stats[:, 0].max())
        metrics.comms_add("comms_exchange_rounds", rounds)
        metrics.comms_high_water(
            "comms_delta_occupancy_hwm", int(stats[:, 1].max())
        )
        metrics.comms_add("comms_delta_spilled", int(stats[:, 2].sum()))
        metrics.comms_add(
            "comms_bytes_exchange", rounds * profile["round_nbytes"]
        )

    def _sharded_blocks_sharding(self):
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        return NamedSharding(self.mesh, P(self._axis))

    def _initial_blocks(self, spec, cfg: StreamConfig):
        return jax.device_put(
            spec.initial_shard_state(cfg, self.num_shards),
            self._sharded_blocks_sharding(),
        )

    def _sharded_wire_fns(self, cfg: StreamConfig, spec, stages, row_len, width, ctx):
        """(exchange, gather) pair for the sharded wire plane.

        ``exchange``: donated (carry, blocks) -> (carry', blocks', stats) —
        folds the per-shard local partial into the owner blocks through the
        spec's delta exchange and resets the local scratch (the carry keeps
        streaming through the SAME per-dispatch step as the replicated
        plane, so the hot path pays zero extra collectives).  ``gather``:
        blocks -> the replicated summary, emit/snapshot boundaries only.
        """
        from jax.sharding import PartitionSpec as P

        from gelly_streaming_tpu.parallel.mesh import shard_map

        agg = self.agg
        spec_ = P(self._axis)

        def strip(t):
            return jax.tree.map(lambda a: a[0], t)

        def lift(t):
            return jax.tree.map(lambda a: a[None], t)

        def make_exchange():
            def ex(carry, blocks):
                states, summary, touched = carry
                blocks2, stats = spec.exchange(strip(summary), strip(blocks), ctx)
                fresh = agg.initial_state(cfg)
                stats_row = jnp.stack(
                    [stats.rounds, stats.delta_hwm, stats.spilled]
                ).astype(jnp.int32)
                return (
                    (states, lift(fresh), touched),
                    lift(blocks2),
                    stats_row[None],
                )

            return shard_map(
                ex,
                mesh=self.mesh,
                in_specs=(spec_, spec_),
                out_specs=(spec_, spec_, spec_),
            )

        def make_gather():
            def g(blocks):
                return spec.gather_state(strip(blocks), ctx)  # gather-ok: emit/snapshot boundary — the lazy replicated view

            return shard_map(
                g, mesh=self.mesh, in_specs=(spec_,), out_specs=P()
            )

        exchange = compile_cache.cached_jit(
            ("mesh_sharded_wire_exchange",)
            + self._sharded_key(spec, cfg, stages, row_len, str(width), ctx.delta_cap),
            make_exchange,
            donate_argnums=(0, 1),
        )
        gather = compile_cache.cached_jit(
            ("mesh_sharded_gather",)
            + self._sharded_key(spec, cfg, ctx.delta_cap),
            make_gather,
        )
        return exchange, gather

    def _wire_sharded_checkpoint_like(self, stream, spec, row_len: int):
        """Sharded wire snapshot layout: O(C/S) owner blocks per shard (the
        S-fold download shrink vs the replicated carry), stage states, and
        the group position — same geometry validation as the replicated
        layout."""
        cfg = stream.cfg
        like = self._wire_mesh_checkpoint_like(stream, row_len)
        del like["summary"], like["touched"]
        like["blocks"] = jax.tree.map(
            np.asarray, spec.initial_shard_state(cfg, self.num_shards)
        )
        return like

    def _wire_records_sharded(
        self,
        stream,
        spec,
        checkpoint_path: Optional[str],
        restore: bool,
    ) -> Iterator[tuple]:
        """Owner-sharded form of ``wire_records``.

        Per dispatch the stream rides the IDENTICAL donated-carry step as
        the replicated plane (local folds, no collectives); at snapshot
        boundaries and stream end the local partials delta-exchange into the
        O(C/S) owner blocks ("changed keys since last exchange"), and the
        replicated view is gathered lazily only to emit.  Snapshots download
        blocks — O(C) total across the mesh instead of O(C*S).
        """
        from gelly_streaming_tpu.io import wire as wire_mod
        from gelly_streaming_tpu.utils import metrics
        from gelly_streaming_tpu.utils.checkpoint import (
            checkpoint_exists,
            load_state,
            save_state,
        )

        cfg = stream.cfg
        agg = self.agg
        S = self.num_shards
        row, n_rows, row_len, width, total_edges = self._wire_mesh_plan(stream)
        n_groups = -(-n_rows // S) if n_rows else 0
        step, _ = self._wire_stream_fns(cfg, stream._stages, row_len, width)
        every_groups = (
            max(1, cfg.wire_checkpoint_batches // S)
            if cfg.wire_checkpoint_batches
            else 0
        )
        # mid-stream exchanges only happen at snapshot boundaries, so the
        # delta buffers must be sized for the WHOLE stream when there is no
        # checkpoint path — an interval-sized cap there would force spill
        # retries and miss the dense-slab switch
        interval_edges = (
            (every_groups if checkpoint_path and every_groups else max(n_groups, 1))
            * S
            * row_len
        )
        ctx = self._shard_ctx(cfg, spec, interval_edges)
        profile = spec.comm_profile(cfg, ctx)
        exchange, gather = self._sharded_wire_fns(
            cfg, spec, stream._stages, row_len, width, ctx
        )
        sharding = self._sharded_blocks_sharding()

        start_group = 0
        blocks = None
        done_blocks = None
        if checkpoint_path and restore and checkpoint_exists(checkpoint_path):
            like = self._wire_sharded_checkpoint_like(stream, spec, row_len)
            try:
                snap = load_state(checkpoint_path, like)
            except ValueError:
                snap = None  # legacy/replicated/mismatched layout: fresh
            if snap is not None:
                if int(snap["row_len"]) != row_len or int(snap["shards"]) != S:
                    raise ValueError(
                        f"mesh wire checkpoint was written at row_len "
                        f"{int(snap['row_len'])} x {int(snap['shards'])} "
                        f"shards; resuming with {row_len} x {S} would "
                        "misalign the stream position"
                    )
                if bool(snap["done"]):
                    done_blocks = snap["blocks"]
                else:
                    start_group = int(snap["next_group"])
                    blocks = jax.device_put(snap["blocks"], sharding)
                    carry_stages = snap["stages"]
        if done_blocks is not None:
            # stream fully folded before the crash: re-emit from the blocks
            # (at-least-once) without re-folding
            metrics.comms_add("comms_bytes_gather", profile["gather_nbytes"])
            out = agg.transform(gather(jax.device_put(done_blocks, sharding)))
            yield out if isinstance(out, tuple) else (out,)
            return
        if blocks is None:
            blocks = self._initial_blocks(spec, cfg)
            carry_stages = None
        like_carry = self._wire_mesh_checkpoint_like(stream, row_len)
        carry = jax.device_put(
            (
                carry_stages if carry_stages is not None else like_carry["stages"],
                like_carry["summary"],
                like_carry["touched"],
            ),
            sharding,
        )

        def save(pos: int, done: bool, blocks_now, carry_now) -> None:
            host_blocks = jax.tree.map(np.asarray, blocks_now)
            host_stages = jax.tree.map(np.asarray, carry_now[0])
            save_state(
                checkpoint_path,
                {
                    "blocks": host_blocks,
                    "stages": host_stages,
                    "next_group": np.full((), pos, np.int64),
                    "row_len": np.full((), row_len, np.int64),
                    "shards": np.full((), S, np.int64),
                    "done": np.full((), done, bool),
                },
            )

        def prepare(g: int):
            # zeros, not empty: BDV replay rows are variable-size payloads
            # padded into the max-width arena (trailing zeros decode as
            # dropped empty varint groups); fixed-width rows fill exactly
            rows = np.zeros((S, wire_mod.wire_nbytes(row_len, width)), np.uint8)
            counts = np.zeros((S,), np.int32)
            for s in range(S):
                i = g * S + s
                if i < n_rows:
                    buf, counts[s] = row(i)
                else:
                    buf, _ = self._pack_padded_row(
                        np.empty((0,), np.int32),
                        np.empty((0,), np.int32),
                        row_len,
                        width,
                    )
                rows[s, : buf.nbytes] = buf
            metrics.wire_record_batch(S, int(counts.sum()), rows.nbytes)
            return g, (rows, counts)

        since_snap = 0
        with wire_mod.Prefetcher(
            range(start_group, n_groups),
            prepare,
            device=sharding,
            depth=cfg.prefetch_depth,
        ) as pf:
            for g, dev in pf:
                rows_d, counts_d = dev
                carry = step(carry, rows_d, counts_d)
                metrics.comms_add("comms_dispatches", 1)
                since_snap += 1
                if checkpoint_path and every_groups and since_snap >= every_groups:
                    # exchange at the snapshot boundary: local partials fold
                    # into the owner blocks (delta buffers), scratch resets
                    carry, blocks, stats = exchange(carry, blocks)
                    self._record_exchange_stats(profile, stats)
                    save(g + 1, False, blocks, carry)
                    since_snap = 0
        if total_edges == 0:
            return
        carry, blocks, stats = exchange(carry, blocks)
        self._record_exchange_stats(profile, stats)
        metrics.comms_add("comms_bytes_gather", profile["gather_nbytes"])
        out = agg.transform(gather(blocks))
        # emit BEFORE the final snapshot (at-least-once emission)
        yield out if isinstance(out, tuple) else (out,)
        if checkpoint_path:
            save(n_groups, True, blocks, carry)

    def wire_records(
        self,
        stream,
        checkpoint_path: Optional[str] = None,
        restore: bool = True,
    ) -> Iterator[tuple]:
        """Sharded STREAMING fold over a wire-backed (untimed) stream.

        Per micro-batch group, S packed rows ship straight to their owner
        shards (row-sharded device_put on the prefetch thread) and fold into
        donated per-shard carries — the stream is folded ONCE, batch by
        batch, exactly like the single-chip wire fast path; the only
        cross-shard communication is the collective merge at stream end.
        Positional checkpoints snapshot the carry plus the group position
        every ``cfg.wire_checkpoint_batches`` rows (synchronously — the
        download is one carry per interval).  Single-process meshes save
        the whole [S, ...] carry to one file; MULTI-PROCESS meshes save per
        process — each host writes only its addressable shard rows
        (`utils.checkpoint.per_process_file`), and restore requires every
        host to agree on validity, position, and row ownership (one
        process_allgather round) or all start fresh together.
        """
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        from gelly_streaming_tpu.io import wire as wire_mod
        from gelly_streaming_tpu.utils import metrics
        from gelly_streaming_tpu.utils.checkpoint import (
            checkpoint_exists,
            load_state,
            save_state,
        )

        cfg = stream.cfg
        spec = self._sharded_spec(cfg)
        if spec is not None:
            # the default: owner-sharded O(C/S) summary blocks with
            # delta-compressed reconciliation at snapshot/stream-end
            # boundaries (core/sharded_state.py)
            yield from self._wire_records_sharded(
                stream, spec, checkpoint_path, restore
            )
            return
        agg = self.agg
        S = self.num_shards
        multi = jax.process_count() > 1
        row, n_rows, row_len, width, total_edges = self._wire_mesh_plan(stream)
        n_groups = -(-n_rows // S) if n_rows else 0
        step, finish = self._wire_stream_fns(
            cfg, stream._stages, row_len, width
        )

        sharding = NamedSharding(self.mesh, P(self._axis))
        start_group = 0
        carry = None
        carry_host = None
        like = None
        if checkpoint_path and restore and multi:
            restored, start_group, was_done = (
                self._wire_mesh_restore_per_process(
                    stream, checkpoint_path, row_len, sharding
                )
            )
            if was_done and restored is not None:
                # stream fully folded before the crash: re-run only the
                # collective finish and re-emit (at-least-once)
                out = agg.transform(finish(restored))
                yield out if isinstance(out, tuple) else (out,)
                return
            carry = restored
        elif checkpoint_path and restore and checkpoint_exists(checkpoint_path):
            like = self._wire_mesh_checkpoint_like(stream, row_len)
            try:
                snap = load_state(checkpoint_path, like)
            except ValueError:
                snap = None  # legacy/mismatched layout: start fresh
            if snap is not None:
                if int(snap["row_len"]) != row_len or int(snap["shards"]) != S:
                    raise ValueError(
                        f"mesh wire checkpoint was written at row_len "
                        f"{int(snap['row_len'])} x {int(snap['shards'])} "
                        f"shards; resuming with {row_len} x {S} would "
                        "misalign the stream position"
                    )
                if bool(snap["done"]):
                    # stream fully folded before the crash: re-run only the
                    # collective finish and re-emit (at-least-once)
                    out = agg.transform(self._finish_host(snap, finish))
                    yield out if isinstance(out, tuple) else (out,)
                    return
                start_group = int(snap["next_group"])
                carry_host = (snap["stages"], snap["summary"], snap["touched"])

        if carry is None:
            if carry_host is None:
                like = like or self._wire_mesh_checkpoint_like(stream, row_len)
                carry_host = (like["stages"], like["summary"], like["touched"])
            carry = jax.device_put(carry_host, sharding)

        every_groups = (
            max(1, cfg.wire_checkpoint_batches // S)
            if cfg.wire_checkpoint_batches
            else 0
        )

        def save(pos: int, done: bool, carry_now):
            if multi:
                self._wire_mesh_save_per_process(
                    checkpoint_path, carry_now, pos, done, row_len
                )
                return
            host = jax.tree.map(np.asarray, carry_now)
            save_state(
                checkpoint_path,
                {
                    "summary": host[1],
                    "stages": host[0],
                    "touched": host[2],
                    "next_group": np.full((), pos, np.int64),
                    "row_len": np.full((), row_len, np.int64),
                    "shards": np.full((), S, np.int64),
                    "done": np.full((), done, bool),
                },
            )

        def prepare(g: int):
            # zeros, not empty: BDV replay rows are variable-size payloads
            # padded into the max-width arena (trailing zeros decode as
            # dropped empty varint groups); fixed-width rows fill exactly
            rows = np.zeros((S, wire_mod.wire_nbytes(row_len, width)), np.uint8)
            counts = np.zeros((S,), np.int32)
            for s in range(S):
                i = g * S + s
                if i < n_rows:
                    buf, counts[s] = row(i)
                else:
                    buf, _ = self._pack_padded_row(
                        np.empty((0,), np.int32),
                        np.empty((0,), np.int32),
                        row_len,
                        width,
                    )
                rows[s, : buf.nbytes] = buf
            metrics.wire_record_batch(S, int(counts.sum()), rows.nbytes)
            return g, (rows, counts)

        since_snap = 0
        with wire_mod.Prefetcher(
            range(start_group, n_groups),
            prepare,
            device=sharding,
            depth=cfg.prefetch_depth,
        ) as pf:
            for g, dev in pf:
                rows_d, counts_d = dev
                carry = step(carry, rows_d, counts_d)
                since_snap += 1
                if checkpoint_path and every_groups and since_snap >= every_groups:
                    save(g + 1, False, carry)
                    since_snap = 0
        if total_edges == 0:
            return
        final = finish(carry)
        out = agg.transform(final)
        # emit BEFORE the final snapshot (at-least-once emission, as in the
        # single-chip wire path)
        yield out if isinstance(out, tuple) else (out,)
        if checkpoint_path:
            save(n_groups, True, carry)

    def _finish_host(self, snap, finish):
        """Re-run the collective finish over a restored done-carry (the
        at-least-once re-emission after a crash between emit and final
        snapshot)."""
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        carry = jax.device_put(
            (snap["stages"], snap["summary"], snap["touched"]),
            NamedSharding(self.mesh, P(self._axis)),
        )
        return finish(carry)

    def _pane_cap(self, total: int) -> int:
        from gelly_streaming_tpu.parallel.routing import pow2_bucket

        per = -(-max(total, 1) // self.num_shards)  # ceil, >= 1
        return pow2_bucket(per)  # the shared shape-bucketing rule

    def _bucket_pane(self, pane: WindowPane):
        """Round-robin the pane's edges into [n_shards, cap] host arrays."""
        n = self.num_shards
        total = len(pane.src)
        cap = self._pane_cap(total)
        src = np.zeros((n, cap), np.int32)
        dst = np.zeros((n, cap), np.int32)
        mask = np.zeros((n, cap), bool)
        val = None
        if pane.val is not None:
            val = jax.tree.map(
                lambda a: np.zeros((n, cap) + a.shape[1:], a.dtype), pane.val
            )
        for shard in range(n):
            idx = np.arange(shard, total, n)
            k = len(idx)
            src[shard, :k] = pane.src[idx]
            dst[shard, :k] = pane.dst[idx]
            mask[shard, :k] = True
            if val is not None:

                def fill(buf, a):
                    buf[shard, :k] = a[idx]
                    return buf

                val = jax.tree.map(fill, val, pane.val)
        return src, dst, val, mask

    def _pack_pane_wire(self, pane: WindowPane, width):
        """Round-robin + pack the pane into per-shard wire rows.

        Returns ([S, nbytes] uint8 rows, [S] int32 fill counts, cap).  The
        pad region packs as zero-id edges; the device step masks them out by
        count, so the transfer volume is the packed wire size — the same
        bytes-per-edge economy as the single-chip fast path, replacing the
        raw [S, cap] int32 uploads (VERDICT r2 missing #3).
        """
        from gelly_streaming_tpu.io import wire

        n = self.num_shards
        total = len(pane.src)
        cap = self._pane_cap(total)
        rows = np.zeros((n, wire.wire_nbytes(cap, width)), np.uint8)
        counts = np.zeros((n,), np.int32)
        s = np.zeros((cap,), np.int32)
        d = np.zeros((cap,), np.int32)
        # EF40 SORTS the bucket, so the pad edges must sort to the END for
        # the count-prefix mask to select exactly the real edges: pad with
        # the maximal id pair (ties with a real max-pair edge are identical
        # pairs, so any count-prefix is the same multiset).  Fixed-width
        # encodings preserve order; zero padding is fine there.
        pad_id = width[1] - 1 if isinstance(width, tuple) else 0
        for shard in range(n):
            idx = np.arange(shard, total, n)
            k = len(idx)
            s[:k] = pane.src[idx]
            d[:k] = pane.dst[idx]
            s[k:] = pad_id
            d[k:] = pad_id
            rows[shard] = wire.pack_edges(s, d, width)
            counts[shard] = k
        return rows, counts, cap

    def _pane_step_sharded(self, cfg: StreamConfig, spec, cap: int, kind, ctx):
        """Compiled sharded pane fold: route -> fold -> exchange -> gather in
        ONE dispatch against the persistent owner blocks.

        ``kind`` is ("wire", width) for packed value-less rows or
        ("raw", has_val) for bucket arrays.  The local fold runs the
        descriptor's ordinary updateFun on a transient scratch; the spec's
        delta exchange reconciles it into the O(C/S) blocks; the replicated
        summary comes out of the emit-boundary gather — there is no
        all_gather of full per-shard partials anywhere on this plane.
        """
        from jax.sharding import PartitionSpec as P

        from gelly_streaming_tpu.io import wire
        from gelly_streaming_tpu.parallel.mesh import shard_map

        agg = self.agg
        spec_p = P(self._axis)

        def strip(t):
            return jax.tree.map(lambda a: a[0], t)

        def lift(t):
            return jax.tree.map(lambda a: a[None], t)

        def tail(blocks, src, dst, val, mask):
            local = agg.update(agg.initial_state(cfg), src, dst, val, mask)
            blocks2, stats = spec.exchange(local, strip(blocks), ctx)
            summary = spec.gather_state(blocks2, ctx)  # gather-ok: emit — every pane close is an emission boundary on the windowed plane
            stats_row = jnp.stack(
                [stats.rounds, stats.delta_hwm, stats.spilled]
            ).astype(jnp.int32)
            return lift(blocks2), summary, stats_row[None]

        if kind[0] == "wire":
            width = kind[1]

            def make():
                def step(blocks, rows, counts):
                    src, dst = wire.unpack_edges(rows[0], cap, width)
                    mask = jnp.arange(cap, dtype=jnp.int32) < counts[0]
                    return tail(blocks, src, dst, None, mask)

                return shard_map(
                    step,
                    mesh=self.mesh,
                    in_specs=(spec_p, spec_p, spec_p),
                    out_specs=(spec_p, P(), spec_p),
                )

            key_tail = (cap, str(width), ctx.delta_cap, "wire")
        else:
            has_val = kind[1]

            def make():
                def step(blocks, src, dst, val, mask):
                    return tail(
                        blocks,
                        src[0],
                        dst[0],
                        None if val is None else jax.tree.map(lambda a: a[0], val),
                        mask[0],
                    )

                val_spec = spec_p if has_val else None

                return shard_map(
                    step,
                    mesh=self.mesh,
                    in_specs=(spec_p, spec_p, spec_p, val_spec, spec_p),
                    out_specs=(spec_p, P(), spec_p),
                )

            key_tail = (cap, has_val, ctx.delta_cap, "raw")

        return compile_cache.cached_jit(
            ("mesh_sharded_pane",) + self._sharded_key(spec, cfg, *key_tail),
            make,
        )

    def _run_sharded(
        self,
        stream,
        spec,
        window_ms: int,
        checkpoint_path: Optional[str],
        restore: bool,
        panes: Optional[Callable],
    ) -> OutputStream:
        """Windowed mesh plane over owner-sharded summary state.

        The persistent cross-window state is the O(C/S) block set; each
        closed pane is routed on the prefetcher's pack thread (host keyBy
        when the spec asks for it — ``spec.route_key`` — else the skew-free
        round-robin), folded + delta-exchanged + lazily gathered in one
        dispatch, and the gathered running summary rides the shared Merger
        loop (``into_running``) so emission order, at-least-once
        semantics, and positional checkpoints are identical to the
        replicated plane — which stays available as the equivalence oracle
        (cfg.sharded_state=0).
        """
        from gelly_streaming_tpu.io import wire as wire_mod
        from gelly_streaming_tpu.parallel.routing import host_route
        from gelly_streaming_tpu.utils import metrics

        cfg = stream.cfg
        agg = self.agg
        S = self.num_shards
        width = agg._wire_width(cfg)
        skip_through, skip_global = agg.restored_position(
            cfg, checkpoint_path, restore
        )

        binned_on, _ = agg._binned_modes(cfg)

        def prepare(pane: WindowPane):
            """Pack-thread routing + packing (keyBy off the dispatch thread):
            value-less panes become packed per-shard wire rows — owner
            buckets under ``spec.route_key``, round-robin otherwise — and
            valued panes ship raw bucket arrays.  With binned ingest on,
            the pane is destination-sorted first (order-free folds see the
            same multiset; per-shard scatters turn segment-local) and the
            keyBy bucketing itself runs on the parallel ingest pool — the
            host_route work moved into the parse/pack pass."""
            already = (0 <= pane.window_id <= skip_through) or (
                pane.window_id == -1 and skip_global
            )
            if already or len(pane.src) == 0:
                return (pane, None, None), None
            pane = agg._maybe_bin_pane(cfg, pane, width)
            if pane.val is None:
                if spec.route_key:
                    if binned_on:
                        from gelly_streaming_tpu.io import ingest as ingest_mod

                        routed = ingest_mod.parallel_host_route(
                            pane.src.astype(np.int32),
                            pane.dst.astype(np.int32),
                            S,
                            key=spec.route_key,
                            workers=cfg.ingest_workers,
                        )
                    else:
                        routed = host_route(
                            pane.src.astype(np.int32),
                            pane.dst.astype(np.int32),
                            S,
                            key=spec.route_key,
                        )
                    counts = routed.mask.sum(axis=1).astype(np.int32)
                    rows = wire_mod.pack_bucket_rows(
                        routed.src, routed.dst, counts, width
                    )
                    return (pane, ("wire", width), routed.src.shape[1]), (
                        rows,
                        counts,
                    )
                rows, counts, cap = self._pack_pane_wire(pane, width)
                return (pane, ("wire", width), cap), (rows, counts)
            if spec.route_key:
                routed = host_route(
                    pane.src.astype(np.int32),
                    pane.dst.astype(np.int32),
                    S,
                    key=spec.route_key,
                    val=pane.val,
                )
                return (pane, ("raw", True), routed.src.shape[1]), (
                    routed.src,
                    routed.dst,
                    routed.val,
                    routed.mask,
                )
            src, dst, val, mask = self._bucket_pane(pane)
            return (pane, ("raw", val is not None), src.shape[1]), (
                src,
                dst,
                val,
                mask,
            )

        def records() -> Iterator[tuple]:
            import collections as _collections

            sharding = self._sharded_blocks_sharding()
            restored = MergerState(agg, cfg, checkpoint_path, restore).running
            if restored is not None:
                blocks = jax.device_put(
                    spec.shard_summary(restored, cfg, S), sharding
                )
            else:
                blocks = self._initial_blocks(spec, cfg)
            initial = blocks if agg.transient_state else None
            pending_stats = _collections.deque()
            profiles = {}

            def drain_stats(limit: int) -> None:
                while len(pending_stats) > limit:
                    stats, profile = pending_stats.popleft()
                    self._record_exchange_stats(profile, stats)

            def fold_prepared(item, _running):
                nonlocal blocks
                (pane, kind, cap), dev = item
                if kind is None:
                    return None
                ctx = self._shard_ctx(cfg, spec, S * cap)
                profile = profiles.get(ctx.delta_cap)
                if profile is None:
                    profile = profiles[ctx.delta_cap] = spec.comm_profile(cfg, ctx)
                if initial is not None:
                    blocks = initial  # transient descriptors reset per window
                step = self._pane_step_sharded(cfg, spec, cap, kind, ctx)
                blocks, summary, stats = step(blocks, *dev)
                metrics.comms_add("comms_dispatches", 1)
                metrics.comms_add(
                    "comms_bytes_gather", profile["gather_nbytes"]
                )
                # stats drain lags the pipeline depth so the async plane
                # never blocks on a per-pane download
                pending_stats.append((stats, profile))
                drain_stats(max(2, cfg.prefetch_depth))
                return summary

            from gelly_streaming_tpu.core.windows import stream_panes as _sp

            pane_iter = panes() if panes is not None else _sp(stream, window_ms)
            try:
                with wire_mod.Prefetcher(
                    pane_iter, prepare, device=sharding, depth=cfg.prefetch_depth
                ) as pf:
                    yield from agg._merge_loop(
                        cfg,
                        ((meta[0], (meta, dev)) for meta, dev in pf),
                        fold_prepared,
                        checkpoint_path,
                        restore,
                        unwrap=True,
                        into_running=True,
                    )
            finally:
                drain_stats(0)

        return OutputStream(records)

    def run(
        self,
        stream,
        window_ms: Optional[int] = None,
        checkpoint_path: Optional[str] = None,
        restore: bool = True,
        panes: Optional[Callable] = None,
    ) -> OutputStream:
        """(transform(running_summary),) per closed window, like run().

        Shares the Merger/checkpoint loop with the simulated runtime
        (`SummaryAggregation._merge_loop`), so positional checkpoints and
        kill-and-resume work identically on the sharded data plane — the
        distributed analog of the reference's ListCheckpointed Merger
        (SummaryAggregation.java:127-135).

        ``panes`` overrides the time plane: a zero-arg callable returning a
        WindowPane iterator (zero-arg so the OutputStream stays re-runnable)
        — e.g. multi-host gated windows merged across ingest hosts
        (`parallel.multihost.merge_pane_shares`).  Without it, panes come
        from the stream's own tumbling assignment.
        """
        cfg = stream.cfg
        window_ms = window_ms or self.agg.window_ms or cfg.window_ms
        agg = self.agg
        spec = self._sharded_spec(cfg)
        if spec is not None:
            # the default windowed mesh plane: owner-sharded blocks +
            # delta exchange + lazy emission gather (core/sharded_state.py);
            # cfg.sharded_state=0 keeps the replicated oracle below
            return self._run_sharded(
                stream, spec, window_ms, checkpoint_path, restore, panes
            )
        from gelly_streaming_tpu.io import wire as wire_mod

        # value-less panes honor the configured wire encoding exactly as the
        # single-shard fast path does (incl. the order-free EF40 gate)
        width = agg._wire_width(cfg)
        skip_through, skip_global = agg.restored_position(
            cfg, checkpoint_path, restore
        )

        def prepare(pane: WindowPane):
            """Background-thread pack: value-less panes become packed wire
            rows; valued panes ship raw bucket arrays.  Either way the
            device_put happens on the prefetch thread, so the transfer of
            pane k+1 overlaps pane k's sharded fold (the same
            pack/transfer/compute overlap as the single-chip fast path).
            Panes a restored checkpoint already folded skip packing — the
            merge loop would drop them unfolded anyway."""
            already = (0 <= pane.window_id <= skip_through) or (
                pane.window_id == -1 and skip_global
            )
            if already or len(pane.src) == 0:
                return (pane, None, None), None
            # destination binning (order-free folds; no-op otherwise): the
            # round-robin strided slices of a sorted pane stay sorted, so
            # each shard's fold scatter is segment-local
            pane = agg._maybe_bin_pane(cfg, pane, width)
            if pane.val is None:
                rows, counts, cap = self._pack_pane_wire(pane, width)
                return (pane, "wire", cap), (rows, counts)
            src, dst, val, mask = self._bucket_pane(pane)
            return (pane, "raw", src.shape[1]), (src, dst, val, mask)

        def fold_prepared(item):
            (pane, kind, cap), dev = item
            if kind is None:
                return None
            if kind == "wire":
                rows, counts = dev
                return self._pane_step_wire(cfg, cap, width)(rows, counts)
            src, dst, val, mask = dev
            return self._pane_step(cfg, cap, val is not None)(src, dst, val, mask)

        def records() -> Iterator[tuple]:
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P

            # every prepared buffer is [S, ...] with the shard axis leading,
            # so one row-sharded placement covers rows/counts/raw buckets —
            # each shard's bytes transfer straight to their owner device
            sharding = NamedSharding(self.mesh, P(self._axis))
            pane_iter = (
                panes() if panes is not None else stream_panes(stream, window_ms)
            )
            with wire_mod.Prefetcher(
                pane_iter, prepare, device=sharding, depth=cfg.prefetch_depth
            ) as pf:
                yield from agg._merge_loop(
                    cfg,
                    ((meta[0], (meta, dev)) for meta, dev in pf),
                    fold_prepared,
                    checkpoint_path,
                    restore,
                    unwrap=True,
                )

        return OutputStream(records)


