"""Ingestion-time pane cutting for untimed streams (VERDICT r3 missing #2).

The reference's DEFAULT mode is ingestion-time tumbling windows with running
emission (SimpleEdgeStream.java:69-73; ConnectedComponentsExample.java:65-67
prints per window).  Without the knobs an untimed stream is one global pane
flushed at end-of-stream — an infinite source would never emit.  These tests
pin the arrival-count cut (deterministic), the wall-clock cut (injected
clock), running emission over an unbounded generator, checkpoint/resume on
synthetic window ids, and that finite-stream goldens are unchanged.
"""

import numpy as np
import pytest

from gelly_streaming_tpu.core.config import StreamConfig
from gelly_streaming_tpu.core.stream import EdgeStream
from gelly_streaming_tpu.core.types import EdgeBatch
from gelly_streaming_tpu.core.windows import assign_ingestion_windows
from gelly_streaming_tpu.library.connected_components import ConnectedComponents


def _batches(chunks):
    def factory():
        for s, d in chunks:
            yield EdgeBatch.from_arrays(
                np.asarray(s, np.int32), np.asarray(d, np.int32)
            )

    return factory


def test_arrival_count_panes_split_mid_batch():
    chunks = [([1, 2, 3], [2, 3, 4]), ([5, 6], [6, 7])]
    panes = list(
        assign_ingestion_windows(_batches(chunks)(), every_edges=2)
    )
    # 5 edges at 2/pane -> panes of 2, 2, 1 with ascending ids
    assert [p.window_id for p in panes] == [0, 1, 2]
    assert [p.num_edges for p in panes] == [2, 2, 1]
    assert list(panes[0].src) == [1, 2] and list(panes[1].src) == [3, 5]
    assert all(p.max_timestamp == -1 for p in panes)


def test_wall_clock_panes_cut_at_batch_boundaries():
    now = [0.0]
    chunks = [([1], [2]), ([3], [4]), ([5], [6])]

    def clock():
        now[0] += 0.6  # 600 ms between batch arrivals
        return now[0]

    panes = list(
        assign_ingestion_windows(
            _batches(chunks)(), every_ms=1000, clock=clock
        )
    )
    # arrivals at 0, 600, 1200 ms relative to the first -> windows 0, 0, 1
    assert [p.window_id for p in panes] == [0, 1]
    assert [p.num_edges for p in panes] == [2, 1]


def test_unbounded_generator_emits_running_components():
    """An infinite untimed source yields one running summary per pane —
    WITHOUT reaching end-of-stream (the generator is never exhausted)."""
    from gelly_streaming_tpu.io.sources import unbounded_generated_stream

    cfg = StreamConfig(
        vertex_capacity=64, batch_size=8, ingest_window_edges=16
    )
    stream = unbounded_generated_stream(cfg, num_vertices=32, max_batches=None)
    out = iter(stream.aggregate(ConnectedComponents()))
    first = next(out)[0]
    second = next(out)[0]
    third = next(out)[0]
    # running merge: component count is non-increasing as edges accumulate
    n1 = len(first.components())
    n3 = len(third.components())
    assert n3 <= n1
    out.close()


def test_arrival_count_panes_fuzz_partition_exactly():
    """Property fuzz: over random batch/pane geometries, the emitted panes
    are EXACTLY the arrival stream re-chunked at every_edges — same edges,
    same order, contiguous ascending ids, all full except the last."""
    rng = np.random.default_rng(17)
    for _ in range(25):
        n_batches = int(rng.integers(0, 6))
        sizes = [int(rng.integers(0, 9)) for _ in range(n_batches)]
        every = int(rng.integers(1, 8))
        chunks = []
        base = 0
        for s in sizes:
            chunks.append(
                (
                    np.arange(base, base + s, dtype=np.int64) % 64,
                    np.arange(base, base + s, dtype=np.int64) * 3 % 64,
                )
            )
            base += s
        panes = list(
            assign_ingestion_windows(_batches(chunks)(), every_edges=every)
        )
        all_src = np.concatenate(
            [c[0] for c in chunks] or [np.empty(0, np.int64)]
        )
        all_dst = np.concatenate(
            [c[1] for c in chunks] or [np.empty(0, np.int64)]
        )
        total = len(all_src)
        want_panes = -(-total // every) if total else 0
        assert [p.window_id for p in panes] == list(range(want_panes))
        got_src = np.concatenate(
            [p.src for p in panes] or [np.empty(0, np.int64)]
        )
        got_dst = np.concatenate(
            [p.dst for p in panes] or [np.empty(0, np.int64)]
        )
        assert np.array_equal(got_src, all_src), (sizes, every)
        assert np.array_equal(got_dst, all_dst), (sizes, every)
        for p in panes[:-1]:
            assert p.num_edges == every, (sizes, every)
        if panes:
            assert panes[-1].num_edges == total - every * (want_panes - 1)


def test_ingest_panes_match_global_pane_final_summary():
    """Finite stream: the LAST running summary equals the single-global-pane
    result (same edges, same order-free fold) and finite goldens without the
    knob are unchanged."""
    rng = np.random.default_rng(3)
    src = rng.integers(0, 64, 200).astype(np.int32)
    dst = rng.integers(0, 64, 200).astype(np.int32)
    plain_cfg = StreamConfig(vertex_capacity=64, batch_size=32)
    ingest_cfg = StreamConfig(
        vertex_capacity=64, batch_size=32, ingest_window_edges=48
    )
    plain = (
        EdgeStream.from_arrays(src, dst, plain_cfg)
        .aggregate(ConnectedComponents())
        .collect()
    )
    assert len(plain) == 1  # single global pane -> one emission
    windowed = (
        EdgeStream.from_arrays(src, dst, ingest_cfg)
        .aggregate(ConnectedComponents())
        .collect()
    )
    assert len(windowed) == -(-200 // 48)  # one emission per pane
    assert windowed[-1][0].components() == plain[-1][0].components()


def test_ingest_panes_checkpoint_resume(tmp_path):
    import os

    rng = np.random.default_rng(5)
    src = rng.integers(0, 64, 160).astype(np.int32)
    dst = rng.integers(0, 64, 160).astype(np.int32)
    cfg = StreamConfig(
        vertex_capacity=64, batch_size=32, ingest_window_edges=40
    )
    ckpt = os.path.join(str(tmp_path), "ingest_cc.npz")
    stream = lambda: EdgeStream.from_arrays(src, dst, cfg)  # noqa: E731
    full = [
        str(r[0])
        for r in stream().aggregate(ConnectedComponents()).collect()
    ]
    it = iter(stream().aggregate(ConnectedComponents(), checkpoint_path=ckpt))
    next(it)
    next(it)
    it.close()
    resumed = [
        str(r[0])
        for r in stream()
        .aggregate(ConnectedComponents(), checkpoint_path=ckpt)
        .collect()
    ]
    # window 0 snapshot landed; window 1's emission re-emits (at-least-once)
    assert resumed == full[1:]


def test_ingest_knobs_validated():
    with pytest.raises(ValueError, match="only one"):
        StreamConfig(ingest_window_edges=4, ingest_window_ms=100)
    with pytest.raises(ValueError, match=">= 0"):
        StreamConfig(ingest_window_edges=-1)
    with pytest.raises(ValueError, match="exactly one"):
        list(assign_ingestion_windows(iter([]), 0, 0))


def test_unbounded_cc_example_prints_per_window(capsys):
    from gelly_streaming_tpu.examples.connected_components import main

    main(["--unbounded=4", "--ingest-window=1024"])
    lines = [
        line
        for line in capsys.readouterr().out.splitlines()
        if line and line[0].isdigit()
    ]
    # 4 batches x 4096 edges at 1024/pane = 16 panes, each printing >= 1
    # component row (vs exactly one print for the whole stream without the
    # ingest knob — the running-emission UX of the reference's example)
    assert len(lines) >= 16


def test_mesh_runner_ingest_panes_match_simulated():
    """Ingestion-time panes flow through the sharded runner identically."""
    rng = np.random.default_rng(7)
    src = rng.integers(0, 64, 128).astype(np.int32)
    dst = rng.integers(0, 64, 128).astype(np.int32)
    single = StreamConfig(vertex_capacity=64, batch_size=16, ingest_window_edges=24)
    sharded = StreamConfig(
        vertex_capacity=64, batch_size=16, num_shards=8, ingest_window_edges=24
    )
    expect = [
        str(r[0])
        for r in EdgeStream.from_arrays(src, dst, single)
        .aggregate(ConnectedComponents())
        .collect()
    ]
    got = [
        str(r[0])
        for r in EdgeStream.from_arrays(src, dst, sharded)
        .aggregate(ConnectedComponents())
        .collect()
    ]
    assert got == expect


def test_wall_clock_panes_refuse_checkpointing(tmp_path):
    import os

    cfg = StreamConfig(vertex_capacity=64, batch_size=8, ingest_window_ms=100)
    stream = EdgeStream.from_collection([(1, 2, 0.0)], cfg, batch_size=2)
    with pytest.raises(ValueError, match="not\\s+replay-deterministic"):
        stream.aggregate(
            ConnectedComponents(),
            checkpoint_path=os.path.join(str(tmp_path), "x.npz"),
        ).collect()


def test_from_wire_tail_rejects_wrapping_ids():
    """Tail bounds must be checked BEFORE the int32 cast (review finding:
    a 64-bit id that wraps into range must not pass)."""
    from gelly_streaming_tpu.io import wire

    cfg = StreamConfig(vertex_capacity=64, batch_size=8)
    ok = wire.pack_edges(
        np.array([1] * 8, np.int32), np.array([2] * 8, np.int32), 2
    )
    with pytest.raises(ValueError, match="tail vertex ids"):
        EdgeStream.from_wire(
            [ok], 8, 2, cfg,
            tail=(
                np.array([(1 << 32) + 5], np.int64),
                np.array([1], np.int64),
            ),
        )


def test_cc_example_ingest_window_applies_to_generated_input(capsys):
    from gelly_streaming_tpu.examples.connected_components import main

    main(["--ingest-window=200"])
    rows = [
        line
        for line in capsys.readouterr().out.splitlines()
        if line and line[0].isdigit()
    ]
    main([])
    rows_plain = [
        line
        for line in capsys.readouterr().out.splitlines()
        if line and line[0].isdigit()
    ]
    # 1000 generated edges at 200/pane -> 5 running emissions vs 1
    assert len(rows) > len(rows_plain)


def test_ingest_panes_stay_on_wire_fast_path_when_aligned(monkeypatch):
    """ingest_window_edges that divides the batch size keeps the stream ON
    the packed-wire fast path with running emission at pane boundaries —
    the unbounded-source UX at full wire speed; outputs match the windowed
    runtime record for record."""
    import gelly_streaming_tpu.core.aggregation as agg_mod

    rng = np.random.default_rng(13)
    src = rng.integers(0, 64, 200).astype(np.int32)
    dst = rng.integers(0, 64, 200).astype(np.int32)

    calls = []
    orig = agg_mod.SummaryAggregation._wire_records

    def spy(self, *a, **k):
        calls.append("wire")
        return orig(self, *a, **k)

    monkeypatch.setattr(agg_mod.SummaryAggregation, "_wire_records", spy)

    # aligned: pane = 64 edges = 2 batches of 32 -> fast path, running panes
    aligned = StreamConfig(
        vertex_capacity=64, batch_size=32, ingest_window_edges=64
    )
    fast = [
        str(r[0])
        for r in EdgeStream.from_arrays(src, dst, aligned)
        .aggregate(ConnectedComponents())
        .collect()
    ]
    assert calls == ["wire"]
    # 200 edges at 64/pane -> panes at 64, 128, 192 + final for the tail 8
    assert len(fast) == 4

    # reference: force the windowed runtime on the same config
    calls.clear()
    monkeypatch.setattr(
        agg_mod.SummaryAggregation, "_wire_eligible", lambda self, s: False
    )
    slow = [
        str(r[0])
        for r in EdgeStream.from_arrays(src, dst, aligned)
        .aggregate(ConnectedComponents())
        .collect()
    ]
    # windowed panes: 64, 64, 64, 8 -> same running records
    assert fast == slow

    # non-aligned pane size must FALL BACK to the windowed runtime
    monkeypatch.undo()  # removes the _wire_eligible override AND the spy...
    monkeypatch.setattr(agg_mod.SummaryAggregation, "_wire_records", spy)
    calls.clear()  # ...so re-install the spy: the path assertion must be real
    odd = StreamConfig(vertex_capacity=64, batch_size=32, ingest_window_edges=48)
    out = (
        EdgeStream.from_arrays(src, dst, odd)
        .aggregate(ConnectedComponents())
        .collect()
    )
    assert calls == []  # not on the fast path
    assert len(out) == -(-200 // 48)


def test_ingest_panes_wire_fast_path_exact_boundary(monkeypatch):
    """A stream ending exactly on a pane boundary emits once per pane, no
    duplicate final record."""
    rng = np.random.default_rng(19)
    src = rng.integers(0, 64, 128).astype(np.int32)
    dst = rng.integers(0, 64, 128).astype(np.int32)
    cfg = StreamConfig(vertex_capacity=64, batch_size=32, ingest_window_edges=64)
    out = (
        EdgeStream.from_arrays(src, dst, cfg)
        .aggregate(ConnectedComponents())
        .collect()
    )
    assert len(out) == 2  # 128 edges, 64/pane, boundary-exact


def test_ingest_panes_fast_path_covers_replay_source():
    """from_wire replay streams with batch-aligned panes also stay on the
    fast path with running emission (eligibility reads the packed batch)."""
    from gelly_streaming_tpu.io import wire
    from gelly_streaming_tpu.library.connected_components import (
        ConnectedComponents,
    )

    rng = np.random.default_rng(29)
    src = rng.integers(0, 64, 256).astype(np.int32)
    dst = rng.integers(0, 64, 256).astype(np.int32)
    width = wire.width_for_capacity(64)
    bufs, tail = wire.pack_stream(src, dst, 32, width)
    assert tail is None
    cfg = StreamConfig(vertex_capacity=64, batch_size=32, ingest_window_edges=64)
    agg = ConnectedComponents()
    stream = EdgeStream.from_wire(bufs, 32, width, cfg)
    assert agg._wire_eligible(stream)
    out = stream.aggregate(agg).collect()
    assert len(out) == 4  # 256 edges at 64/pane, boundary-exact
    # final pane equals the plain single-emission run
    plain = (
        EdgeStream.from_wire(
            bufs, 32, width, StreamConfig(vertex_capacity=64, batch_size=32)
        )
        .aggregate(ConnectedComponents())
        .collect()
    )
    assert out[-1][0].components() == plain[-1][0].components()


# ---------------------------------------------------------------------------
# the pane cutter reads a stage-less source's own batches (no pipeline step)
# ---------------------------------------------------------------------------


def _host_batches(src, dst, batch, time=None):
    """Numpy-leaf batches, the network source's kind; the last one padded."""

    def factory():
        for i in range(0, len(src), batch):
            if time is None:
                yield EdgeBatch.from_host_arrays(
                    src[i : i + batch], dst[i : i + batch], pad_to=batch
                )
                continue
            n = len(src[i : i + batch])
            pad = np.zeros(batch - n, np.int32)
            yield EdgeBatch(
                src=np.concatenate([src[i : i + batch], pad]),
                dst=np.concatenate([dst[i : i + batch], pad]),
                mask=np.arange(batch) < n,
                time=np.concatenate([time[i : i + batch], pad]),
            )

    return factory


def _device_batches(src, dst, batch, time=None):
    def factory():
        for i in range(0, len(src), batch):
            yield EdgeBatch.from_arrays(
                src[i : i + batch],
                dst[i : i + batch],
                time=None if time is None else time[i : i + batch],
                pad_to=batch,
            )

    return factory


@pytest.mark.parametrize(
    "leaves, every_edges",
    [
        ("host", 64),  # window = two batches, padded last batch
        ("host", 40),  # cuts land mid-batch
        ("device", 64),
        ("host", 0),  # timed: event-time tumbling panes
        ("device", 0),
    ],
)
def test_pane_batches_cut_the_same_panes(leaves, every_edges):
    """A stage-less stream's panes, cut from the source's own batches, are
    the panes its compiled identity step would have given the cutter: the
    same window ids, edges and order, host or device leaves, count or
    event-time cuts."""
    from gelly_streaming_tpu.core.windows import (
        assign_tumbling_windows,
        stream_panes,
    )

    rng = np.random.default_rng(47)
    n, batch = 200, 32
    src = rng.integers(0, 64, n).astype(np.int32)
    dst = rng.integers(0, 64, n).astype(np.int32)
    time = None if every_edges else np.sort(rng.integers(0, 900, n)).astype(np.int32)
    factory = (_host_batches if leaves == "host" else _device_batches)(
        src, dst, batch, time
    )
    cfg = StreamConfig(
        vertex_capacity=64, batch_size=batch, ingest_window_edges=every_edges
    )
    stream = EdgeStream.from_batches(factory, cfg)
    got = list(stream_panes(stream, 100))
    step = stream._compiled_step()
    stepped = (step((), b)[1] for b in factory())
    if every_edges:
        want = list(assign_ingestion_windows(stepped, every_edges))
    else:
        want = list(assign_tumbling_windows(stepped, 100))
    assert len(got) == len(want) > 2
    for g, w in zip(got, want):
        assert (g.window_id, g.max_timestamp) == (w.window_id, w.max_timestamp)
        np.testing.assert_array_equal(g.src, w.src)
        np.testing.assert_array_equal(g.dst, w.dst)
        assert (g.time is None) == (w.time is None)
        if g.time is not None:
            np.testing.assert_array_equal(g.time, w.time)
    assert np.concatenate([p.src for p in got]).tolist() == src.tolist()


def test_stage_less_panes_run_no_pipeline_step(monkeypatch):
    """A stage-less stream never touches the compiled pipeline step:
    ``batches()`` yields the source's own batch objects and the panes are
    cut from them."""
    from gelly_streaming_tpu.core.windows import stream_panes

    def no_step(self):
        raise AssertionError("a stage-less stream ran the pipeline step")

    monkeypatch.setattr(EdgeStream, "_compiled_step", no_step)
    rng = np.random.default_rng(53)
    src = rng.integers(0, 64, 160).astype(np.int32)
    dst = rng.integers(0, 64, 160).astype(np.int32)
    cfg = StreamConfig(vertex_capacity=64, batch_size=32, ingest_window_edges=48)
    factory = _host_batches(src, dst, 32)
    stream = EdgeStream.from_batches(factory, cfg)
    for own, got in zip(factory(), stream.batches()):
        assert type(got.src) is np.ndarray
        np.testing.assert_array_equal(got.src, own.src)
    panes = list(stream_panes(stream, 0))
    assert [p.num_edges for p in panes] == [48, 48, 48, 16]
    assert np.concatenate([p.dst for p in panes]).tolist() == dst.tolist()


def test_staged_panes_still_step(monkeypatch):
    """A staged stream's panes come through its compiled step and hold what
    the stage made of the edges."""
    from gelly_streaming_tpu.core.windows import stream_panes

    steps = []
    orig = EdgeStream._compiled_step

    def spy(self):
        steps.append(self._stages)
        return orig(self)

    monkeypatch.setattr(EdgeStream, "_compiled_step", spy)
    rng = np.random.default_rng(59)
    src = rng.integers(0, 64, 160).astype(np.int32)
    dst = rng.integers(0, 64, 160).astype(np.int32)
    cfg = StreamConfig(vertex_capacity=64, batch_size=32, ingest_window_edges=48)
    stream = EdgeStream.from_batches(_host_batches(src, dst, 32), cfg).reverse()
    panes = list(stream_panes(stream, 0))
    assert len(steps) == 1 and len(steps[0]) == 1
    assert np.concatenate([p.src for p in panes]).tolist() == dst.tolist()
    assert np.concatenate([p.dst for p in panes]).tolist() == src.tolist()
