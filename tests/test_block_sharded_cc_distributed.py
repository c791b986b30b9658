"""Multi-process per-process checkpointing for BlockShardedCC (VERDICT r3
item 5): a 2-process jax.distributed CPU cluster (4 local devices each, 8
mesh shards) runs the block-distributed CC with checkpointing, is KILLED
mid-stream, and resumes from each host's own per-process shard snapshot —
no host ever materializes another host's blocks.  The resumed labels must
equal a host union-find over the full stream even though the resumed run's
replayed prefix is poisoned (proof the restored carry was used)."""

import json
import os
import socket
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_WORKER = textwrap.dedent(
    """
    import json, os, sys
    sys.path.insert(0, %(repo)r)
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 4)

    coord, pid, phase, ckpt = (
        sys.argv[1], int(sys.argv[2]), sys.argv[3], sys.argv[4]
    )
    from gelly_streaming_tpu.parallel import multihost as mh

    env = mh.distributed_env(
        coordinator_address=coord, num_processes=2, process_id=pid
    )
    assert len(jax.devices()) == 8, jax.devices()

    import numpy as np

    from gelly_streaming_tpu.core.config import StreamConfig
    from gelly_streaming_tpu.core.stream import EdgeStream
    from gelly_streaming_tpu.core.types import EdgeBatch
    from gelly_streaming_tpu.library.connected_components import (
        BlockShardedCC,
        unshard_labels,
    )

    C = 1 << 10
    rng = np.random.default_rng(11)
    src = rng.integers(0, C, 256).astype(np.int32)
    dst = rng.integers(0, C, 256).astype(np.int32)
    # two ingestion panes of 128 edges each (deterministic arrival cut)
    cfg = StreamConfig(
        vertex_capacity=C, batch_size=64, ingest_window_edges=128
    )
    use_src = src.copy()
    if phase == "resume":
        # poison the already-folded prefix: only the restored snapshot can
        # still produce the right labels
        use_src[:128] = 0

    def batches():
        for i in range(0, 256, 64):
            yield EdgeBatch.from_arrays(use_src[i:i+64], dst[i:i+64])

    cc = BlockShardedCC()
    out = cc.run(
        EdgeStream.from_batches(batches, cfg), checkpoint_path=ckpt
    )
    it = iter(out)
    first = next(it)  # pane 0 folded (snapshot runs when the gen resumes)
    if phase == "crash":
        next(it)  # resuming past the yield writes pane 0's snapshot
        proc_file = ckpt[:-4] + f".proc{pid}.npz"
        assert os.path.exists(proc_file), proc_file
        print("RESULT " + json.dumps({"crashed_after": 1}), flush=True)
        sys.exit(0)  # "crash": no further panes folded
    rest = list(it)
    final = rest[-1][0] if rest else first[0]
    from jax.experimental import multihost_utils

    full = multihost_utils.process_allgather(final, tiled=True)
    labels = unshard_labels(full)
    print("RESULT " + json.dumps({"labels": labels.tolist()}), flush=True)
    """
)


def _run_pair(tmp_path, phase, ckpt):
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    coord = f"127.0.0.1:{port}"
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    procs, logs = [], []
    for pid in (0, 1):
        out_f = open(tmp_path / f"{phase}{pid}.out", "w+")
        err_f = open(tmp_path / f"{phase}{pid}.err", "w+")
        logs.append((out_f, err_f))
        procs.append(
            subprocess.Popen(
                [
                    sys.executable, "-c", _WORKER % {"repo": REPO},
                    coord, str(pid), phase, ckpt,
                ],
                stdout=out_f, stderr=err_f, env=env, text=True,
            )
        )
    outs = []
    try:
        for p in procs:
            p.wait(timeout=240)
    except subprocess.TimeoutExpired:
        for q in procs:
            q.kill()
        raise
    for p, (out_f, err_f) in zip(procs, logs):
        out_f.seek(0)
        err_f.seek(0)
        stdout, stderr = out_f.read(), err_f.read()
        out_f.close()
        err_f.close()
        if "Multiprocess computations aren't implemented" in stderr:
            import pytest

            pytest.skip(
                "this jax build's CPU backend has no multi-process "
                "collectives (jax.distributed over CPU unsupported)"
            )
        assert p.returncode == 0, stderr[-3000:]
        line = [l for l in stdout.splitlines() if l.startswith("RESULT ")][-1]
        outs.append(json.loads(line[len("RESULT "):]))
    return outs


def test_block_sharded_cc_multiprocess_kill_and_resume(tmp_path):
    import numpy as np

    ckpt = str(tmp_path / "blockcc.npz")
    crash = _run_pair(tmp_path, "crash", ckpt)
    assert all(o == {"crashed_after": 1} for o in crash)
    base = ckpt[:-4]
    assert os.path.exists(base + ".proc0.npz")
    assert os.path.exists(base + ".proc1.npz")

    resumed = _run_pair(tmp_path, "resume", ckpt)
    labels = np.array(resumed[0]["labels"])
    assert resumed[1]["labels"] == resumed[0]["labels"]

    # host union-find over the TRUE full stream (the resume run's replayed
    # prefix was poisoned, so matching labels prove the snapshot was used)
    C = 1 << 10
    rng = np.random.default_rng(11)
    src = rng.integers(0, C, 256).astype(np.int64)
    dst = rng.integers(0, C, 256).astype(np.int64)
    parent = np.arange(C)

    def find(v):
        while parent[v] != v:
            parent[v] = parent[parent[v]]
            v = parent[v]
        return v

    for a, b in zip(src, dst):
        ra, rb = find(int(a)), find(int(b))
        if ra != rb:
            parent[max(ra, rb)] = min(ra, rb)
    expect = np.array([find(v) for v in range(C)])
    assert np.array_equal(labels, expect)
