"""Per-process checkpointing for the sharded streaming wire fold on a REAL
2-process jax.distributed CPU cluster: kill mid-stream, resume from each
host's own shard snapshot with a poisoned replay prefix — matching final
components prove the restored per-process carries were used."""

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

    mh.distributed_env(coordinator_address=coord, num_processes=2, process_id=pid)
    assert len(jax.devices()) == 8

    import numpy as np

    from gelly_streaming_tpu.core.config import StreamConfig
    from gelly_streaming_tpu.core.stream import EdgeStream
    from gelly_streaming_tpu.library.connected_components import ConnectedComponents

    C = 256
    rng = np.random.default_rng(31)
    src = rng.integers(0, C, 512).astype(np.int32)
    dst = rng.integers(0, C, 512).astype(np.int32)
    use_src = src.copy()
    if phase == "resume":
        # poison the WHOLE replay: every group is covered by the crash
        # run's last positional snapshot, so only the restored per-process
        # carries can still produce the true labels
        use_src[:] = 0
    # batch 32 over 8 shards -> row_len 4, 128 rows, 16 groups; snapshot
    # every 32 rows = every 4 groups
    cfg = StreamConfig(
        vertex_capacity=C, batch_size=32, num_shards=8,
        wire_checkpoint_batches=32,
    )
    agg = ConnectedComponents()
    out = EdgeStream.from_arrays(use_src, dst, cfg).aggregate(
        agg, checkpoint_path=ckpt
    )
    if phase == "crash":
        # the streaming fold yields once at stream end, AFTER all
        # mid-stream positional snapshots but BEFORE the final done-save;
        # consuming that one record and exiting abandons the generator at
        # the yield, so the last snapshot on disk is positional (not done)
        # — the crash-between-emit-and-final-save case
        it = iter(out)
        next(it)
        from gelly_streaming_tpu.utils.checkpoint import per_process_file
        assert os.path.exists(per_process_file(ckpt)), per_process_file(ckpt)
        print("RESULT " + json.dumps({"crashed": True}), flush=True)
        sys.exit(0)
    res = list(out)
    comps = res[-1][0].components()
    print("RESULT " + json.dumps({"comps": sorted(
        tuple(sorted(v)) for v in comps.values()
    )}), flush=True)
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


def test_mesh_wire_fold_multiprocess_resume(tmp_path):
    """Kill after the emission (before the final done-save), resume over a
    fully poisoned replay: the restored per-process carries must reproduce
    the TRUE stream's components exactly."""
    import numpy as np

    ckpt = str(tmp_path / "meshwire.npz")
    crash = _run_pair(tmp_path, "crash", ckpt)
    assert all(o == {"crashed": True} for o in crash)

    resumed = _run_pair(tmp_path, "resume", ckpt)
    assert resumed[0] == resumed[1]

    C = 256
    rng = np.random.default_rng(31)
    src = rng.integers(0, C, 512).astype(np.int64)
    dst = rng.integers(0, C, 512).astype(np.int64)
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
    comps = {}
    seen = set(src.tolist()) | set(dst.tolist())
    for v in sorted(seen):
        comps.setdefault(find(v), []).append(v)
    expect = sorted(tuple(vs) for vs in comps.values())
    got = sorted(tuple(c) for c in resumed[0]["comps"])
    assert got == expect
