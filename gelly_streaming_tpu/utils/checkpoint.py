"""Checkpoint/resume for operator state.

The reference checkpoints exactly one piece of state — the Merger's running
summary via ListCheckpointed (SummaryAggregation.java:93,127-135) — while every
other operator's state (degree maps, distinct sets, neighborhood TreeSets,
sampler states) is plain JVM fields that a restore silently resets (SURVEY.md
§5.3-4 flags this gap).  Here *all* state is pytrees of dense arrays by
construction, so any of it checkpoints uniformly: flatten to leaves, store as
an .npz with the treedef, restore exactly.
"""

from __future__ import annotations

import json
import os
import re
from typing import Any

import jax
import numpy as np


def _normalize(path: str) -> str:
    """np.savez appends .npz to bare paths; make that explicit everywhere so
    exists()-checks and load paths agree with what save actually wrote."""
    return path if path.endswith(".npz") else path + ".npz"


def _keeps_part(node: Any) -> bool:
    """A state node whose snapshot leaves some of its leaves out (such as
    a fold's counters): it has ``snapshot()`` and ``from_snapshot(snap)``."""
    return hasattr(node, "snapshot") and hasattr(node, "from_snapshot")


def _snapshot_view(state: Any) -> Any:
    """The pytree a snapshot of ``state`` holds."""
    return jax.tree.map(
        lambda x: x.snapshot() if _keeps_part(x) else x,
        state,
        is_leaf=_keeps_part,
    )


def save_state(path: str, state: Any) -> None:
    """Snapshot any pytree-of-arrays state to ``path`` (.npz), atomically:
    a crash mid-save must never destroy the previous good snapshot."""
    path = _normalize(path)
    state = _snapshot_view(state)
    leaves, treedef = jax.tree.flatten(state)
    arrays = {f"leaf_{i}": np.asarray(leaf) for i, leaf in enumerate(leaves)}
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    tmp = path + ".tmp.npz"
    np.savez(tmp, __treedef__=np.frombuffer(
        json.dumps(_treedef_token(state)).encode(), dtype=np.uint8
    ), **arrays)
    os.replace(tmp, path)


def checkpoint_exists(path: str) -> bool:
    return os.path.exists(_normalize(path))


def load_state(path: str, like: Any) -> Any:
    """Restore a snapshot into the structure of ``like`` (same pytree shape);
    the leaves a node's snapshot leaves out come from ``like``."""
    path = _normalize(path)
    view = _snapshot_view(like)
    with np.load(path) as data:
        leaves_like, treedef = jax.tree.flatten(view)
        n = len(leaves_like)
        # validate the layout BEFORE touching leaves, so a structure change
        # (e.g. a legacy snapshot) surfaces as ValueError, not KeyError
        token = json.loads(bytes(data["__treedef__"]).decode())
        if token != _treedef_token(view):
            raise ValueError(
                f"checkpoint structure mismatch: stored {token}, "
                f"expected {_treedef_token(view)}"
            )
        stored = [data[f"leaf_{i}"] for i in range(n)]
    # numpy leaves (host-side metadata like stream positions) restore as
    # numpy — routing them through jnp would down-cast int64 under the
    # default x64-disabled config; device arrays restore as device arrays.
    restored = [
        np.asarray(s, dtype=l.dtype)
        if isinstance(l, np.ndarray)
        else jax.numpy.asarray(s, dtype=l.dtype)
        for s, l in zip(stored, leaves_like)
    ]
    return jax.tree.map(
        lambda node, snap: node.from_snapshot(snap) if _keeps_part(node) else snap,
        like,
        jax.tree.unflatten(treedef, restored),
        is_leaf=_keeps_part,
    )


def _treedef_token(state: Any):
    """A stable, comparable description of the pytree layout for validation."""
    leaves, treedef = jax.tree.flatten(state)
    return {
        "treedef": str(treedef),
        "shapes": [list(np.shape(l)) for l in leaves],
        "dtypes": [str(np.asarray(l).dtype) for l in leaves],
    }


def per_job_file(path: str, job_id: str) -> str:
    """Per-job snapshot file under a shared checkpoint prefix.

    The job runtime (runtime/manager.py) gives every submitted job an
    INDEPENDENT positional checkpoint — two jobs crash-resume from their own
    positions, never a merged one — by keying the shared prefix with the
    job id, normalized so the .npz extension stays terminal and an id with
    path separators cannot escape the checkpoint directory.
    """
    base = path[: -len(".npz")] if path.endswith(".npz") else path
    safe = re.sub(r"[^A-Za-z0-9._-]", "_", str(job_id))
    return f"{base}.job_{safe}.npz"


def per_process_file(path: str) -> str:
    """Per-process snapshot file name for multi-process sharded saves.

    Each host writes only its ADDRESSABLE shard rows (the orbax-style
    per-host save); the suffix keys the process index, normalized so the
    .npz extension stays terminal.
    """
    base = path[: -len(".npz")] if path.endswith(".npz") else path
    return f"{base}.proc{jax.process_index()}.npz"
