"""Core value types: padded COO edge micro-batches and enums.

The reference's wire type is Flink's ``Edge<K, EV>`` tuple flowing record-by-record
through a JVM dataflow (SimpleEdgeStream.java:55).  The TPU-native unit of work is
instead a *padded COO micro-batch*: fixed-shape int32 src/dst arrays plus a
validity mask, so every downstream kernel is a statically-shaped XLA program.
``EventType`` mirrors EventType.java:24-27 (additions/deletions) as a sign array.
"""

from __future__ import annotations

import dataclasses
import enum
from typing import ClassVar, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np


class EventType(enum.Enum):
    """Edge event kind (reference: EventType.java:24-27)."""

    EDGE_ADDITION = 1
    EDGE_DELETION = -1


class EdgeDirection(enum.Enum):
    """Neighborhood direction for slice()/degree ops (Flink's EdgeDirection)."""

    IN = "in"
    OUT = "out"
    ALL = "all"


def _as_i32(x) -> jax.Array:
    return jnp.asarray(x, dtype=jnp.int32)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class EdgeBatch:
    """A padded COO micro-batch of edge events.

    Fields are equal-length 1-D arrays of static size B:
      src, dst: interned (dense) vertex ids, int32.
      mask:     validity — False rows are padding and must be ignored.
      val:      optional edge values (any dtype) — ``None`` for NullValue graphs.
      time:     optional event-time timestamps (relative ms, int32; host owns time).
      sign:     optional +1/-1 event sign (EventType); ``None`` means all additions.
    """

    src: jax.Array
    dst: jax.Array
    mask: jax.Array
    val: Optional[jax.Array] = None
    time: Optional[jax.Array] = None
    sign: Optional[jax.Array] = None

    # ---- construction -------------------------------------------------------

    @staticmethod
    def from_arrays(
        src,
        dst,
        val=None,
        time=None,
        sign=None,
        mask=None,
        pad_to: Optional[int] = None,
    ) -> "EdgeBatch":
        """Build a batch from host/device arrays, optionally padding to a capacity."""
        src = _as_i32(src)
        dst = _as_i32(dst)
        n = src.shape[0]
        if mask is None:
            mask = jnp.ones((n,), dtype=bool)
        else:
            mask = jnp.asarray(mask, dtype=bool)
        if val is not None:
            val = jax.tree.map(jnp.asarray, val)
        if time is not None:
            # Relative stream time in ms (int32): windows are assigned on the
            # host, so device timestamps only need to order events within a
            # run.  Epoch-scale timestamps (~1.7e12 ms) would silently WRAP
            # in the cast — fail loudly instead (same philosophy as the
            # vertex-id bounds check in EdgeStream.from_arrays): rebase to
            # stream-relative ms first.
            # Traced construction (inside a jitted step) stays legal.  A
            # concrete device jax.Array is judged by DTYPE alone — no
            # np.asarray, which would force a device->host sync per batch
            # on timed hot paths: a signed integer dtype of <= 32 bits
            # cannot wrap in the int32 cast, anything wider (or
            # float/uint32+) could hold epoch-scale values and is refused
            # without materializing.
            # Host inputs (lists, numpy) keep the exact value check.
            if isinstance(time, jax.core.Tracer):
                pass
            elif isinstance(time, jax.Array):
                dt = np.dtype(time.dtype)
                safe = (dt.kind == "i" and dt.itemsize <= 4) or (
                    dt.kind == "u" and dt.itemsize <= 2
                )
                if not safe:
                    raise ValueError(
                        f"device timestamp arrays must use a signed integer "
                        f"dtype of <= 32 bits (got {dt}): wider or "
                        "non-integer values could wrap in the int32 cast; "
                        "rebase to stream-relative ms on host first"
                    )
            else:
                t_host = np.asarray(time)
                if t_host.size and (
                    t_host.max() > np.iinfo(np.int32).max
                    or t_host.min() < np.iinfo(np.int32).min
                ):
                    raise ValueError(
                        "event timestamps must be stream-relative ms fitting "
                        "int32; rebase epoch timestamps (subtract the stream "
                        "start) before ingest — host owns time"
                    )
            time = jnp.asarray(time, dtype=jnp.int32)
        if sign is not None:
            sign = jnp.asarray(sign, dtype=jnp.int8)
        batch = EdgeBatch(src=src, dst=dst, mask=mask, val=val, time=time, sign=sign)
        if pad_to is not None and pad_to != n:
            batch = batch.pad_to(pad_to)
        return batch

    # shared all-ones host masks by size, read-only so every batch may
    # alias one safely (the pane cutter np.asarray's it without writing)
    _HOST_MASKS: ClassVar[dict] = {}

    @staticmethod
    def from_host_arrays(src, dst, pad_to: Optional[int] = None) -> "EdgeBatch":
        """Host-plane batch: contiguous NUMPY int32 leaves, no device
        conversion, the all-ones mask shared (read-only) across batches.

        For value-less/untimed sources whose consumer is the HOST pane
        cutter (core/windows.py ``np.asarray``'s every field before any
        device work): ``from_arrays`` would round-trip each batch through
        three eager jnp conversions (~ms-scale per batch — the measured
        ceiling of the serving ingest path, ISSUE 14) only for the cutter
        to convert straight back.  Numpy leaves are ordinary pytree
        leaves, so consumers that DO dispatch a batch still work — they
        pay the transfer exactly once, at the device boundary.
        """
        src = np.ascontiguousarray(src, dtype=np.int32)
        dst = np.ascontiguousarray(dst, dtype=np.int32)
        n = src.shape[0]
        if dst.shape[0] != n:
            raise ValueError("src/dst length mismatch")
        size = n if pad_to is None else int(pad_to)
        if size < n:
            raise ValueError(f"cannot pad batch of size {n} down to {size}")
        if size != n:
            pad = size - n
            src = np.concatenate([src, np.zeros(pad, np.int32)])
            dst = np.concatenate([dst, np.zeros(pad, np.int32)])
            mask = np.zeros(size, bool)
            mask[:n] = True
        else:
            mask = EdgeBatch._HOST_MASKS.get(size)
            if mask is None:
                mask = np.ones(size, bool)
                mask.flags.writeable = False
                EdgeBatch._HOST_MASKS[size] = mask
        return EdgeBatch(src=src, dst=dst, mask=mask)

    @staticmethod
    def from_edges(
        edges: Sequence[tuple], pad_to: Optional[int] = None, with_time: bool = False
    ) -> "EdgeBatch":
        """Build from a list of (src, dst[, val[, time]]) tuples (host-side helper)."""
        if not edges:
            size = pad_to or 0
            return EdgeBatch(
                src=jnp.zeros((size,), jnp.int32),
                dst=jnp.zeros((size,), jnp.int32),
                mask=jnp.zeros((size,), bool),
            )
        src = np.array([e[0] for e in edges], dtype=np.int32)
        dst = np.array([e[1] for e in edges], dtype=np.int32)
        val = None
        time = None
        if len(edges[0]) > 2:
            first = edges[0][2]
            if isinstance(first, tuple):
                # tuple-valued edges become a tuple-of-columns pytree
                val = tuple(
                    np.array([e[2][k] for e in edges]) for k in range(len(first))
                )
            else:
                val = np.array([e[2] for e in edges])
        if with_time and len(edges[0]) > 3:
            # int64 here so from_arrays' epoch-overflow guard sees the raw
            # values (an int32 build would wrap or raise before it runs)
            time = np.array([e[3] for e in edges], dtype=np.int64)
        return EdgeBatch.from_arrays(src, dst, val=val, time=time, pad_to=pad_to)

    # ---- shape/padding ------------------------------------------------------

    @property
    def size(self) -> int:
        """Static batch capacity B (including padding)."""
        return int(self.src.shape[0])

    def num_valid(self) -> jax.Array:
        return jnp.sum(self.mask.astype(jnp.int32))

    def pad_to(self, capacity: int) -> "EdgeBatch":
        n = self.size
        if capacity < n:
            raise ValueError(f"cannot pad batch of size {n} down to {capacity}")
        if capacity == n:
            return self
        pad = capacity - n

        def _pad1(x, fill=0):
            return jnp.concatenate(
                [x, jnp.full((pad,) + x.shape[1:], fill, dtype=x.dtype)]
            )

        def _pad(x, fill=0):
            if x is None:
                return None
            return jax.tree.map(lambda leaf: _pad1(leaf, fill), x)

        return EdgeBatch(
            src=_pad1(self.src),
            dst=_pad1(self.dst),
            mask=jnp.concatenate([self.mask, jnp.zeros((pad,), bool)]),
            val=_pad(self.val),
            time=_pad(self.time),
            sign=_pad(self.sign, fill=1),
        )

    # ---- transforms used by the stream API ---------------------------------

    def reversed(self) -> "EdgeBatch":
        """Swap src/dst (reference: SimpleEdgeStream.java:328)."""
        return dataclasses.replace(self, src=self.dst, dst=self.src)

    def replace(self, **kw) -> "EdgeBatch":
        return dataclasses.replace(self, **kw)

    def concat(self, other: "EdgeBatch") -> "EdgeBatch":
        def _cat(a, b, field, fill=None):
            if a is None and b is None:
                return None
            # One-sided optional field: synthesize the field's *semantic
            # default* for the side missing it (sign=None means "all
            # additions" -> fill +1; val -> zeros).  Event time cannot be
            # invented, so a one-sided time is an error.
            if (a is None) != (b is None):
                if fill is None:
                    raise ValueError(
                        f"cannot concat batches where only one side has {field!r}"
                    )
                length = (self.src if a is None else other.src).shape[0]

                def synth(leaf):
                    return jnp.full((length,) + leaf.shape[1:], fill, leaf.dtype)

                if a is None:
                    a = jax.tree.map(synth, b)
                else:
                    b = jax.tree.map(synth, a)
            return jax.tree.map(lambda x, y: jnp.concatenate([x, y]), a, b)

        return EdgeBatch(
            src=jnp.concatenate([self.src, other.src]),
            dst=jnp.concatenate([self.dst, other.dst]),
            mask=jnp.concatenate([self.mask, other.mask]),
            val=_cat(self.val, other.val, "val", fill=0),
            time=_cat(self.time, other.time, "time"),
            sign=_cat(self.sign, other.sign, "sign", fill=1),
        )

    # ---- host-side inspection ----------------------------------------------

    def to_tuples(self) -> list:
        """Materialize valid edges as host tuples (testing/sinks only).

        A pytree-valued ``val`` (e.g. a tuple of arrays from mapEdges-to-tuple)
        renders as a nested tuple per row, matching Flink's Tuple CSV rendering.
        """
        src = np.asarray(self.src)
        dst = np.asarray(self.dst)
        mask = np.asarray(self.mask)
        val = (
            None
            if self.val is None
            else jax.tree.map(np.asarray, self.val)
        )
        val_leaves, val_def = (
            (None, None) if val is None else jax.tree.flatten(val)
        )
        out = []
        for i in range(len(src)):
            if not mask[i]:
                continue
            if val is None:
                out.append((int(src[i]), int(dst[i])))
            else:
                leaves_i = [leaf[i].item() for leaf in val_leaves]
                v = jax.tree.unflatten(val_def, leaves_i)
                out.append((int(src[i]), int(dst[i]), v))
        return out


