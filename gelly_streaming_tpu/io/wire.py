"""Compact host->device wire format + prefetching transfer pipeline.

The reference's data plane rides Flink's Netty shuffle; records cross process
boundaries in serialized tuple form and the network is the throughput ceiling.
In the TPU framework the analogous boundary is the host->device link, and the
ingest side must (a) minimise bytes per edge and (b) keep transfers in flight
while the device computes.  This module supplies both:

* **Wire format** — an edge micro-batch is packed as the src block then the
  dst block, each vertex id truncated to the narrowest little-endian byte
  width (2/3/4) that covers the stream's vertex capacity.  A 24-bit width
  (vertex spaces up to 16M) cuts transfer volume 25% vs raw int32 pairs; a
  16-bit width (up to 64K vertices) halves it.  Packing is done by the native
  library (native/edge_parser.cpp pack_edges) with a pure-numpy fallback;
  unpacking runs on device inside the consumer's jitted step, where the byte
  shuffles fuse into the surrounding kernel.

* **WirePrefetcher** — a two-stage background pipeline (a pack thread and a
  transfer thread) keeping a bounded number of batches ahead of the
  consumer: packing item k+1 overlaps transferring item k, and both overlap
  device compute (the Flink analog: source operators run concurrently with
  downstream tasks, buffering on the network stack).
"""

from __future__ import annotations

import ctypes
import queue
import threading
from typing import Iterable, Iterator, Optional, Tuple

import numpy as np

from ..utils.envswitch import resolve_switch
from ..utils.native import load_ingest_lib


PAIR40 = "pair40"  # 5-byte (src, dst) pair packing for capacities <= 2^20
EF40 = "ef40"  # sorted Elias-Fano multiset packing (order-free folds only)
BDV = "bdv"  # destination-binned delta/varint packing (order-free folds only)

# BDV ids (and zigzag values) are bounded so every varint fits 4 bytes and
# the device decoder's uint32 shifts cannot overflow (ops/wire_decode.py)
BDV_MAX_ID_BITS = 28
# the native sorter covers the whole BDV id range (counting sorts to 2^22,
# packed-key radix beyond); numpy lexsort is the no-library fallback only
_BDV_NATIVE_SORT_CAP = 1 << 28


def resolve_binned_ingest(cfg) -> bool:
    """Effective destination-binning switch: config > env > off.

    ``cfg.binned_ingest``: 1 forces on, 0 forces off, -1 (default) defers to
    the ``GELLY_BINNED_INGEST`` env var, defaulting OFF — the unbinned
    arrival-order layout stays the equivalence oracle.  Compression implies
    binning (delta encoding needs the sorted bins), so a resolved
    ``wire_compress`` turns this on too — but an EXPLICIT
    ``binned_ingest=0`` pins the oracle even against an ambient
    ``GELLY_WIRE_COMPRESS=1`` (config beats env on both switches).
    """
    if getattr(cfg, "binned_ingest", -1) == 0:
        return False
    if resolve_wire_compress(cfg):
        return True
    return resolve_switch(getattr(cfg, "binned_ingest", -1), "GELLY_BINNED_INGEST")


def resolve_wire_compress(cfg) -> bool:
    """Effective wire-compression switch: config > env > off (the plain
    fixed-width layout remains the oracle).  ``cfg.wire_compress``: 1 on,
    0 off, -1 defers to ``GELLY_WIRE_COMPRESS``.  An explicit
    ``binned_ingest=0`` pins the arrival-order oracle, so ambient env
    compression cannot ride it (the config-forced combination is already
    rejected in ``StreamConfig.__post_init__``)."""
    if (
        getattr(cfg, "binned_ingest", -1) == 0
        and getattr(cfg, "wire_compress", -1) != 1
    ):
        return False
    return resolve_switch(getattr(cfg, "wire_compress", -1), "GELLY_WIRE_COMPRESS")


def width_for_capacity(capacity: int):
    """Tightest supported encoding covering ids in [0, capacity).

    Returns a byte width (2/3/4, ids packed in separate src/dst blocks) or
    ``PAIR40`` (each edge as one 5-byte 20+20-bit pair) — the narrowest wins:
    capacities in (2^16, 2^20] get 5 bytes/edge instead of 6.
    """
    if capacity <= 1 << 16:
        return 2  # 4 bytes/edge
    if capacity <= 1 << 20:
        return PAIR40  # 5 bytes/edge
    if capacity <= 1 << 24:
        return 3  # 6 bytes/edge
    return 4


def _pack_edges40(src: np.ndarray, dst: np.ndarray) -> np.ndarray:
    n = src.shape[0]
    lib = load_ingest_lib()
    if lib is not None and hasattr(lib, "pack_edges40"):
        out = np.empty(5 * n, np.uint8)
        wrote = lib.pack_edges40(
            src.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            dst.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            n,
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        )
        if wrote == out.nbytes:
            return out
    # numpy fallback: widen to u64 words, take the low 5 little-endian bytes
    w = (src.astype(np.uint64) & 0xFFFFF) | (
        (dst.astype(np.uint64) & 0xFFFFF) << np.uint64(20)
    )
    b = w.view(np.uint8).reshape(-1, 8)[:, :5]
    return np.ascontiguousarray(b).reshape(-1)


def _unpack_edges40(wire, n: int, xp=None):
    """40-bit pair decode; ``xp`` is the array namespace (jnp on device —
    the default — or np for the host-side replay slow path: ONE
    implementation serves both so the formats cannot drift)."""
    if xp is None:
        import jax.numpy as xp

    b = wire.reshape(n, 5).astype(xp.uint32)
    lo = b[:, 0] | (b[:, 1] << 8) | (b[:, 2] << 16)  # bits 0..23
    src = (lo & 0xFFFFF).astype(xp.int32)
    hi = (b[:, 2] >> 4) | (b[:, 3] << 4) | (b[:, 4] << 12)  # bits 20..39
    dst = hi.astype(xp.int32)
    return src, dst


def wire_nbytes(n: int, width) -> int:
    """Wire bytes for an n-edge batch at a fixed-width encoding.

    BDV buffers are data-dependent (that is the point); this returns their
    WORST-CASE bound, the validation/arena ceiling — actual buffers are
    pow2-padded payloads at or under it.
    """
    if width == PAIR40:
        return 5 * n
    if isinstance(width, tuple):
        if width[0] == BDV:
            return bdv_max_nbytes(n)
        return ef40_nbytes(n, width[1])  # (EF40, capacity)
    return 2 * n * width


def ef40_nbytes(n: int, capacity: int) -> int:
    """Wire bytes for an EF40-packed batch of n edges over `capacity` ids."""
    return (n + capacity + 7) // 8 + ((n + 1) // 2) * 5


def _pack_edges_ef40(src: np.ndarray, dst: np.ndarray, capacity: int) -> np.ndarray:
    """Src-grouped Elias-Fano multiset pack (see native pack_edges_ef40).

    Legal only when the consumer's fold is order-free: the batch ships as a
    multiset, not the arrival sequence.  Layout: unary src histogram
    bitvector (n + capacity bits — the i-th grouped edge's one sits at
    position src_i + i) followed by the dst stream in src-grouped order
    (stable within a group: a counting sort by src suffices; dst order
    within a group is immaterial to the decoded multiset), packed 20-bit
    two-per-5-bytes.  ~2.6-2.9 B/edge vs 5 for PAIR40.
    """
    n = src.shape[0]
    out = np.empty(ef40_nbytes(n, capacity), np.uint8)
    lib = load_ingest_lib()
    if lib is not None and hasattr(lib, "pack_edges_ef40"):
        wrote = lib.pack_edges_ef40(
            src.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            dst.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            n,
            capacity,
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
            out.nbytes,
        )
        if wrote == out.nbytes:
            return out
    order = np.argsort(src, kind="stable")  # group by src, arrival within
    s_grouped = src[order].astype(np.int64)
    d_grouped = dst[order].astype(np.int64) & 0xFFFFF
    bits = np.zeros((n + capacity,), np.uint8)
    bits[s_grouped + np.arange(n, dtype=np.int64)] = 1
    bv = np.packbits(bits, bitorder="little")
    pad = d_grouped if n % 2 == 0 else np.append(d_grouped, 0)
    pairs = pad[0::2].astype(np.uint64) | (pad[1::2].astype(np.uint64) << np.uint64(20))
    low = np.ascontiguousarray(
        pairs.view(np.uint8).reshape(-1, 8)[:, :5]
    ).reshape(-1)
    out[: bv.nbytes] = bv
    out[bv.nbytes :] = low
    return out


def unpack_edges_ef40(wire, n: int, capacity: int):
    """Device-side EF40 unpack: wire uint8 -> src-grouped (src, dst) int32[n].

    Jit-friendly (static n/capacity): bit expansion + one cumsum recovers the
    unary src ranks; the dst stream unpacks like PAIR40 lows.  The extra
    device work (a [n+capacity] cumsum and an n-scatter) is trivial next to
    the 2x wire-byte saving the format buys on multi-core hosts.
    """
    import jax.numpy as jnp

    bvbytes = (n + capacity + 7) // 8
    bv = wire[:bvbytes]
    bits = ((bv[:, None] >> jnp.arange(8, dtype=jnp.uint8)) & 1).reshape(-1)
    bits = bits[: n + capacity].astype(jnp.int32)
    r = jnp.cumsum(bits) - 1  # rank of the one at each position
    pos = jnp.arange(n + capacity, dtype=jnp.int32)
    src = (
        jnp.zeros((n,), jnp.int32)
        .at[jnp.where(bits == 1, r, n)]
        .max(pos - r, mode="drop")
    )
    npairs = (n + 1) // 2
    b = wire[bvbytes : bvbytes + 5 * npairs].reshape(npairs, 5).astype(jnp.uint32)
    lo = (b[:, 0] | (b[:, 1] << 8) | (b[:, 2] << 16)) & 0xFFFFF
    hi = (b[:, 2] >> 4) | (b[:, 3] << 4) | (b[:, 4] << 12)
    dst = jnp.stack([lo, hi], axis=1).reshape(-1)[:n].astype(jnp.int32)
    return src, dst


# ---------------------------------------------------------------------------
# BDV: destination-binned delta/varint wire format (ISSUE 6).
#
# Propagation blocking (arXiv:2011.08451) applied to the host->device link: a
# micro-batch is binned/sorted by (dst, src) — legal only for ORDER-FREE folds,
# which see the same multiset — then shipped as one interleaved varint stream:
# per edge a dst delta (sorted, so mostly 0/tiny = 1 byte), then the src
# (absolute at each dst-run start, an ascending delta within the run).  A
# valued batch appends a zigzag-varint int32 value per edge.  On graphs with
# any destination locality this lands well under the fixed-width floor (the
# bench's skewed sample measures ~2-2.5 B/edge vs 5 for PAIR40 and 8 raw),
# and the sorted batch makes the consumer's fold scatter SEGMENT-LOCAL — the
# cache-win half of the papers (arXiv:1608.01362).  Buffers pow2-pad for
# shape-stable transfers; the device decoder (ops/wire_decode.py) drops the
# padding as empty varint groups.


def bdv_max_nbytes(n: int, valued: bool = False) -> int:
    """Worst-case BDV bytes for an n-edge batch: a 4-byte dst-delta varint
    plus a 5-byte zigzag src-delta varint per edge (plus a 5-byte zigzag
    value when valued)."""
    return (14 if valued else 9) * max(int(n), 1)


def _sort_edges_bdv(src: np.ndarray, dst: np.ndarray, capacity: int, val=None):
    """(dst, src)-stable-sorted copy of a batch: native cache-blocked
    counting sort when available (value-less, capacity in table range),
    else numpy lexsort — identical output order either way."""
    n = src.shape[0]
    if val is None and n and capacity <= _BDV_NATIVE_SORT_CAP:
        lib = load_ingest_lib()
        if lib is not None and hasattr(lib, "sort_edges_dst_src"):
            out_s = np.empty(n, np.int32)
            out_d = np.empty(n, np.int32)
            rows = lib.sort_edges_dst_src(
                src.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
                dst.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
                n,
                capacity,
                out_s.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
                out_d.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            )
            if rows == n:
                return out_s, out_d, None
    order = np.lexsort((src, dst))
    return (
        src[order],
        dst[order],
        None if val is None else jax_tree_take(val, order),
    )


def jax_tree_take(val, order):
    """Permute every leaf of a per-edge value pytree by ``order`` (host)."""
    import jax

    return jax.tree.map(lambda a: np.asarray(a)[order], val)


def _varint_encode_np(vals: np.ndarray) -> np.ndarray:
    """uint32-ish value array -> group-varint bytes (control block of 2-bit
    lengths, then little-endian value bytes) — byte-identical to the native
    encoder's stream (vectorized)."""
    vals = np.asarray(vals, np.uint64)
    count = len(vals)
    ctrl = (count + 3) // 4
    lens = np.ones(count, np.int64)
    for k in (8, 16, 24):
        lens += vals >= (np.uint64(1) << np.uint64(k))
    ends = np.cumsum(lens)
    total = ctrl + (int(ends[-1]) if count else 0)
    out = np.zeros(total, np.uint8)
    k = np.arange(count)
    np.bitwise_or.at(
        out, k >> 2, ((lens - 1) << (2 * (k & 3))).astype(np.uint8)
    )
    starts = ctrl + ends - lens
    for j in range(4):
        sel = lens > j
        if not sel.any():
            break
        out[starts[sel] + j] = (
            (vals[sel] >> np.uint64(8 * j)) & np.uint64(0xFF)
        ).astype(np.uint8)
    return out


def _varint_decode_np(buf: np.ndarray, count: int) -> np.ndarray:
    """Host twin of ops.wire_decode.decode_varints (numpy, same layout).

    Unlike the device decoder (whose clipped gathers silently read garbage
    from a short buffer — devices cannot raise), this host path REFUSES a
    buffer shorter than its own control block + payload: it is the
    validation front door (``EdgeStream.from_wire``'s smoke guard and the
    replay slow path), so truncation must be a clean error."""
    b = np.asarray(buf, np.uint8).astype(np.int64)
    ctrl = (count + 3) // 4
    nb_in = len(b)
    if nb_in < ctrl:
        raise ValueError(
            f"BDV buffer truncated: {count} varints need a {ctrl}-byte "
            f"control block, got {nb_in} bytes total"
        )
    k = np.arange(count)
    lens = ((b[k >> 2] >> (2 * (k & 3))) & 3) + 1 if count else np.zeros(0, np.int64)
    needed = ctrl + (int(lens.sum()) if count else 0)
    if nb_in < needed:
        raise ValueError(
            f"BDV buffer truncated: control block declares {needed} bytes, "
            f"got {nb_in}"
        )
    starts = ctrl + np.cumsum(lens) - lens
    vals = np.zeros(count, np.int64)
    nb = len(b)
    for j in range(4):
        idx = np.minimum(starts + j, nb - 1)
        vals |= np.where(lens > j, b[idx] << (8 * j), 0)
    return vals


def _zigzag_encode_np(v: np.ndarray) -> np.ndarray:
    v = np.asarray(v, np.int64)
    return np.asarray((v << 1) ^ (v >> 63), np.uint64)


def _encode_bdv_np(src_s, dst_s, val_i32=None) -> np.ndarray:
    """Varint-encode a dst-sorted batch (numpy fallback encoder —
    byte-identical to the native encode_edges_bdv): unsigned dst deltas
    interleaved with GLOBAL zigzag src deltas (src[-1] = 0), so the decode
    is a pair of cumsums."""
    n = len(src_s)
    per = 2 if val_i32 is None else 3
    s = np.asarray(src_s, np.int64)
    d = np.asarray(dst_s, np.int64)
    d_delta = np.empty(n, np.int64)
    s_delta = np.empty(n, np.int64)
    if n:
        d_delta[0] = d[0]
        d_delta[1:] = np.diff(d)
        s_delta[0] = s[0]
        s_delta[1:] = np.diff(s)
    stream = np.empty(per * n, np.uint64)
    stream[0::per] = d_delta.astype(np.uint64)
    stream[1::per] = _zigzag_encode_np(s_delta) & np.uint64(0xFFFFFFFF)
    if val_i32 is not None:
        stream[2::per] = _zigzag_encode_np(np.asarray(val_i32, np.int64))
    return _varint_encode_np(stream)


def sort_edges_binned(
    src: np.ndarray,
    dst: np.ndarray,
    capacity: int,
    record_stats: bool = False,
):
    """Destination-bin a value-less batch: the (dst, src) stable sort every
    binned-ingest site shares (native sorter when available, numpy lexsort
    fallback — identical order either way).  ``record_stats`` bumps the
    wire-path bin-occupancy high-water (utils.metrics) — hot-path callers
    only.  Returns ``(src_sorted, dst_sorted)``."""
    s, d, _ = _sort_edges_bdv(
        np.ascontiguousarray(src, dtype=np.int32),
        np.ascontiguousarray(dst, dtype=np.int32),
        capacity,
    )
    if record_stats:
        from ..utils import metrics as _metrics

        _metrics.wire_high_water("wire_bin_occupancy_hwm", max_dst_run(d))
    return s, d


def max_dst_run(dst_sorted: np.ndarray) -> int:
    """Longest equal-dst run of a sorted dst column — the bin-occupancy
    figure the wire metrics high-water (utils.metrics wire counters)."""
    n = len(dst_sorted)
    if n == 0:
        return 0
    bounds = np.flatnonzero(np.diff(dst_sorted) != 0)
    edges = np.concatenate([[-1], bounds, [n - 1]])
    return int(np.max(np.diff(edges)))


def pack_edges_bdv(
    src: np.ndarray,
    dst: np.ndarray,
    capacity: int,
    val_i32: Optional[np.ndarray] = None,
    sort: bool = True,
    record_stats: bool = False,
) -> np.ndarray:
    """Bin + compress an edge batch into a bucket-padded BDV wire buffer.

    Sorts by (dst, src) unless the caller already did (``sort=False``),
    varint-encodes (native encoder on the value-less path, numpy fallback
    byte-identical), and zero-pads to the byte bucket
    (``bdv_bucket_nbytes``) so same-shape batches reuse one compiled
    decode+fold executable.  Ships a MULTISET: order-free consumers only
    (the same contract as EF40).  ``record_stats`` bumps the wire-path
    bin-occupancy high-water (utils.metrics) — hot-path callers only.
    """
    if capacity <= 0 or capacity > (1 << BDV_MAX_ID_BITS):
        raise ValueError(
            f"BDV needs 0 < capacity <= 2^{BDV_MAX_ID_BITS} (got {capacity})"
        )
    src = np.ascontiguousarray(src, dtype=np.int32)
    dst = np.ascontiguousarray(dst, dtype=np.int32)
    n = src.shape[0]
    if dst.shape[0] != n:
        raise ValueError("src/dst length mismatch")
    if sort:
        src, dst, val_i32 = _sort_edges_bdv(src, dst, capacity, val_i32)
    if record_stats:
        from ..utils import metrics as _metrics

        _metrics.wire_high_water("wire_bin_occupancy_hwm", max_dst_run(dst))
    payload = None
    if val_i32 is None:
        lib = load_ingest_lib()
        if lib is not None and hasattr(lib, "encode_edges_bdv"):
            out = np.empty(bdv_max_nbytes(n) + 8, np.uint8)
            wrote = lib.encode_edges_bdv(
                src.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
                dst.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
                n,
                out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
                out.nbytes,
            )
            if wrote >= 0:
                payload = out[:wrote]
    if payload is None:
        payload = _encode_bdv_np(src, dst, val_i32)
    # bucket padding clamps at the documented worst-case bound: wire_nbytes
    # is the validation/arena ceiling (EdgeStream.from_wire, the mesh replay
    # rows), so a near-worst-case payload must never bucket PAST it
    bucket = min(
        bdv_bucket_nbytes(len(payload)),
        bdv_max_nbytes(n, val_i32 is not None),
    )
    buf = np.zeros(bucket, np.uint8)
    buf[: len(payload)] = payload
    return buf


def bdv_bucket_nbytes(payload_nbytes: int) -> int:
    """Shape bucket for a BDV payload: the next size of form {4,5,6,7}<<k.

    Pure pow2 bucketing wastes up to half the transfer on padding — real
    bytes on the link the format exists to relieve; quarter-octave buckets
    cap the pad at 25% while keeping the compiled-shape set small and
    stable (4 sizes per octave, so same-regime batches still reuse one
    decode+fold executable — the retrace guard pins it).
    """
    n = max(int(payload_nbytes), 4)
    k = max((n - 1).bit_length() - 3, 0)
    return -(-n >> k) << k  # ceil to a multiple of 2^k


def unpack_edges_bdv_host(buf: np.ndarray, n: int, valued: bool = False):
    """Host (numpy) BDV decode -> (src, dst[, val]) int32[n] in the packed
    (dst, src)-sorted multiset order — the replay slow path and the
    device-decode oracle (host==device pinned by tests/test_wire_bdv.py)."""
    per = 3 if valued else 2
    vals = _varint_decode_np(np.asarray(buf, np.uint8), per * n)
    d_delta = vals[0::per]
    s_enc = vals[1::per].astype(np.uint64)
    dst = np.cumsum(d_delta).astype(np.int32)
    # global zigzag src deltas: the chain telescopes, so src is one cumsum
    s_delta = ((s_enc >> np.uint64(1)).astype(np.int64)) ^ -(
        s_enc & np.uint64(1)
    ).astype(np.int64)
    src = np.cumsum(s_delta).astype(np.int32)
    if not valued:
        return src, dst
    z = vals[2::per].astype(np.uint64)
    val = ((z >> np.uint64(1)).astype(np.int64)) ^ -(z & np.uint64(1)).astype(
        np.int64
    )
    return src, dst, val.astype(np.int32)


def pack_edges(src: np.ndarray, dst: np.ndarray, width) -> np.ndarray:
    """Pack an edge batch into a uint8 wire buffer.

    ``width`` is a byte width (2/3/4: src block then dst block, ids truncated
    to little-endian bytes) or ``PAIR40`` (5-byte packed pairs).
    """
    if width not in (2, 3, 4, PAIR40) and not (
        isinstance(width, tuple) and width[0] in (EF40, BDV)
    ):
        raise ValueError(f"unsupported wire width {width}")
    src = np.ascontiguousarray(src, dtype=np.int32)
    dst = np.ascontiguousarray(dst, dtype=np.int32)
    n = src.shape[0]
    if dst.shape[0] != n:
        raise ValueError("src/dst length mismatch")
    if isinstance(width, tuple):  # (EF40 | BDV, capacity)
        if width[0] == BDV:
            return pack_edges_bdv(src, dst, width[1])
        return _pack_edges_ef40(src, dst, width[1])
    if width == PAIR40:
        return _pack_edges40(src, dst)
    lib = load_ingest_lib()
    if lib is not None and hasattr(lib, "pack_edges"):
        out = np.empty(2 * n * width, np.uint8)
        wrote = lib.pack_edges(
            src.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            dst.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            n,
            width,
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        )
        if wrote == out.nbytes:
            return out
    # numpy fallback: little-endian int32 bytes, keep the low `width` of each 4
    def low_bytes(x: np.ndarray) -> np.ndarray:
        b = x.view(np.uint8).reshape(-1, 4)[:, :width]
        return np.ascontiguousarray(b).reshape(-1)

    return np.concatenate([low_bytes(src), low_bytes(dst)])


def pack_edges_into(src: np.ndarray, dst: np.ndarray, width, out: np.ndarray) -> None:
    """Pack an edge batch directly into ``out`` (a ``uint8[wire_nbytes]``
    slice, e.g. one row of a superbatch transfer arena).

    The native packers write through the destination pointer with the GIL
    released — the zero-re-copy path the parallel ingest pool
    (io/ingest.py) rides; without the native library the packed bytes are
    copied in from the allocating packer (one extra memcpy, same bytes).
    """
    if isinstance(width, tuple) and width[0] == BDV:
        # BDV rows are data-dependent sizes; fixed-slice arena packing has
        # no meaningful contract for them — group arenas bucket to the
        # group's own max instead (io/ingest.pack_bdv_group)
        raise ValueError("BDV buffers are variable-size; use pack_edges_bdv")
    src = np.ascontiguousarray(src, dtype=np.int32)
    dst = np.ascontiguousarray(dst, dtype=np.int32)
    n = src.shape[0]
    if dst.shape[0] != n:
        raise ValueError("src/dst length mismatch")
    expect = wire_nbytes(n, width)
    if out.dtype != np.uint8 or out.nbytes != expect or not out.flags.c_contiguous:
        raise ValueError(
            f"out must be a contiguous uint8 buffer of {expect} bytes"
        )
    lib = load_ingest_lib()
    if lib is not None:
        out_p = out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))
        src_p = src.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))
        dst_p = dst.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))
        if isinstance(width, tuple) and hasattr(lib, "pack_edges_ef40"):
            if lib.pack_edges_ef40(src_p, dst_p, n, width[1], out_p, expect) == expect:
                return
        elif width == PAIR40 and hasattr(lib, "pack_edges40"):
            if lib.pack_edges40(src_p, dst_p, n, out_p) == expect:
                return
        elif width in (2, 3, 4) and hasattr(lib, "pack_edges"):
            if lib.pack_edges(src_p, dst_p, n, width, out_p) == expect:
                return
    out[:] = pack_edges(src, dst, width)


def unpack_edges(wire, n: int, width, xp=None):
    """Wire uint8 buffer -> (src, dst) int32[n].

    Device-side by default (jit-friendly, static n/width; the byte combines
    fuse into the caller's surrounding kernel so the unpack adds no extra
    HBM round trip).  Pass ``xp=np`` for a host-side decode of the
    fixed-width encodings — the same code path, so host and device cannot
    disagree.  EF40 needs the device scatter (or ``unpack_edges_host``).
    """
    if isinstance(width, tuple):  # (EF40 | BDV, capacity)
        if width[0] == BDV:
            from gelly_streaming_tpu.ops import wire_decode

            return wire_decode.decode_bdv(wire, n)
        return unpack_edges_ef40(wire, n, width[1])
    if xp is None:
        import jax.numpy as xp

    if width == PAIR40:
        return _unpack_edges40(wire, n, xp)
    b = wire.reshape(2, n, width).astype(xp.uint32)
    v = b[..., 0]
    for k in range(1, width):
        v = v | (b[..., k] << (8 * k))
    v = v.astype(xp.int32)
    return v[0], v[1]


def replay_width(capacity: int, batch: int, order_free: bool = True):
    """Encoding policy for a replay producer: whichever legal encoding ships
    the fewest wire bytes for this (capacity, batch).

    EF40 is only legal for order-free folds with ids in 20 bits, and only
    *smaller* when its per-batch unary bitvector ((batch + capacity)/8 B) is
    outweighed by the 2.5 B/edge dst stream — i.e. capacity small relative
    to batch; for capacity >> batch the fixed-width pack wins despite its 5
    B/edge."""
    fixed = width_for_capacity(capacity)
    if (
        order_free
        and capacity <= 1 << 20
        and ef40_nbytes(batch, capacity) < wire_nbytes(batch, fixed)
    ):
        return (EF40, capacity)
    return fixed


def pack_stream(
    src: np.ndarray, dst: np.ndarray, batch: int, width
) -> Tuple[list, Optional[Tuple[np.ndarray, np.ndarray]]]:
    """Pre-pack a finite edge stream into per-batch wire buffers.

    Returns ``(bufs, tail)``: full-batch uint8 buffers plus the raw
    ``(src, dst)`` remainder (or None).  This is the producer side of the
    replay contract (``EdgeStream.from_wire``): in the reference, records
    reach the hot operator already serialized by the upstream network stack
    (SummaryBulkAggregation.java:76-83 consumes Flink's wire tuples); the
    TPU analog is a stream recorded in — or delivered already in — the
    framework's own wire format.
    """
    src = np.ascontiguousarray(src, dtype=np.int32)
    dst = np.ascontiguousarray(dst, dtype=np.int32)
    n_full = len(src) // batch
    bufs = [
        pack_edges(src[i * batch : (i + 1) * batch], dst[i * batch : (i + 1) * batch], width)
        for i in range(n_full)
    ]
    rem = len(src) - n_full * batch
    tail = (src[n_full * batch :], dst[n_full * batch :]) if rem else None
    return bufs, tail


def pack_bucket_rows(
    src2d: np.ndarray, dst2d: np.ndarray, counts: np.ndarray, width
) -> np.ndarray:
    """Pack per-shard edge buckets into wire rows: the mesh feed's keyBy form.

    ``src2d``/``dst2d`` are [S, cap] host buckets (e.g. ``routing.host_route``
    output, produced on the prefetcher's pack thread) with ``counts[s]``
    valid edges per row.  Returns ``uint8[S, wire_nbytes(cap, width)]`` rows
    whose pad region obeys the count-prefix contract of the sharded device
    steps: fixed-width encodings keep position (zero pads are fine), EF40
    sorts — pads are rewritten to the maximal id pair so they sort to the
    END and a count prefix selects exactly the real edges (the same
    invariant as ``MeshAggregationRunner._pack_pane_wire``).
    """
    n_rows, cap = src2d.shape
    rows = np.zeros((n_rows, wire_nbytes(cap, width)), np.uint8)
    pad_id = width[1] - 1 if isinstance(width, tuple) else 0
    s = np.empty((cap,), np.int32)
    d = np.empty((cap,), np.int32)
    for r in range(n_rows):
        k = int(counts[r])
        s[:k] = src2d[r, :k]
        d[:k] = dst2d[r, :k]
        s[k:] = pad_id
        d[k:] = pad_id
        pack_edges_into(s, d, width, rows[r])
    return rows


# ---------------------------------------------------------------------------
# Serving-plane decode (ISSUE 14): one-pass native validate + decode (+ bin)
# of a pushed wire buffer into caller-owned transfer arenas.  The numpy twin
# below is the equivalence oracle — the decode pool's GELLY_DECODE_WORKERS=0
# path and the refusal phrasing both come from it, so the native fast path
# can never drift observably from the pure-Python plane.

# decode_wire_into's native width codes: fixed byte widths pass through,
# PAIR40/BDV get codes past the byte widths (EF40 never crosses the push
# boundary — width_for_capacity never returns it)
_NATIVE_DECODE_CODES = {2: 2, 3: 3, 4: 4, PAIR40: 5}


def decode_wire_np(buf, n: int, width, capacity: int, sort: bool = False):
    """Numpy twin of the native ``decode_wire_into``: the full
    ``core/stream.validate_wire_buffer`` guard set (size bounds, host
    decode, BOTH ends of the id range) plus the optional (dst, src)
    binning pass.  This is the oracle: its typed ``ValueError``s are the
    refusals the serving plane sends, whichever implementation ran."""
    from ..core.stream import validate_wire_buffer

    s, d = validate_wire_buffer(buf, n, width, capacity, decode_ids=True)
    if sort:
        s, d = sort_edges_binned(s, d, capacity)
    return s, d


def decode_wire_into(
    buf,
    n: int,
    width,
    capacity: int,
    out_src: np.ndarray,
    out_dst: np.ndarray,
    sort: bool = False,
) -> bool:
    """Native one-pass validate + decode (+ bin) of one wire buffer into
    ``out_src``/``out_dst`` (contiguous int32[n], e.g. the rows of a
    decode-pool transfer arena), with the GIL released for the whole call.

    Returns True when the native path ran and validated the buffer; False
    when it is unavailable (no compiled library, an encoding it does not
    cover, an internal fallback) — the caller then runs ``decode_wire_np``.
    A REFUSED buffer raises the oracle's own typed ``ValueError``: the
    native code only detects, the numpy twin phrases, so the error surface
    is byte-identical to the pure-Python path by construction.
    """
    code = (
        6
        if (isinstance(width, tuple) and width[0] == BDV)
        else _NATIVE_DECODE_CODES.get(width)
    )
    lib = load_ingest_lib()
    if code is None or lib is None or not hasattr(lib, "decode_wire_into"):
        return False
    b = np.asarray(buf)
    if (
        b.dtype != np.uint8
        or not b.flags.c_contiguous
        or out_src.dtype != np.int32
        or out_dst.dtype != np.int32
        or out_src.shape != (n,)
        or out_dst.shape != (n,)
        or not out_src.flags.c_contiguous
        or not out_dst.flags.c_contiguous
    ):
        return False  # odd layouts take the twin (which also phrases dtype refusals)
    rc = lib.decode_wire_into(
        b.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        b.nbytes,
        n,
        code,
        capacity,
        1 if sort else 0,
        out_src.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        out_dst.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
    )
    if rc == n:
        return True
    if rc == -4:
        # internal (alloc failure / sort bounds): not a client refusal —
        # the numpy twin serves the request instead
        return False
    # typed refusal: let the oracle raise the canonical error for THIS
    # buffer; reaching past it means the two decoders disagree, which the
    # fuzz suite (tests/test_decode_pool.py) pins as unreachable
    decode_wire_np(buf, n, width, capacity, sort=sort)
    raise RuntimeError(
        f"native decode refused (rc={rc}) a buffer the numpy oracle "
        "accepts — decoder drift; re-run tests/test_decode_pool.py"
    )


def unpack_edges_host(buf: np.ndarray, n: int, width):
    """Host-side (numpy) decode of one wire buffer -> (src, dst) int32[n].

    The replay source's slow-path materializer: consumers outside the fused
    wire path (windowed ops, snapshots) get ordinary EdgeBatches.  The
    fixed-width encodings reuse the device decode with ``xp=np``; EF40 —
    whose device form needs a jax scatter — decodes the unary bitvector via
    flatnonzero, with host==device equality pinned by tests/test_wire.py.
    EF40 buffers decode to src-grouped order (the multiset, not the arrival
    sequence — same contract as the device unpack).
    """
    buf = np.asarray(buf, np.uint8)
    if isinstance(width, tuple) and width[0] == BDV:
        return unpack_edges_bdv_host(buf, n)
    if isinstance(width, tuple):  # (EF40, capacity)
        capacity = width[1]
        bvbytes = (n + capacity + 7) // 8
        bits = np.unpackbits(buf[:bvbytes], bitorder="little")[: n + capacity]
        src = (np.flatnonzero(bits) - np.arange(n, dtype=np.int64)).astype(np.int32)
        dst_lo, dst_hi = _unpack_edges40(
            buf[bvbytes : bvbytes + 5 * ((n + 1) // 2)], (n + 1) // 2, np
        )
        dst = np.stack([dst_lo & 0xFFFFF, dst_hi], axis=1).reshape(-1)[:n]
        return src, dst.astype(np.int32)
    return unpack_edges(buf, n, width, xp=np)


# ---------------------------------------------------------------------------
# Emission-plane packing (device -> host), the mirror of the ingest wire: a
# property-trace record (vertex id, running value) packs on DEVICE into 48
# bits + 1 mask bit before download, vs 9 B for raw int32 columns + bool
# mask — on a downlink-bound link that is a ~1.5x faster trace.


def pack_records48(ids, vals):
    """Device-side: (ids < 2^20, vals < 2^28) -> uint8[B*6] little-endian.

    Split across two uint32 lanes (no uint64 under the default x64-disabled
    config): lo = id | (val & 0xFFF) << 20, hi = val >> 12 (16 bits).
    """
    import jax.numpy as jnp

    ids_u = ids.astype(jnp.uint32)
    vals_u = jnp.clip(vals, 0, (1 << 28) - 1).astype(jnp.uint32)
    lo = ids_u | ((vals_u & 0xFFF) << 20)
    hi = vals_u >> 12
    shifts4 = jnp.arange(4, dtype=jnp.uint32) * 8
    shifts2 = jnp.arange(2, dtype=jnp.uint32) * 8
    b_lo = ((lo[:, None] >> shifts4) & 0xFF).astype(jnp.uint8)
    b_hi = ((hi[:, None] >> shifts2) & 0xFF).astype(jnp.uint8)
    return jnp.concatenate([b_lo, b_hi], axis=1).reshape(-1)


def pack_mask_bits(mask):
    """Device-side: bool[B] -> uint8[ceil(B/8)] little-endian bit packing."""
    import jax.numpy as jnp

    b = mask.shape[0]
    pad = (-b) % 8
    m = jnp.concatenate([mask, jnp.zeros((pad,), bool)]) if pad else mask
    weights = (1 << jnp.arange(8, dtype=jnp.uint32)).astype(jnp.uint32)
    return jnp.sum(
        m.reshape(-1, 8).astype(jnp.uint32) * weights[None, :], axis=1
    ).astype(jnp.uint8)


def unpack_records48(packed: np.ndarray, maskbits: np.ndarray, n: int):
    """Host-side decode: (uint8[n*6], uint8[ceil(n/8)]) -> (ids, vals, mask)."""
    b = np.asarray(packed, np.uint8).reshape(n, 6).astype(np.uint32)
    lo = b[:, 0] | (b[:, 1] << 8) | (b[:, 2] << 16) | (b[:, 3] << 24)
    hi = b[:, 4] | (b[:, 5] << 8)
    ids = (lo & 0xFFFFF).astype(np.int64)
    vals = ((lo >> 20) | (hi << 12)).astype(np.int64)
    bits = np.unpackbits(np.asarray(maskbits, np.uint8), bitorder="little")[:n]
    return ids, vals, bits.astype(bool)


class Prefetcher:
    """Prepare + transfer items ahead of the device consumer.

    Wraps an iterator; ``prepare(item) -> (meta, host_arrays)`` (host-side
    packing) runs on one background thread and the ``device_put`` of the
    arrays (a pytree, or None to skip the transfer) on a SECOND, so packing
    item k+1 overlaps transferring item k — on a multi-core host the
    pipeline's rate is max(pack, transfer) instead of their sum (device_put
    is synchronous: it occupies its thread for the whole transfer).  Yields
    ``(meta, device_arrays)`` in order with up to ``depth`` results in
    flight per stage.  ``close()`` (or use as a context manager) releases
    the threads and any in-flight buffers if the consumer stops early;
    exhausting the iterator closes implicitly.
    """

    _SENTINEL = object()

    def __init__(self, items: Iterable, prepare, device=None, depth: int = 4):
        import jax

        from ..utils import metrics as _metrics

        _metrics.pipeline_high_water("pipeline_prefetch_depth", depth)
        self._prepare = prepare
        self._device = device if device is not None else jax.devices()[0]
        self._midq: "queue.Queue" = queue.Queue(maxsize=depth)
        self._q: "queue.Queue" = queue.Queue(maxsize=depth)
        self._error: Optional[BaseException] = None
        self._stop = threading.Event()
        self._threads = [
            threading.Thread(target=self._run_pack, args=(iter(items),), daemon=True),
            threading.Thread(target=self._run_put, daemon=True),
        ]
        for t in self._threads:
            t.start()

    def _put(self, q: "queue.Queue", item) -> bool:
        """Bounded put that gives up when the consumer has closed."""
        while not self._stop.is_set():
            try:
                q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def _get(self, q: "queue.Queue"):
        while not self._stop.is_set():
            try:
                return q.get(timeout=0.1)
            except queue.Empty:
                continue
        return self._SENTINEL

    def _run_pack(self, it: Iterator):
        import time as _time

        from ..utils import metrics as _metrics

        try:
            for item in it:
                if self._stop.is_set():
                    return
                prepared = self._prepare(item)
                t0 = _time.perf_counter()
                ok = self._put(self._midq, prepared)
                # pack-stage stall: downstream (transfer/consumer)
                # backpressure held the packed item out of the queue
                _metrics.pipeline_add(
                    "pipeline_pack_stall_s", _time.perf_counter() - t0
                )
                if not ok:
                    return
        except BaseException as e:  # surfaced on the consumer thread
            if self._error is None:  # keep the FIRST failure (root cause)
                self._error = e
        finally:
            self._put(self._midq, self._SENTINEL)

    def _run_put(self):
        import time as _time

        import jax

        from ..utils import metrics as _metrics
        from ..utils import tracing as _tracing

        try:
            while True:
                t0 = _time.perf_counter()
                got = self._get(self._midq)
                # transfer-stage stall: the transfer thread starved waiting
                # for the pack stage (utils.metrics pipeline counters)
                _metrics.pipeline_add(
                    "pipeline_transfer_stall_s", _time.perf_counter() - t0
                )
                if got is self._SENTINEL:
                    return
                meta, host = got
                # a sampled window's span rides the meta: time its
                # device_put as the "transfer" stage (the active() gate
                # keeps untraced processes at zero extra work here)
                span = (
                    _tracing.find_span(meta) if _tracing.active() else None
                )
                t_put = _time.perf_counter() if span is not None else 0.0
                # device_put blocks this thread for the transfer; the pack
                # thread keeps preparing the next items meanwhile
                dev = None if host is None else jax.device_put(host, self._device)
                if span is not None and host is not None:
                    span.mark("transfer", t_put)
                if not self._put(self._q, (meta, dev)):
                    return
        except BaseException as e:
            if self._error is None:
                self._error = e
        finally:
            self._put(self._q, self._SENTINEL)

    def close(self):
        """Stop the producers and drop queued buffers (idempotent).

        Joins BEFORE draining: with the stop flag set the bounded puts give
        up within their timeout, and only once the threads have exited can
        no in-flight put repopulate a queue after the drain (which would pin
        a device buffer until GC)."""
        self._stop.set()
        for t in self._threads:
            t.join(timeout=5.0)
        for q in (self._midq, self._q):
            while True:
                try:
                    q.get_nowait()
                except queue.Empty:
                    break

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def __iter__(self):
        try:
            while True:
                item = self._q.get()
                if item is self._SENTINEL:
                    if self._error is not None:
                        raise self._error
                    return
                yield item
        finally:
            self.close()


class WirePrefetcher(Prefetcher):
    """Pack + transfer edge batches ahead of the device consumer.

    Wraps an iterator of (src, dst) numpy batches; yields
    ``(device wire buffer, batch length)`` pairs in order (see Prefetcher for
    the threading/backpressure contract).
    """

    def __init__(
        self,
        batches: Iterable[Tuple[np.ndarray, np.ndarray]],
        width,
        device=None,
        depth: int = 4,
    ):
        def prepare(item):
            src, dst = item
            return src.shape[0], pack_edges(src, dst, width)

        super().__init__(batches, prepare, device=device, depth=depth)

    def __iter__(self):
        for n, buf in super().__iter__():
            yield buf, n


def prefetch_to_host(device_iter, depth: int = 4):
    """Emission-plane mirror of the ingest Prefetcher: overlap device->host
    downloads with device compute.

    Wraps an iterator of per-batch DEVICE pytrees (e.g. `_kernel_stream`
    outputs): each item's ``copy_to_host_async`` starts the moment it is
    produced, up to ``depth`` stay in flight, and items materialize
    (np.asarray, instant once the async copy landed) in order.  Without
    this, a trace consumer blocks the device pipeline on every batch's
    synchronous download — on a narrow link the round trips
    serialize and the emission plane runs far under the downlink rate;
    with it the steady-state rate is
    min(downlink, host decode), not their serialized sum with RTTs.
    """
    import collections

    import jax

    pending = collections.deque()
    for outs in device_iter:
        for leaf in jax.tree.leaves(outs):
            try:
                leaf.copy_to_host_async()
            except AttributeError:
                pass  # host-side leaves (numpy) need no copy
        pending.append(outs)
        if len(pending) > depth:
            yield jax.tree.map(np.asarray, pending.popleft())
    while pending:
        yield jax.tree.map(np.asarray, pending.popleft())
