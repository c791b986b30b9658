"""Loader for the native (C++) host-plane helpers.

Builds ``native/edge_parser.cpp`` into a shared library on first use (g++ is in
the image; pybind11 is not, so the boundary is a plain C ABI via ctypes),
named by a hash of the source it was built from, and exposes a typed
wrapper.  Falls back cleanly to ``None`` when no compiler is
available — callers keep a pure-numpy path.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import threading
from typing import Optional

logger = logging.getLogger(__name__)

_PKG_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_REPO_ROOT = os.path.dirname(_PKG_ROOT)


def _find_src():
    """The canonical C++ source is the PACKAGED copy
    (gelly_streaming_tpu/native_src/edge_parser.cpp — shipped as package
    data so pip installs keep the native ingest path); the repo-layout
    ``native/edge_parser.cpp`` is a one-``#include`` reference stub, so
    there is exactly one source of truth to edit (the drift guard is
    tests/test_native_source_sync.py).  Returns (path, is_repo_layout) —
    the layout flag only picks where builds land."""
    pkg_src = os.path.join(_PKG_ROOT, "native_src", "edge_parser.cpp")
    repo_stub = os.path.join(_REPO_ROOT, "native", "edge_parser.cpp")
    if os.path.exists(pkg_src):
        return pkg_src, os.path.exists(repo_stub)
    return repo_stub, True


_SRC, _IS_REPO_LAYOUT = _find_src()
# A checkout builds inside itself (native/build, which git ignores); an
# installed package, whose site-packages may be read-only, builds in the
# per-user cache.  Either way the library's name carries the source hash,
# so a build from other bytes (another checkout's, a stale one) never loads.
_BUILD_DIR = (
    os.path.join(_REPO_ROOT, "native", "build")
    if _IS_REPO_LAYOUT
    else os.path.join(
        os.environ.get("XDG_CACHE_HOME", os.path.expanduser("~/.cache")),
        "gelly_streaming_tpu",
    )
)

_lock = threading.Lock()
_lib = None
_tried = False

# ---------------------------------------------------------------------------
# Single-source C ABI signature table.
#
# One row per extern "C" export of native_src/edge_parser.cpp:
# name -> (argument type tokens, result type token).  The loader below
# binds ctypes argtypes/restype FROM this table, and graftcheck's
# native-abi pass (analysis/nativecheck.py, NATIVEABI) parses the same
# literal out of this file with ``ast`` and diffs it against the C++
# signatures — so a drifting export fails the gate instead of silently
# corrupting memory across the language boundary.  Keep the value a PURE
# LITERAL (no computed entries): the analyzer reads it without importing.
#
# Type tokens: scalars ``int32``/``int64``/``double``; pointers with a
# trailing ``*``.  ``char*`` binds as c_char_p (Python bytes in), which is
# ABI-identical to ``uint8*`` — the analyzer treats 1-byte-pointee
# pointers as one class.
NATIVE_SIGNATURES = {
    "count_rows": (("char*",), "int64"),
    "fill_edges": (
        ("char*", "int64*", "int64*", "double*", "int64*", "int32*",
         "int64", "int32*"),
        "int64",
    ),
    "fill_edges_range": (
        ("char*", "int64", "int64", "int64*", "int64*", "double*",
         "int64*", "int32*", "int64", "int32*"),
        "int64",
    ),
    "count_rows_range": (("char*", "int64", "int64"), "int64"),
    "pack_edges": (
        ("int32*", "int32*", "int64", "int32", "uint8*"),
        "int64",
    ),
    "pack_edges40": (("int32*", "int32*", "int64", "uint8*"), "int64"),
    "pack_edges_ef40": (
        ("int32*", "int32*", "int64", "int32", "uint8*", "int64"),
        "int64",
    ),
    "sort_edges_dst_src": (
        ("int32*", "int32*", "int64", "int32", "int32*", "int32*"),
        "int64",
    ),
    "encode_edges_bdv": (
        ("int32*", "int32*", "int64", "uint8*", "int64"),
        "int64",
    ),
    "route_edges": (
        ("int32*", "int32*", "int64", "int32", "int32", "int64",
         "int32*", "int32*", "int64*"),
        "int64",
    ),
    "cc_baseline": (
        ("int32*", "int32*", "int64", "int32*", "int32"),
        "int64",
    ),
    "flink_proxy_cc": (
        ("int32*", "int32*", "int64", "int32*", "int32"),
        "int64",
    ),
    "flink_proxy_degrees": (
        ("int32*", "int32*", "int64", "int64*", "int32"),
        "int64",
    ),
    # serving data plane (ISSUE 14): GLY1 frame probe + one-pass wire
    # decode into transfer arenas (runtime/protocol.py, io/wire.py)
    "gly1_probe_prefix": (
        ("char*", "int64", "int64", "int64*", "int64*"),
        "int32",
    ),
    "decode_wire_into": (
        ("uint8*", "int64", "int64", "int32", "int32", "int32",
         "int32*", "int32*"),
        "int64",
    ),
}

_CTYPE_TOKENS = {
    "char*": ctypes.c_char_p,
    "int32": ctypes.c_int32,
    "int64": ctypes.c_int64,
    "double": ctypes.c_double,
    "uint8*": ctypes.POINTER(ctypes.c_uint8),
    "int32*": ctypes.POINTER(ctypes.c_int32),
    "int64*": ctypes.POINTER(ctypes.c_int64),
    "double*": ctypes.POINTER(ctypes.c_double),
}


def _source_digest() -> str:
    """Hash of the canonical source's bytes: the built library's name
    carries it, so a library built from any other source is never loaded
    (an mtime check would accept a stale build whose file is newer)."""
    with open(_SRC, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()[:16]


def _build() -> Optional[str]:
    try:
        digest = _source_digest()
    except OSError:
        return None  # no source shipped: callers keep the numpy path
    so = os.path.join(_BUILD_DIR, f"libgelly_ingest-{digest}.so")
    if os.path.exists(so):
        return so
    # compile to a private name, then rename: a concurrent process sees
    # either no library or the whole one
    tmp = f"{so}.{os.getpid()}.{threading.get_ident()}.tmp"
    try:
        os.makedirs(_BUILD_DIR, exist_ok=True)
        subprocess.run(
            ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", "-pthread",
             _SRC, "-o", tmp],
            check=True,
            capture_output=True,
            timeout=120,
        )
        os.replace(tmp, so)
        return so
    except (subprocess.CalledProcessError, subprocess.TimeoutExpired, OSError) as e:
        logger.warning(
            "native ingest library unavailable (%s: %s); using the numpy path",
            type(e).__name__,
            e,
        )
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return None


def load_ingest_lib():
    """The compiled ingest library, or None if unavailable."""
    global _lib, _tried
    with _lock:
        if _tried:
            return _lib
        _tried = True
        so = _build()
        if so is None:
            return None
        lib = ctypes.CDLL(so)
        # bind every declared export straight from the signature table (the
        # library is built from the current source, so all of them exist)
        for name, (arg_tokens, ret_token) in NATIVE_SIGNATURES.items():
            fn = getattr(lib, name)
            fn.argtypes = [_CTYPE_TOKENS[t] for t in arg_tokens]
            fn.restype = _CTYPE_TOKENS[ret_token]
        _lib = lib
        return _lib


# The repo-layout stub's entire sanctioned contents: one include of the
# canonical packaged source (plus comments).  There is no longer a second
# copy to hand-sync — the old ``--sync`` helper copied native/ over the
# packaging copy; single-sourcing made it (and the drift it managed)
# structurally impossible, and the guard test now pins THIS shape instead.
STUB_INCLUDE_LINE = '#include "../gelly_streaming_tpu/native_src/edge_parser.cpp"'


def stub_is_reference_only(path: "str | None" = None) -> bool:
    """True iff the repo-layout ``native/edge_parser.cpp`` carries no code
    of its own: every non-empty line is a comment except exactly one line,
    the canonical include (``STUB_INCLUDE_LINE``)."""
    if path is None:
        path = os.path.join(_REPO_ROOT, "native", "edge_parser.cpp")
    with open(path, "r", encoding="utf-8") as f:
        lines = [ln.strip() for ln in f]
    code = [ln for ln in lines if ln and not ln.startswith("//")]
    return code == [STUB_INCLUDE_LINE]
