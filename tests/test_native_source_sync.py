"""Drift guard for the single-sourced native layer.

The canonical C++ source is the PACKAGED copy
(``gelly_streaming_tpu/native_src/edge_parser.cpp``); the repo-layout
``native/edge_parser.cpp`` is a one-``#include`` reference stub.  The two
can no longer drift because only one of them holds code — and this test
pins exactly that shape, so a well-meaning edit that re-introduces a
second hand-synced copy (the pre-ISSUE-14 state) fails tier-1 at the file
that did it.
"""

import os

from gelly_streaming_tpu.utils import native as native_mod

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STUB = os.path.join(ROOT, "native", "edge_parser.cpp")
CANONICAL = os.path.join(
    ROOT, "gelly_streaming_tpu", "native_src", "edge_parser.cpp"
)


def test_repo_stub_is_reference_only():
    assert native_mod.stub_is_reference_only(STUB), (
        "native/edge_parser.cpp must stay a reference stub — comments plus "
        f"exactly one {native_mod.STUB_INCLUDE_LINE!r} line.  The canonical "
        "source to edit is gelly_streaming_tpu/native_src/edge_parser.cpp "
        "(the packaged copy); a second code-carrying file would be a "
        "hand-synced fork, the drift this guard exists to prevent."
    )


def test_stub_include_resolves_to_canonical():
    """The stub's include path must actually reach the canonical source
    (a rename/move that breaks the relative path would otherwise only
    surface at the next cold native build)."""
    with open(STUB, "r", encoding="utf-8") as f:
        lines = [ln.strip() for ln in f if ln.strip().startswith("#include")]
    assert lines == [native_mod.STUB_INCLUDE_LINE]
    rel = lines[0].split('"')[1]
    resolved = os.path.normpath(os.path.join(os.path.dirname(STUB), rel))
    assert os.path.samefile(resolved, CANONICAL)


def test_loader_compiles_the_canonical_source():
    """The build path must compile the packaged source (one truth for the
    binary too), and the canonical file must be the code-carrying one."""
    assert os.path.samefile(native_mod._SRC, CANONICAL)
    with open(CANONICAL, "r", encoding="utf-8") as f:
        body = f.read()
    # spot-check that the canonical copy carries the real entry points
    for symbol in ("fill_edges_range", "sort_edges_dst_src", "decode_wire_into"):
        assert symbol in body


def test_stub_guard_rejects_code_carrying_copy(tmp_path):
    fork = tmp_path / "edge_parser.cpp"
    fork.write_text(
        "// comment\n"
        f"{native_mod.STUB_INCLUDE_LINE}\n"
        "int64_t sneaky() { return 0; }\n"
    )
    assert not native_mod.stub_is_reference_only(str(fork))
    missing = tmp_path / "missing_include.cpp"
    missing.write_text("// only comments, no include\n")
    assert not native_mod.stub_is_reference_only(str(missing))


def test_library_is_keyed_on_the_source_bytes(tmp_path, monkeypatch):
    """A library built from other source bytes is never loaded: the build's
    name carries the source's hash, so an edited source builds anew."""
    import shutil

    import pytest

    if shutil.which("g++") is None:
        pytest.skip("no C++ toolchain in this image")
    src = tmp_path / "edge_parser.cpp"
    shutil.copyfile(CANONICAL, src)
    monkeypatch.setattr(native_mod, "_SRC", str(src))
    monkeypatch.setattr(native_mod, "_BUILD_DIR", str(tmp_path / "build"))
    first = native_mod._build()
    assert first.endswith(f"-{native_mod._source_digest()}.so")
    assert native_mod._build() == first  # found by name, not rebuilt
    with open(src, "a") as f:
        f.write("// edited\n")
    second = native_mod._build()
    assert second != first and os.path.exists(second)
