"""Shared CLI plumbing for the example programs.

The reference examples hand-parse positional argv, print a usage line, and fall
back to generated input when no args are given (e.g.
ConnectedComponentsExample.java:81-140, WindowTriangles.java:146-171).  The
same contract holds here: ``<program> [input-path output-path ...knobs]`` with
a built-in default dataset when run bare.
"""

from __future__ import annotations

import sys
from typing import List, Optional, Tuple

from gelly_streaming_tpu.core import compile_cache
from gelly_streaming_tpu.core.config import StreamConfig
from gelly_streaming_tpu.core.output import OutputStream
from gelly_streaming_tpu.core.stream import EdgeStream
from gelly_streaming_tpu.io.sources import file_stream, generated_stream

DEFAULT_CFG = StreamConfig(vertex_capacity=1 << 16, max_degree=256, batch_size=1 << 12)


def extract_flags(argv, usage: str, allowed):
    """Split ``--name[=value]`` tokens from positionals (shared by the
    example CLIs so their flag contract cannot diverge): returns
    ``(positionals, {name: value-str-or-True})``; an unrecognized ``--``
    token prints the usage line and exits 2 instead of falling through as a
    filename."""
    args = list(sys.argv[1:] if argv is None else argv)
    flags = {}
    rest = []
    for a in args:
        if a.startswith("--"):
            name, _, value = a[2:].partition("=")
            if name not in allowed:
                print(usage, file=sys.stderr)
                raise SystemExit(2)
            flags[name] = value if value else True
        else:
            rest.append(a)
    return rest, flags


def flag_value(flags, name: str, usage: str):
    """Value of --name=VALUE, None if absent; a bare --name (no value)
    prints usage and exits 2 — shared so every example rejects the
    valueless form identically."""
    v = flags.get(name)
    if v is True:
        print(usage, file=sys.stderr)
        raise SystemExit(2)
    return v


def parse_argv(
    argv: Optional[List[str]], usage: str, max_positional: int
) -> List[str]:
    # every example's main() starts here: the entry point that places the
    # persistent compile cache before the first compile
    compile_cache.use_persistent_cache()
    args = list(sys.argv[1:] if argv is None else argv)
    if len(args) > max_positional:
        print(usage, file=sys.stderr)
        raise SystemExit(2)
    if not args:
        print("Executing example with default parameters and built-in default data.")
        print(f"  Provide parameters to read input data from a file.\n  Usage: {usage}")
    return args


def input_stream(
    args: List[str], cfg: StreamConfig = DEFAULT_CFG, generated_edges: int = 1000
) -> Tuple[EdgeStream, Optional[str]]:
    """(stream, output_path) from positional [input [output ...]] args."""
    if args:
        stream, _ = file_stream(args[0], cfg)
    else:
        stream = generated_stream(cfg, generated_edges, num_vertices=100)
    output = args[1] if len(args) > 1 else None
    return stream, output


def emit(out: OutputStream, output_path: Optional[str]) -> None:
    if output_path:
        out.write_csv(output_path)
    else:
        out.print()
