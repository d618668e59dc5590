#!/usr/bin/env python3
"""Cut a recorded profiler trace down to what ``trace_reduce`` reads.

    python bench/trace_extract.py <trace dir or .xplane.pb> <out.xspace.txt>

Keeps each TPU device plane's ``XLA Ops`` line and
the one host line that holds the ``bench.window`` annotation, with every
event's name, start and duration, and writes them as an XSpace text proto,
which ``trace_reduce.load`` reads like the binary trace.  A trace cut so is
small enough to keep beside the tests.
"""
from __future__ import annotations

import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from bench.trace_reduce import (DEVICE_PLANE, HOST_PLANE, OPS_LINE,  # noqa: E402
                                WINDOW, find_xplane)


def _quote(s: str) -> str:
    return '"' + s.replace("\\", "\\\\").replace('"', '\\"').replace(
        "\n", "\\n") + '"'


def extract(src: str) -> str:
    from jax.profiler import ProfileData
    path = find_xplane(src) if os.path.isdir(src) else src
    data = ProfileData.from_file(path)
    out = []
    for pid, plane in enumerate(data.planes, 1):
        if DEVICE_PLANE.match(plane.name):
            keep = [ln for ln in plane.lines if ln.name == OPS_LINE]
        elif plane.name == HOST_PLANE:
            keep = [ln for ln in plane.lines
                    if any(e.name == WINDOW for e in ln.events)]
        else:
            continue
        names = {}
        lines = []
        for lid, line in enumerate(keep, 1):
            evs = []
            for e in line.events:
                mid = names.setdefault(e.name, len(names) + 1)
                evs.append(f"    events {{ metadata_id: {mid} offset_ps: "
                           f"{round(e.start_ns * 1000)} duration_ps: "
                           f"{round(e.duration_ns * 1000)} }}")
            lines.append(f"  lines {{\n    id: {lid}\n    name: "
                         f"{_quote(line.name)}\n    timestamp_ns: 0\n"
                         + "\n".join(evs) + "\n  }")
        meta = [f"  event_metadata {{ key: {i} value {{ id: {i} name: "
                f"{_quote(n)} }} }}" for n, i in names.items()]
        out.append(f"planes {{\n  id: {pid}\n  name: {_quote(plane.name)}\n"
                   + "\n".join(lines + meta) + "\n}")
    return "\n".join(out) + "\n"


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 2:
        print(__doc__.strip().splitlines()[2], file=sys.stderr)
        return 2
    with open(argv[1], "w") as f:
        f.write(extract(argv[0]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
