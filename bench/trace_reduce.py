"""Reduce a JAX profiler trace to the numbers the metrics read.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote, keeps the
window between the start and the end of the host annotation
``bench.window`` (the drivers put it around the measured window), and
collects from every TPU device plane the events of its ``XLA Ops`` line:
one event per device operation, with its start and duration in ns.

* busy time of a chip: the union of its operations' intervals inside the
  window; the device is idle for the rest.  ``busy_s`` averages the chips.
* an operation's time: the sum of its events' durations (``op_seconds``).
  A TPU trace names an event by the HLO instruction's text,
  ``%task_gradients_mnp.6 = f32[768,2048]{...} custom-call(...)``;
  ``Event.op`` is the instruction's own name (``task_gradients_mnp.6``),
  which is what the metrics match: the text also names the operands.
  Events nest (a ``while`` holds the operations of its body), so
  ``top_ops`` ranks operations by self time, their time less their
  children's.
* ``idle_gaps``: the longest gaps between the busy intervals of the first
  chip, each named by the innermost host event of the dispatching thread
  that covers the gap's middle (what the host was doing meanwhile).
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Callable, Dict, List, Optional, Tuple

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"


_OPCODE = re.compile(r" ([a-z][a-z0-9-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: int                  # ns
    end: int                    # ns

    @property
    def op(self) -> str:
        """The HLO instruction's own name, or the whole name."""
        head, sep, _ = self.name.partition(" = ")
        return head.lstrip("%") if sep else self.name

    @property
    def label(self) -> str:
        """``op (opcode)``, with a custom call's target: a short name."""
        _, sep, rest = self.name.partition(" = ")
        if not sep:
            return self.name
        code = _OPCODE.search(rest)
        kind = code.group(1) if code else "?"
        target = _TARGET.search(rest)
        if target:
            kind += " " + target.group(1)
        return f"{self.op} ({kind})"


@dataclasses.dataclass
class Trace:
    window: Tuple[int, int]                 # ns, from the host annotation
    devices: Dict[str, List[Event]]         # plane -> its ops, by start
    host: List[Event]                       # the dispatching thread's events

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _events(line) -> List[Event]:
    out = [Event(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
           for e in line.events]
    out.sort(key=lambda e: e.start)
    return out


def load(path: str) -> Trace:
    """Read a trace file (or the newest one under a directory); a file
    ending in ``.txt`` is an XSpace text proto (``trace_extract.py``)."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = find_xplane(path)
    if path.endswith(".txt"):
        with open(path) as f:
            data = ProfileData.from_text_proto(f.read())
    else:
        data = ProfileData.from_file(path)
    window, host, devices = None, [], {}
    for plane in data.planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                evs = _events(line)
                win = [e for e in evs if e.name == WINDOW]
                if win:
                    window = (win[0].start, win[0].end)
                    host = evs
        elif DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = _events(line)
    if window is None:
        raise ValueError(f"{path}: no host annotation {WINDOW!r}")
    lo, hi = window

    def inside(planes):
        return {k: [e for e in v if e.end > lo and e.start < hi]
                for k, v in sorted(planes.items(), key=_plane_order)}

    return Trace(window=window, devices=inside(devices), host=host)


def _plane_order(item) -> int:
    return int(item[0].rsplit(":", 1)[1])


def merged(events: List[Event], lo: int, hi: int) -> List[Tuple[int, int]]:
    """Union of the events' intervals, clipped to [lo, hi], in order."""
    out: List[List[int]] = []
    for e in events:
        a, b = max(e.start, lo), min(e.end, hi)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_s(trace: Trace) -> Optional[float]:
    """Seconds in which an operation ran, averaged over the chips."""
    if not trace.devices:
        return None
    lo, hi = trace.window
    tot = [sum(b - a for a, b in merged(evs, lo, hi))
           for evs in trace.devices.values()]
    return sum(tot) / len(tot) * 1e-9


def idle_share(trace: Trace) -> Optional[float]:
    busy = busy_s(trace)
    if busy is None or trace.window_s <= 0:
        return None
    return 1.0 - busy / trace.window_s


def op_seconds(trace: Trace, match: Callable[[str], bool]
               ) -> Tuple[float, int]:
    """(seconds, events) of the operations whose ``Event.op`` ``match``
    accepts, averaged over the chips."""
    planes = list(trace.devices.values())
    if not planes:
        return 0.0, 0
    picked = [[e for e in evs if match(e.op)] for evs in planes]
    secs = [sum(e.end - e.start for e in evs) for evs in picked]
    counts = [len(evs) for evs in picked]
    return sum(secs) / len(planes) * 1e-9, round(sum(counts) / len(planes))


def self_times(events: List[Event]) -> Dict[str, int]:
    """ns of each ``label`` less the time of the events nested in it."""
    tot: Dict[str, int] = {}
    stack: List[List] = []                # [event, time of its children]

    def close(item):
        e, kids = item
        tot[e.label] = tot.get(e.label, 0) + (e.end - e.start) - kids
        if stack:
            stack[-1][1] += e.end - e.start

    for e in sorted(events, key=lambda e: (e.start, -e.end)):
        while stack and stack[-1][0].end <= e.start:
            close(stack.pop())
        stack.append([e, 0])
    while stack:
        close(stack.pop())
    return tot


def top_ops(trace: Trace, k: int = 10) -> List[List]:
    """[label, seconds] of the operations with the most device self time,
    averaged over the chips; an event that overlaps the window counts
    whole."""
    tot: Dict[str, int] = {}
    for evs in trace.devices.values():
        for name, ns in self_times(evs).items():
            tot[name] = tot.get(name, 0) + ns
    n = max(len(trace.devices), 1)
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / n * 1e-9] for name, ns in ranked]


def idle_gaps(trace: Trace, k: int = 10) -> List[List]:
    """[host activity, seconds] of the first chip's longest idle gaps
    inside the window, each named by the innermost host event covering
    its middle."""
    if not trace.devices:
        return []
    lo, hi = trace.window
    busy = merged(next(iter(trace.devices.values())), lo, hi)
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = b
    if hi > t:
        gaps.append((t, hi))
    named = []
    stack: List[Event] = []               # open host events, nested
    nxt = 0
    for a, b in gaps:
        mid = (a + b) // 2
        while nxt < len(trace.host) and trace.host[nxt].start <= mid:
            e = trace.host[nxt]
            nxt += 1
            while stack and stack[-1].end <= e.start:
                stack.pop()
            stack.append(e)
        while stack and stack[-1].end <= mid:
            stack.pop()
        named.append([stack[-1].name if stack else "(no host event)",
                      (b - a) * 1e-9])
    return sorted(named, key=lambda g: -g[1])[:k]
