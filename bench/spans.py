"""The program's own host spans, read from a reduced trace.

The program opens ``jax.profiler`` annotations at its layer boundaries
(``repro.obs.tracing``): ``solve`` and its ``solve.trace`` /
``solve.lower`` / ``solve.compile`` / ``solve.run`` stages, and around each
``MTLServer.score`` call ``serve.score`` holding ``serve.prepare``,
``serve.pad``, ``serve.dispatch`` and ``serve.sync``.  They share the
dispatching thread with ``bench.window``, so they are in ``Trace.host``,
on the clock of the device's events.

Only spans that lie wholly inside the window count, and only in a trace
of a chip run (one with a TPU plane): the readers of these spans return
None otherwise, as they do for a program that opens no such span.
"""
from __future__ import annotations

import statistics
from typing import Dict, List, Optional, Sequence

from bench.trace_reduce import Event, Trace


def inside(trace: Optional[Trace], names: Sequence[str]) -> List[Event]:
    """The host events named in ``names`` that lie inside the window, by
    start; none without a trace of a chip run."""
    if trace is None or not trace.devices:
        return []
    lo, hi = trace.window
    keep = set(names)
    return [e for e in trace.host
            if e.name in keep and e.start >= lo and e.end <= hi]


def seconds(events: Sequence[Event]) -> float:
    return sum(e.end - e.start for e in events) * 1e-9


def per_call(trace: Optional[Trace], call: str,
             parts: Sequence[str]) -> List[float]:
    """For each ``call`` span inside the window, the seconds of the
    ``parts`` spans nested in it."""
    calls = inside(trace, [call])
    kids = inside(trace, parts)
    out: List[float] = []
    j = 0
    for c in calls:
        while j < len(kids) and kids[j].start < c.start:
            j += 1
        ns = 0
        while j < len(kids) and kids[j].start < c.end:
            if kids[j].end <= c.end:
                ns += kids[j].end - kids[j].start
            j += 1
        out.append(ns * 1e-9)
    return out


def median_ms(trace: Optional[Trace], call: str,
              parts: Sequence[str]) -> Optional[float]:
    """Median over the calls of ``per_call`` in ms; None without calls or
    without any of the parts."""
    if not inside(trace, parts):
        return None
    times = per_call(trace, call, parts)
    return 1e3 * statistics.median(times) if times else None


def counts(trace: Optional[Trace], names: Sequence[str]) -> Dict[str, int]:
    out = {n: 0 for n in names}
    for e in inside(trace, names):
        out[e.name] += 1
    return out
