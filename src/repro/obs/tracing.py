"""Host span tracing: where a solve/serve run spends its time.

One tracer, two tiers, one clock.

* Lifecycle spans (ring + profiler) — ``with trace_span("ckpt.save",
  step=25): ...``.  A :class:`Tracer` keeps a bounded in-memory ring of
  events and, optionally, streams them to a JSONL file under a
  configured run directory, so a run directory holds its own timeline
  with no profiler running.  Each span also opens a
  ``jax.profiler.TraceAnnotation`` of the same name (with its str, int,
  float and bool attrs), so under a ``jax.profiler`` trace it lands on
  the profiler's host plane, on the same clock as the device's
  ``XLA Ops`` events.  These mark the rare, expensive phases: ``solve``
  and its round program's ``solve.trace`` (the ring event's ``hit``
  says whether the process had the program compiled already) and, on a
  miss, ``solve.lower`` / ``solve.compile``
  (``repro.runtime.base.round_jit``), checkpoint saves, server install
  / hot-swap / onboard, streaming refreshes.
* Per-call spans (profiler only) — ``with annotate("serve.score"):
  ...`` is a ``TraceAnnotation`` and nothing else: no ring
  append, no lock, no clock read.  It exists for boundaries crossed
  hundreds of times a second (each ``MTLServer.score`` call and its
  prepare / pad / dispatch / sync steps, each round program's dispatch
  under ``solve.run``): in the ring they would evict
  every lifecycle event within seconds and pay a locked append on
  every call.  With no profiler trace running, entering and leaving
  one costs about a microsecond.

Two event shapes share the ring's schema:

* spans — one COMPLETE event at exit: start wall time + a
  ``perf_counter``-measured duration (wall stamps order events on a
  timeline; durations never come from the wall clock, so NTP slews
  can't corrupt them);
* instants — ``emit_event("recovery.rollback", step=75)`` records a
  zero-duration marker.

JSONL schema (one object per line, the round-trip contract tested in
tests/test_obs.py)::

    {"name": str, "ph": "X" | "i", "t_wall_s": float,
     "dur_s": float | null, "pid": int, "tid": int, "attrs": {...}}

``export_chrome_trace`` rewrites the ring (or a JSONL file) into the
Chrome/Perfetto ``trace.json`` event format, so a run directory opens
directly in ``chrome://tracing`` / https://ui.perfetto.dev.

Everything here is host-side and allocation-light: an unconfigured
tracer costs one deque append and one inactive annotation per span,
and none of it runs inside jit (the device-resident metrics pillar
rides the scan carry instead — DESIGN.md §15).
"""
from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from collections import deque
from typing import Any, Dict, Iterator, List, Optional

from jax.profiler import TraceAnnotation

__all__ = [
    "Tracer", "trace_span", "annotate", "emit_event", "default_tracer",
    "configure", "export_chrome_trace", "read_events_jsonl",
    "EVENTS_JSONL", "TRACE_JSON",
]

EVENTS_JSONL = "OBS_events.jsonl"
TRACE_JSON = "OBS_trace.json"

# environment hook: set REPRO_OBS_DIR to stream the default tracer's
# events without touching call sites (used by the CI obs-smoke job)
_ENV_DIR = "REPRO_OBS_DIR"


class Tracer:
    """Bounded event ring + optional JSONL stream."""

    def __init__(self, capacity: int = 4096):
        self.capacity = int(capacity)
        self._ring: deque = deque(maxlen=self.capacity)
        self._lock = threading.Lock()
        self._jsonl_path: Optional[str] = None
        env_dir = os.environ.get(_ENV_DIR)
        if env_dir:
            self.configure(env_dir)

    # -- configuration --------------------------------------------------
    def configure(self, run_dir: Optional[str]) -> Optional[str]:
        """Stream subsequent events to ``run_dir/OBS_events.jsonl``
        (append mode — a resumed run extends its predecessor's
        timeline).  ``None`` turns streaming off.  Returns the path."""
        with self._lock:
            if run_dir is None:
                self._jsonl_path = None
                return None
            os.makedirs(run_dir, exist_ok=True)
            self._jsonl_path = os.path.join(run_dir, EVENTS_JSONL)
            return self._jsonl_path

    @property
    def jsonl_path(self) -> Optional[str]:
        return self._jsonl_path

    # -- emission -------------------------------------------------------
    @staticmethod
    def _jsonable(v: Any) -> Any:
        if isinstance(v, (str, int, float, bool)) or v is None:
            return v
        if isinstance(v, (list, tuple)):
            return [Tracer._jsonable(x) for x in v]
        if isinstance(v, dict):
            return {str(k): Tracer._jsonable(x) for k, x in v.items()}
        try:                                   # np/jnp scalars
            return v.item()
        except Exception:
            return repr(v)

    def emit(self, name: str, *, ph: str = "i",
             t_wall_s: Optional[float] = None,
             dur_s: Optional[float] = None, **attrs) -> Dict[str, Any]:
        ev = {
            "name": str(name),
            "ph": ph,
            "t_wall_s": time.time() if t_wall_s is None else t_wall_s,
            "dur_s": dur_s,
            "pid": os.getpid(),
            "tid": threading.get_ident() & 0xFFFF,
            "attrs": {k: self._jsonable(v) for k, v in attrs.items()},
        }
        with self._lock:
            self._ring.append(ev)
            path = self._jsonl_path
        if path is not None:
            line = json.dumps(ev, sort_keys=True)
            with self._lock:
                with open(path, "a") as f:
                    f.write(line + "\n")
        return ev

    @contextlib.contextmanager
    def span(self, name: str, **attrs) -> Iterator[Dict[str, Any]]:
        """Time a block; the event records even when the block raises
        (with ``attrs["error"]`` set to the exception type).  The block
        also runs under a profiler annotation of the same name."""
        t_wall = time.time()
        t0 = time.perf_counter()
        extra: Dict[str, Any] = {}
        try:
            with annotate(name, **{k: v for k, v in attrs.items()
                                   if isinstance(v, (str, int, float))}):
                yield extra
        except BaseException as e:
            extra["error"] = type(e).__name__
            raise
        finally:
            dur = time.perf_counter() - t0
            self.emit(name, ph="X", t_wall_s=t_wall, dur_s=dur,
                      **{**attrs, **extra})

    # -- inspection / export -------------------------------------------
    def events(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._ring)

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()

    def export_chrome_trace(self, path: str) -> str:
        return export_chrome_trace(self.events(), path)


def export_chrome_trace(events: List[Dict[str, Any]], path: str) -> str:
    """Write events (ring dicts or JSONL rows) as Chrome ``trace.json``:
    ``{"traceEvents": [...]}`` with microsecond timestamps."""
    out = []
    for ev in events:
        ch = {
            "name": ev["name"],
            "ph": "X" if ev.get("ph") == "X" else "i",
            "ts": ev["t_wall_s"] * 1e6,
            "pid": ev.get("pid", 0),
            "tid": ev.get("tid", 0),
            "args": ev.get("attrs", {}),
        }
        if ch["ph"] == "X":
            ch["dur"] = (ev.get("dur_s") or 0.0) * 1e6
        else:
            ch["s"] = "p"                      # process-scoped instant
        out.append(ch)
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump({"traceEvents": out,
                   "displayTimeUnit": "ms"}, f)
    os.replace(tmp, path)
    return path


def read_events_jsonl(path: str) -> List[Dict[str, Any]]:
    """Parse an ``OBS_events.jsonl`` file back into event dicts."""
    events = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                events.append(json.loads(line))
    return events


def annotate(name: str, **attrs) -> TraceAnnotation:
    """A profiler-only span for per-call boundaries: ``with
    annotate("serve.dispatch"): ...`` shows on a ``jax.profiler`` trace
    and nowhere else (no ring event, no JSONL line)."""
    return TraceAnnotation(name, **attrs)


_DEFAULT = Tracer()


def default_tracer() -> Tracer:
    return _DEFAULT


def configure(run_dir: Optional[str]) -> Optional[str]:
    """Point the default tracer's JSONL stream at ``run_dir``."""
    return _DEFAULT.configure(run_dir)


def trace_span(name: str, **attrs):
    """``with trace_span("solve", method="proxgd"): ...`` on the
    default tracer."""
    return _DEFAULT.span(name, **attrs)


def emit_event(name: str, **attrs) -> Dict[str, Any]:
    """Record an instant event on the default tracer."""
    return _DEFAULT.emit(name, **attrs)
