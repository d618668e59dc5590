"""repro.obs — unified telemetry across train, recovery, streaming, serve.

Three pillars (DESIGN.md §15):

* device-resident round metrics — ``repro.solve(..., metrics=True)``
  stacks a per-round :mod:`~repro.obs.device` pytree through the scan
  carry into ``MTLResult.extras["metrics"]``; metrics off ⇒
  bit-identical solves, metrics on ⇒ bit-identical W + ledger;
* host span tracing — :func:`trace_span` / :func:`emit_event` around
  solve calls and their round programs' staging, checkpoint
  saves, resume/rollback, streaming refresh phases, and server
  install/hot-swap/onboard, exportable as JSONL and Chrome
  ``trace.json`` and present on a ``jax.profiler`` trace, plus
  profiler-only :func:`annotate` spans around each scoring call and
  each round-program dispatch
  (:mod:`~repro.obs.tracing`);
* serving SLO metrics — latency histograms / counters / staleness
  gauges in a shared :class:`MetricsRegistry` with JSONL + Prometheus
  snapshot exporters (:mod:`~repro.obs.metrics`).

``python -m repro.obs summarize RUN_DIR`` renders a run directory;
``python -m repro.obs smoke --out RUN_DIR`` runs the instrumented tiny
solve + serve path CI gates on.
"""
from .device import OBS_KEY, RoundMetricsSink, obs_init, obs_round  # noqa: F401
from .metrics import (  # noqa: F401
    Counter, Gauge, LatencyHistogram, MetricsRegistry, bucket_edges,
    default_registry, device_bucket_counts,
)
from .tracing import (  # noqa: F401
    Tracer, annotate, configure, default_tracer, emit_event,
    export_chrome_trace, read_events_jsonl, trace_span,
)

__all__ = [
    "OBS_KEY", "RoundMetricsSink", "obs_init", "obs_round",
    "Counter", "Gauge", "LatencyHistogram", "MetricsRegistry",
    "bucket_edges", "default_registry", "device_bucket_counts",
    "Tracer", "annotate", "configure", "default_tracer", "emit_event",
    "export_chrome_trace", "read_events_jsonl", "trace_span",
]
