"""Observability: unified metrics registry + per-query tracing (PR 8).

Two pieces, one import surface:

  * `metrics` -- named counters/gauges/histograms in one process
    registry; every subsystem (pager, executor, front door, scheduler,
    engine) registers into `default_registry()` under labeled scopes so
    `MicroNN.stats()` is a derived view of a single source of truth.
  * `trace` -- the host-stage hook `stage()` (per-query `QueryTrace`
    spans, and `micronn.*` events when a JAX profiler collects), the
    bounded `TraceRing` of recent traces + maintenance events, and the
    slow-query log.
  * `recorder` -- the workload flight recorder (PR 10): bounded,
    sampled on-disk capture of (ts_offset, tenant, spec, vectors) and
    the deterministic `replay()` harness asserting bit-identical
    ResultSets.
  * `http` -- the live exposition endpoint (PR 10): stdlib HTTP daemon
    thread serving /metrics, /healthz, /traces, /slow, /events.
"""
from . import http, metrics, recorder, trace
from .http import ExpositionServer
from .metrics import (Counter, Gauge, Histogram, MetricsRegistry, Scope,
                      default_registry, next_instance)
from .recorder import FlightRecorder, ReplayReport, recording, replay
from .trace import (MaintEvent, QueryTrace, Span, TraceRing, activate,
                    current, enabled, set_enabled)

__all__ = [
    "metrics", "trace", "recorder", "http",
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "Scope",
    "default_registry", "next_instance",
    "MaintEvent", "QueryTrace", "Span", "TraceRing",
    "activate", "current", "enabled", "set_enabled",
    "FlightRecorder", "ReplayReport", "recording", "replay",
    "ExpositionServer",
]
