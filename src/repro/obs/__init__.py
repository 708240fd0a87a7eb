"""ACCL-X observability: comm-event tracing + metrics.

The telemetry substrate under the comm stack — what lets you *see* where
communication time goes (the paper's per-configuration/per-edge breakdowns,
ACCL+'s collective-engine timing feed):

- :mod:`repro.obs.trace`   — the program's tracing API (``REPRO_TRACE`` env
  gate, thread-safe ring buffer, Chrome ``trace_event`` export for
  Perfetto).  ``span`` times host regions (driver segments and set-up,
  sweep candidates, watchdog events) and, when on, lands them in a JAX
  profiler trace; ``scope`` names code under ``jit`` (solver phases,
  collectives, wire chunks) in the compiled HLO.
- :mod:`repro.obs.metrics` — always-on registry of counters, gauges, and
  fixed-bucket latency histograms (plan-cache hit/miss, bytes per edge,
  rounds per exchange, sweep candidates pruned, straggler events).
- :mod:`repro.obs.report`  — ``python -m repro.obs.report trace.json``
  prints per-edge / per-collective latency tables from an exported trace.
"""
from repro.obs import metrics, trace
from repro.obs.metrics import registry
from repro.obs.trace import (configure, enabled, events, flush, instant,
                             scope, span)

__all__ = ["configure", "enabled", "events", "flush", "instant", "metrics",
           "registry", "scope", "span", "trace"]
