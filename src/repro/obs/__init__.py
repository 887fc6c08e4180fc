"""Observability: distributed EEG spans + unified metrics (DESIGN.md §16).

- :mod:`repro.obs.spans` — cheap start/end span events from the real
  execution paths (fused executors, wire RPCs, rendezvous waits), and
  ``span()``, a JAX profiler span for the jitted serving and data paths.
- :mod:`repro.obs.metrics` — the process-global registry of named
  counters/gauges/histograms (absorbs the legacy ``STATS`` dicts).
- :mod:`repro.obs.export` — merges per-process streams into one
  Chrome-trace/Perfetto JSON with clock-offset alignment.
- :mod:`repro.obs.profile` — ``python -m repro.obs.profile`` summary CLI.
"""
from .metrics import (Counter, Gauge, Histogram, MetricsRegistry, REGISTRY,
                      StatsDict)
from .spans import SpanRecorder
from .export import merge_streams, validate_trace, write_trace

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "REGISTRY",
    "StatsDict", "SpanRecorder", "merge_streams", "validate_trace",
    "write_trace",
]
