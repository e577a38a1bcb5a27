"""Observability: the telemetry subsystem.

* ``repro.obs.trace`` — the program's host spans (``<layer>.<step>``) on the
  ``perf_counter_ns`` clock, in one bounded ring per process, each also a
  ``jax.profiler.TraceAnnotation``.  The engine's per-walk counters
  (``io_wait_s``, ``compute_s``, ``fetch_s``, ``put_s``) are sums over its
  spans.
* ``repro.obs.metrics`` — counters, gauges, and histograms with Prometheus
  text exposition: the one place the stats scattered across
  ``SamplingService.stats()``, the kernel autotuner cache, the transport
  fault counters, and the per-walk engine I/O consolidate (served at
  ``GET /metrics`` by ``repro.serve.gateway``).
"""
from repro.obs import trace
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               instrument_dispatch, instrument_service)

__all__ = ["Counter", "Gauge", "Histogram", "MetricsRegistry",
           "instrument_dispatch", "instrument_service", "trace"]
