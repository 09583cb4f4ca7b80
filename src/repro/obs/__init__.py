"""Observability: span tracing, serving metrics, and the regression gate.

The third leg next to measurement (``repro.bench``) and search
(``repro.tuning``) — everything between "scenario start" and "median µs"
becomes inspectable events:

  trace      nested context-manager spans on the monotonic clock, a
             thread-safe buffer, JSONL sink, and Chrome-trace/Perfetto
             export; recorded when enabled or while a JAX profiler
             session is active, where each span is also a
             ``TraceAnnotation`` on the profiler's clock; off otherwise
             (an attribute check and a profiler query, no allocation)
  metrics    labeled counters / gauges / histograms with quantile
             snapshots (the serving loop's TTFT & per-token latencies)
  compare    noise-aware BENCH_*.json regression gate — median +/- k*IQR
             per cell, optional host-speed normalization
  cli        python -m repro.obs.cli {summary,export-trace,compare,profile}

Import note: only ``trace``/``metrics`` (stdlib-only) are imported
eagerly — ``bench.timing`` imports ``obs.trace`` while ``repro.bench``
itself may be mid-import, so this package must not import ``compare``
(which needs ``bench.results``) at import time.  Import ``repro.obs
.compare`` / ``repro.obs.cli`` directly.
"""
from . import trace                                         # noqa: F401
from .trace import Span, Tracer, chrome_trace, tracer
from . import metrics                                       # noqa: F401
from .metrics import (Counter, Gauge, Histogram, Registry, counter, gauge,
                      histogram, registry)

__all__ = [
    "Counter", "Gauge", "Histogram", "Registry", "Span", "Tracer",
    "chrome_trace", "counter", "gauge", "histogram", "metrics", "registry",
    "trace", "tracer",
]
