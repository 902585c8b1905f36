"""Unified observability: sim-clock tracing, host spans, one metrics registry.

- :class:`Tracer` — nested spans / instants / counters on the simulated
  clock, off by default (every layer holds ``tracer = None`` and guards
  each emission), provably free when disabled.
- :func:`host_span` — ``rrto.*`` spans of the program's host work on the
  host clock, in JAX's profiler trace beside the device's operations.
- :class:`MetricsRegistry` — the single store behind every stats surface
  in the stack; ``snapshot()`` on a root registry reports the whole
  fleet in one call.
- :func:`write_chrome_trace` — Perfetto-loadable Chrome trace-event
  JSON, one track per client / replica / resource.
"""

from repro.obs.export import to_chrome_trace, write_chrome_trace
from repro.obs.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    RegistryBackedStats,
    percentile,
)
from repro.obs.trace import CounterSample, Instant, Span, Tracer, host_span

__all__ = [
    "Counter",
    "CounterSample",
    "Gauge",
    "Histogram",
    "Instant",
    "MetricsRegistry",
    "RegistryBackedStats",
    "Span",
    "Tracer",
    "host_span",
    "percentile",
    "to_chrome_trace",
    "write_chrome_trace",
]
