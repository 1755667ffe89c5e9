"""Observability for the timed ZapRAID stack (DESIGN.md §13).

Four parts, all observe-only; the first three on the virtual clock:

* :mod:`repro.obs.trace` -- span tracing with a Chrome/Perfetto
  ``trace_event`` JSON exporter (request-scoped async spans + resource
  tracks for drives/cache/array);
* :mod:`repro.obs.metrics` -- counters/gauges/histograms plus the
  periodic :class:`MetricsSampler` actor and the stock
  :func:`standard_collector` catalog;
* :mod:`repro.obs.slo` -- the windowed-p99 :class:`SloMonitor` driving
  dynamic per-class admission through
  ``BlockDeviceService.class_caps``;
* :mod:`repro.obs.hostspans` -- wall-clock host spans and dispatch
  counters at the service, array, media, checksum and codec boundaries,
  on the profiler's clock (:class:`HostSpans`, :func:`host_span`).

Every hook site in the stack guards on ``tracer is None`` /
``obs_event is None`` (the defaults), and ``host_span`` hands out a
shared no-op while no recorder is installed, so with nothing attached
the timed and untimed datapaths compute bit-identically to a build
without this package.
"""
from repro.obs.hostspans import HostSpans, host_span
from repro.obs.metrics import (
    Histogram,
    MetricsRegistry,
    MetricsSampler,
    standard_collector,
    validate_metrics_series,
)
from repro.obs.slo import SloMonitor
from repro.obs.trace import Tracer, validate_trace_events

__all__ = [
    "Histogram",
    "HostSpans",
    "MetricsRegistry",
    "MetricsSampler",
    "SloMonitor",
    "Tracer",
    "host_span",
    "standard_collector",
    "validate_metrics_series",
    "validate_trace_events",
]
