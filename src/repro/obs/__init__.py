"""Observability subsystem: request tracing, per-stage latency
histograms, and profiler hooks for the geo serving stack
(DESIGN.md §15).

Public surface:

    from repro.obs import Tracer                  # per-request spans
    from repro.obs import LatencyHistogram        # mergeable log buckets
    from repro.obs import profile_range           # jax.profiler range
    from repro.obs import start_profile, stop_profile

The tracer attaches to a server (``GeoServer(..., tracer=Tracer())``)
and exports both a raw span dump and a Chrome-trace file; the
histograms back ``ServerMetrics``' per-stage breakdown and its
Prometheus-style ``expose_text()``.
"""
from repro.obs.hist import LatencyHistogram
from repro.obs.profile import (profile_range, profiler_available,
                               start_profile, stop_profile)
from repro.obs.trace import RequestTrace, Span, SpanBuffer, Tracer

__all__ = [
    "LatencyHistogram", "RequestTrace", "Span", "SpanBuffer", "Tracer",
    "profile_range", "profiler_available", "start_profile",
    "stop_profile",
]
