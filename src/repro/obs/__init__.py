"""Engine observability layer (DESIGN.md §8).

One ``Observability`` bundle per engine holds ONE metrics registry and ONE
step tracer, and opens the host spans.  Its pieces, one per module:

* ``metrics`` — the registry (counters / gauges / fixed-memory streaming
  histograms) behind stable names; the engine's historical counter
  attributes are thin views over it, and every host span's wall time
  accumulates on a ``host_ns/<span>`` counter.
* ``trace`` — the structured step tracer (one event per scheduling
  quantum, request transitions, prefill spans; the per-slot decode spans
  are drawn from the quantum records at export) with JSONL and
  Chrome-trace/Perfetto export, the ``Observability`` bundle, and the host
  spans (``obs.span(name)``: a ``specinf.<name>`` profiler annotation plus
  the ``host_ns/<name>`` counter; the span list is in its docstring).
* ``attribution`` — per-request SLO decomposition (queueing / prefill /
  decode / preempted) computed from trace transitions on the engine's
  clock.
* ``schema`` — the trace's authoritative field list and the
  dependency-free validator CI runs over the JSONL artifact.

Two clocks: the engine's clock (virtual under a collocated runtime) stamps
the tracer's events and the requests' ``*_time`` fields, and is what the
policy reads; the wall clock ``wall_ns`` times the host spans and the
requests' ``*_wall_ns`` stamps, for observability only.
"""
from repro.obs.attribution import RequestAttribution, attribute
from repro.obs.metrics import (
    STABLE_NAMES,
    Counter,
    Gauge,
    MetricsRegistry,
    StreamingHistogram,
)
from repro.obs.schema import validate_events, validate_jsonl
from repro.obs.trace import Observability, StepTracer, chrome_trace, wall_ns

__all__ = [
    "Counter",
    "Gauge",
    "MetricsRegistry",
    "Observability",
    "RequestAttribution",
    "STABLE_NAMES",
    "StepTracer",
    "StreamingHistogram",
    "attribute",
    "chrome_trace",
    "validate_events",
    "validate_jsonl",
    "wall_ns",
]
