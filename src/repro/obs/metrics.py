"""Metrics registry (DESIGN.md §8): counters, gauges, and fixed-memory
streaming histograms behind stable names.

Before this subsystem the engine's self-knowledge was ad-hoc attributes
scattered across ``InferenceEngine`` (``d2h_transfers``, ``spec_*``, ...),
``FillingMetrics`` (unbounded latency lists), and hand-maintained bench
counters — three divergent sources for the same quantities.  The registry
is the ONE place those numbers live:

* ``Counter`` — monotone-ish integer cell (``inc``/``set``).  The engine's
  historical attributes survive as *thin views* over registry counters
  (``repro.serving.engine.RegistryCounterView``), so ``engine.d2h_transfers
  += 1`` and the registry's ``engine/d2h_transfers`` are the same cell and
  can never diverge.  ``scripts/check_api_surface.py`` pins the view ->
  stable-name mapping.
* ``Gauge`` — last-value cell sampled per scheduling quantum (queue depths,
  pool occupancy, active slots), with min/max/count over the run.
* ``StreamingHistogram`` — fixed-memory distribution sketch with EXACT
  percentiles at bench scale: raw samples are kept verbatim up to
  ``exact_cap`` (so ``percentile(95)`` is bit-for-bit
  ``np.percentile(samples, 95)``, preserving every historical bench/metric
  value), then collapse once into ``num_bins`` fixed-width bins, after
  which memory is bounded regardless of load (the trace-driven 10-100x
  regime) and percentiles are linearly interpolated within a bin.

Stable names are path-shaped (``engine/...``, ``core/...``).  Re-requesting
a name returns the SAME instrument; requesting it as a different type is an
error (one name, one meaning).
"""
from __future__ import annotations

import math
from typing import Optional

import numpy as np

__all__ = [
    "Counter",
    "Gauge",
    "StreamingHistogram",
    "MetricsRegistry",
    "STABLE_NAMES",
]

#: The stable metric names the serving stack registers (the observability
#: API surface — ``scripts/check_api_surface.py`` pins the engine-attribute
#: views onto the ``engine/*`` entries).  New metrics may be added freely;
#: renaming or retyping one of these is a breaking change.
STABLE_NAMES = {
    # engine compute counters (thin-view attributes on InferenceEngine)
    "engine/d2h_transfers": "counter",
    "engine/steps_executed": "counter",
    "engine/generated_tokens": "counter",
    "engine/prefill_prompt_tokens": "counter",
    "engine/prefill_skipped_tokens": "counter",
    "engine/prefill_metered_tokens": "counter",
    "engine/spec_rounds": "counter",
    "engine/spec_drafted": "counter",
    "engine/spec_accepted": "counter",
    # serving copies the engine cast to its compute dtype, or gathered
    # from a sharded tree; block tables its decode loop moved onto its
    # device
    "engine/serving_param_casts": "counter",
    "engine/serving_param_gathers": "counter",
    "engine/placement_moves": "counter",
    # decode readbacks collected after the next quantum was dispatched
    # (the fill's one-in-flight order, DESIGN.md §6)
    "engine/overlapped_readbacks": "counter",
    # pluggable speculation proposers (DESIGN.md §10)
    "spec/proposer/rounds/draft": "counter",
    "spec/proposer/rounds/ngram": "counter",
    "spec/proposer/rounds/suffix": "counter",
    "spec/proposer/proposed/draft": "counter",
    "spec/proposer/proposed/ngram": "counter",
    "spec/proposer/proposed/suffix": "counter",
    "spec/proposer/accepted/draft": "counter",
    "spec/proposer/accepted/ngram": "counter",
    "spec/proposer/accepted/suffix": "counter",
    "spec/proposer/acceptance/draft": "gauge",
    "spec/proposer/acceptance/ngram": "gauge",
    "spec/proposer/acceptance/suffix": "gauge",
    "spec/proposer/tree_nodes": "gauge",
    "spec/proposer/router_switches": "counter",
    "spec/proposer/no_match_fallbacks": "counter",
    # request-lifecycle counters (EngineCore)
    "core/preemptions": "counter",
    "core/finish_reason/stop": "counter",
    "core/finish_reason/length": "counter",
    "core/finish_reason/abort": "counter",
    "core/finish_reason/expired": "counter",
    "core/finish_reason/error": "counter",
    "core/finished/online": "counter",
    "core/finished/offline": "counter",
    "core/generated_tokens/online": "counter",
    "core/generated_tokens/offline": "counter",
    "core/starved_quanta": "counter",
    "core/quanta": "counter",
    # host spans (repro.obs.trace): wall nanoseconds inside each span
    "host_ns/runtime.train_step": "counter",
    "host_ns/runtime.fill": "counter",
    "host_ns/runtime.monitor": "counter",
    "host_ns/core.step": "counter",
    "host_ns/core.plan": "counter",
    "host_ns/core.admit": "counter",
    "host_ns/core.collect": "counter",
    "host_ns/core.record": "counter",
    "host_ns/engine.tables": "counter",
    "host_ns/engine.prefill": "counter",
    "host_ns/engine.decode": "counter",
    "host_ns/engine.fetch": "counter",
    "host_ns/engine.gather_params": "counter",
    # failure containment + graceful degradation (DESIGN.md §9)
    "fault/injected": "counter",
    "fault/nan_quarantines": "counter",
    "fault/alloc_failures": "counter",
    "fault/requeues": "counter",
    "fault/retry_exhausted": "counter",
    "fault/revocations": "counter",
    "fault/early_resume": "counter",
    "fault/shed/online": "counter",
    "fault/shed/offline": "counter",
    "fault/ladder_escalations": "counter",
    "fault/ladder_steps/normal": "counter",
    "fault/ladder_steps/spec_off": "counter",
    "fault/ladder_steps/k_shrink": "counter",
    "fault/ladder_steps/shed_offline": "counter",
    "fault/ladder_steps/shed_online": "counter",
    "fault/ladder_stage": "gauge",
    "fault/revocation_overrun_s": "histogram",
    "fault/decays": "counter",
    # crash durability: write-ahead journal + replay recovery (DESIGN.md §11)
    "journal/appends": "counter",
    "journal/fsyncs": "counter",
    "journal/bytes": "counter",
    "recovery/restores": "counter",
    "recovery/replayed_tokens": "counter",
    "recovery/requeued_waiting": "counter",
    "recovery/resumed_inflight": "counter",
    "recovery/skipped_finished": "counter",
    "recovery/torn_tail": "counter",
    "recovery/duration_s": "gauge",
    "recovery/snapshot_saves": "counter",
    "recovery/snapshot_nodes": "counter",
    "recovery/snapshot_discarded": "counter",
    # per-quantum gauges
    "core/queue_depth/online": "gauge",
    "core/queue_depth/offline": "gauge",
    "engine/slots_active": "gauge",
    "engine/slots_prefilling": "gauge",
    "engine/pool/pages_in_use": "gauge",
    "engine/pool/available": "gauge",
    "engine/pool/reserved": "gauge",
    # latency distributions (FillingMetrics' derived views)
    "core/online_ttft_s": "histogram",
    "core/online_latency_s": "histogram",
    "core/offline_latency_s": "histogram",
}


class Counter:
    """Integer cell.  ``value`` is directly readable (the thin-view
    attributes return it), so hot paths pay one attribute load."""

    __slots__ = ("name", "value")

    def __init__(self, name: str):
        self.name = name
        self.value = 0

    def inc(self, n: int = 1) -> None:
        self.value += n

    def set(self, v) -> None:
        self.value = v


class Gauge:
    """Last-value cell with run-level min/max/sample-count — ``set`` once
    per scheduling quantum gives the end-of-run summary its peak queue
    depth / pool occupancy without keeping a sample list."""

    __slots__ = ("name", "value", "min", "max", "samples")

    def __init__(self, name: str):
        self.name = name
        self.value = 0.0
        self.min = math.inf
        self.max = -math.inf
        self.samples = 0

    def set(self, v) -> None:
        v = float(v)
        self.value = v
        if v < self.min:
            self.min = v
        if v > self.max:
            self.max = v
        self.samples += 1


class StreamingHistogram:
    """Fixed-memory streaming histogram with exact percentiles at bench
    scale.

    Samples are stored verbatim while ``count <= exact_cap`` — in that
    regime ``percentile(q)`` is literally ``np.percentile(samples, q)``, so
    every percentile the old unbounded lists produced reproduces
    bit-for-bit.  The first record past the cap collapses the buffer into
    ``num_bins`` fixed-width bins spanning the observed range; from then on
    memory is O(num_bins) forever and percentiles interpolate linearly
    within a bin (error bounded by one bin width; min/max/count/sum stay
    exact).  Out-of-range records after collapse clamp into the edge bins
    (true min/max still tracked)."""

    __slots__ = (
        "name", "exact_cap", "num_bins", "count", "sum", "min", "max",
        "_samples", "_bins", "_edges",
    )

    def __init__(self, name: str = "", exact_cap: int = 8192,
                 num_bins: int = 256):
        assert exact_cap >= 1 and num_bins >= 2
        self.name = name
        self.exact_cap = exact_cap
        self.num_bins = num_bins
        self.count = 0
        self.sum = 0.0
        self.min = math.inf
        self.max = -math.inf
        self._samples: Optional[list] = []
        self._bins: Optional[np.ndarray] = None
        self._edges: Optional[np.ndarray] = None

    @property
    def exact(self) -> bool:
        """True while every recorded sample is still held verbatim."""
        return self._samples is not None

    def record(self, x) -> None:
        x = float(x)
        self.count += 1
        self.sum += x
        if x < self.min:
            self.min = x
        if x > self.max:
            self.max = x
        if self._samples is not None:
            self._samples.append(x)
            if len(self._samples) > self.exact_cap:
                self._collapse()
        else:
            i = int(np.searchsorted(self._edges, x, side="right")) - 1
            self._bins[min(max(i, 0), self.num_bins - 1)] += 1

    def _collapse(self) -> None:
        lo, hi = self.min, self.max
        if not hi > lo:  # all samples identical (or a single value)
            hi = lo + 1.0
        self._edges = np.linspace(lo, hi, self.num_bins + 1)
        self._bins, _ = np.histogram(self._samples, bins=self._edges)
        self._bins = self._bins.astype(np.int64)
        self._samples = None

    def values(self) -> list:
        """The exact sample list (the historical unbounded-list view).
        Only available while ``exact``; past the cap the samples no longer
        exist — use ``percentile``/``count``/``sum`` instead."""
        if self._samples is None:
            raise RuntimeError(
                f"histogram {self.name!r} collapsed to bins after "
                f"{self.exact_cap} samples; exact values are gone — query "
                "percentile()/count/sum instead"
            )
        return list(self._samples)

    def percentile(self, q: float) -> float:
        """q-th percentile (0..100).  Exact (``np.percentile``) while under
        the cap; bin-interpolated after.  NaN when empty."""
        if self.count == 0:
            return float("nan")
        if self._samples is not None:
            return float(np.percentile(self._samples, q))
        # nearest-rank walk over the bin CDF, interpolated within the bin
        target = q / 100.0 * self.count
        cum = np.cumsum(self._bins)
        i = int(np.searchsorted(cum, target, side="left"))
        i = min(i, self.num_bins - 1)
        prev = float(cum[i - 1]) if i > 0 else 0.0
        inbin = float(self._bins[i])
        frac = (target - prev) / inbin if inbin > 0 else 0.0
        lo, hi = float(self._edges[i]), float(self._edges[i + 1])
        return float(min(max(lo + frac * (hi - lo), self.min), self.max))

    def mean(self) -> float:
        return self.sum / self.count if self.count else float("nan")


class MetricsRegistry:
    """Name -> instrument map with get-or-create semantics.  One registry
    per engine (``InferenceEngine.obs.metrics``); the core, the runtime,
    and the benches all read the same cells."""

    def __init__(self):
        self._metrics: dict = {}

    def _get(self, name: str, cls, *args, **kw):
        m = self._metrics.get(name)
        if m is None:
            m = cls(name, *args, **kw)
            self._metrics[name] = m
        elif not isinstance(m, cls):
            raise TypeError(
                f"metric {name!r} already registered as "
                f"{type(m).__name__}, requested {cls.__name__}"
            )
        return m

    def counter(self, name: str) -> Counter:
        return self._get(name, Counter)

    def gauge(self, name: str) -> Gauge:
        return self._get(name, Gauge)

    def histogram(self, name: str, **kw) -> StreamingHistogram:
        return self._get(name, StreamingHistogram, **kw)

    def names(self) -> list:
        return sorted(self._metrics)

    def snapshot(self) -> dict:
        """JSON-able dump of every instrument (the end-of-run summary and
        the trace meta header read this)."""
        out = {}
        for name in sorted(self._metrics):
            m = self._metrics[name]
            if isinstance(m, Counter):
                out[name] = {"type": "counter", "value": m.value}
            elif isinstance(m, Gauge):
                out[name] = {
                    "type": "gauge", "value": m.value, "samples": m.samples,
                    "min": None if m.samples == 0 else m.min,
                    "max": None if m.samples == 0 else m.max,
                }
            else:
                out[name] = {
                    "type": "histogram", "count": m.count, "sum": m.sum,
                    "min": None if m.count == 0 else m.min,
                    "max": None if m.count == 0 else m.max,
                    "exact": m.exact,
                    "p50": None if m.count == 0 else m.percentile(50),
                    "p95": None if m.count == 0 else m.percentile(95),
                    "p99": None if m.count == 0 else m.percentile(99),
                }
        return out
