"""Structured step tracer and host spans (DESIGN.md §8).

The step tracer records one event per scheduling quantum, plus request
state transitions and prefill spans, exported as JSONL and as a Chrome
trace (open in ``chrome://tracing`` or https://ui.perfetto.dev).

Two clocks, each for its own reader:

* The ENGINE'S clock stamps every tracer event (the caller stamps; the
  tracer never reads a clock of its own).  A collocated runtime binds it to
  its virtual clock, on which Algorithm 1's gates and the SLO attribution
  run, so a collocated trace is entirely on the virtual timebase.
* The WALL clock, ``wall_ns`` (``time.perf_counter_ns``), times the host
  spans (``Observability.span``) and the requests' wall stamps
  (``EngineRequest.arrival_wall_ns`` / ``admit_wall_ns`` /
  ``first_token_wall_ns`` / ``finish_wall_ns``), and each quantum record
  carries its ``wall_ns: [t0, t1]``.  It is for observability only: no
  scheduling decision reads it.

Event kinds (``repro.obs.schema`` is the authoritative field list):

* ``quantum`` — one per ``EngineCore.step()``: the grant, the policy plan
  (k / gamma / admissions / preemptions / prefill budget), realized token
  costs, the clock advance, the bubble-monitor window state when a
  SpecInF runtime drove the step, the slots the fused loop decoded
  (``decoded: {slot: request_id}``, from ``decode_t0`` to ``t1``) and the
  wall-clock interval of the step.
* ``transition`` — one per request state change (WAITING at submission,
  admissions, preemptions, finishes), the raw material SLO attribution
  (``repro.obs.attribution``) decomposes into queueing / prefill / decode /
  preempted segments.
* ``span`` — an interval on a named track: ``train`` carries training
  compute and bubble spans; ``slot{i}`` carries that slot's prefill
  chunks.  Intra-quantum sub-spans are positioned by the plan's
  deterministic cost split (exact token counts ride in ``args``).  The
  per-slot decode / spec-round intervals are not recorded as spans: the
  Chrome export draws them on the slot tracks from each quantum's
  ``decoded`` map.
* ``instant`` — point events (a request's first token).

Memory is bounded: past ``max_events`` the tracer counts drops instead of
growing (``dropped``); a disabled tracer records nothing and costs one
attribute check per call site.

Host spans: ``with obs.span("core.step"):`` enters a
``jax.profiler.TraceAnnotation`` named ``specinf.core.step`` (so the span
lands in any profiler trace on the device operations' clock) and adds its
wall time to the registry counter ``host_ns/core.step``.  Spans of one
name never nest (opening one inside itself raises).  The spans, outermost
first:

* ``runtime.train_step`` — the train-step call of ``SpecInFRuntime.run``
  and the loss's fetch; ``runtime.fill`` — one bubble's fill;
  ``runtime.monitor`` — the bubble monitor and Algorithm 1 over a run of
  2-ms windows (in the fill, and over the compute segments).
* ``core.step`` — one ``EngineCore.step`` (annotated with its quantum
  record's ``seq``), holding ``core.plan`` (deadline sweep, overload
  ladder, the policy's plan), ``core.admit`` (preemptions and
  admissions), ``engine.prefill`` / ``engine.decode`` (the engine's host
  work and dispatches around its fetches), ``core.collect`` (absorbing
  outputs, finishes, per-request deltas, the journal append) and
  ``core.record`` (gauges and the tracer).
* ``engine.tables`` — block-table top-ups and uploads; ``engine.fetch`` —
  every blocking device-to-host copy of the engine: the host waiting on
  the device; ``engine.gather_params`` — at construction, the cast and
  gather of a sharded tree into the engine's serving copy.

``core/quanta`` counts ``EngineCore.step`` calls.
"""
from __future__ import annotations

import json
import math
import time
from typing import Optional

from jax.profiler import TraceAnnotation

__all__ = ["StepTracer", "Observability", "chrome_trace", "wall_ns",
           "TRACE_VERSION"]

TRACE_VERSION = 1

#: the wall clock of host spans and request wall stamps (nanoseconds)
wall_ns = time.perf_counter_ns

#: prefix of every host span's name in a profiler trace
SPAN_PREFIX = "specinf."


def _num(x):
    """JSON-safe number: infinities (unbounded grants) map to None."""
    if x is None:
        return None
    x = float(x)
    if math.isinf(x) or math.isnan(x):
        return None
    return x


class StepTracer:
    """Append-only structured event log on the engine's clock."""

    def __init__(self, enabled: bool = True, max_events: int = 200_000):
        self.enabled = enabled
        self.max_events = max_events
        self.events: list = []
        self.dropped = 0
        self._seq = 0
        #: bubble-monitor window state for the NEXT quantum event — a
        #: SpecInF runtime sets it right before ``EngineCore.step`` and the
        #: core folds it into the quantum record (then clears it, so a
        #: non-runtime step never carries a stale window).
        self.window_state: Optional[dict] = None

    # ------------------------------------------------------------------
    def _emit(self, ev: dict) -> Optional[int]:
        """Record ``ev``; returns its ``seq``, or None if it was not kept."""
        if not self.enabled:
            return None
        if len(self.events) >= self.max_events:
            self.dropped += 1
            return None
        ev["seq"] = seq = self._seq
        self._seq += 1
        self.events.append(ev)
        return seq

    def quantum(self, t0: float, t1: float, **args) -> Optional[int]:
        return self._emit({
            "type": "quantum", "t0": float(t0), "t1": float(t1),
            "args": args,
        })

    def span(self, name: str, track: str, t0: float, t1: float,
             **args) -> None:
        self._emit({
            "type": "span", "name": name, "track": track,
            "t0": float(t0), "t1": float(t1), "args": args,
        })

    def instant(self, name: str, t: float, track: str = "control",
                **args) -> None:
        self._emit({
            "type": "instant", "name": name, "t": float(t), "track": track,
            "args": args,
        })

    def transition(self, request_id: int, frm: Optional[str], to: str,
                   t: float, priority: Optional[str] = None) -> None:
        self._emit({
            "type": "transition", "request_id": int(request_id),
            "frm": frm, "to": to, "t": float(t), "priority": priority,
        })

    def restamp_arrival(self, request_id: int, t: float) -> None:
        """Rewrite a request's WAITING (submission) transition timestamp —
        the hook ``SpecInFRuntime`` uses when it restamps wall-clock
        arrivals onto the virtual epoch, so the trace and the request
        records stay on one timebase."""
        for ev in self.events:
            if (ev["type"] == "transition"
                    and ev["request_id"] == request_id
                    and ev["to"] == "waiting"):
                ev["t"] = float(t)

    # ------------------------------------------------------------------
    # Derived views
    # ------------------------------------------------------------------
    def attribution(self):
        """Per-request SLO attribution computed from this trace's
        transition events (``repro.obs.attribution.attribute``)."""
        from repro.obs.attribution import attribute

        return attribute(self.events)

    # ------------------------------------------------------------------
    # Export
    # ------------------------------------------------------------------
    def meta(self, **extra) -> dict:
        m = {
            "type": "meta", "version": TRACE_VERSION,
            "events": len(self.events), "dropped": self.dropped,
        }
        m.update(extra)
        return m

    def jsonl_lines(self, **meta):
        yield json.dumps(self.meta(**meta))
        for ev in self.events:
            yield json.dumps(ev)

    def write_jsonl(self, path: str, **meta) -> None:
        with open(path, "w") as f:
            for line in self.jsonl_lines(**meta):
                f.write(line + "\n")

    def write_chrome(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(chrome_trace(self.events), f)


def chrome_trace(events: list) -> dict:
    """Render structured events as a Chrome trace (catapult JSON): spans and
    quanta become complete ('X') events, instants/transitions become
    instant ('i') events, and each track becomes a named thread so Perfetto
    shows training, bubbles, the control plane, and every slot as parallel
    timelines.  A quantum's ``decoded`` slots become ``decode`` (or
    ``spec_round``) spans on their slot tracks, from its ``decode_t0`` to
    its end.  Timestamps convert from engine-clock seconds to µs."""
    tids: dict = {}

    def tid(track: str) -> int:
        if track not in tids:
            tids[track] = len(tids) + 1
        return tids[track]

    # stable track order: control first, then train, then slots
    tid("control")
    tid("train")
    out = []
    for ev in events:
        kind = ev["type"]
        if kind == "quantum":
            out.append({
                "ph": "X", "name": "quantum", "cat": "quantum",
                "ts": ev["t0"] * 1e6,
                "dur": max(ev["t1"] - ev["t0"], 0.0) * 1e6,
                "pid": 0, "tid": tid("control"), "args": ev["args"],
            })
            a = ev["args"]
            decoded = a.get("decoded") or {}
            t_mid = a.get("decode_t0", ev["t0"])
            name = "spec_round" if a.get("gamma") is not None else "decode"
            for slot, rid in decoded.items():
                out.append({
                    "ph": "X", "name": name, "cat": "span",
                    "ts": t_mid * 1e6,
                    "dur": max(ev["t1"] - t_mid, 0.0) * 1e6,
                    "pid": 0, "tid": tid(f"slot{slot}"),
                    "args": {"k": a.get("k"), "gamma": a.get("gamma"),
                             "proposer": a.get("proposer"),
                             "request_id": rid},
                })
        elif kind == "span":
            out.append({
                "ph": "X", "name": ev["name"], "cat": "span",
                "ts": ev["t0"] * 1e6,
                "dur": max(ev["t1"] - ev["t0"], 0.0) * 1e6,
                "pid": 0, "tid": tid(ev["track"]), "args": ev["args"],
            })
        elif kind == "instant":
            out.append({
                "ph": "i", "s": "t", "name": ev["name"], "cat": "instant",
                "ts": ev["t"] * 1e6, "pid": 0, "tid": tid(ev["track"]),
                "args": ev["args"],
            })
        elif kind == "transition":
            out.append({
                "ph": "i", "s": "t",
                "name": f"req{ev['request_id']}:{ev['to']}",
                "cat": "transition", "ts": ev["t"] * 1e6,
                "pid": 0, "tid": tid("control"),
                "args": {"request_id": ev["request_id"],
                         "from": ev["frm"], "priority": ev["priority"]},
            })
    meta = [{
        "ph": "M", "name": "process_name", "pid": 0,
        "args": {"name": "specinf-engine"},
    }]
    for track, t in tids.items():
        meta.append({
            "ph": "M", "name": "thread_name", "pid": 0, "tid": t,
            "args": {"name": track},
        })
        meta.append({
            "ph": "M", "name": "thread_sort_index", "pid": 0, "tid": t,
            "args": {"sort_index": t},
        })
    return {"traceEvents": meta + out, "displayTimeUnit": "ms"}


class Observability:
    """The per-engine observability bundle: ONE metrics registry + ONE step
    tracer.  Constructed by ``InferenceEngine`` when the caller does not
    inject its own; the core, the SpecInF runtime, and the benches all
    share the engine's instance, which is what makes the registry the
    single source of truth."""

    def __init__(self, tracing: bool = True, max_events: int = 200_000):
        from repro.obs.metrics import MetricsRegistry

        self.metrics = MetricsRegistry()
        self.tracer = StepTracer(enabled=tracing, max_events=max_events)
        #: span name -> its reusable ``_HostSpan`` (spans of one name never
        #: nest, so one object per name serves every use)
        self._spans: dict = {}

    def span(self, name: str):
        """Host span ``name`` (module docstring): a profiler annotation
        ``specinf.<name>`` plus its wall time on ``host_ns/<name>``.  The
        context's value has ``set_metadata(**kw)``, which annotates the
        span in the profile."""
        sp = self._spans.get(name)
        if sp is None:
            sp = self._spans[name] = _HostSpan(
                name, self.metrics.counter("host_ns/" + name)
            )
        return sp


class _HostSpan:
    __slots__ = ("label", "counter", "open", "_ann", "_t0")

    def __init__(self, name: str, counter):
        self.label = SPAN_PREFIX + name
        self.counter = counter
        self.open = False

    def __enter__(self) -> TraceAnnotation:
        if self.open:
            raise RuntimeError(f"host span {self.label!r} opened inside itself")
        self.open = True
        self._ann = ann = TraceAnnotation(self.label)
        ann.__enter__()
        self._t0 = wall_ns()
        return ann

    def __exit__(self, *exc) -> None:
        self.counter.value += wall_ns() - self._t0
        self._ann.__exit__(*exc)
        self.open = False

