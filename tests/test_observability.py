"""Engine observability layer (DESIGN.md §8): metrics registry, structured
step tracer, and SLO attribution.

The collocated fixture runs a real virtual-clock SpecInF fill over a real
engine so the trace/attribution tests exercise the actual emission sites;
the unit tests below cover the registry/histogram/tracer/schema contracts
in isolation.
"""
import itertools
import json

import jax
import numpy as np
import pytest

from repro import configs
from repro.configs.base import SpecInFConfig
from repro.core import SpecInFRuntime
from repro.core.profiles import dp_profile
from repro.models import transformer as T
from repro.obs import (
    STABLE_NAMES,
    MetricsRegistry,
    Observability,
    StepTracer,
    StreamingHistogram,
    attribute,
    chrome_trace,
    validate_events,
    validate_jsonl,
)
from repro.serving.core import Priority, SamplingParams
from repro.serving.engine import InferenceEngine, RegistryCounterView, Request


@pytest.fixture(scope="module")
def tiny():
    cfg = configs.smoke_config("olmo-1b")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _collocated_run(tiny, obs=None):
    """One collocated virtual-clock run: 2 offline requests filling
    bubbles, 6 online Poisson-ish arrivals."""
    cfg, params = tiny
    engine = InferenceEngine(cfg, params, max_slots=2, max_seq=96, obs=obs)
    for _ in range(2):
        engine.core.submit(
            np.arange(8), SamplingParams(max_new_tokens=32),
            priority=Priority.OFFLINE, arrival_time=0.0,
        )
    reqs = [
        Request(prompt=np.arange(4), max_new_tokens=3,
                arrival_time=0.03 * i, online=True)
        for i in range(6)
    ]
    rt = SpecInFRuntime(
        train_step=lambda s, b: (s, {"loss": 0.0}), train_state=None,
        batch_iter=itertools.repeat({}),
        profile=dp_profile("tiny", compute_s=0.03, comm_s=0.04),
        engine=engine, online_requests=reqs,
        cfg=SpecInFConfig(busy_hold_ms=5.0), decode_microstep_s=0.002,
    )
    metrics = rt.run(num_iterations=12)
    return engine, metrics


@pytest.fixture(scope="module")
def traced_run(tiny):
    engine, metrics = _collocated_run(tiny)
    assert engine.obs.tracer.enabled, "engines trace by default"
    return engine, metrics


@pytest.fixture(scope="module")
def untraced_run(tiny):
    engine, metrics = _collocated_run(tiny, Observability(tracing=False))
    assert not engine.obs.tracer.enabled and not engine.obs.tracer.events
    return engine, metrics


# ----------------------------------------------------------------------
# streaming histogram
# ----------------------------------------------------------------------
def test_streaming_histogram_exact_regime_is_bit_for_bit():
    rng = np.random.default_rng(0)
    xs = [float(x) for x in rng.exponential(0.05, 500)]
    h = StreamingHistogram("t")
    for x in xs:
        h.record(x)
    assert h.exact
    assert h.values() == xs, "the historical unbounded-list view"
    for q in (50, 90, 95, 99):
        assert h.percentile(q) == float(np.percentile(xs, q))
    assert h.count == 500
    assert h.min == min(xs) and h.max == max(xs)
    assert h.mean() == pytest.approx(np.mean(xs))


def test_streaming_histogram_collapse_bounds_memory():
    h = StreamingHistogram("t", exact_cap=64, num_bins=32)
    rng = np.random.default_rng(1)
    xs = rng.uniform(0.0, 1.0, 1000)
    for x in xs:
        h.record(float(x))
    assert not h.exact, "past the cap the raw samples are gone"
    with pytest.raises(RuntimeError):
        h.values()
    # exact aggregates survive the collapse; percentiles stay within a
    # few bin widths of the true value
    assert h.count == 1000
    assert h.sum == pytest.approx(float(xs.sum()))
    assert h.min == float(xs.min()) and h.max == float(xs.max())
    for q in (50, 95):
        assert abs(h.percentile(q) - float(np.percentile(xs, q))) < 0.1


def test_streaming_histogram_empty_is_nan():
    h = StreamingHistogram("t")
    assert np.isnan(h.percentile(95))
    assert np.isnan(h.mean())


# ----------------------------------------------------------------------
# registry + thin counter views
# ----------------------------------------------------------------------
def test_registry_get_or_create_and_type_conflict():
    r = MetricsRegistry()
    c = r.counter("a")
    assert r.counter("a") is c, "get-or-create returns the same cell"
    with pytest.raises(TypeError):
        r.gauge("a")
    g = r.gauge("g")
    g.set(3)
    g.set(1)
    assert (g.value, g.min, g.max, g.samples) == (1.0, 1.0, 3.0, 2)
    snap = r.snapshot()
    assert snap["a"]["type"] == "counter"
    assert snap["g"] == {"type": "gauge", "value": 1.0, "samples": 2,
                         "min": 1.0, "max": 3.0}


def test_counter_view_shares_the_registry_cell():
    class Holder:
        steps = RegistryCounterView("engine/steps_executed")

        def __init__(self):
            self.obs = Observability(tracing=False)

    h = Holder()
    cell = h.obs.metrics.counter("engine/steps_executed")
    assert h.steps == 0 and cell.value == 0
    h.steps += 5
    assert cell.value == 5, "attribute writes hit the registry cell"
    cell.inc(2)
    assert h.steps == 7, "registry writes are visible through the attribute"


def test_engine_counter_attrs_are_pinned_views():
    import inspect

    for attr in ("d2h_transfers", "steps_executed", "generated_tokens_total",
                 "prefill_prompt_tokens", "spec_rounds", "spec_accepted"):
        view = inspect.getattr_static(InferenceEngine, attr)
        assert isinstance(view, RegistryCounterView)
        assert STABLE_NAMES.get(view.name) == "counter"


# ----------------------------------------------------------------------
# tracer mechanics
# ----------------------------------------------------------------------
def test_tracer_bounds_memory_and_counts_drops():
    tr = StepTracer(max_events=10)
    for i in range(25):
        tr.instant("tick", float(i))
    assert len(tr.events) == 10 and tr.dropped == 15
    off = StepTracer(enabled=False)
    off.quantum(0.0, 1.0)
    assert off.events == [] and off.dropped == 0


def test_restamp_arrival_rewrites_only_the_waiting_edge():
    tr = StepTracer()
    tr.transition(7, None, "waiting", 123.4, priority="offline")
    tr.transition(7, "waiting", "running", 123.5)
    tr.restamp_arrival(7, 0.0)
    assert tr.events[0]["t"] == 0.0
    assert tr.events[1]["t"] == 123.5


# ----------------------------------------------------------------------
# schema validator
# ----------------------------------------------------------------------
def test_schema_validator_accepts_tracer_output_and_rejects_junk():
    tr = StepTracer()
    tr.quantum(0.0, 0.1, k=2)
    tr.transition(1, None, "waiting", 0.0, priority="online")
    tr.span("decode", "slot0", 0.0, 0.1, tokens=2)
    tr.instant("first_token", 0.1, request_id=1)
    assert validate_events(tr.events) == []

    bad = [
        {"type": "nope", "seq": 0},
        {"type": "quantum", "t0": 0.0, "seq": 1, "args": {}},  # no t1
        {"type": "transition", "request_id": 1, "frm": None, "to": "zombie",
         "t": 0.0, "seq": 2, "priority": None},
        {"type": "span", "name": "s", "track": "t", "t0": 1.0, "t1": 0.5,
         "seq": 3, "args": {}},  # t1 < t0
    ]
    errs = validate_events(bad)
    assert len(errs) >= 4

    dup_seq = [
        {"type": "quantum", "t0": 0.0, "t1": 1.0, "seq": 5, "args": {}},
        {"type": "quantum", "t0": 1.0, "t1": 2.0, "seq": 5, "args": {}},
    ]
    assert any("not increasing" in e for e in validate_events(dup_seq))


# ----------------------------------------------------------------------
# attribution unit cases
# ----------------------------------------------------------------------
def test_attribution_monolithic_first_token_splits_running():
    tr = StepTracer()
    tr.transition(1, None, "waiting", 0.0, priority="online")
    tr.transition(1, "waiting", "running", 1.0)
    tr.instant("first_token", 1.25, request_id=1)
    tr.transition(1, "running", "finished_stopped", 2.0)
    ra = attribute(tr.events)[1]
    assert ra.queueing == pytest.approx(1.0)
    assert ra.prefill == pytest.approx(0.25)
    assert ra.decode == pytest.approx(0.75)
    assert ra.ttft_s == pytest.approx(1.25)
    assert ra.total == pytest.approx(ra.latency_s)
    assert ra.finish_state == "finished_stopped"


def test_attribution_charges_preempted_time():
    tr = StepTracer()
    tr.transition(2, None, "waiting", 0.0, priority="offline")
    tr.transition(2, "waiting", "running", 1.0)
    tr.transition(2, "running", "preempted", 2.0)
    tr.transition(2, "preempted", "running", 3.0)
    tr.transition(2, "running", "finished_length", 4.0)
    ra = attribute(tr.events)[2]
    assert ra.queueing == pytest.approx(1.0)
    assert ra.decode == pytest.approx(2.0)
    assert ra.preempted == pytest.approx(1.0)
    assert ra.preemptions == 1
    assert ra.total == pytest.approx(ra.latency_s)


# ----------------------------------------------------------------------
# collocated virtual-clock run: timebase integrity + derived views
# ----------------------------------------------------------------------
def test_collocated_trace_stays_on_the_virtual_timebase(traced_run):
    """Regression: no wall-clock (``time.monotonic``) timestamp may leak
    into a collocated trace.  Wall time since boot is orders of magnitude
    beyond the sub-second virtual horizon, so a single leaked stamp blows
    the bound."""
    engine, metrics = traced_run
    tr = engine.obs.tracer
    assert tr.events and tr.dropped == 0
    assert validate_events(tr.events) == []
    # bubble spans may extend one profiled bubble past the final quantum
    horizon = metrics.virtual_time_s + 0.05 + 1e-9
    for ev in tr.events:
        for key in ("t", "t0", "t1"):
            if key in ev:
                assert 0.0 <= ev[key] <= horizon, (ev["type"], key, ev[key])


def test_collocated_attribution_sums_to_latency(traced_run):
    engine, metrics = traced_run
    att = engine.obs.tracer.attribution()
    finished = [ra for ra in att.values() if ra.finish_time is not None]
    assert finished
    for ra in finished:
        assert abs(ra.total - ra.latency_s) < 1e-9, ra.as_dict()
    online = [ra for ra in finished if ra.priority == "online"]
    assert len(online) == metrics.online_served >= 2
    # the trace's TTFT view and the registry histogram are two projections
    # of the same stamped events
    from_trace = sorted(ra.ttft_s for ra in online)
    from_registry = sorted(metrics.online_ttft_s)
    assert from_trace == pytest.approx(from_registry, abs=1e-12)


@pytest.mark.parametrize("row", ["virtual_time_s", "online_served",
                                 "online_ttft_s"])
def test_tracing_leaves_the_schedule_unchanged(traced_run, untraced_run,
                                               row):
    """The tracer only records: the same collocated run with it off ends at
    the same virtual time, serves the same online requests, and stamps the
    same TTFTs, exactly."""
    traced, untraced = traced_run[1], untraced_run[1]
    assert getattr(traced, row) == getattr(untraced, row)


def test_filling_metrics_are_registry_views(traced_run):
    engine, metrics = traced_run
    m = engine.obs.metrics
    assert metrics.online_latencies_s == \
        m.histogram("core/online_latency_s").values()
    assert metrics.online_ttft_s == m.histogram("core/online_ttft_s").values()
    assert metrics.online_served == m.counter("core/finished/online").value
    assert metrics.preemptions == m.counter("core/preemptions").value
    # bit-for-bit with the historical list-based percentiles
    assert metrics.p95_latency_s() == \
        float(np.percentile(metrics.online_latencies_s, 95))
    assert metrics.p95_ttft_s() == \
        float(np.percentile(metrics.online_ttft_s, 95))
    # per-quantum gauges were sampled
    assert m.gauge("engine/slots_active").samples > 0
    assert m.gauge("core/queue_depth/online").samples > 0
    assert m.gauge("engine/pool/pages_in_use").samples > 0


def test_trace_export_roundtrip(traced_run, tmp_path):
    engine, _ = traced_run
    tr = engine.obs.tracer
    p = tmp_path / "trace.jsonl"
    tr.write_jsonl(str(p), metrics=engine.obs.metrics.snapshot())
    n, errors = validate_jsonl(str(p))
    assert errors == []
    assert n == len(tr.events)
    head = json.loads(p.read_text().splitlines()[0])
    assert head["version"] == 1 and "metrics" in head

    cp = tmp_path / "trace.chrome.json"
    tr.write_chrome(str(cp))
    doc = json.loads(cp.read_text())
    threads = {e["args"]["name"] for e in doc["traceEvents"]
               if e.get("name") == "thread_name"}
    assert {"control", "train"} <= threads
    assert any(t.startswith("slot") for t in threads), \
        "per-slot tracks must exist"
    assert any(e.get("name") == "quantum" for e in doc["traceEvents"])
    assert any(e.get("name") == "train_compute" for e in doc["traceEvents"])


# ----------------------------------------------------------------------
# host spans, wall stamps, per-slot decode drawn at export
# ----------------------------------------------------------------------
def test_host_span_counters_advance_and_nest(traced_run):
    engine, _ = traced_run
    m = engine.obs.metrics
    ns = {name: m.counter("host_ns/" + name).value for name in (
        "runtime.train_step", "runtime.fill", "runtime.monitor", "core.step",
        "core.plan", "core.admit", "core.collect", "core.record",
        "engine.tables", "engine.prefill", "engine.decode", "engine.fetch",
    )}
    assert all(v > 0 for v in ns.values()), ns
    assert ns["runtime.fill"] >= ns["core.step"] >= ns["engine.fetch"]
    inner = sum(ns[n] for n in ("core.plan", "core.admit", "engine.prefill",
                                "engine.decode", "engine.fetch",
                                "core.collect", "core.record"))
    assert inner <= ns["core.step"]
    quanta = [ev for ev in engine.obs.tracer.events if ev["type"] == "quantum"]
    assert m.counter("core/quanta").value == len(quanta) > 0
    assert all(name in STABLE_NAMES for name in m.names()
               if name.startswith("host_ns/"))


def test_span_records_annotation_and_refuses_to_nest_in_itself():
    obs = Observability()
    with obs.span("engine.tables") as sp:
        sp.set_metadata(seq=1)
        with pytest.raises(RuntimeError, match="inside itself"):
            with obs.span("engine.tables"):
                pass
    t = obs.metrics.counter("host_ns/engine.tables").value
    assert obs.metrics.names() == ["host_ns/engine.tables"] and t > 0
    with obs.span("engine.tables"):  # closed again: reusable
        pass
    assert obs.metrics.counter("host_ns/engine.tables").value > t


def test_wall_stamps_are_ordered(traced_run):
    engine, metrics = traced_run
    done = [cr for cr in engine.core.requests.values()
            if cr.state.finished and cr.output_tokens]
    assert len(done) >= metrics.online_served >= 2
    for cr in done:
        stamps = (cr.arrival_wall_ns, cr.admit_wall_ns,
                  cr.first_token_wall_ns, cr.finish_wall_ns)
        assert all(isinstance(x, int) for x in stamps), stamps
        assert stamps[0] <= stamps[1] <= stamps[2] <= stamps[3], stamps
    quanta = [ev for ev in engine.obs.tracer.events if ev["type"] == "quantum"]
    walls = [ev["args"]["wall_ns"] for ev in quanta]
    assert all(a <= b for a, b in walls)
    # one quantum in flight: a quantum is dispatched before the one ahead
    # of it is recorded, so the intervals overlap, in order at both ends
    assert all(a0 <= a1 and b0 <= b1
               for (a0, b0), (a1, b1) in zip(walls, walls[1:]))


def test_chrome_draws_slot_tracks_without_per_slot_decode_spans(traced_run):
    engine, _ = traced_run
    events = engine.obs.tracer.events
    assert not [ev for ev in events if ev["type"] == "span"
                and ev["name"] in ("decode", "spec_round")]
    decoded = [ev for ev in events if ev["type"] == "quantum"
               and ev["args"]["decoded"]]
    assert decoded
    doc = chrome_trace(json.loads(json.dumps(events)))  # as a JSONL reader
    threads = {e["tid"]: e["args"]["name"] for e in doc["traceEvents"]
               if e.get("name") == "thread_name"}
    drawn = [e for e in doc["traceEvents"]
             if e.get("ph") == "X" and e["name"] == "decode"]
    assert len(drawn) == sum(len(ev["args"]["decoded"]) for ev in decoded)
    assert {threads[e["tid"]] for e in drawn} <= {
        f"slot{i}" for i in range(engine.max_slots)}
    first = decoded[0]
    slot, rid = next(iter(first["args"]["decoded"].items()))
    match = [e for e in drawn if e["args"]["request_id"] == rid
             and threads[e["tid"]] == f"slot{slot}"
             and e["ts"] == first["args"]["decode_t0"] * 1e6]
    assert match and match[0]["args"]["k"] == first["args"]["k"]


def test_schema_checks_quantum_wall_and_decoded_args():
    ok = {"type": "quantum", "t0": 0.0, "t1": 1.0, "seq": 0,
          "args": {"decoded": {"0": 3}, "decode_t0": 0.5, "wall_ns": [5, 9]}}
    assert validate_events([ok]) == []
    for bad_args in ({"decoded": [0, 3]}, {"wall_ns": [9, 5]},
                     {"wall_ns": [1.5, 2.0]}, {"decode_t0": "x"}):
        bad = dict(ok, args=bad_args)
        assert validate_events([bad]), bad_args
