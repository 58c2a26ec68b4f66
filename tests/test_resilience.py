"""Failure containment and graceful degradation (DESIGN.md §9): seeded
fault-injector determinism, NaN quarantine with byte-identical recovery,
allocator-fault containment, pool-exhaustion recovery (property test),
queue deadlines, degenerate grants, revocable grants with exact
partial-quantum accounting, the overload ladder's hysteresis, and the
runtime's bounded early-resume yield."""
import jax
import numpy as np
import pytest

from repro import configs
from repro.configs.base import SpecDecodeConfig, SpecInFConfig, draft_config
from repro.models import transformer as T
from repro.resilience import (
    FaultInjector,
    FaultSpec,
    LadderConfig,
    LadderStage,
    OverloadLadder,
)
from repro.serving.core import (
    EngineCore,
    Grant,
    Priority,
    PriorityPolicy,
    RequestState,
    RevocationSignal,
    SamplingParams,
)
from repro.serving.engine import InferenceEngine
from repro.serving.kv_pool import PageAllocError, PagePool

CFG = configs.smoke_config("qwen3-1.7b")
PARAMS = T.init_params(CFG, jax.random.PRNGKey(0))
DCFG = draft_config(CFG)
DPARAMS = T.init_params(DCFG, jax.random.PRNGKey(1))


def _engine(paged=True, spec=False, **kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("max_seq", 128)
    kw.setdefault("kv_page_size", None if paged else 0)
    if spec:
        kw.update(draft_cfg=DCFG, draft_params=DPARAMS,
                  spec=SpecDecodeConfig(mode="greedy"))
    return InferenceEngine(CFG, PARAMS, **kw)


def _drain(core, limit=300):
    n = 0
    while core.has_unfinished:
        core.step()
        n += 1
        assert n < limit, "core.step() made no progress"


# ---------------------------------------------------------------------------
# FaultInjector unit behavior
# ---------------------------------------------------------------------------


def test_fault_spec_rejects_unknown_point():
    with pytest.raises(ValueError):
        FaultSpec("engine/made_up_point")


def test_injector_deterministic_and_point_independent():
    specs = (
        FaultSpec("engine/nan_logits", probability=0.3),
        FaultSpec("pool/alloc_fail", probability=0.3),
    )
    a = FaultInjector(seed=11, specs=specs)
    b = FaultInjector(seed=11, specs=specs)
    # interleave consultations differently: per-point streams must not shift
    pat_a = [a.should_fire("engine/nan_logits") for _ in range(20)]
    [a.should_fire("pool/alloc_fail") for _ in range(5)]
    [b.should_fire("pool/alloc_fail") for _ in range(5)]
    pat_b = [b.should_fire("engine/nan_logits") for _ in range(20)]
    assert pat_a == pat_b
    assert FaultInjector(seed=12, specs=specs) is not None  # other seeds fine
    c = FaultInjector(seed=12, specs=specs)
    assert [c.should_fire("engine/nan_logits") for _ in range(20)] != pat_a


def test_injector_after_and_max_fires_do_not_shift_stream():
    spec0 = (FaultSpec("engine/nan_logits", probability=0.5),)
    spec1 = (FaultSpec("engine/nan_logits", probability=0.5, after=3,
                       max_fires=2),)
    base = FaultInjector(seed=5, specs=spec0)
    capped = FaultInjector(seed=5, specs=spec1)
    raw = [base.should_fire("engine/nan_logits") for _ in range(30)]
    got = [capped.should_fire("engine/nan_logits") for _ in range(30)]
    assert capped.total_fires <= 2
    # capped fires are a subset of the raw stream's hits, never new ones
    assert all(not g or r for g, r in zip(got, raw))
    assert not any(got[:3])  # warmup consultations never fire
    # unarmed points are inert
    assert not base.should_fire("core/step_overrun")


# ---------------------------------------------------------------------------
# NaN quarantine and allocator-fault containment
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_nan_quarantine_recovers_byte_identical(paged):
    """A poisoned-KV fused dispatch must quarantine only the poisoned slot
    and requeue it; the retried stream is byte-identical to fault-free."""

    def run(inj):
        core = _engine(paged=paged, fault_injector=inj).core
        core.fault_backoff_s = 0.0  # wall clock here; gate tested separately
        reqs = [core.submit(np.arange(6 + i), SamplingParams(max_new_tokens=10))
                for i in range(2)]
        _drain(core)
        return [list(r.output_tokens) for r in reqs], core

    base, _ = run(None)
    inj = FaultInjector(seed=3, specs=(
        FaultSpec("engine/nan_logits", probability=1.0, after=1, max_fires=1),
    ))
    faulty, core = run(inj)
    assert inj.total_fires == 1
    m = core.obs.metrics
    assert m.counter("fault/nan_quarantines").value == 1
    assert m.counter("fault/requeues").value == 1
    assert faulty == base
    assert all(len(t) == 10 for t in faulty)


def test_retry_backoff_gates_readmission():
    """The requeued request is ineligible until its backoff elapses —
    exponential in the fault count — and eligible right after."""
    from repro.serving.core import SchedulerPolicy

    core = _engine().core
    r = core.submit(np.arange(4), SamplingParams(max_new_tokens=2),
                    arrival_time=0.0)
    r.faults = 2
    r.retry_at = 0.0 + core.fault_backoff_s * 2 ** (r.faults - 1)
    pol = SchedulerPolicy()
    assert not pol.eligible(r, Grant(now=r.retry_at - 1e-6))
    assert pol.eligible(r, Grant(now=r.retry_at))


def test_retry_budget_exhaustion_finishes_error():
    inj = FaultInjector(seed=3, specs=(
        FaultSpec("engine/nan_logits", probability=1.0),
    ))
    core = _engine(paged=True, fault_injector=inj).core
    core.fault_backoff_s = 0.0  # retry immediately; every retry is poisoned
    r = core.submit(np.arange(6), SamplingParams(max_new_tokens=10))
    _drain(core)
    assert r.state is RequestState.FINISHED_ERROR
    assert r.finish_reason == "error"
    assert r.faults == core.max_fault_retries + 1
    m = core.obs.metrics
    assert m.counter("fault/retry_exhausted").value == 1
    assert m.counter("core/finish_reason/error").value == 1
    assert core.engine.num_active == 0  # the poisoned slot was released


def test_alloc_fault_contained_and_byte_identical():
    def run(inj):
        core = _engine(paged=True, kv_page_size=8,
                       fault_injector=inj).core
        reqs = [core.submit(np.arange(9), SamplingParams(max_new_tokens=12)),
                core.submit(np.arange(17), SamplingParams(max_new_tokens=12))]
        _drain(core)
        return [list(r.output_tokens) for r in reqs], core

    base, _ = run(None)
    inj = FaultInjector(seed=9, specs=(
        FaultSpec("pool/alloc_fail", probability=1.0, after=2, max_fires=2),
    ))
    faulty, core = run(inj)
    assert inj.total_fires >= 1
    assert faulty == base
    assert all(len(t) == 12 for t in faulty)


@pytest.fixture(scope="module")
def exhaustion_reference():
    """Fault-free bytes per prompt length, from a pool that never blocks."""
    big = _engine(paged=True, kv_page_size=8, kv_pool_pages=256).core
    want = {}
    for n in range(4, 8):
        r = big.submit(np.arange(n), SamplingParams(max_new_tokens=10))
        _drain(big)
        want[n] = list(r.output_tokens)
    return want


def _exhaustion_roundtrip(prompt_lens, want):
    """Property: genuine pool exhaustion never raises — admission blocks on
    capacity and resumes as slots retire, and every request completes with
    the unconstrained pool's exact bytes."""
    # tiny pool: worst-case need of one request is ~3 pages, so several
    # admissions must block on capacity and recover
    core = _engine(paged=True, kv_page_size=8, kv_pool_pages=9).core
    reqs = [core.submit(np.arange(n), SamplingParams(max_new_tokens=10))
            for n in prompt_lens]
    _drain(core, limit=500)
    for n, r in zip(prompt_lens, reqs):
        assert r.state is RequestState.FINISHED_LENGTH
        assert list(r.output_tokens) == want[n]
    assert core.engine.pool.reserved == 0


@pytest.mark.parametrize("lens", [
    [4], [7, 6, 5, 4], [5, 5, 5, 5], [6, 4, 7],
], ids=["one", "desc", "same", "mixed"])
def test_pool_exhaustion_blocks_admission_and_recovers(
    lens, exhaustion_reference
):
    _exhaustion_roundtrip(lens, exhaustion_reference)


def test_pool_exhaustion_property(exhaustion_reference):
    """Hypothesis widening of the seeded sweep (skipped when the package
    is absent — the parametrized cases above always run)."""
    hypothesis = pytest.importorskip("hypothesis")
    given, settings, st = (
        hypothesis.given, hypothesis.settings, hypothesis.strategies,
    )

    @given(st.lists(st.integers(min_value=4, max_value=7),
                    min_size=1, max_size=4))
    @settings(max_examples=6, deadline=None)
    def prop(prompt_lens):
        _exhaustion_roundtrip(prompt_lens, exhaustion_reference)

    prop()


# ---------------------------------------------------------------------------
# Deadlines, degenerate grants
# ---------------------------------------------------------------------------


def test_queue_deadline_expires_without_slot():
    core = _engine().core
    slow = core.submit(np.arange(6), SamplingParams(max_new_tokens=4,
                                                    deadline_s=0.5),
                       arrival_time=0.0)
    out = core.step(Grant(now=1.0))
    assert slow.state is RequestState.FINISHED_EXPIRED
    assert slow.finish_reason == "expired"
    assert slow.request_id not in out.admitted
    assert slow.output_tokens == []
    d = {o.request_id: o for o in out.outputs}
    assert d[slow.request_id].state is RequestState.FINISHED_EXPIRED
    assert core.obs.metrics.counter("core/finish_reason/expired").value == 1
    # expiry never counts toward served latency (it would poison the p95)
    assert core.obs.metrics.histogram("core/offline_latency_s").count == 0
    # deadline-less work is untouched and still serves normally
    keep = core.submit(np.arange(6), SamplingParams(max_new_tokens=4),
                       arrival_time=0.0)
    _drain(core)
    assert keep.state is RequestState.FINISHED_LENGTH
    assert core.obs.metrics.histogram("core/offline_latency_s").count == 1


def test_deadline_never_fires_once_running():
    core = _engine().core
    r = core.submit(np.arange(6), SamplingParams(max_new_tokens=6,
                                                 deadline_s=0.5),
                    arrival_time=0.0)
    core.step(Grant(now=0.0))  # admitted before the deadline
    assert r.state is RequestState.RUNNING
    while not r.state.finished:
        core.step(Grant(now=2.0))  # long past the deadline
    assert r.state is RequestState.FINISHED_LENGTH


def test_degenerate_grant_is_explicit_noop():
    core = _engine().core
    r = core.submit(np.arange(6), SamplingParams(max_new_tokens=4))
    out = core.step(Grant(token_budget=0.0))
    assert out.k == 0 and out.cost_steps == 0.0 and out.prefill_tokens == 0
    assert not out.admitted and r.state is RequestState.WAITING
    m = core.obs.metrics
    assert m.counter("core/starved_quanta").value == 1
    # the quantum still advanced the trace
    ev = [e for e in core.obs.tracer.events if e.get("type") == "quantum"]
    assert len(ev) == 1
    # deadline sweeps still land inside a starved quantum
    doomed = core.submit(np.arange(4), SamplingParams(max_new_tokens=2,
                                                      deadline_s=0.1),
                         arrival_time=0.0)
    core.step(Grant(now=5.0, token_budget=0.0))
    assert doomed.state is RequestState.FINISHED_EXPIRED
    assert m.counter("core/starved_quanta").value == 2


# ---------------------------------------------------------------------------
# Revocable grants
# ---------------------------------------------------------------------------


def _clocked(core):
    """Pin the engine to a controllable virtual clock; returns the grant
    factory: one microstep of cost advances the clock by 1.0."""
    clk = [0.0]
    core.engine.clock = lambda: clk[0]

    def grant(**kw):
        base = clk[0]
        kw.setdefault("now", base)
        kw.setdefault(
            "advance_clock",
            lambda steps, _b=base: clk.__setitem__(0, _b + steps),
        )
        return Grant(**kw)

    return clk, grant


@pytest.mark.parametrize("spec", [False, True], ids=["plain", "spec"])
def test_revocation_yields_within_bound_exact_accounting(spec):
    """An armed signal tripping mid-quantum stops the fused loop within one
    sub-dispatch; the quantum's cost is re-priced to what actually ran, and
    resuming with fresh grants reproduces the fault-free bytes."""

    def run(revoke_at):
        core = _engine(paged=True, max_slots=1, spec=spec).core
        clk, grant = _clocked(core)
        r = core.submit(np.arange(8), SamplingParams(max_new_tokens=24),
                        arrival_time=0.0)
        sig = RevocationSignal()
        sig.arm(revoke_at)
        outs = []
        while not r.state.finished:
            # the signal rides every grant until it trips; afterwards the
            # runtime would stop filling — here we resume with fresh grants
            s = sig if not sig.revoked else None
            outs.append(core.step(grant(revocation=s, revoke_check_steps=1)))
            assert len(outs) < 100
        return list(r.output_tokens), outs, core

    base, outs0, _ = run(revoke_at=float("inf"))
    assert len(base) == 24 and not any(o.revoked for o in outs0)
    # first quantum admits + prefills; revoke 2 microsteps into the second
    cut = outs0[0].cost_steps + 2.0
    toks, outs, core = run(revoke_at=cut)
    revoked = [o for o in outs if o.revoked]
    assert len(revoked) == 1
    ro = revoked[0]
    # exact partial-quantum accounting: each ran microstep priced like the
    # plan's, none of the unran remainder billed
    per = 1.0 if not spec else outs0[1].cost_steps / outs0[1].k
    assert ro.cost_steps == pytest.approx(ro.k * per)
    assert ro.k < outs0[1].k  # genuinely cut short
    # yield bound: at most revoke_check_steps microsteps ran past the
    # signal -> with one slot, <= ceil(2/per)+1 microsteps total
    assert ro.k * per <= 2.0 + per
    assert core.obs.metrics.counter("fault/revocations").value == 1
    # the interrupted stream resumes byte-identical
    assert toks == base


def test_unarmed_signal_is_byte_identical_to_single_dispatch():
    def run(revocable):
        core = _engine(paged=True).core
        clk, grant = _clocked(core)
        r = core.submit(np.arange(8), SamplingParams(max_new_tokens=16),
                        arrival_time=0.0)
        ks, costs = [], []
        while not r.state.finished:
            sig = RevocationSignal() if revocable else None
            out = core.step(grant(revocation=sig, revoke_check_steps=2))
            ks.append(out.k)
            costs.append(out.cost_steps)
        return list(r.output_tokens), ks, costs, clk[0]

    plain = run(False)
    sub = run(True)
    assert sub[0] == plain[0]  # same bytes
    assert sub[1] == plain[1] and sub[2] == plain[2]  # same quantum shapes
    assert sub[3] == pytest.approx(plain[3])  # same virtual end time


def test_injected_mid_quantum_revocation_point():
    inj = FaultInjector(seed=1, specs=(
        FaultSpec("core/revoke_mid_quantum", probability=1.0, after=1,
                  max_fires=1),
    ))
    core = _engine(paged=True, max_slots=1, fault_injector=inj).core
    clk, grant = _clocked(core)
    r = core.submit(np.arange(8), SamplingParams(max_new_tokens=16),
                    arrival_time=0.0)
    sig = RevocationSignal()  # unarmed: only the injector can trip it
    outs = []
    while not r.state.finished:
        outs.append(core.step(grant(revocation=sig, revoke_check_steps=1)))
        if sig.revoked:
            break
    assert sig.revoked and sig.reason == "injected_revocation"
    assert any(o.revoked for o in outs)
    assert core.obs.metrics.counter("fault/revocations").value == 1


def test_injected_step_overrun_inflates_cost():
    def run(inj):
        core = _engine(paged=True, fault_injector=inj).core
        clk, grant = _clocked(core)
        costs = []
        r = core.submit(np.arange(8), SamplingParams(max_new_tokens=8),
                        arrival_time=0.0)
        while not r.state.finished:
            costs.append(core.step(grant()).cost_steps)
        return list(r.output_tokens), costs, clk[0]

    base_toks, base_costs, base_end = run(None)
    inj = FaultInjector(seed=2, specs=(
        FaultSpec("core/step_overrun", probability=1.0, max_fires=1),
    ))
    toks, costs, end = run(inj)
    assert toks == base_toks  # a slow step never corrupts the stream
    assert inj.total_fires == 1
    assert costs[0] > base_costs[0] and costs[1:] == base_costs[1:]
    assert end > base_end  # the overrun consumed real virtual time


# ---------------------------------------------------------------------------
# Overload ladder
# ---------------------------------------------------------------------------


def _ladder_core(n_offline=10):
    core = _engine(paged=True).core
    core.ladder = OverloadLadder(LadderConfig(
        high_queue_depth=4, low_queue_depth=1, up_dwell=2, down_dwell=3,
        offline_keep_depth=2,
    ))
    for i in range(n_offline):
        core.submit(np.arange(5), SamplingParams(max_new_tokens=2),
                    priority=Priority.OFFLINE, arrival_time=0.0)
    return core


def test_ladder_escalates_with_dwell_and_sheds_offline():
    core = _ladder_core()
    lad = core.ladder
    g = Grant(now=0.0)
    lad.update(core, g)
    assert lad.stage is LadderStage.NORMAL  # 1 pressured quantum < up_dwell
    lad.update(core, g)
    assert lad.stage is LadderStage.SPEC_OFF
    lad.update(core, g)
    lad.update(core, g)
    assert lad.stage is LadderStage.K_SHRINK
    lad.update(core, g)
    lad.update(core, g)
    assert lad.stage is LadderStage.SHED_OFFLINE
    # queue trimmed to keep-depth, newest first; oldest work survives
    assert len(core.waiting[Priority.OFFLINE]) == 2
    m = core.obs.metrics
    assert m.counter("fault/shed/offline").value == 8
    assert m.counter("fault/ladder_escalations").value == 3
    assert m.gauge("fault/ladder_stage").value == int(LadderStage.SHED_OFFLINE)


def test_ladder_hysteresis_no_flapping():
    core = _ladder_core(n_offline=0)
    lad = core.ladder
    lad.stage = LadderStage.SPEC_OFF
    # alternating pressured/calm quanta must hold the stage (each flip
    # resets the other dwell) — no flapping around the threshold
    for i in range(6):
        for _ in range(10 if i % 2 else 0):
            core.submit(np.arange(4), SamplingParams(max_new_tokens=1),
                        priority=Priority.OFFLINE, arrival_time=0.0)
        lad.update(core, Grant(now=0.0))
        core.waiting[Priority.OFFLINE].clear()
        assert lad.stage is LadderStage.SPEC_OFF
    # sustained calm de-escalates after down_dwell
    for _ in range(3):
        lad.update(core, Grant(now=0.0))
    assert lad.stage is LadderStage.NORMAL


def test_ladder_sheds_doomed_online_and_downshifts_plan():
    core = _ladder_core(n_offline=0)
    lad = core.ladder
    lad.stage = LadderStage.SHED_ONLINE
    doomed = core.submit(np.arange(4), SamplingParams(max_new_tokens=2,
                                                      deadline_s=1.0),
                         priority=Priority.ONLINE, arrival_time=0.0)
    safe = core.submit(np.arange(4), SamplingParams(max_new_tokens=2,
                                                    deadline_s=100.0),
                       priority=Priority.ONLINE, arrival_time=0.0)
    lad.update(core, Grant(now=1.5))
    assert doomed.state is RequestState.FINISHED_EXPIRED
    assert safe.state is RequestState.WAITING
    assert core.obs.metrics.counter("fault/shed/online").value == 1
    # plan downshift: spec off and k shrunk to the smallest bucket
    from repro.serving.core import StepPlan
    plan = StepPlan(k=8, gamma=4, cost_steps=40.0)
    lad.apply(core, Grant(now=1.5), plan)
    assert plan.gamma is None
    assert plan.k == 1 and plan.cost_steps == pytest.approx(1.0)


def test_ladder_in_step_loop_recovers_service():
    """Integration: with the ladder installed, a burst beyond capacity
    sheds down to the keep-depth but every surviving request completes."""
    core = _ladder_core(n_offline=16)
    n = 0
    while core.has_unfinished:
        core.step(Grant(now=float(n)))
        n += 1
        assert n < 200
    states = [cr.state for q in core.waiting.values() for cr in q]
    assert not states  # nothing stranded
    m = core.obs.metrics
    done = m.counter("core/finished/offline").value
    shed = m.counter("fault/shed/offline").value
    assert done == 16 and shed > 0  # shed requests still FINISH (expired)
    assert m.counter("core/finish_reason/expired").value == shed
    assert m.counter("core/finish_reason/length").value == 16 - shed
    assert m.counter("fault/ladder_escalations").value >= 3


def _burst_metrics(ladder, step_s=0.002):
    """An OFFLINE backlog plus a burst of deadline-bearing ONLINE arrivals
    at 10x the slot concurrency, on a virtual clock (one microstep ==
    2 ms), with preemption off so the ladder is the only mitigation.
    Returns the metrics registry."""
    vnow = [0.0]
    core = EngineCore(_engine(paged=True, clock=lambda: vnow[0]),
                      policy=PriorityPolicy(preemption=False))
    if ladder:
        core.ladder = OverloadLadder(LadderConfig(
            high_queue_depth=6, low_queue_depth=2, up_dwell=2,
            down_dwell=6, offline_keep_depth=2, online_slack_s=0.05,
        ))
    rng = np.random.default_rng(0)
    for _ in range(10):
        core.submit(rng.integers(0, CFG.vocab_size, 8),
                    SamplingParams(max_new_tokens=32),
                    priority=Priority.OFFLINE, arrival_time=0.0)
    for t in 0.05 + np.cumsum(rng.exponential(0.002, 20)):
        core.submit(rng.integers(0, CFG.vocab_size, 8),
                    SamplingParams(max_new_tokens=4, deadline_s=0.25),
                    priority=Priority.ONLINE, arrival_time=float(t))
    while core.has_unfinished:
        base = vnow[0]
        out = core.step(Grant(
            now=base, token_budget=16,
            advance_clock=lambda steps, b=base: vnow.__setitem__(
                0, b + steps * step_s),
        ))
        if out.cost_steps == 0 and not out.admitted:
            vnow[0] += step_s  # idle until the next arrival
    return core.obs.metrics


def test_ladder_does_not_worsen_online_p95_under_burst():
    """Same burst with and without the overload ladder: the ladder sheds,
    and served-online p95 is no worse.  Exact on the virtual clock."""
    with_ladder = _burst_metrics(ladder=True)
    without = _burst_metrics(ladder=False)
    shed = (with_ladder.counter("fault/shed/offline").value
            + with_ladder.counter("fault/shed/online").value)
    assert shed > 0
    p95 = with_ladder.histogram("core/online_latency_s").percentile(95)
    p95_without = without.histogram("core/online_latency_s").percentile(95)
    assert p95 <= p95_without


# ---------------------------------------------------------------------------
# Runtime early-resume (training comes back before the predicted bubble end)
# ---------------------------------------------------------------------------


def test_runtime_early_resume_bounded_overrun():
    from repro.core import SpecInFRuntime
    from repro.core.profiles import dp_profile
    from repro.serving.engine import Request

    def make(faults):
        eng = _engine(paged=True)
        for _ in range(2):
            eng.add_request(Request(prompt=np.arange(8), max_new_tokens=1000))
        return SpecInFRuntime(
            train_step=lambda s, b: (s, {}),
            train_state=None,
            batch_iter=iter(lambda: {}, None),
            profile=dp_profile("tiny", compute_s=0.02, comm_s=0.04),
            engine=eng,
            cfg=SpecInFConfig(),
            decode_microstep_s=0.004,
            faults=faults,
        )

    inj = FaultInjector(seed=4, specs=(
        FaultSpec("runtime/early_resume", probability=1.0, max_fires=1),
    ))
    rt = make(inj)
    rt.run(num_iterations=4)
    assert inj.total_fires == 1
    m = rt.engine.obs.metrics
    assert m.counter("fault/early_resume").value == 1
    assert m.counter("fault/revocations").value >= 0  # boundary trips are ok
    h = m.histogram("fault/revocation_overrun_s")
    assert h.count == 1
    # yield bound on the virtual clock: at most one sub-dispatch of
    # ``revoke_check_steps`` (=1) microsteps past the resume instant
    assert max(h.values()) <= rt.decode_microstep_s * 3 + 1e-9
    assert rt.monitor.interrupts == 1
    # training still ran to completion and the run stayed deterministic
    assert rt.metrics.train_iterations == 4
    # per dp_profile iteration: compute_s + exposed comm (overlap 0.3)
    assert rt.metrics.virtual_time_s == pytest.approx(
        4 * (0.02 + 0.04 * 0.7)
    )

    # reproducibility: the same seed fires the same schedule
    inj2 = FaultInjector(seed=4, specs=(
        FaultSpec("runtime/early_resume", probability=1.0, max_fires=1),
    ))
    rt2 = make(inj2)
    rt2.run(num_iterations=4)
    h2 = rt2.engine.obs.metrics.histogram("fault/revocation_overrun_s")
    assert h2.values() == h.values()


@pytest.mark.parametrize("resumes", [0, 2], ids=["fault_free", "early_resume"])
def test_collocated_training_holds_the_no_serving_baseline(resumes):
    """The training guarantee on the virtual clock: with bubbles filled,
    fault-free or with training resuming early (revocation makes serving
    pay for the overrun), training's virtual time is no longer than the
    no-serving baseline of compute plus exposed communication."""
    import itertools

    from repro.core import SpecInFRuntime
    from repro.core.profiles import dp_profile

    iterations, compute_s, comm_s = 6, 0.02, 0.04
    eng = _engine(paged=True)
    for _ in range(2):
        eng.core.submit(np.arange(8), SamplingParams(max_new_tokens=10**9),
                        priority=Priority.OFFLINE, arrival_time=0.0)
    inj = FaultInjector(seed=4, specs=(
        FaultSpec("runtime/early_resume", probability=1.0,
                  max_fires=resumes),
    )) if resumes else None
    rt = SpecInFRuntime(
        train_step=lambda s, b: (s, {"loss": 0.0}), train_state={},
        batch_iter=itertools.repeat({}),
        profile=dp_profile("tiny", compute_s=compute_s, comm_s=comm_s),
        engine=eng, cfg=SpecInFConfig(), decode_microstep_s=0.004,
        faults=inj,
    )
    metrics = rt.run(num_iterations=iterations)
    assert eng.obs.metrics.counter("fault/early_resume").value == resumes
    assert eng.generated_tokens_total > 0  # the bubbles were filled
    # dp_profile exposes comm_s * (1 - overlap) per iteration (overlap 0.3)
    baseline_s = iterations * (compute_s + comm_s * 0.7)
    assert metrics.virtual_time_s <= baseline_s + 1e-12
