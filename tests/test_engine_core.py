"""EngineCore request lifecycle (DESIGN.md §6): state machine, priority
preemption, preempt->resume byte-identity (dense + paged, spec on/off),
abort resource release, stop tokens, streaming, and the deprecated-shim
equivalence sweep."""
import jax
import numpy as np
import pytest

from repro import configs
from repro.configs.base import SpecDecodeConfig, draft_config
from repro.models import transformer as T
from repro.serving.core import (
    EngineCore,
    Grant,
    Priority,
    PriorityPolicy,
    RequestState,
    SamplingParams,
)
from repro.serving.engine import InferenceEngine, Request

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


def _drain(core, limit=200):
    n = 0
    while core.has_unfinished:
        core.step()
        n += 1
        assert n < limit, "core.step() made no progress"


# ---------------------------------------------------------------------------
# Lifecycle basics
# ---------------------------------------------------------------------------


def test_lifecycle_waiting_running_finished():
    core = _engine().core
    r = core.submit(np.arange(6), SamplingParams(max_new_tokens=3))
    assert r.state is RequestState.WAITING and core.num_waiting == 1
    out = core.step()
    assert r.request_id in out.admitted
    # prefill produced the first token in the same quantum
    deltas = {o.request_id: o for o in out.outputs}
    assert len(deltas[r.request_id].new_tokens) >= 1
    assert deltas[r.request_id].ttft_s is not None  # stamped exactly once
    _drain(core)
    assert r.state is RequestState.FINISHED_LENGTH
    assert r.finish_reason == "length"
    assert len(r.output_tokens) == 3
    assert r.first_token_time is not None and r.finish_time is not None


def test_submit_rejects_structurally_impossible():
    core = _engine(max_seq=32).core
    with pytest.raises(ValueError):
        core.submit(np.arange(64), SamplingParams(max_new_tokens=1))


def test_ttft_reported_exactly_once():
    core = _engine().core
    r = core.submit(np.arange(4), SamplingParams(max_new_tokens=6))
    stamps = []
    n = 0
    while core.has_unfinished:
        out = core.step()
        stamps += [o.ttft_s for o in out.outputs
                   if o.request_id == r.request_id and o.ttft_s is not None]
        n += 1
        assert n < 50
    assert len(stamps) == 1 and stamps[0] >= 0.0


def test_stream_yields_full_sequence():
    core = _engine().core
    r = core.submit(np.arange(5), SamplingParams(max_new_tokens=4))
    toks = list(core.stream(r))
    assert toks == r.output_tokens and len(toks) == 4
    assert r.state.finished


def test_stop_token_finishes_early_and_frees_slot():
    core = _engine().core
    probe = core.submit(np.arange(5), SamplingParams(max_new_tokens=8))
    _drain(core)
    assert len(probe.output_tokens) == 8
    stop = probe.output_tokens[3]
    first = probe.output_tokens.index(stop)  # may repeat earlier
    r = core.submit(
        np.arange(5), SamplingParams(max_new_tokens=8, stop_token_ids=(stop,))
    )
    _drain(core)
    assert r.state is RequestState.FINISHED_STOPPED
    assert r.finish_reason == "stop"
    # trimmed at (and including) the first stop-token occurrence
    assert r.output_tokens == probe.output_tokens[: first + 1]
    assert core.engine.num_active == 0  # slot released despite early stop


# ---------------------------------------------------------------------------
# Preemption / resume
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
@pytest.mark.parametrize("spec", [False, True], ids=["plain", "spec"])
def test_preempt_resume_byte_identical(paged, spec):
    """A preempted-then-resumed greedy stream must be byte-identical to an
    uninterrupted run: resume re-prefills prompt+generated (paged engines
    recover the prompt pages from the radix cache) and greedy decode is
    deterministic."""

    def run(preempt_at):
        core = _engine(paged=paged, spec=spec).core
        r = core.submit(np.arange(20), SamplingParams(max_new_tokens=24))
        n = 0
        while not r.state.finished:
            core.step()
            n += 1
            if n == preempt_at and not r.state.finished:
                assert core.preempt(r) is r
                assert r.state is RequestState.PREEMPTED
            assert n < 100
        return list(r.output_tokens), r

    base, _ = run(preempt_at=10**9)
    resumed, req = run(preempt_at=1)
    assert req.preemptions == 1
    assert resumed == base and len(base) == 24


def test_preempt_releases_pages_and_resume_hits_prefix():
    eng = _engine(paged=True)
    core = eng.core
    r = core.submit(np.arange(32), SamplingParams(max_new_tokens=16))
    core.step()
    slot = core.slot_of(r)
    held = len(eng._slot_pages[slot])
    assert held >= 2
    in_use = eng.pool.pages_in_use
    skipped0 = eng.prefill_skipped_tokens
    core.preempt(r)
    # only the radix-cached prompt pages survive the eviction
    assert eng.pool.pages_in_use < in_use
    assert eng.pool.reserved == 0
    assert eng.prefix_cache.evictable_pages() > 0
    _drain(core)
    # resume recomputed via the prefix hit: prefill compute was skipped
    assert eng.prefill_skipped_tokens > skipped0
    assert r.state is RequestState.FINISHED_LENGTH


def test_online_preempts_offline_and_offline_resumes():
    """The paper's protection story: an ONLINE arrival claims capacity from
    a RUNNING OFFLINE slot instead of queueing behind it, and the offline
    stream is unchanged by the round-trip."""
    eng = _engine(max_slots=1)
    core = eng.core
    off = core.submit(np.arange(8), SamplingParams(max_new_tokens=20),
                      priority=Priority.OFFLINE)
    core.step()
    assert off.state is RequestState.RUNNING
    on = core.submit(np.arange(5), SamplingParams(max_new_tokens=4),
                     priority=Priority.ONLINE)
    out = core.step()
    assert off.request_id in out.preempted
    assert on.request_id in out.admitted
    _drain(core)
    assert on.finish_time <= off.finish_time
    assert off.preemptions == 1 and off.state is RequestState.FINISHED_LENGTH

    ref = _engine(max_slots=1).core
    ref_off = ref.submit(np.arange(8), SamplingParams(max_new_tokens=20))
    _drain(ref)
    assert off.output_tokens == ref_off.output_tokens


def test_no_preemption_policy_queues_online():
    eng = _engine(max_slots=1)
    core = EngineCore(eng, policy=PriorityPolicy(preemption=False))
    off = core.submit(np.arange(8), SamplingParams(max_new_tokens=6),
                      priority=Priority.OFFLINE)
    core.step()
    on = core.submit(np.arange(5), SamplingParams(max_new_tokens=2),
                     priority=Priority.ONLINE)
    out = core.step()
    assert not out.preempted and on.state is RequestState.WAITING
    _drain(core)
    assert off.preemptions == 0
    assert on.state.finished and off.state.finished


def _mixed_load_online_p95(preemption, step_s=0.002):
    """Two long OFFLINE requests plus ten short ONLINE arrivals on a virtual
    clock (one microstep == 2 ms); returns (online latency p95,
    preemptions)."""
    vnow = [0.0]
    eng = _engine(max_slots=2, clock=lambda: vnow[0])
    core = EngineCore(eng, policy=PriorityPolicy(preemption=preemption))
    rng = np.random.default_rng(0)
    for _ in range(2):
        core.submit(rng.integers(0, CFG.vocab_size, 8),
                    SamplingParams(max_new_tokens=48),
                    priority=Priority.OFFLINE, arrival_time=0.0)
    for t in np.cumsum(rng.exponential(0.02, 10)):
        core.submit(rng.integers(0, CFG.vocab_size, 8),
                    SamplingParams(max_new_tokens=4),
                    priority=Priority.ONLINE, arrival_time=float(t))
    while core.has_unfinished:
        out = core.step(Grant(
            now=vnow[0],
            advance_clock=lambda steps: vnow.__setitem__(
                0, vnow[0] + steps * step_s),
        ))
        if out.cost_steps == 0 and not out.admitted:
            vnow[0] += step_s  # idle until the next arrival
    assert all(r.state.finished for r in core.requests.values())
    p95 = eng.obs.metrics.histogram("core/online_latency_s").percentile(95)
    return p95, core.preemption_count


def test_preemption_does_not_worsen_online_p95_under_mixed_load():
    """Same arrivals, prompts and budgets; only the policy's preemption
    switch differs.  Exact on the virtual clock."""
    p95, n_preempt = _mixed_load_online_p95(preemption=True)
    p95_queued, n_queued = _mixed_load_online_p95(preemption=False)
    assert n_preempt > 0 and n_queued == 0  # the workload does preempt
    assert p95 <= p95_queued


# ---------------------------------------------------------------------------
# Abort
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("spec", [False, True], ids=["plain", "spec"])
def test_abort_mid_decode_releases_pages_and_draft_state(spec):
    eng = _engine(paged=True, spec=spec)
    core = eng.core
    a = core.submit(np.arange(24), SamplingParams(max_new_tokens=30))
    b = core.submit(np.arange(24, 48), SamplingParams(max_new_tokens=30))
    core.step()
    slot = core.slot_of(a)
    assert a.state is RequestState.RUNNING and slot is not None
    in_use = eng.pool.pages_in_use
    reserved = eng.pool.reserved
    core.abort(a)
    assert a.state is RequestState.FINISHED_ABORTED
    assert a.finish_reason == "abort"
    assert eng.pool.pages_in_use < in_use, "abort must release pages"
    assert eng.pool.reserved < reserved, "abort must release reservations"
    assert eng.slots[slot] is None
    assert int(eng.cache["index"][slot]) == 0
    if spec:
        assert int(eng.draft_cache["index"][slot]) == 0, (
            "mid-decode abort left draft-cache state behind"
        )
    # the freed slot admits new work, and survivors run to completion
    c = core.submit(np.arange(5), SamplingParams(max_new_tokens=2))
    _drain(core)
    assert b.state.finished and c.state.finished
    assert len(a.output_tokens) < 30  # aborted mid-decode


def test_abort_waiting_request_never_runs():
    core = _engine(max_slots=1).core
    a = core.submit(np.arange(4), SamplingParams(max_new_tokens=4))
    b = core.submit(np.arange(4), SamplingParams(max_new_tokens=4))
    core.abort(b)
    assert b.state is RequestState.FINISHED_ABORTED and core.num_waiting == 1
    _drain(core)
    assert a.state.finished and b.output_tokens == []


# ---------------------------------------------------------------------------
# Shim-vs-core equivalence sweep
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
@pytest.mark.parametrize("spec", [False, True], ids=["plain", "spec"])
def test_shim_vs_core_equivalence(paged, spec):
    """The deprecated add_request/decode_loop surface and the
    submit()/step() lifecycle must produce identical token streams for the
    same workload — the shim really is a thin delegate."""
    prompts = [np.arange(4), np.arange(7, 19), np.arange(30, 36)]
    budgets = [3, 9, 6]

    eng_a = _engine(paged=paged, spec=spec, max_slots=3)
    legacy = [Request(prompt=p, max_new_tokens=m)
              for p, m in zip(prompts, budgets)]
    for r in legacy:
        assert eng_a.add_request(r)
    for _ in range(20):
        if spec:
            eng_a.spec_decode_loop(2, 2)
        else:
            eng_a.decode_loop(4)
        if eng_a.num_active == 0:
            break
    assert eng_a.num_active == 0

    eng_b = _engine(paged=paged, spec=spec, max_slots=3)
    core = eng_b.core
    reqs = [core.submit(p, SamplingParams(max_new_tokens=m))
            for p, m in zip(prompts, budgets)]
    _drain(core)

    for lr, cr in zip(legacy, reqs):
        assert [int(t) for t in lr.generated] == cr.output_tokens
    # the shim registers its requests in the same lifecycle
    for lr in legacy:
        assert eng_a.core.requests[lr.request_id].state.finished


# ---------------------------------------------------------------------------
# Grants
# ---------------------------------------------------------------------------


def test_grant_gates_online_admission():
    core = _engine().core
    r = core.submit(np.arange(4), SamplingParams(max_new_tokens=2),
                    priority=Priority.ONLINE)
    out = core.step(Grant(online_ok=False))
    assert not out.admitted and r.state is RequestState.WAITING
    out = core.step(Grant(online_ok=True))
    assert r.request_id in out.admitted


def test_grant_advance_clock_stamps_quantum_end():
    vnow = [0.0]
    eng = _engine(clock=lambda: vnow[0])
    core = eng.core
    r = core.submit(np.arange(4), SamplingParams(max_new_tokens=3),
                    arrival_time=0.0)
    n = 0
    while core.has_unfinished:
        core.step(Grant(
            now=vnow[0],
            advance_clock=lambda steps: vnow.__setitem__(
                0, vnow[0] + steps * 0.002),
        ))
        n += 1
        assert n < 20
    assert r.finish_time == pytest.approx(vnow[0])
    assert r.first_token_time is not None
    assert r.first_token_time <= r.finish_time
