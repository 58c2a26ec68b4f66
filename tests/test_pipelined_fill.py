"""One quantum in flight (DESIGN.md §6): ``EngineCore.step_overlapped``
dispatches quantum n+1's device work before it collects quantum n.

* streams: the overlapped order serves byte-identical streams to the
  synchronous ``step()`` order — dense and paged, XLA and Pallas (interpret
  mode on the CPU) — through a scenario with a request finishing by length
  inside the quantum in flight, prompts completing their prefill in it, an
  online admission that preempts, and an abort of a request in flight;
* the packed readback: quantum n's outputs are read after quantum n+1 has
  donated the cache that quantum n's loop returned, so they must be a
  fresh output of the loop (the cache's index is not);
* ``engine/overlapped_readbacks`` counts the decode readbacks collected
  after the next dispatch;
* the speculative loop, a revocable grant, an attached fault injector or
  journal take the synchronous order;
* ``SpecInFRuntime.run`` returns with no quantum in flight.
"""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.configs.base import SpecDecodeConfig, SpecInFConfig, draft_config
from repro.core import SpecInFRuntime
from repro.core.profiles import dp_profile
from repro.models import transformer as T
from repro.resilience.faults import FaultInjector
from repro.resilience.journal import RequestJournal
from repro.serving.core import (
    Grant,
    Priority,
    RequestState,
    RevocationSignal,
    SamplingParams,
)
from repro.serving.engine import InferenceEngine

CFG = configs.smoke_config("qwen3-1.7b")
PARAMS = T.init_params(CFG, jax.random.PRNGKey(0))
DCFG = draft_config(CFG)
DPARAMS = T.init_params(DCFG, jax.random.PRNGKey(1))
STEP_S = 0.002


def _engine(paged=True, impl="xla", **kw):
    vnow = [0.0]
    kw.setdefault("max_slots", 3)
    eng = InferenceEngine(
        CFG, PARAMS, max_seq=96, compute_dtype=jnp.float32,
        decode_impl=impl, prefill_chunk=8,
        kv_page_size=None if paged else 0, clock=lambda: vnow[0], **kw,
    )
    return eng, vnow


def _grant(vnow, **kw):
    """A fill's grant: the quantum's priced cost moves the virtual clock to
    ``now + cost`` before its device work runs."""
    base = vnow[0]
    return Grant(
        now=base, max_cost_steps=2, token_budget=10,
        advance_clock=lambda steps: vnow.__setitem__(0, base + steps * STEP_S),
        **kw,
    )


def _submit(core, prompt_len, max_new, seed, priority=Priority.OFFLINE):
    prompt = np.random.default_rng(seed).integers(1, CFG.vocab_size, prompt_len)
    return core.submit(prompt.astype(np.int32),
                       SamplingParams(max_new_tokens=max_new), priority=priority)


def _scenario(overlapped, paged, impl):
    """Offline requests of mixed lengths on three slots, an abort of a
    request in flight before any slot has turned over (so both orders have
    dispatched the same quanta by then), and an online arrival that
    preempts once every slot is busy.  Returns the streams in submission
    order and what the overlapped run saw in flight."""
    eng, vnow = _engine(paged, impl)
    core = eng.core
    reqs = [_submit(core, 5, 14, 1), _submit(core, 20, 9, 2),
            _submit(core, 13, 4, 3), _submit(core, 30, 5, 4),
            _submit(core, 9, 12, 5)]
    seen = {"finished_in_flight": 0, "landed_in_flight": 0, "aborted_in_flight": 0,
            "preempted": 0}
    for n in itertools.count():
        assert n < 400, "the scenario made no progress"
        if n == 3:
            victim = reqs[0]
            assert victim.state is RequestState.RUNNING
            if overlapped:
                seen["aborted_in_flight"] += any(
                    victim._internal in q.readback.reqs for q in core._in_flight
                )
            core.abort(victim)
        if n == 9:
            reqs.append(_submit(core, 11, 5, 6, Priority.ONLINE))
        if not core.has_unfinished:
            break
        before = {cr.request_id for cr in reqs if cr.state.finished}
        if overlapped:
            out = core.step_overlapped(_grant(vnow))
        else:
            out = core.step(_grant(vnow))
        seen["preempted"] += len(out.preempted)
        if core._in_flight:
            seen["landed_in_flight"] += any(q.readback.first
                                            for q in core._in_flight)
            seen["finished_in_flight"] += sum(
                1 for cr in reqs
                if cr.state.finished and cr.request_id not in before
                and cr.finish_reason == "length"
            )
        if not out.k and not out.prefill_tokens:
            vnow[0] += STEP_S  # an empty quantum: the fill moves on a window
    assert eng.num_active == 0
    if paged:
        assert eng.pool.reserved == 0
    streams = [(cr.output_tokens, cr.finish_reason) for cr in reqs]
    return streams, seen, core


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_overlapped_streams_equal_the_synchronous_order(paged, impl):
    sync, _, _ = _scenario(False, paged, impl)
    ovl, seen, core = _scenario(True, paged, impl)
    assert ovl == sync
    reasons = [r for _, r in sync]
    assert reasons.count("abort") == 1 and reasons.count("length") == 5
    # the situations the order has to get right all happened in flight
    assert seen["finished_in_flight"] > 0
    assert seen["landed_in_flight"] > 0
    assert seen["aborted_in_flight"] == 1
    assert seen["preempted"] > 0
    assert core.obs.metrics.counter("engine/overlapped_readbacks").value > 0


def test_readback_outlives_the_cache_the_next_loop_donates(monkeypatch):
    """Quantum n's decode outputs are fetched after quantum n+1's loop has
    consumed (donated) the cache that quantum n's loop returned: the packed
    readback is a fresh array, never a leaf of that cache."""
    eng, vnow = _engine(paged=True)
    core = eng.core
    _submit(core, 6, 12, 7)
    _submit(core, 5, 12, 8)
    fetched = []
    fetch = type(eng)._fetch

    def spy(self, tree):
        leaves = jax.tree.leaves(tree)
        assert not any(a.is_deleted() for a in leaves)
        fetched.append(leaves)
        return fetch(self, tree)

    monkeypatch.setattr(type(eng), "_fetch", spy)
    while not core.slot_requests or any(
            eng.slot_prefilling(s) for s in core.slot_requests):
        core.step(_grant(vnow))
    core.step_overlapped(_grant(vnow))  # quantum n: a decode loop in flight
    rb = core._in_flight[-1].readback
    assert rb.packed is not None
    cache_n = dict(eng.cache)  # what quantum n's loop returned
    n_fetches = len(fetched)
    core.step_overlapped(_grant(vnow))  # dispatch n+1, then collect n
    assert cache_n["index"].is_deleted(), "the next loop donates the cache"
    assert len(fetched) == n_fetches + 1
    assert any(a is rb.packed for a in fetched[-1])
    assert not any(a is leaf for a in fetched[-1]
                   for leaf in jax.tree.leaves(cache_n))
    core.drain()


def test_overlapped_readbacks_count_decode_quanta_collected_late():
    """A decode readback counts once it is collected by a call that had
    dispatched the next quantum's device work first; the last one, which
    nothing follows, counts nothing."""
    eng, vnow = _engine(paged=True, max_slots=2)
    core = eng.core
    _submit(core, 10, 9, 9)
    _submit(core, 7, 4, 10)
    counter = core.obs.metrics.counter("engine/overlapped_readbacks")
    outs, expect = [], 0
    while core.has_unfinished:
        waiting = [o for o in outs if o.k and not o.collected]
        out = core.step_overlapped(_grant(vnow))
        outs.append(out)
        if out.k or out.prefill_tokens:
            expect += sum(1 for o in waiting if o.collected)
        else:
            vnow[0] += STEP_S
        assert counter.value == expect
        assert len(outs) < 100
    decode = [o for o in outs if o.k]
    assert expect == len(decode) - 1
    core.drain()
    assert all(o.collected for o in outs) and counter.value == expect


def _sync_case(kind, tmp_path):
    kw, grant_kw = {}, {}
    if kind == "spec":
        kw = dict(draft_cfg=DCFG, draft_params=DPARAMS,
                  spec=SpecDecodeConfig(mode="greedy"))
    elif kind == "fault_injector":
        kw = dict(fault_injector=FaultInjector(seed=0))
    elif kind == "revocable":
        grant_kw = dict(revocation=RevocationSignal())
    eng, vnow = _engine(paged=True, **kw)
    if kind == "journal":
        RequestJournal(str(tmp_path / "journal.jsonl")).attach(eng.core)
    return eng, vnow, grant_kw


@pytest.mark.parametrize(
    "kind", ["plain", "spec", "revocable", "fault_injector", "journal"]
)
def test_quanta_that_need_host_state_take_the_synchronous_order(kind, tmp_path):
    eng, vnow, grant_kw = _sync_case(kind, tmp_path)
    core = eng.core
    _submit(core, 5, 8, 11)
    outs = [core.step_overlapped(_grant(vnow, **grant_kw)) for _ in range(4)]
    overlapped = core.obs.metrics.counter("engine/overlapped_readbacks").value
    if kind == "plain":
        # the control: the order engages where nothing needs host state
        assert core._in_flight and not outs[-1].collected
        assert overlapped > 0
    else:
        assert not core._in_flight and all(o.collected for o in outs)
        assert overlapped == 0


def test_runtime_run_returns_with_no_quantum_in_flight():
    eng, _ = _engine(paged=True)
    rt = SpecInFRuntime(
        train_step=lambda s, b: (s, {"loss": 0.0}), train_state=None,
        batch_iter=itertools.repeat({}),
        profile=dp_profile("tiny", compute_s=0.03, comm_s=0.06),
        engine=eng, cfg=SpecInFConfig(), decode_microstep_s=STEP_S,
    )
    core = rt.core
    reqs = [_submit(core, 6 + 3 * i, 40, 20 + i) for i in range(4)]
    for _ in range(3):
        rt.run(1)
        assert not core._in_flight and not rt._uncollected
        # every token the device made is on the host
        assert all(cr._internal is None or cr._internal.pending == 0
                   for cr in reqs)
    assert core.obs.metrics.counter("engine/overlapped_readbacks").value > 0
    assert rt.metrics.offline_microsteps > 0
    made = sum(len(cr.output_tokens) for cr in reqs)
    assert made == core.obs.metrics.counter(
        "core/generated_tokens/offline").value
