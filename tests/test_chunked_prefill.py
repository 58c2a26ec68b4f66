"""Chunked prefill unified with decode (DESIGN.md §7).

Four layers of validation:

* kernel: the ragged prefill-attention kernel (dense + paged, XLA fallback
  AND Pallas interpret mode, the ``test_paged_kv`` CI pattern) equals an
  independent masked-softmax reference on random ragged chunks;
* engine: chunked-prefill greedy streams are byte-identical to monolithic
  prefill across dense/paged × spec on/off, with chunk boundaries landing
  mid-page and at page edges, and the program zoo pinned to one fixed-width
  compile per model;
* core: token-budgeted steps stream prompts through WAITING -> PREFILLING
  -> RUNNING without ever exceeding the granted mixed-batch budget, and
  produce the same stream as a permissive run;
* lifecycle: preempt during PREFILLING resumes byte-identically.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.configs.base import draft_config
from repro.kernels import ops
from repro.models import transformer as T
from repro.serving.core import (
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
DPARAMS = T.init_params(DCFG, jax.random.PRNGKey(5))


# ---------------------------------------------------------------------------
# Kernel: ragged chunk attention == independent reference
# ---------------------------------------------------------------------------


def _reference(q, k, v, starts, lens):
    """Masked-softmax reference: row t attends kpos <= starts + t, rows
    past chunk_lens are zeros."""
    b, c, h, hd = q.shape
    s, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    kk = jnp.broadcast_to(
        k[:, :, :, None], (b, s, kvh, g, hd)
    ).reshape(b, s, h, hd)
    vv = jnp.broadcast_to(
        v[:, :, :, None], (b, s, kvh, g, hd)
    ).reshape(b, s, h, hd)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, kk).astype(jnp.float32)
    scores = scores * hd**-0.5
    kpos = jnp.arange(s)
    bound = starts[:, None] + jnp.arange(c)[None, :]
    valid = jnp.arange(c)[None, :] < lens[:, None]
    mask = (kpos[None, None, :] <= bound[:, :, None]) & valid[:, :, None]
    scores = jnp.where(mask[:, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, vv)
    return jnp.where(valid[:, :, None, None], out, 0.0)


def _paged_from_dense(k, v, page, rng):
    b, s, kvh, hd = k.shape
    npages = s // page
    pool_n = 1 + b * npages
    perm = rng.permutation(np.arange(1, pool_n))
    bt = perm.reshape(b, npages)
    k_pool = np.zeros((pool_n, page, kvh, hd), np.float32)
    v_pool = np.zeros((pool_n, page, kvh, hd), np.float32)
    for i in range(b):
        for j in range(npages):
            k_pool[bt[i, j]] = np.asarray(k[i, j * page:(j + 1) * page])
            v_pool[bt[i, j]] = np.asarray(v[i, j * page:(j + 1) * page])
    bt = np.concatenate([bt, np.zeros((b, 1), np.int64)], axis=1)
    return (jnp.asarray(k_pool), jnp.asarray(v_pool),
            jnp.asarray(bt, jnp.int32))


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_prefill_chunk_matches_reference(impl):
    """Property (seeded sweep): ragged chunked-prefill attention equals the
    reference on random starts / chunk lengths, including empty chunks,
    single-token chunks, and chunks wider than the remaining prefix."""
    geoms = [(4, 2, 16), (4, 4, 16), (2, 1, 32)]
    for seed in range(10):
        rng = np.random.RandomState(2000 + seed)
        h, kvh, hd = geoms[seed % len(geoms)]
        b = rng.randint(1, 4)
        c = int(rng.choice([8, 16, 24, 40]))
        s = int(rng.choice([64, 96, 128]))
        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        q = jax.random.normal(ks[0], (b, c, h, hd), jnp.float32)
        k = jax.random.normal(ks[1], (b, s, kvh, hd), jnp.float32)
        v = jax.random.normal(ks[2], (b, s, kvh, hd), jnp.float32)
        picks = [0, 1, c, c - 1]
        lens = jnp.asarray(
            [picks[rng.randint(0, 4)] if rng.rand() < 0.5
             else rng.randint(0, c + 1) for _ in range(b)], jnp.int32)
        starts = jnp.asarray(
            [rng.randint(0, s - c + 1) for _ in range(b)], jnp.int32)
        ref = _reference(q, k, v, starts, lens)
        out = ops.prefill_chunk_attention(q, k, v, starts, lens, impl=impl)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5,
            err_msg=f"seed={seed} b={b} c={c} s={s} "
                    f"starts={np.asarray(starts)} lens={np.asarray(lens)}",
        )


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_paged_prefill_chunk_matches_dense(impl):
    """Property (seeded sweep): the block-table prefill kernel equals the
    dense one under randomly-permuted physical page placement."""
    geoms = [(4, 2, 16), (8, 2, 32), (2, 1, 16)]
    for seed in range(8):
        rng = np.random.RandomState(3000 + seed)
        h, kvh, hd = geoms[seed % len(geoms)]
        b = rng.randint(1, 4)
        c = int(rng.choice([8, 16, 24]))
        page = int(rng.choice([8, 16]))
        s = page * rng.randint(3, 7)
        if s < c:
            s = page * (-(-c // page) + 1)
        ks = jax.random.split(jax.random.PRNGKey(100 + seed), 3)
        q = jax.random.normal(ks[0], (b, c, h, hd), jnp.float32)
        k = jax.random.normal(ks[1], (b, s, kvh, hd), jnp.float32)
        v = jax.random.normal(ks[2], (b, s, kvh, hd), jnp.float32)
        lens = jnp.asarray([rng.randint(0, c + 1) for _ in range(b)],
                           jnp.int32)
        starts = jnp.asarray([rng.randint(0, s - c + 1) for _ in range(b)],
                             jnp.int32)
        k_pool, v_pool, bt = _paged_from_dense(k, v, page, rng)
        ref = ops.prefill_chunk_attention(q, k, v, starts, lens, impl="xla")
        out = ops.paged_prefill_chunk_attention(
            q, k_pool, v_pool, bt, starts, lens, impl=impl
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5,
            err_msg=f"seed={seed} b={b} c={c} page={page} s={s} "
                    f"starts={np.asarray(starts)} lens={np.asarray(lens)}",
        )


# ---------------------------------------------------------------------------
# Engine: chunked streams == monolithic streams
# ---------------------------------------------------------------------------


def _drain(engine, k=4, guard=200):
    while engine.num_active and guard:
        engine.decode_loop(k)
        guard -= 1
    assert engine.num_active == 0


#: ragged prompts; 33 crosses two pages, 47 ends mid-page, one request
#: hits the sequence horizon
CASES = [(5, 12), (17, 7), (33, 20), (47, 9)]


def _run_engine(paged, chunk, spec=False, cases=CASES):
    kw: dict = {"kv_page_size": None if paged else 0, "prefill_chunk": chunk}
    if spec:
        kw.update(draft_cfg=DCFG, draft_params=DPARAMS,
                  compute_dtype=jnp.float32)
    eng = InferenceEngine(CFG, PARAMS, max_slots=4, max_seq=64, **kw)
    reqs = [Request(prompt=np.arange(1, n + 1), max_new_tokens=m)
            for n, m in cases]
    for r in reqs:
        assert eng.add_request(r)
    if spec:
        guard = 100
        while eng.num_active and guard:
            eng.spec_decode_loop(2, 2)
            guard -= 1
        assert eng.num_active == 0
    else:
        _drain(eng)
    return [r.generated for r in reqs], eng


@pytest.mark.parametrize("spec", [False, True], ids=["plain", "spec"])
@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
@pytest.mark.parametrize("chunk", [8, 16, 24])
def test_chunked_stream_byte_identical_to_monolithic(paged, spec, chunk):
    """The acceptance property: greedy streams are byte-identical whether
    the prompt prefilled monolithically or streamed in chunks — across
    dense/paged layouts, spec on/off, and chunk widths that land on page
    edges (16), mid-page (24 with page 16), and below a page (8)."""
    mono, _ = _run_engine(paged, 0, spec)
    chunked, eng = _run_engine(paged, chunk, spec)
    assert chunked == mono
    counts = eng.prefill_compile_counts()
    assert counts["target/chunk"] == 1
    assert "target/bucket" not in counts  # the bucket zoo is gone
    if spec:
        assert counts["draft/chunk"] == 1
        assert "draft/bucket" not in counts  # one wave, no per-req dispatch


def test_chunked_prefix_hit_skips_and_matches():
    """Prefix sharing composes with chunking: the radix-covered prefix is
    skipped (zero prefill FLOPs, counter-verified) and the stream equals
    both the cold chunked run and a monolithic engine's."""
    eng = InferenceEngine(CFG, PARAMS, max_slots=2, max_seq=64,
                          prefill_chunk=8)
    prompt = np.arange(1, 40)
    cold = Request(prompt=prompt, max_new_tokens=10)
    assert eng.add_request(cold)
    _drain(eng)
    assert eng.prefill_skipped_tokens == 0
    assert eng.prefix_cache.pages_cached == 2
    warm = Request(prompt=prompt, max_new_tokens=10)
    assert eng.add_request(warm)
    assert eng.prefill_skipped_tokens == 32
    _drain(eng)
    assert warm.generated == cold.generated
    mono, _ = _run_engine(True, 0, cases=[(39, 10)])
    assert cold.generated == mono[0]


def test_chunked_pool_accounting_clean_after_drain():
    """Pages, reservations, and radix refcounts settle exactly as the
    monolithic path's: nothing leaks across chunk waves or completions."""
    _, eng = _run_engine(True, 8)
    assert eng.pool.pages_in_use == eng.prefix_cache.pages_cached
    assert eng.pool.reserved == 0
    _, eng = _run_engine(True, 16, spec=True)
    assert eng.pool.pages_in_use == eng.prefix_cache.pages_cached
    assert eng.pool.reserved == 0


# ---------------------------------------------------------------------------
# Core: token-budgeted streaming through PREFILLING
# ---------------------------------------------------------------------------


def _core_engine(**kw):
    kw.setdefault("prefill_chunk", 8)
    eng = InferenceEngine(CFG, PARAMS, max_slots=2, max_seq=128, **kw)
    return eng, eng.core


def test_budgeted_steps_stream_prefilling_state():
    """A token budget below the prompt length forces PREFILLING to span
    steps; the final stream equals the permissive run and no step's mixed
    batch (prefill chunk tokens + generated tokens) exceeds the budget."""
    def run(budget):
        eng, core = _core_engine()
        r = core.submit(np.arange(1, 50), SamplingParams(max_new_tokens=6))
        states, max_step_tokens, steps = [], 0, 0
        while core.has_unfinished:
            g0 = eng.generated_tokens_total
            out = core.step(Grant(token_budget=budget))
            states.append(r.state)
            max_step_tokens = max(
                max_step_tokens,
                out.prefill_tokens + (eng.generated_tokens_total - g0),
            )
            steps += 1
            assert steps < 100, "budgeted stream stalled"
        return r.output_tokens, states, max_step_tokens

    toks_inf, states_inf, _ = run(math.inf)
    toks_b, states_b, max_tokens = run(16)
    assert toks_b == toks_inf
    assert max_tokens <= 16  # the grant is a hard mixed-batch ceiling
    assert RequestState.PREFILLING in states_b
    assert RequestState.PREFILLING not in states_inf  # one-quantum prefill


def test_prefill_cost_charges_the_virtual_clock():
    """With a profiled per-token cost, streaming a prompt advances the
    virtual clock in proportion to the tokens streamed — the bubble-
    deadline accounting SpecInFPolicy's grants rely on."""
    vnow = [0.0]
    eng, core = _core_engine(clock=lambda: vnow[0])
    core.policy = PriorityPolicy(prefill_token_cost_steps=0.125)
    r = core.submit(np.arange(1, 33), SamplingParams(max_new_tokens=1))
    out = core.step(Grant(
        token_budget=math.inf,
        advance_clock=lambda steps: vnow.__setitem__(0, vnow[0] + steps),
    ))
    # 32 prefill tokens * 0.125 steps/token = 4 steps of prefill cost,
    # plus the decode quantum the policy planned
    assert out.prefill_tokens == 32
    assert out.cost_steps >= 4.0
    assert vnow[0] == out.cost_steps
    assert r.state.finished or r.state is RequestState.RUNNING


def test_preempt_during_prefilling_resumes_byte_identical():
    """Eviction mid-PREFILLING drops the pending chunk streams; resume
    re-enters PREFILLING from the radix-covered prefix and the final
    stream is byte-identical to an uninterrupted run."""
    def run(preempt_at):
        eng, core = _core_engine()
        r = core.submit(np.arange(1, 50), SamplingParams(max_new_tokens=6))
        steps = 0
        preempted = 0
        while core.has_unfinished:
            core.step(Grant(token_budget=16))
            steps += 1
            if steps == preempt_at and r.state is RequestState.PREFILLING:
                assert core.preempt(r) is r
                assert r.state is RequestState.PREEMPTED
                preempted += 1
            assert steps < 120
        return r.output_tokens, preempted, r.preemptions

    base, _, _ = run(10**9)
    resumed, hit, count = run(2)
    assert hit == 1 and count == 1
    assert resumed == base


def test_abort_during_prefilling_releases_slot():
    eng, core = _core_engine()
    r = core.submit(np.arange(1, 50), SamplingParams(max_new_tokens=6))
    core.step(Grant(token_budget=8))
    assert r.state is RequestState.PREFILLING
    core.abort(r)
    assert r.state is RequestState.FINISHED_ABORTED
    assert eng.num_active == 0
    assert eng.pool.reserved == 0
    assert eng.num_prefilling == 0


def test_mixed_step_decodes_running_while_prefilling():
    """The unified step: a RUNNING slot keeps decoding in the same quanta
    that stream another slot's prompt chunks — and the decode stream is
    unaffected by the concurrent prefill traffic."""
    eng, core = _core_engine()
    short = core.submit(np.arange(1, 6), SamplingParams(max_new_tokens=12))
    core.step(Grant(token_budget=math.inf))  # short is RUNNING
    assert short.state is RequestState.RUNNING
    core.submit(np.arange(1, 49), SamplingParams(max_new_tokens=4))  # long
    saw_overlap = False
    steps = 0
    while core.has_unfinished:
        out = core.step(Grant(token_budget=12))
        if out.prefill_tokens and out.k:
            saw_overlap = True
        steps += 1
        assert steps < 200
    assert saw_overlap, "no step mixed prefill chunks with decode"
    # reference: the same short request alone, no concurrent prefill
    ref_eng = InferenceEngine(CFG, PARAMS, max_slots=2, max_seq=128,
                              prefill_chunk=8)
    ref = Request(prompt=np.arange(1, 6), max_new_tokens=12)
    assert ref_eng.add_request(ref)
    _drain(ref_eng)
    assert short.output_tokens == ref.generated


def test_spec_draft_index_survives_mixed_spec_steps():
    """Regression: the fused speculative loop pins frozen slots' draft
    index to their target index; mid-prefill the two streams differ, so a
    spec quantum running beside a PREFILLING slot must not corrupt its
    draft progress (the stream would silently diverge)."""
    eng = InferenceEngine(
        CFG, PARAMS, max_slots=2, max_seq=128, prefill_chunk=8,
        draft_cfg=DCFG, draft_params=DPARAMS, compute_dtype=jnp.float32,
    )
    core = eng.core
    short = core.submit(np.arange(1, 6), SamplingParams(max_new_tokens=10))
    core.step(Grant(token_budget=math.inf))
    long = core.submit(np.arange(1, 49), SamplingParams(max_new_tokens=4))
    steps = 0
    while core.has_unfinished:
        core.step(Grant(token_budget=10))
        steps += 1
        assert steps < 200
    # reference: same requests, monolithic spec engine, sequential
    ref_eng = InferenceEngine(
        CFG, PARAMS, max_slots=2, max_seq=128, prefill_chunk=0,
        draft_cfg=DCFG, draft_params=DPARAMS, compute_dtype=jnp.float32,
    )
    r1 = Request(prompt=np.arange(1, 6), max_new_tokens=10)
    r2 = Request(prompt=np.arange(1, 49), max_new_tokens=4)
    assert ref_eng.add_request(r1) and ref_eng.add_request(r2)
    guard = 100
    while ref_eng.num_active and guard:
        ref_eng.spec_decode_loop(2, 2)
        guard -= 1
    assert short.output_tokens == r1.generated
    assert long.output_tokens == r2.generated


# ---------------------------------------------------------------------------
# Core: chunked vs monolithic admission under mixed load
# ---------------------------------------------------------------------------

#: the token grant every quantum of the mixed-load runs gets
MIXED_BUDGET = 32


@pytest.fixture(scope="module")
def mixed_load():
    """Long-prompt OFFLINE churn (160-token prompts) beside short ONLINE
    arrivals on a virtual clock (one microstep == 2 ms, a 16-token chunk
    priced as one microstep), run twice: prompts streamed as budgeted
    chunks, and admitted as one monolithic prefill.  Same arrivals,
    prompts and grants; only ``prefill_chunk`` differs."""
    step_s = 0.002

    def run(chunk):
        vnow = [0.0]
        eng = InferenceEngine(CFG, PARAMS, max_slots=2, max_seq=256,
                              clock=lambda: vnow[0], prefill_chunk=chunk)
        core = eng.core
        core.policy = PriorityPolicy(prefill_token_cost_steps=1.0 / 16.0)
        rng = np.random.default_rng(0)
        for _ in range(6):
            core.submit(rng.integers(0, CFG.vocab_size, 160),
                        SamplingParams(max_new_tokens=12),
                        priority=Priority.OFFLINE, arrival_time=0.0)
        online = [
            core.submit(rng.integers(0, CFG.vocab_size, 8),
                        SamplingParams(max_new_tokens=4),
                        priority=Priority.ONLINE, arrival_time=float(t))
            for t in np.cumsum(rng.exponential(0.02, 12))
        ]
        max_step_tokens = 0
        while core.has_unfinished:
            g0 = eng.generated_tokens_total
            out = core.step(Grant(
                now=vnow[0], token_budget=MIXED_BUDGET,
                advance_clock=lambda steps: vnow.__setitem__(
                    0, vnow[0] + steps * step_s),
            ))
            max_step_tokens = max(
                max_step_tokens,
                out.prefill_tokens + (eng.generated_tokens_total - g0),
            )
            if out.cost_steps == 0 and not out.admitted:
                vnow[0] += step_s  # idle until the next arrival
        assert all(r.state.finished for r in online)
        return {
            "max_step_tokens": max_step_tokens,
            "ttft_p95": eng.obs.metrics.histogram(
                "core/online_ttft_s").percentile(95),
            "programs": eng.prefill_compile_count,
        }

    return {"chunked": run(None), "monolithic": run(0)}


@pytest.mark.parametrize("invariant", [
    "chunked_step_within_grant",
    "monolithic_step_overruns_grant",
    "chunked_ttft_p95_no_worse",
    "chunked_compiles_one_program",
])
def test_chunked_prefill_under_mixed_load(mixed_load, invariant):
    """Exact virtual-clock invariants of the unified token-budget step
    (DESIGN.md §7).  The monolithic overrun is the staleness guard: if it
    stops overrunning, the workload no longer tests the grant."""
    chunked, mono = mixed_load["chunked"], mixed_load["monolithic"]
    if invariant == "chunked_step_within_grant":
        assert chunked["max_step_tokens"] <= MIXED_BUDGET
    elif invariant == "monolithic_step_overruns_grant":
        assert mono["max_step_tokens"] > MIXED_BUDGET
    elif invariant == "chunked_ttft_p95_no_worse":
        assert chunked["ttft_p95"] <= mono["ttft_p95"]
    else:
        assert chunked["programs"] == 1
