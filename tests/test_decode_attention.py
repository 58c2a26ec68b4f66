"""Flash-decode kernel validation + sync-free engine equivalence.

The Pallas decode-attention kernel (interpret mode on CPU) is asserted
against the length-masked XLA reference across GQA ratios, ragged per-slot
lengths, and empty (length=0) slots; the engine's fused ``decode_loop(k)``
must produce exactly the tokens of k sequential ``decode_loop(1)`` calls.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.kernels import ops
from repro.kernels.decode_attention import decode_attention
from repro.models import transformer as T
from repro.serving.engine import InferenceEngine, Request


def _inputs(b, h, kvh, s, hd, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, h, hd), dtype)
    k = jax.random.normal(ks[1], (b, s, kvh, hd), dtype)
    v = jax.random.normal(ks[2], (b, s, kvh, hd), dtype)
    return q, k, v


def _ref(q, k, v, lengths):
    """Length-masked dense decode attention (the seed path)."""
    b, h, hd = q.shape
    s, kvh = k.shape[1], k.shape[2]
    reps = h // kvh
    kk = jnp.repeat(k, reps, axis=2)
    vv = jnp.repeat(v, reps, axis=2)
    scores = jnp.einsum("bhd,bshd->bhs", q, kk).astype(jnp.float32) * hd**-0.5
    mask = jnp.arange(s)[None, None, :] < lengths[:, None, None]
    scores = jnp.where(mask, scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    p = jnp.where(lengths[:, None, None] > 0, p, 0.0)
    return jnp.einsum("bhs,bshd->bhd", p, vv.astype(jnp.float32)).astype(q.dtype)


@pytest.mark.parametrize(
    "b,h,kvh,s,hd,block_k",
    [
        (4, 4, 2, 64, 16, 16),     # GQA 2:1, several kv tiles
        (2, 4, 4, 128, 32, 128),   # MHA, single tile
        (3, 8, 2, 96, 16, 32),     # GQA 4:1, ragged tile count
        (2, 4, 1, 80, 16, 32),     # MQA, non-multiple-of-block length
        (1, 2, 2, 48, 64, 64),     # block_k > s (clamped)
    ],
)
def test_decode_kernel_matches_reference(b, h, kvh, s, hd, block_k):
    q, k, v = _inputs(b, h, kvh, s, hd)
    # ragged lengths incl. boundary cases: empty, single, mid, full
    lengths = jnp.asarray(([0, 1, s // 3, s] * b)[:b], jnp.int32)
    out = decode_attention(q, k, v, lengths, block_k=block_k, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(_ref(q, k, v, lengths)), rtol=2e-5, atol=2e-5
    )


def test_decode_kernel_empty_slot_is_zero():
    q, k, v = _inputs(2, 4, 2, 32, 16)
    lengths = jnp.array([0, 7], jnp.int32)
    out = decode_attention(q, k, v, lengths, block_k=16, interpret=True)
    assert np.all(np.asarray(out[0]) == 0.0)
    assert np.all(np.isfinite(np.asarray(out)))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_kernel_dtypes(dtype):
    q, k, v = _inputs(2, 4, 2, 64, 32, dtype=dtype)
    lengths = jnp.array([5, 64], jnp.int32)
    out = decode_attention(q, k, v, lengths, block_k=32, interpret=True)
    assert out.dtype == dtype
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32),
        np.asarray(_ref(q, k, v, lengths), np.float32),
        rtol=tol, atol=tol,
    )


def test_ops_dispatch_pallas_equals_xla():
    q, k, v = _inputs(3, 4, 2, 64, 16, seed=3)
    lengths = jnp.array([0, 11, 64], jnp.int32)
    out_x = ops.decode_attention(q, k, v, lengths, impl="xla")
    out_p = ops.decode_attention(q, k, v, lengths, impl="pallas")
    np.testing.assert_allclose(
        np.asarray(out_p), np.asarray(out_x), rtol=2e-5, atol=2e-5
    )


def test_attention_decode_layer_uses_fast_path():
    """layers.attention_decode with the pallas core == xla core (same cache
    updates, same outputs) across per-slot ragged indices."""
    from repro.models import layers as L

    cfg = configs.smoke_config("qwen3-1.7b")  # GQA arch
    p = L.init_attention(cfg, jax.random.PRNGKey(0), cfg.d_model, jnp.float32)
    b, s_max = 3, 32
    hd, kvh = cfg.resolved_head_dim, cfg.num_kv_heads
    x = jax.random.normal(jax.random.PRNGKey(1), (b, 1, cfg.d_model))
    kc = jax.random.normal(jax.random.PRNGKey(2), (b, s_max, kvh, hd))
    vc = jax.random.normal(jax.random.PRNGKey(3), (b, s_max, kvh, hd))
    idx = jnp.array([0, 5, 31], jnp.int32)
    y_x, (k_x, v_x) = L.attention_decode(cfg, p, x, (kc, vc), idx, impl="xla")
    y_p, (k_p, v_p) = L.attention_decode(
        cfg, p, x, (kc, vc), idx, impl="pallas"
    )
    np.testing.assert_allclose(np.asarray(y_p), np.asarray(y_x),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(k_p), np.asarray(k_x))
    np.testing.assert_allclose(np.asarray(v_p), np.asarray(v_x))


# ---------------------------------------------------------------------------
# Engine: fused loop == sequential one-step loops
# ---------------------------------------------------------------------------


def _engine_with_requests(cfg, params, prompts, max_new, **kw):
    engine = InferenceEngine(cfg, params, max_slots=3, max_seq=32, **kw)
    reqs = [
        Request(prompt=np.asarray(p), max_new_tokens=m)
        for p, m in zip(prompts, max_new)
    ]
    for r in reqs:
        assert engine.add_request(r)
    return engine, reqs


@pytest.mark.parametrize("arch", ["qwen3-1.7b", "falcon-mamba-7b"])
def test_decode_loop_equals_sequential_microsteps(arch):
    cfg = configs.smoke_config(arch)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    prompts = [np.arange(4), np.arange(9), np.arange(2)]
    max_new = [3, 8, 5]  # ragged budgets: slots finish mid-loop

    e1, r1 = _engine_with_requests(cfg, params, prompts, max_new)
    e2, r2 = _engine_with_requests(cfg, params, prompts, max_new)

    k = 6
    fin_seq = []
    for _ in range(k):
        fin_seq += e1.decode_loop(1)
    fin_fused = e2.decode_loop(k)

    for a, b in zip(r1, r2):
        # both freeze a slot exactly at its budget, so the streams match
        # token for token, not just as a prefix
        assert b.generated == a.generated, (
            f"fused tokens diverge for prompt len {len(a.prompt)}"
        )
        assert len(b.generated) <= b.max_new_tokens
    done_seq = {i for i, r in enumerate(r1) if any(r is f for f in fin_seq)}
    done_fused = {i for i, r in enumerate(r2) if any(r is f for f in fin_fused)}
    assert done_seq == done_fused and done_fused
    # exactly one device->host transfer for the whole fused loop
    d2h_before = e2.d2h_transfers
    e2.decode_loop(2)
    assert e2.d2h_transfers - d2h_before <= 1


def test_decode_loop_freezes_finished_slots():
    cfg = configs.smoke_config("olmo-1b")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    engine, (short, long) = _engine_with_requests(
        cfg, params, [np.arange(4), np.arange(4)], [2, 10]
    )
    finished = engine.decode_loop(8)
    finished_ids = {id(r) for r in finished}
    assert id(short) in finished_ids
    assert len(short.generated) == 2  # froze at its budget mid-loop
    assert id(long) not in finished_ids and len(long.generated) == 9
    # freed slot accepts a new request (prefill_into_slot refills the cache)
    again = Request(prompt=np.arange(5), max_new_tokens=2)
    assert engine.add_request(again)
    engine.decode_loop(2)
    assert len(again.generated) == 2


def test_prefill_bucketing_bounds_compiles():
    cfg = configs.smoke_config("olmo-1b")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    engine = InferenceEngine(cfg, params, max_slots=1, max_seq=64)
    for n in (3, 5, 7, 8, 9, 15, 17, 30):
        engine.slots = [None]
        engine.add_request(Request(prompt=np.arange(n), max_new_tokens=1))
    # 8 distinct lengths -> buckets {8, 16, 32}
    assert engine.prefill_compile_count <= 3


@pytest.mark.parametrize("arch", ["falcon-mamba-7b", "zamba2-2.7b"])
def test_bucketed_prefill_exact_for_ssm_state(arch):
    """The dt-masked padded prefill must leave the recurrent SSM/conv state
    exactly where the real tokens left it: prefill-logits identical AND the
    subsequent decode trajectory identical to an unpadded prefill."""
    cfg = configs.smoke_config(arch)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (1, 6), 0, cfg.vocab_size
    )
    logits_r, cache_r = T.prefill(cfg, params, tokens, 32)
    padded = jnp.zeros((1, 16), jnp.int32).at[:, :6].set(tokens)
    logits_p, cache_p = T.prefill(cfg, params, padded, 32, length=jnp.int32(6))
    np.testing.assert_array_equal(np.asarray(logits_r), np.asarray(logits_p))
    # observable-state check: four decode steps stay bit-identical
    tok_r = tok_p = jnp.argmax(logits_r, -1).astype(jnp.int32)
    for _ in range(4):
        l_r, cache_r = T.decode_step(cfg, params, tok_r, cache_r)
        l_p, cache_p = T.decode_step(cfg, params, tok_p, cache_p)
        np.testing.assert_array_equal(np.asarray(l_r), np.asarray(l_p))
        tok_r = jnp.argmax(l_r, -1).astype(jnp.int32)
        tok_p = jnp.argmax(l_p, -1).astype(jnp.int32)


def test_arrival_time_stamped_from_engine_clock():
    """Default (epoch-zero) arrivals are stamped from the engine clock at
    admission; explicit arrival times are preserved — latency metrics never
    mix an epoch-zero arrival with a monotonic/virtual now."""
    cfg = configs.smoke_config("olmo-1b")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    now = [123.0]
    engine = InferenceEngine(
        cfg, params, max_slots=2, max_seq=32, clock=lambda: now[0]
    )
    default_req = Request(prompt=np.arange(3), max_new_tokens=2)
    explicit_req = Request(prompt=np.arange(3), max_new_tokens=2,
                           arrival_time=120.5)
    assert engine.add_request(default_req)
    assert engine.add_request(explicit_req)
    assert default_req.arrival_time == 123.0
    assert explicit_req.arrival_time == 120.5
    now[0] = 125.0
    while engine.num_active:
        engine.decode_loop(2)
    assert default_req.finish_time - default_req.arrival_time == 2.0
    assert explicit_req.finish_time - explicit_req.arrival_time == 4.5
    # an ONLINE epoch-zero arrival is a real instant on a virtual clock:
    # it must survive admission so queueing delay stays in the latency
    online_req = Request(prompt=np.arange(3), max_new_tokens=1,
                         arrival_time=0.0, online=True)
    assert engine.add_request(online_req)
    assert online_req.arrival_time == 0.0


def test_add_request_rejects_overlong_prompt():
    cfg = configs.smoke_config("olmo-1b")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    engine = InferenceEngine(cfg, params, max_slots=1, max_seq=16)
    with pytest.raises(ValueError, match="max_seq"):
        engine.add_request(Request(prompt=np.arange(17), max_new_tokens=1))


def test_bucketed_prefill_token_matches_unpadded():
    """The first generated token must be identical whether the prompt is
    prefilled exactly or padded to its bucket."""
    cfg = configs.smoke_config("qwen3-1.7b")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    prompt = np.arange(5)
    logits, _ = T.prefill(
        cfg, params, jnp.asarray(prompt, jnp.int32)[None, :], 64
    )
    expect = int(jnp.argmax(logits[0]))
    engine = InferenceEngine(cfg, params, max_slots=1, max_seq=64)
    req = Request(prompt=prompt, max_new_tokens=4)
    engine.add_request(req)
    assert req.generated[0] == expect
