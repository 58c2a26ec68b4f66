"""End-to-end behaviour of the paper's system over REAL JAX compute:
the SpecInF runtime collocating a real training loop with a real
continuous-batching inference engine, plus the beyond-paper fused step."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.configs.base import SpecInFConfig, TrainConfig
from repro.core import SpecInFRuntime, make_collocated_step, pick_bucket
from repro.core.profiles import dp_profile
from repro.data.pipeline import SyntheticDataset
from repro.models import transformer as T
from repro.optim import adamw_init, adamw_update, make_schedule
from repro.serving.engine import InferenceEngine, Request


@pytest.fixture(scope="module")
def tiny():
    cfg = configs.smoke_config("olmo-1b")
    key = jax.random.PRNGKey(0)
    params = T.init_params(cfg, key)
    return cfg, params


def _make_train(cfg, params):
    tcfg = TrainConfig(learning_rate=1e-2, warmup_steps=2, total_steps=50)
    sched = make_schedule(tcfg)
    state = {"params": params, "opt": adamw_init(params)}

    @jax.jit
    def step(state, batch):
        def loss_fn(p):
            loss, m = T.lm_loss(cfg, p, batch["inputs"], batch["labels"])
            return loss, m

        (loss, m), g = jax.value_and_grad(loss_fn, has_aux=True)(state["params"])
        g = jax.tree.map(lambda x: x.astype(jnp.float32), g)
        new_p, new_opt = adamw_update(
            g, state["opt"], state["params"], lr=sched(state["opt"]["step"]),
            cfg=tcfg,
        )
        return {"params": new_p, "opt": new_opt}, {"loss": loss}

    ds = SyntheticDataset(cfg=cfg, seq_len=32, global_batch=4)

    def batches():
        while True:
            b = ds.next_batch()
            yield {"inputs": jnp.asarray(b["inputs"]),
                   "labels": jnp.asarray(b["labels"])}

    return step, state, batches()


def test_runtime_trains_and_fills_offline(tiny):
    cfg, params = tiny
    step, state, batches = _make_train(cfg, params)
    engine = InferenceEngine(cfg, params, max_slots=2, max_seq=48)
    for _ in range(2):
        engine.add_request(Request(prompt=np.arange(8), max_new_tokens=1000))
    profile = dp_profile("tiny", compute_s=0.05, comm_s=0.03)
    rt = SpecInFRuntime(
        train_step=step, train_state=state, batch_iter=batches,
        profile=profile, engine=engine, cfg=SpecInFConfig(),
        decode_microstep_s=0.004,
    )
    metrics = rt.run(num_iterations=8)
    assert metrics.train_iterations == 8
    assert metrics.offline_microsteps > 0, "bubbles must admit offline work"
    assert metrics.offline_tokens_generated > 0
    # training made progress (loss finite and generally decreasing)
    assert np.isfinite(metrics.train_losses).all()
    assert metrics.train_losses[-1] < metrics.train_losses[0] + 0.1
    # Algorithm 1 visited all three phases
    assert set(metrics.phase_counts) >= {"conservative", "stable"}


def test_runtime_serves_online_within_bubbles(tiny):
    cfg, params = tiny
    step, state, batches = _make_train(cfg, params)
    engine = InferenceEngine(cfg, params, max_slots=2, max_seq=32)
    reqs = [
        Request(prompt=np.arange(4), max_new_tokens=3, arrival_time=0.02 * i,
                online=True)
        for i in range(6)
    ]
    profile = dp_profile("tiny", compute_s=0.04, comm_s=0.05)
    rt = SpecInFRuntime(
        train_step=step, train_state=state, batch_iter=batches,
        profile=profile, engine=engine, online_requests=reqs,
        cfg=SpecInFConfig(busy_hold_ms=5.0), decode_microstep_s=0.002,
    )
    metrics = rt.run(num_iterations=14)
    assert metrics.online_served >= 3
    assert np.isfinite(metrics.p95_latency_s())
    # TTFT is recorded per served online request (arrival -> first token)
    # and can never exceed the end-to-end latency it is a prefix of
    assert len(metrics.online_ttft_s) >= metrics.online_served
    assert np.isfinite(metrics.p95_ttft_s())
    assert all(t >= 0.0 for t in metrics.online_ttft_s)


def test_preempted_legacy_offline_resumes_on_virtual_clock(tiny):
    """Regression: a request admitted via the legacy shim BEFORE the
    runtime exists is stamped on the wall clock; after an online arrival
    preempts it, re-admission is gated on the virtual clock — the runtime
    must restamp RUNNING slots to the virtual epoch or the offline request
    starves forever."""
    import itertools

    cfg, params = tiny
    engine = InferenceEngine(cfg, params, max_slots=1, max_seq=64)
    off = Request(prompt=np.arange(8), max_new_tokens=12)
    assert engine.add_request(off)  # wall-clock arrival stamp
    rt = SpecInFRuntime(
        train_step=lambda s, b: (s, {"loss": 0.0}), train_state=None,
        batch_iter=itertools.repeat({}),
        profile=dp_profile("tiny", compute_s=0.03, comm_s=0.06),
        engine=engine,
        online_requests=[Request(prompt=np.arange(4), max_new_tokens=2,
                                 arrival_time=0.01, online=True)],
        cfg=SpecInFConfig(busy_hold_ms=5.0), decode_microstep_s=0.002,
    )
    metrics = rt.run(num_iterations=25)
    assert metrics.online_served == 1
    assert metrics.preemptions >= 1, "online must preempt the lone slot"
    cr_off = engine.core.requests[off.request_id]
    assert cr_off.state.finished, "preempted offline request starved"
    assert len(cr_off.output_tokens) == 12
    assert not engine.core.has_unfinished


def test_fused_collocated_step_preserves_training(tiny):
    """Beyond-paper fused program: train result must be bit-identical to the
    unfused train step, and the decode chain must advance the cache."""
    cfg, params = tiny
    tcfg = TrainConfig(learning_rate=1e-2)

    def train_step(state, batch):
        def loss_fn(p):
            loss, _ = T.lm_loss(cfg, p, batch["inputs"], batch["labels"])
            return loss

        loss, g = jax.value_and_grad(loss_fn)(state["params"])
        g = jax.tree.map(lambda x: x.astype(jnp.float32), g)
        new_p, new_opt = adamw_update(
            g, state["opt"], state["params"], lr=0.01, cfg=tcfg
        )
        return {"params": new_p, "opt": new_opt}, {"loss": loss}

    def decode_fn(p, tokens, cache):
        return T.decode_step(cfg, p, tokens, cache)

    fused = make_collocated_step(train_step, decode_fn, k_buckets=(0, 2))

    ds = SyntheticDataset(cfg=cfg, seq_len=32, global_batch=4)
    b = ds.next_batch()
    batch = {"inputs": jnp.asarray(b["inputs"]), "labels": jnp.asarray(b["labels"])}
    state = {"params": params, "opt": adamw_init(params)}

    cache = T.init_cache(cfg, 2, 32)
    tokens = jnp.array([1, 2], jnp.int32)

    ref_state, ref_m = jax.jit(train_step)(
        jax.tree.map(jnp.copy, state), batch
    )
    new_state, m, toks0, cache0 = fused[0](
        jax.tree.map(jnp.copy, state), batch, params, tokens,
        jax.tree.map(jnp.copy, cache),  # cache arg is donated by the jit
    )
    new_state2, m2, toks2, cache2 = fused[2](
        jax.tree.map(jnp.copy, state), batch, params, tokens,
        jax.tree.map(jnp.copy, cache),
    )
    # training result identical regardless of collocated decode volume
    np.testing.assert_allclose(float(ref_m["loss"]), float(m2["loss"]), rtol=1e-6)
    for a, b2 in zip(
        jax.tree.leaves(new_state["params"]), jax.tree.leaves(new_state2["params"])
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b2), atol=1e-6)
    # k=0 leaves tokens untouched; k=2 advanced the cache index by 2
    assert int(cache0["index"]) == 0
    assert int(cache2["index"]) == 2
    assert toks2.shape == (2,)


def test_pick_bucket_respects_token_grant():
    assert pick_bucket(0.0, 1.0) == 0
    assert pick_bucket(3.0, 1.0) == 2
    assert pick_bucket(8.0, 1.0) == 8
    assert pick_bucket(7.9, 1.0) == 4
    assert pick_bucket(100.0, 12.0) == 8


def test_engine_continuous_batching(tiny):
    cfg, params = tiny
    engine = InferenceEngine(cfg, params, max_slots=2, max_seq=32)
    r1 = Request(prompt=np.arange(4), max_new_tokens=2)
    r2 = Request(prompt=np.arange(6), max_new_tokens=5)
    assert engine.add_request(r1) and engine.add_request(r2)
    assert engine.num_active == 2
    done = []
    for _ in range(8):
        done += engine.decode_loop(1)
        if engine.num_active == 0:
            break
    done_ids = {r.request_id for r in done}
    assert r1.request_id in done_ids and r2.request_id in done_ids
    assert len(r1.generated) >= 2 and len(r2.generated) >= 5
    # freed slots accept new work (slot reuse)
    r3 = Request(prompt=np.arange(3), max_new_tokens=1)
    assert engine.add_request(r3)
    assert engine.num_active == 1
