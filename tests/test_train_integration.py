"""Integration tests for the production train step builder on a 1x1 dev
mesh: loss descent, microbatch equivalence, fault-tolerant resume, and the
Trainer's bounded retry of failing steps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.checkpoint.checkpointer import Checkpointer
from repro.configs.base import TrainConfig
from repro.data.pipeline import SyntheticDataset
from repro.launch.mesh import make_dev_mesh
from repro.models.act_sharding import activation_sharding, shard
from repro.runtime.step import make_train_step
from repro.runtime.trainer import Trainer


@pytest.fixture(scope="module")
def mesh():
    return make_dev_mesh(data=1, model=1)


def _batch(ds):
    b = ds.next_batch()
    return {"inputs": jnp.asarray(b["inputs"]), "labels": jnp.asarray(b["labels"])}


def test_loss_decreases_over_steps(mesh):
    cfg = configs.smoke_config("qwen3-1.7b")
    tcfg = TrainConfig(
        learning_rate=3e-3, warmup_steps=5, total_steps=60, microbatches=1,
        fsdp=False, zero1=False, remat_policy="dots",
    )
    art = make_train_step(cfg, tcfg, mesh)
    step = art.jitted(donate=False)
    state = art.init_state(jax.random.PRNGKey(0))
    ds = SyntheticDataset(cfg=cfg, seq_len=32, global_batch=8)
    losses = []
    for _ in range(30):
        state, m = step(state, _batch(ds))
        losses.append(float(m["loss"]))
    assert np.isfinite(losses).all()
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.2, losses


def test_microbatching_matches_single_batch(mesh):
    """Gradient accumulation must be numerically equivalent (same data)."""
    cfg = configs.smoke_config("olmo-1b")
    ds = SyntheticDataset(cfg=cfg, seq_len=32, global_batch=8)
    batch = _batch(ds)
    outs = {}
    for n_micro in (1, 4):
        tcfg = TrainConfig(
            learning_rate=1e-2, microbatches=n_micro, fsdp=False, zero1=False,
            compute_dtype="float32",
        )
        art = make_train_step(cfg, tcfg, mesh)
        state = art.init_state(jax.random.PRNGKey(1))
        new_state, m = art.jitted(donate=False)(state, batch)
        outs[n_micro] = (new_state, m)
    p1 = jax.tree.leaves(outs[1][0]["params"])
    p4 = jax.tree.leaves(outs[4][0]["params"])
    for a, b in zip(p1, p4):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)
    assert float(outs[1][1]["loss"]) == pytest.approx(
        float(outs[4][1]["loss"]), rel=1e-3
    )


def test_moe_and_ssm_train_steps(mesh):
    for arch in ("moonshot-v1-16b-a3b", "falcon-mamba-7b", "zamba2-2.7b"):
        cfg = configs.smoke_config(arch)
        tcfg = TrainConfig(microbatches=2, fsdp=False, zero1=False)
        art = make_train_step(cfg, tcfg, mesh)
        state = art.init_state(jax.random.PRNGKey(2))
        ds = SyntheticDataset(cfg=cfg, seq_len=32, global_batch=4)
        state, m = art.jitted(donate=False)(state, _batch(ds))
        assert np.isfinite(float(m["loss"])), arch


def test_checkpoint_resume_reproduces_trajectory(mesh, tmp_path):
    """Fault-tolerance: kill after step k, restore, and the continued
    trajectory must equal the uninterrupted one (data stream included)."""
    cfg = configs.smoke_config("olmo-1b")
    tcfg = TrainConfig(learning_rate=1e-3, microbatches=1, fsdp=False,
                       zero1=False, compute_dtype="float32")
    art = make_train_step(cfg, tcfg, mesh)
    step = art.jitted(donate=False)

    def run(n, state, ds):
        ms = []
        for _ in range(n):
            state, m = step(state, _batch(ds))
            ms.append(float(m["loss"]))
        return state, ms

    # uninterrupted 6 steps
    ds = SyntheticDataset(cfg=cfg, seq_len=16, global_batch=4)
    ref_state, ref_losses = run(6, art.init_state(jax.random.PRNGKey(3)), ds)

    # interrupted at 3, checkpoint, "crash", restore, continue
    ds2 = SyntheticDataset(cfg=cfg, seq_len=16, global_batch=4)
    state, _ = run(3, art.init_state(jax.random.PRNGKey(3)), ds2)
    ck = Checkpointer(str(tmp_path))
    ck.save(3, state)
    del state
    template = jax.eval_shape(lambda: art.init_state(jax.random.PRNGKey(3)))
    template = jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype), template
    )
    restored, step_no = ck.restore(template)
    assert step_no == 3
    ds3 = SyntheticDataset(cfg=cfg, seq_len=16, global_batch=4, _step=3)
    _, resumed_losses = run(3, restored, ds3)
    np.testing.assert_allclose(resumed_losses, ref_losses[3:], rtol=1e-5)


def test_grad_compression_state_threads_through(mesh):
    cfg = configs.smoke_config("olmo-1b")
    tcfg = TrainConfig(microbatches=1, fsdp=False, zero1=False,
                       grad_compression="int8_ef")
    art = make_train_step(cfg, tcfg, mesh)
    state = art.init_state(jax.random.PRNGKey(0))
    state["err"] = jax.tree.map(
        lambda p: jnp.zeros(p.shape, jnp.float32), state["params"]
    )
    ds = SyntheticDataset(cfg=cfg, seq_len=16, global_batch=4)
    new_state, m = art.jitted(donate=False)(state, _batch(ds))
    assert "err" in new_state
    err_norm = sum(float(jnp.abs(e).sum()) for e in jax.tree.leaves(new_state["err"]))
    assert err_norm > 0  # quantization residual captured
    assert np.isfinite(float(m["loss"]))


def test_activation_constraint_under_jit_on_dev_mesh(mesh):
    """Regression: ``make_dev_mesh`` builds Auto axes, so an activation
    constraint traced under jit resolves instead of raising "can only refer
    to Auto axes" (the default axis type of ``jax.make_mesh`` is Explicit)."""
    from jax.sharding import PartitionSpec as P

    @jax.jit
    def f(x):
        with activation_sharding(mesh, {"btd": P("data", None, "model")}):
            return shard(x, "btd") * 2.0

    np.testing.assert_array_equal(np.asarray(f(jnp.ones((2, 4, 8)))), 2.0)


def test_train_step_refuses_forward_only_flash_kernel(mesh):
    cfg = configs.smoke_config("olmo-1b")
    with pytest.raises(ValueError, match="forward only"):
        make_train_step(cfg, TrainConfig(fsdp=False), mesh, impl="pallas")


def test_train_step_auto_attention_is_xla_where_forward_auto_is_the_kernel(
    mesh, monkeypatch
):
    """On a TPU ``ops.attention(impl="auto")`` picks the forward-only flash
    kernel; the train step's "auto" must pick the XLA family instead."""
    from repro.kernels import ops

    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    q = jnp.zeros((1, 8, 2, 16))
    fwd = jax.make_jaxpr(lambda q: ops.attention(q, q, q))(q)
    assert "pallas_call" in str(fwd)

    cfg = configs.smoke_config("olmo-1b")
    art = make_train_step(cfg, TrainConfig(fsdp=False, zero1=False), mesh)
    state = art.init_state(jax.random.PRNGKey(0))
    batch = _batch(SyntheticDataset(cfg=cfg, seq_len=16, global_batch=4))
    assert "pallas_call" not in str(jax.make_jaxpr(art.step)(state, batch))


def test_collocated_runtime_serves_the_trainer_params(mesh):
    """``launch/train.py --collocate``: the engine serves the trainer's
    initial params cast once to its bf16 compute dtype, in buffers of its
    own, and filling leaves the training trajectory finite."""
    from repro.launch.train import collocated_runtime
    from repro.models import transformer as T

    cfg = configs.smoke_config("qwen3-1.7b")
    tcfg = TrainConfig(fsdp=False, zero1=False)
    trainer = Trainer(cfg, tcfg, mesh, seq_len=32, global_batch=4)
    rt = collocated_runtime(cfg, trainer, max_seq=32)
    given = trainer.state["params"]
    want = T.cast_params(given, jnp.bfloat16)
    served = rt.engine.params
    assert jax.tree.structure(served) == jax.tree.structure(want)
    for s, w in zip(jax.tree.leaves(served), jax.tree.leaves(want)):
        assert s.dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(s, np.float32),
                                      np.asarray(w, np.float32))
    trainer_buffers = {x.unsafe_buffer_pointer()
                       for x in jax.tree.leaves(given)}
    assert not any(x.unsafe_buffer_pointer() in trainer_buffers
                   for x in jax.tree.leaves(served))
    metrics = rt.run(2)
    assert metrics.train_iterations == 2
    assert np.isfinite(metrics.train_losses).all()
    # freed before each train step, made again by each iteration's fill
    casts = rt.engine.obs.metrics.counter("engine/serving_param_casts")
    assert casts.value == 1 + metrics.train_iterations


def _trainer(mesh, tmp_path):
    cfg = configs.smoke_config("olmo-1b")
    tcfg = TrainConfig(learning_rate=1e-3, microbatches=1, fsdp=False,
                       zero1=False)
    return Trainer(cfg, tcfg, mesh, seq_len=16, global_batch=4,
                   checkpoint_dir=str(tmp_path), checkpoint_every=2)


def test_trainer_resumes_after_one_failed_step(mesh, tmp_path):
    trainer = _trainer(mesh, tmp_path)
    failed = []

    def fail_once_at_3(step_no):
        if step_no == 3 and not failed:
            failed.append(step_no)
            return True
        return False

    trainer.fail_hook = fail_once_at_3
    report = trainer.train(5)
    assert failed == [3]
    assert trainer.step_no == 5
    assert report.restores == 1
    assert np.isfinite(report.losses).all()


def test_trainer_reraises_a_step_that_always_fails(mesh, tmp_path):
    """A step that fails on every fresh or restored state (a compile error,
    a device OOM) ends the run after a bounded number of attempts."""
    trainer = _trainer(mesh, tmp_path)
    attempts = []

    def always(step_no):
        attempts.append(step_no)
        return True

    trainer.fail_hook = always
    with pytest.raises(RuntimeError, match="injected failure"):
        trainer.train(3)
    assert len(attempts) == Trainer.MAX_FAILURES_WITHOUT_PROGRESS
    assert trainer.report.restores == Trainer.MAX_FAILURES_WITHOUT_PROGRESS - 1
    assert trainer.report.steps == 0


def test_trainer_reraises_a_step_that_always_fails_after_a_checkpoint(
    mesh, tmp_path
):
    """Steps between the restored checkpoint and the failing step succeed on
    every retry; that is no progress, so the run still ends."""
    trainer = _trainer(mesh, tmp_path)  # checkpoints every 2 steps
    attempts = []

    def always_at_3(step_no):
        attempts.append(step_no)
        if len(attempts) > 30:  # BaseException: escapes the retry loop
            pytest.fail("the trainer kept retrying the failing step")
        return step_no == 3

    trainer.fail_hook = always_at_3
    with pytest.raises(RuntimeError, match="injected failure @ step 3"):
        trainer.train(6)
    assert attempts.count(3) == Trainer.MAX_FAILURES_WITHOUT_PROGRESS
    assert trainer.report.restores == Trainer.MAX_FAILURES_WITHOUT_PROGRESS - 1
    assert trainer.report.steps > 3  # steps before 3 were re-run
