"""The training step over a device mesh.

``make_train_step`` assembles the full training step (microbatched grad
accumulation -> global-norm clip -> AdamW with schedule) and returns it with
matching sharding trees, so callers (trainer, launcher, tests) never
re-derive specs by hand.

The ``abstract_*`` helpers are allocation-free: ShapeDtypeStructs via
``jax.eval_shape``, which the sharding tests feed to ``.lower()``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig, TrainConfig
from repro.models import transformer as T
from repro.models.act_sharding import activation_sharding
from repro.optim import adamw_init, adamw_update, clip_by_global_norm, make_schedule
from repro.runtime import sharding as S

Tree = Any


# ---------------------------------------------------------------------------
# Train step
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class TrainStepArtifacts:
    step: Callable[[Tree, Tree], tuple[Tree, Tree]]
    cfg: ModelConfig
    tcfg: TrainConfig
    mesh: Any
    state_specs: Tree
    batch_specs: Tree
    metric_specs: Tree

    # -- shardings (NamedSharding trees) ---------------------------------
    def state_shardings(self) -> Tree:
        return S.named(self.mesh, self.state_specs)

    def batch_shardings(self) -> Tree:
        return S.named(self.mesh, self.batch_specs)

    def jitted(self, donate: bool = True):
        return jax.jit(
            self.step,
            in_shardings=(self.state_shardings(), self.batch_shardings()),
            out_shardings=(
                self.state_shardings(),
                S.named(self.mesh, self.metric_specs),
            ),
            donate_argnums=(0,) if donate else (),
        )

    # -- abstract inputs for AOT lowering ---------------------------------
    def abstract_state(self) -> Tree:
        return abstract_train_state(self.cfg, self.tcfg)

    def abstract_batch(self, shape: ShapeConfig) -> Tree:
        return abstract_batch(self.cfg, shape)

    # -- real initialization ----------------------------------------------
    def init_state(self, key) -> Tree:
        params = T.init_params(self.cfg, key, jnp.dtype(self.tcfg.param_dtype))
        return {"params": params, "opt": adamw_init(params)}


def _microbatch_split(x: jax.Array, n_micro: int) -> jax.Array:
    """[B, ...] -> [n_micro, B/n_micro, ...] keeping the batch shards local:
    reshape peels the microbatch index off the *minor* position of the batch
    dim (each shard keeps contiguous rows), then moves it to axis 0."""
    b = x.shape[0]
    assert b % n_micro == 0, (b, n_micro)
    x = x.reshape(b // n_micro, n_micro, *x.shape[1:])
    return jnp.swapaxes(x, 0, 1)


def make_train_step(
    cfg: ModelConfig,
    tcfg: TrainConfig,
    mesh,
    *,
    impl: str = "auto",
) -> TrainStepArtifacts:
    """``impl`` is the attention path of the loss.  The Pallas flash kernel
    has no VJP, so "auto" means "xla_auto" here (the XLA family on every
    backend) and "pallas" is refused."""
    if impl == "pallas":
        raise ValueError(
            "the Pallas flash attention kernel is forward only (no VJP); "
            "train with impl='auto', 'xla_auto', 'xla' or 'xla_flash'"
        )
    if impl == "auto":
        impl = "xla_auto"
    schedule = make_schedule(tcfg)
    compute_dtype = jnp.dtype(tcfg.compute_dtype)
    n_micro = max(1, tcfg.microbatches)

    # Specs up front: the step body pins intermediate shardings with
    # with_sharding_constraint — without it GSPMD mis-propagates through the
    # microbatch reshape/swapaxes and replicates the batch over ``data``
    # (observed: 16x redundant compute on the dry-run HLO).
    state_abs = abstract_train_state(cfg, tcfg)
    param_sp = S.param_specs(
        cfg, state_abs["params"], mesh=mesh, fsdp=tcfg.fsdp, layout=tcfg.layout
    )
    dp = S.dp_axes(mesh, tcfg.layout)
    param_sh = S.named(mesh, param_sp)

    def _constrain_micro(x):
        spec = P(None, dp, *([None] * (x.ndim - 2)))
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

    act_specs = S.activation_specs(
        cfg, mesh, batch_sharded=True, layout=tcfg.layout
    )

    def loss_fn(params, inputs, labels):
        with activation_sharding(mesh, act_specs):
            return T.lm_loss(
                cfg,
                params,
                inputs,
                labels,
                impl=impl,
                remat_policy=tcfg.remat_policy,
                compute_dtype=compute_dtype,
            )

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def train_step(state, batch):
        params, opt = state["params"], state["opt"]
        inputs, labels = batch["inputs"], batch["labels"]

        # the phases run under name scopes, so a profile attributes each
        # operation to forward_backward, clip_grads or adamw_update
        with jax.named_scope("forward_backward"):
            if n_micro == 1:
                (loss, metrics), grads = grad_fn(params, inputs, labels)
                grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
            else:
                inputs_m = _constrain_micro(_microbatch_split(inputs, n_micro))
                labels_m = _constrain_micro(_microbatch_split(labels, n_micro))

                def micro(acc, xs):
                    inp, lab = xs
                    (l, m), g = grad_fn(params, inp, lab)
                    acc = jax.tree.map(
                        lambda a, gg: a + gg.astype(jnp.float32), acc, g
                    )
                    acc = jax.lax.with_sharding_constraint(acc, param_sh)
                    return acc, (l, m["ce"], m["moe_aux"])

                acc0 = jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params
                )
                grads, (losses, ces, auxes) = jax.lax.scan(
                    micro, acc0, (inputs_m, labels_m)
                )
                grads = jax.tree.map(lambda g: g / n_micro, grads)
                loss = losses.mean()
                metrics = {"ce": ces.mean(), "moe_aux": auxes.mean()}
            grads = jax.lax.with_sharding_constraint(grads, param_sh)

        if tcfg.grad_compression == "int8_ef":
            # int8 error-feedback quantization of the cross-device gradient
            # (wire-level savings measured via the shard_map pod exchange in
            # the §Perf harness; here the EF loop keeps optimizer math honest)
            from repro.optim import ef_int8_compress_decompress

            err = state["err"]
            pairs = jax.tree.map(ef_int8_compress_decompress, grads, err)
            grads = jax.tree.map(
                lambda t: t[0], pairs, is_leaf=lambda t: isinstance(t, tuple)
            )
            new_err = jax.tree.map(
                lambda t: t[1], pairs, is_leaf=lambda t: isinstance(t, tuple)
            )

        with jax.named_scope("clip_grads"):
            grads, gnorm = clip_by_global_norm(grads, tcfg.grad_clip_norm)
        with jax.named_scope("adamw_update"):
            lr = schedule(opt["step"])
            new_params, new_opt = adamw_update(
                grads, opt, params, lr=lr, cfg=tcfg
            )
        new_state = {"params": new_params, "opt": new_opt}
        if tcfg.grad_compression == "int8_ef":
            new_state["err"] = new_err
        out_metrics = {
            "loss": loss,
            "ce": metrics["ce"],
            "moe_aux": metrics["moe_aux"],
            "grad_norm": gnorm,
            "lr": lr,
        }
        return new_state, out_metrics

    # -- specs -------------------------------------------------------------
    state_specs = {
        "params": param_sp,
        "opt": S.opt_state_specs(
            cfg, state_abs["params"], tcfg.zero1, mesh, fsdp=tcfg.fsdp,
            layout=tcfg.layout,
        ),
    }
    if tcfg.grad_compression == "int8_ef":
        state_specs["err"] = param_sp
    batch_sp = S.batch_specs(cfg, None, mesh, layout=tcfg.layout)
    metric_specs = {k: P() for k in ("loss", "ce", "moe_aux", "grad_norm", "lr")}
    return TrainStepArtifacts(
        step=train_step,
        cfg=cfg,
        tcfg=tcfg,
        mesh=mesh,
        state_specs=state_specs,
        batch_specs=batch_sp,
        metric_specs=metric_specs,
    )


# ---------------------------------------------------------------------------
# Abstract pytrees (ShapeDtypeStruct stand-ins; no allocation)
# ---------------------------------------------------------------------------


def abstract_params(cfg: ModelConfig, dtype=jnp.float32) -> Tree:
    return jax.eval_shape(
        lambda: T.init_params(cfg, jax.random.PRNGKey(0), jnp.dtype(dtype))
    )


def abstract_train_state(cfg: ModelConfig, tcfg: TrainConfig) -> Tree:
    params = abstract_params(cfg, jnp.dtype(tcfg.param_dtype))
    state = {"params": params, "opt": jax.eval_shape(adamw_init, params)}
    if tcfg.grad_compression == "int8_ef":
        state["err"] = jax.eval_shape(
            lambda p: jax.tree.map(
                lambda x: jnp.zeros(x.shape, jnp.float32), p
            ),
            params,
        )
    return state


def abstract_batch(cfg: ModelConfig, shape: ShapeConfig) -> Tree:
    b, s = shape.global_batch, shape.seq_len
    sds = jax.ShapeDtypeStruct
    batch = {"labels": sds((b, s), jnp.int32)}
    if cfg.embed_inputs:
        batch["inputs"] = sds((b, s, cfg.d_model), jnp.float32)
    else:
        batch["inputs"] = sds((b, s), jnp.int32)
    return batch


def abstract_cache(
    cfg: ModelConfig, batch: int, max_seq: int, dtype=jnp.bfloat16
) -> Tree:
    return jax.eval_shape(
        functools.partial(T.init_cache, cfg, batch, max_seq, jnp.dtype(dtype))
    )
