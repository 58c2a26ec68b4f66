"""Fault-tolerant training driver.

Production-loop features (DESIGN.md §3 runtime):
  * checkpoint/restart  -- atomic async checkpoints every N steps; on step
    failure the loop restores the latest complete checkpoint (params, opt
    state, data-stream position) and continues.  A step that keeps failing
    (a compile error, a device out-of-memory) re-raises after
    ``MAX_FAILURES_WITHOUT_PROGRESS`` failures in which no step got past the
    furthest failed one, instead of restoring forever
  * straggler mitigation -- per-step EMA timing; a straggling step (or an
    external straggler signal) triggers SpecInF *filling backoff*: the
    collocated-inference token ceiling is scaled down so recovery compute
    isn't contended (the paper's training-first guarantee, inverted into
    the control plane)
  * elastic re-mesh      -- ``remesh()`` rebuilds the jitted step on a new
    mesh and re-shards the live state onto it (grow/shrink events)
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Optional

import jax
import numpy as np

from repro.checkpoint.checkpointer import Checkpointer
from repro.configs.base import ModelConfig, TrainConfig
from repro.data.pipeline import SyntheticDataset
from repro.runtime.step import make_train_step


@dataclasses.dataclass
class TrainerReport:
    steps: int = 0
    losses: list = dataclasses.field(default_factory=list)
    step_times_s: list = dataclasses.field(default_factory=list)
    restores: int = 0
    straggler_events: int = 0
    checkpoints: int = 0


class Trainer:
    #: failed steps, with no step succeeding past the furthest of them, after
    #: which ``train`` re-raises the step's exception
    MAX_FAILURES_WITHOUT_PROGRESS = 3

    def __init__(
        self,
        cfg: ModelConfig,
        tcfg: TrainConfig,
        mesh,
        *,
        seq_len: int,
        global_batch: int,
        checkpoint_dir: Optional[str] = None,
        checkpoint_every: int = 50,
        straggler_factor: float = 3.0,
        on_straggler: Optional[Callable[[], None]] = None,
        host_index: int = 0,
        host_count: int = 1,
    ):
        self.cfg, self.tcfg, self.mesh = cfg, tcfg, mesh
        self.seq_len, self.global_batch = seq_len, global_batch
        self.artifacts = make_train_step(cfg, tcfg, mesh)
        self.step_fn = self.artifacts.jitted(donate=False)
        self.dataset = SyntheticDataset(
            cfg=cfg, seq_len=seq_len, global_batch=global_batch,
            host_index=host_index, host_count=host_count, seed=tcfg.seed,
        )
        self.state = self.artifacts.init_state(jax.random.PRNGKey(tcfg.seed))
        self.step_no = 0
        self.ckpt = Checkpointer(checkpoint_dir) if checkpoint_dir else None
        self.checkpoint_every = checkpoint_every
        self.straggler_factor = straggler_factor
        self.on_straggler = on_straggler
        self._ema: Optional[float] = None
        self.report = TrainerReport()
        # failure-injection hook for tests: callable(step_no) -> bool
        self.fail_hook: Optional[Callable[[int], bool]] = None

    # ------------------------------------------------------------------
    def _snapshot(self) -> dict:
        return {"state": self.state, "data_step": np.int64(self.dataset._step)}

    def _maybe_checkpoint(self) -> None:
        if self.ckpt and self.step_no % self.checkpoint_every == 0:
            self.ckpt.save(self.step_no, self._snapshot(), blocking=False)
            self.report.checkpoints += 1

    def restore_latest(self) -> bool:
        if not self.ckpt or self.ckpt.latest_step() is None:
            return False
        template = self._snapshot()
        restored, step = self.ckpt.restore(template)
        self.state = restored["state"]
        self.dataset._step = int(restored["data_step"])
        self.step_no = step
        self.report.restores += 1
        return True

    # ------------------------------------------------------------------
    def _batch(self):
        b = self.dataset.next_batch()
        shardings = self.artifacts.batch_shardings()
        return {
            k: jax.device_put(v, shardings[k]) for k, v in b.items()
        }

    def train(self, num_steps: int) -> TrainerReport:
        target = self.step_no + num_steps
        # failed steps since the run last got past the furthest step that
        # failed: re-running the steps between a checkpoint and a step that
        # always fails is no progress
        failures, failed_at = 0, -1
        while self.step_no < target:
            batch = self._batch()
            t0 = time.monotonic()
            try:
                if self.fail_hook and self.fail_hook(self.step_no):
                    raise RuntimeError(f"injected failure @ step {self.step_no}")
                self.state, metrics = self.step_fn(self.state, batch)
                loss = float(metrics["loss"])
            except Exception:
                failures += 1
                failed_at = max(failed_at, self.step_no)
                if failures >= self.MAX_FAILURES_WITHOUT_PROGRESS:
                    raise
                if not self.restore_latest():
                    # no checkpoint yet: restart from scratch, same seed
                    self.state = self.artifacts.init_state(
                        jax.random.PRNGKey(self.tcfg.seed)
                    )
                    self.dataset._step = 0
                    self.step_no = 0
                    self.report.restores += 1
                continue
            if self.step_no >= failed_at:
                failures = 0
            dt = time.monotonic() - t0
            self.step_no += 1
            self.report.steps += 1
            self.report.losses.append(loss)
            self.report.step_times_s.append(dt)
            # straggler detection on the step-time EMA
            if self._ema is not None and dt > self.straggler_factor * self._ema:
                self.report.straggler_events += 1
                if self.on_straggler:
                    self.on_straggler()
            self._ema = dt if self._ema is None else 0.9 * self._ema + 0.1 * dt
            self._maybe_checkpoint()
        if self.ckpt:
            self.ckpt.save(self.step_no, self._snapshot(), blocking=True)
            self.report.checkpoints += 1
        return self.report

    # ------------------------------------------------------------------
    def remesh(self, new_mesh) -> None:
        """Elastic scaling: rebuild step artifacts on ``new_mesh`` and
        re-shard the live state onto it."""
        self.mesh = new_mesh
        self.artifacts = make_train_step(self.cfg, self.tcfg, new_mesh)
        self.step_fn = self.artifacts.jitted(donate=False)
        shardings = self.artifacts.state_shardings()
        self.state = jax.tree.map(
            lambda x, s: jax.device_put(np.asarray(x), s), self.state, shardings
        )


def specinf_backoff(scheduler) -> Callable[[], None]:
    """Straggler -> filling backoff: halve the collocated-inference token
    ceiling on the live Algorithm-1 scheduler (restored by the next
    conservative->stable cycle's config)."""

    def backoff():
        scheduler._tokens = scheduler._tokens / 2.0

    return backoff
