"""Training CLI — end-to-end driver with optional SpecInF collocation.

Examples (CPU dev mesh):
  PYTHONPATH=src python -m repro.launch.train --arch olmo-1b --smoke \\
      --steps 50 --global-batch 8 --seq-len 64
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b --smoke \\
      --steps 200 --collocate --ckpt-dir /tmp/ckpt

``--smoke`` selects the reduced config (CPU-runnable); omit it on real
hardware to train the full assigned architecture.
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from repro import configs
from repro.launch.compile_cache import enable_compile_cache
from repro.configs.base import SpecInFConfig, TrainConfig
from repro.launch.mesh import make_dev_mesh
from repro.runtime.trainer import Trainer


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=list(configs.ARCH_IDS), default="olmo-1b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--collocate", action="store_true",
                    help="fill training bubbles with a collocated inference "
                         "engine (SpecInF)")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = configs.smoke_config(args.arch) if args.smoke else configs.get_config(args.arch)
    tcfg = TrainConfig(
        learning_rate=args.lr, warmup_steps=max(args.steps // 10, 1),
        total_steps=args.steps, microbatches=args.microbatches,
        fsdp=not args.smoke, zero1=not args.smoke,
        remat_policy="dots" if args.smoke else "full",
    )
    trainer = Trainer(
        cfg, tcfg, make_dev_mesh(), seq_len=args.seq_len, global_batch=args.global_batch,
        checkpoint_dir=args.ckpt_dir, checkpoint_every=args.ckpt_every,
    )
    if args.ckpt_dir and trainer.restore_latest():
        print(f"[train] resumed from step {trainer.step_no}")

    if args.collocate:
        _train_collocated(args, cfg, trainer)
        return

    t0 = time.time()
    report = trainer.train(args.steps)
    dt = time.time() - t0
    toks = args.steps * args.global_batch * args.seq_len
    print(
        f"[train] {report.steps} steps in {dt:.1f}s "
        f"({toks/dt:.0f} tok/s) loss {report.losses[0]:.3f} -> "
        f"{report.losses[-1]:.3f} restores={report.restores} "
        f"checkpoints={report.checkpoints}"
    )


def collocated_runtime(cfg, trainer, *, max_seq: int, train_step=None,
                       batch_iter=None):
    """SpecInF end-to-end: the trainer's step runs under the
    speculative-filling runtime, and an inference engine serving the
    trainer's initial params decodes four offline requests in the bubbles
    of a ``dp_profile``.  The engine serves a bf16 copy on one device: cast
    while it serves from params on one device, or gathered once and kept
    from params sharded over the trainer's mesh (see ``InferenceEngine``).

    ``train_step`` and ``batch_iter`` default to the trainer's jitted step
    and data stream; the runtime starts from ``trainer.state``."""
    from repro.core import SpecInFRuntime
    from repro.core.profiles import dp_profile
    from repro.serving.core import Priority, SamplingParams
    from repro.serving.engine import InferenceEngine

    engine = InferenceEngine(cfg, trainer.state["params"], max_slots=4,
                             max_seq=max_seq)
    for i in range(4):
        engine.core.submit(np.arange(8 + i) % cfg.vocab_size,
                           SamplingParams(max_new_tokens=10**9),
                           priority=Priority.OFFLINE)

    def batches():
        while True:
            yield trainer._batch()

    return SpecInFRuntime(
        train_step=train_step or trainer.step_fn,
        train_state=trainer.state, batch_iter=batch_iter or batches(),
        profile=dp_profile(cfg.name, compute_s=0.05, comm_s=0.025),
        engine=engine, cfg=SpecInFConfig(), decode_microstep_s=0.004,
    )


def _train_collocated(args, cfg, trainer) -> None:
    rt = collocated_runtime(cfg, trainer, max_seq=args.seq_len)
    t0 = time.time()
    metrics = rt.run(args.steps)
    dt = time.time() - t0
    print(
        f"[train+fill] {metrics.train_iterations} train steps, "
        f"{metrics.offline_tokens_generated} collocated inference tokens "
        f"in {dt:.1f}s; loss {metrics.train_losses[0]:.3f} -> "
        f"{metrics.train_losses[-1]:.3f}; phases={metrics.phase_counts}"
    )


if __name__ == "__main__":
    main()
