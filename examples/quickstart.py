"""Quickstart: build an assigned architecture, train a few steps, decode.

  PYTHONPATH=src python examples/quickstart.py [--arch qwen3-1.7b]

Runs the REDUCED (smoke) config so it finishes on CPU in seconds; on real
hardware drop ``smoke_config`` for ``get_config`` and size ``make_dev_mesh``
to the chips.
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.configs.base import TrainConfig
from repro.data.pipeline import SyntheticDataset
from repro.launch.mesh import make_dev_mesh
from repro.runtime.step import make_train_step
from repro.serving.core import Priority, SamplingParams
from repro.serving.engine import InferenceEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b", choices=list(configs.ARCH_IDS))
    ap.add_argument("--steps", type=int, default=20)
    args = ap.parse_args()

    cfg = configs.smoke_config(args.arch)
    mesh = make_dev_mesh()
    tcfg = TrainConfig(learning_rate=3e-3, warmup_steps=2, total_steps=args.steps,
                       fsdp=False, zero1=False)

    # ---- train a few steps -------------------------------------------------
    art = make_train_step(cfg, tcfg, mesh)
    step = art.jitted(donate=False)
    state = art.init_state(jax.random.PRNGKey(0))
    ds = SyntheticDataset(cfg=cfg, seq_len=64, global_batch=8)
    for i in range(args.steps):
        b = ds.next_batch()
        batch = {k: jnp.asarray(v) for k, v in b.items()}
        state, m = step(state, batch)
        if i % 5 == 0 or i == args.steps - 1:
            print(f"step {i:3d} loss {float(m['loss']):.4f} "
                  f"lr {float(m['lr']):.2e} gnorm {float(m['grad_norm']):.2f}")

    # ---- greedy decode through the engine lifecycle core ------------------
    # submit() queues the request; stream() yields tokens as EngineCore.step()
    # quanta produce them (prefill -> first token, fused decode -> the rest).
    params = state["params"]
    engine = InferenceEngine(cfg, params, max_slots=1, max_seq=32)
    prompt = np.arange(8) % cfg.vocab_size
    req = engine.core.submit(
        prompt, SamplingParams(max_new_tokens=9), priority=Priority.ONLINE
    )
    out = list(engine.core.stream(req))
    print("prompt:", prompt.tolist())
    print("generated:", out, f"({req.finish_reason})")


if __name__ == "__main__":
    main()
