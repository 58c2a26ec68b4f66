#!/usr/bin/env python3
"""Smoke run of the main path on a TPU: serving, then collocated training.

  python chip_smoke.py                # one chip: serving + collocated phases
  python chip_smoke.py --four-chips   # collocated phase only, on a
                                      # data=2 x model=2 mesh of four chips

Serving builds qwen3-1.7b at its published config with random weights and
answers a few requests through ``InferenceEngine`` / ``EngineCore`` (paged
KV cache, chunked prefill, the Pallas kernels).  The Pallas path's greedy
tokens must equal the engine's XLA path (``decode_impl="xla"``) on the same
weights and prompts; that comparison runs in float32 at the highest matmul
precision, so it checks the kernels' semantics rather than bf16 rounding.
The lowered serving programs must contain ``tpu_custom_call`` (a kernel
compiled for the chip, not interpreted).

The collocated phase builds its ``SpecInFRuntime`` with
``launch.train.collocated_runtime``, the code behind ``launch/train.py
--collocate`` — the engine serves a bf16 copy of the trainer's params in
the bubbles of a ``dp_profile`` — at qwen3-1.7b widths with the depth cut
to what fits one chip.  The same train steps run first without filling, from
the same state and data; the losses must agree bit for bit.

Times printed are a smoke, not a benchmark.  Every phase that fails ends
the run with a non-zero exit; the last line of a passing run is
``{"ok": true, "device": {...}}``.  Without a TPU the script exits non-zero
before any work.  ``JAX_COMPILATION_CACHE_DIR`` picks the compilation
cache; without it the cache is ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "qwen3-1.7b"
#: decoder layers of the collocated phase.  The train step keeps params and
#: both AdamW moments in f32 and, because the engine serves the same param
#: buffers, runs undonated (old and new state live at once).  Its compiled
#: footprint for a v5e at seq 256 x batch 4 (``memory_analysis``,
#: arguments + outputs + temporaries): 9.85 / 10.98 / 12.27 / 13.58 GiB at
#: 1 / 2 / 3 / 4 layers, plus the initial params the engine keeps serving
#: (1.53 GiB at 2 layers, 1.72 at 3).  3 layers ran out of the 15.75 GiB a
#: v5e gives JAX during the filled run, so 2 is the deepest cut that fits.
COLLOCATED_LAYERS = 2


def log(msg: str) -> None:
    print(msg, flush=True)


def device_report() -> dict:
    devs = jax.devices()
    d = devs[0]
    log(f"device: platform={d.platform} kind={d.device_kind} count={len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


def memory_report(tag: str) -> None:
    stats = jax.devices()[0].memory_stats() or {}
    gib = lambda k: stats.get(k, float("nan")) / 2**30
    log(f"[{tag}] device 0 memory: peak_bytes_in_use={gib('peak_bytes_in_use'):.2f} "
        f"GiB bytes_limit={gib('bytes_limit'):.2f} GiB")


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------


def serve_round(engine, prompts, max_new: int):
    """Submit every prompt at t=0 and step the core until all finish.
    Returns (EngineRequests, wall seconds)."""
    from repro.serving.core import Priority, SamplingParams

    t0 = time.monotonic()
    engine.clock = lambda: time.monotonic() - t0
    core = engine.core
    reqs = [
        core.submit(p, SamplingParams(max_new_tokens=max_new),
                    priority=Priority.ONLINE, arrival_time=0.0)
        for p in prompts
    ]
    while core.has_unfinished:
        core.step()
    jax.block_until_ready(engine.cache)
    return reqs, time.monotonic() - t0


def lowered_has_kernel(engine) -> dict:
    """Lower the engine's decode-loop and chunked-prefill programs with its
    live arguments and report whether each holds a compiled Pallas kernel."""
    b, c = engine.max_slots, engine.prefill_chunk
    dec = engine._decode_loop.lower(
        engine.params, engine.tokens, engine.cache,
        jnp.zeros((b,), jnp.int32), k=1,
    ).as_text()
    pre = engine._prefill_chunks.lower(
        engine.params, jnp.zeros((b, c), jnp.int32),
        jnp.zeros((b,), jnp.int32), engine.cache,
    ).as_text()
    return {"decode": "tpu_custom_call" in dec,
            "prefill": "tpu_custom_call" in pre}


def serving_phase(cfg, *, seed: int, requests: int, prompt_lens: tuple,
                  max_new: int, max_seq: int) -> None:
    from repro.models import transformer as T
    from repro.serving.engine import InferenceEngine

    log(f"[serve] {cfg.name}: layers={cfg.num_layers} d_model={cfg.d_model} "
        f"heads={cfg.num_heads}/{cfg.num_kv_heads} head_dim="
        f"{cfg.resolved_head_dim} vocab={cfg.vocab_size} "
        f"params={cfg.param_count() / 1e9:.2f}B (random, seed {seed})")
    rng = np.random.default_rng(seed)
    lens = rng.integers(prompt_lens[0], prompt_lens[1] + 1, requests)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in lens]
    # same lengths, other tokens: compiles every program of the timed round
    # without seeding the prefix cache with the timed prompts
    warm = [rng.integers(0, cfg.vocab_size, n).astype(np.int32) for n in lens]
    log(f"[serve] {requests} requests, prompt lengths {lens.tolist()}, "
        f"{max_new} new tokens each, max_seq {max_seq}")

    t0 = time.monotonic()
    params = T.init_params(cfg, jax.random.PRNGKey(seed), jnp.bfloat16)
    jax.block_until_ready(params)
    log(f"[serve] init params (bf16): {time.monotonic() - t0:.1f}s")

    # -- the default engine: bf16, paged KV, chunked prefill, Pallas kernels
    eng = InferenceEngine(cfg, params, max_slots=requests, max_seq=max_seq)
    check(eng.paged and eng.prefill_chunk > 0,
          "default engine is not paged + chunked")
    log(f"[serve] engine: paged page={eng.kv_page_size} pool_pages="
        f"{eng.pool.num_pages} prefill_chunk={eng.prefill_chunk}")
    t0 = time.monotonic()
    serve_round(eng, warm, max_new)
    log(f"[serve] warm-up round (compiles): {time.monotonic() - t0:.1f}s")
    reqs, wall = serve_round(eng, prompts, max_new)
    ttft = np.array([r.first_token_time for r in reqs])
    ntok = sum(len(r.output_tokens) for r in reqs)
    check(all(r.finish_reason == "length" and len(r.output_tokens) == max_new
              for r in reqs), "bf16 round: a request did not finish at length")
    log(f"[serve] bf16 pallas (smoke, not a benchmark): {ntok} tokens in "
        f"{wall:.3f}s = {ntok / wall:.1f} tok/s; TTFT p50 "
        f"{np.percentile(ttft, 50) * 1e3:.1f}ms max {ttft.max() * 1e3:.1f}ms")
    kern = lowered_has_kernel(eng)
    log(f"[serve] lowered programs hold tpu_custom_call: {kern}")
    check(all(kern.values()), "a serving program lowered without its kernel")
    bf16_tokens = [list(r.output_tokens) for r in reqs]
    memory_report("serve bf16")
    del eng, reqs
    gc.collect()  # engine <-> core cycles: free the device cache now

    # -- Pallas vs XLA on the same (bf16-valued) weights, in float32
    params32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    del params
    outs = {}
    with jax.default_matmul_precision("highest"):
        for impl in ("pallas", "xla"):
            e = InferenceEngine(
                cfg, params32, max_slots=requests, max_seq=max_seq,
                compute_dtype=jnp.float32,
                decode_impl="auto" if impl == "pallas" else "xla",
            )
            t0 = time.monotonic()
            rs, _ = serve_round(e, prompts, max_new)
            outs[impl] = [list(r.output_tokens) for r in rs]
            kern = lowered_has_kernel(e)
            log(f"[serve] f32 {impl}: round {time.monotonic() - t0:.1f}s "
                f"(compiles included); tpu_custom_call {kern}")
            check(all(kern.values()) == (impl == "pallas"),
                  f"f32 {impl} engine: unexpected kernel presence {kern}")
            del e, rs
            gc.collect()
    same = [a == b for a, b in zip(outs["pallas"], outs["xla"])]
    log(f"[serve] greedy tokens pallas == xla (f32, highest precision): "
        f"{sum(same)}/{len(same)} requests")
    check(all(same), "Pallas and XLA serving paths disagree")
    agree = [
        next((i for i, (x, y) in enumerate(zip(a, b)) if x != y), len(a))
        for a, b in zip(bf16_tokens, outs["pallas"])
    ]
    log(f"[serve] bf16 vs f32 tokens agree for the first {agree} tokens "
        f"(information only: bf16 rounding)")
    del params32
    gc.collect()
    memory_report("serve f32")


# ---------------------------------------------------------------------------
# Collocated training
# ---------------------------------------------------------------------------


def collocated_phase(mesh, *, layers: int, steps: int, seq_len: int,
                     global_batch: int) -> None:
    from repro import configs
    from repro.configs.base import ShapeConfig, TrainConfig
    from repro.data.pipeline import SyntheticDataset
    from repro.launch.train import collocated_runtime
    from repro.runtime.trainer import Trainer

    full = configs.get_config(ARCH)
    cfg = dataclasses.replace(full, num_layers=layers)
    log(f"[colloc] reduced: num_layers {full.num_layers}->{layers} "
        f"(widths as published); mesh {dict(mesh.shape)}; "
        f"seq {seq_len} x batch {global_batch}")
    tcfg = TrainConfig(learning_rate=1e-4, warmup_steps=1, total_steps=100,
                       fsdp=False, zero1=False, remat_policy="full")
    trainer = Trainer(cfg, tcfg, mesh, seq_len=seq_len,
                      global_batch=global_batch)
    art = trainer.artifacts
    t0 = time.monotonic()
    compiled = trainer.step_fn.lower(
        trainer.state,
        art.abstract_batch(ShapeConfig("smoke", seq_len=seq_len,
                                       global_batch=global_batch,
                                       kind="train")),
    ).compile()
    mem = compiled.memory_analysis()
    log(f"[colloc] train step compiled in {time.monotonic() - t0:.1f}s; "
        f"per device: arguments {mem.argument_size_in_bytes / 2**30:.2f} "
        f"outputs {mem.output_size_in_bytes / 2**30:.2f} temporaries "
        f"{mem.temp_size_in_bytes / 2**30:.2f} GiB")

    def batches():
        ds = SyntheticDataset(cfg=cfg, seq_len=seq_len,
                              global_batch=global_batch, seed=tcfg.seed)
        sh = art.batch_shardings()
        while True:
            b = ds.next_batch()
            yield {k: jax.device_put(v, sh[k]) for k, v in b.items()}

    def timed(times):
        def step(state, batch):
            t = time.perf_counter()
            state, metrics = compiled(state, batch)
            jax.block_until_ready((state, metrics))
            times.append(time.perf_counter() - t)
            return state, metrics
        return step

    # the initial state goes to host memory so both runs start from it
    state0 = jax.device_get(trainer.state)
    trainer.state = None
    shardings = art.state_shardings()
    put_state = lambda: jax.tree.map(jax.device_put, state0, shardings)

    # -- without filling
    base_times, base_losses = [], []
    step, state, it = timed(base_times), put_state(), batches()
    for _ in range(steps):
        state, m = step(state, next(it))
        base_losses.append(float(m["loss"]))
    del state
    log(f"[colloc] no filling: step times "
        f"{[round(t * 1e3, 2) for t in base_times]} ms; losses {base_losses}")

    # -- with filling, through the entry point of ``launch/train.py
    # --collocate``: the engine serves its bf16 copy of the trainer's
    # params (on a multi-chip mesh, made on the first chip)
    trainer.state = put_state()
    fill_times = []
    rt = collocated_runtime(cfg, trainer, max_seq=seq_len,
                            train_step=timed(fill_times), batch_iter=batches())
    engine, profile = rt.engine, rt.profile

    def where(tag):
        log(f"[colloc] engine arrays {tag}: params "
            f"{jax.tree.leaves(engine.params)[0].sharding}; KV pool "
            f"{engine.cache['layers']['k'].sharding}; tokens "
            f"{engine.tokens.sharding}")

    where("before the run")
    t0 = time.monotonic()
    metrics = rt.run(steps)
    jax.block_until_ready(rt.state)
    wall = time.monotonic() - t0
    log(f"[colloc] with filling: step times "
        f"{[round(t * 1e3, 2) for t in fill_times]} ms; losses "
        f"{metrics.train_losses}; {metrics.offline_tokens_generated} offline "
        f"tokens in {metrics.offline_microsteps} microsteps; wall {wall:.1f}s "
        f"(engine compiles included); phases {metrics.phase_counts}")
    where("after the run")
    log(f"[colloc] bubble lengths come from dp_profile (compute "
        f"{profile.compute_s * 1e3:.1f}ms, bubbles {profile.bubble_s * 1e3:.1f}"
        f"ms per iteration, virtual), not measured on the chip")
    med = lambda xs: float(np.median(xs[1:] if len(xs) > 1 else xs))
    log(f"[colloc] median train step (smoke, not a benchmark): no filling "
        f"{med(base_times) * 1e3:.2f}ms, with filling "
        f"{med(fill_times) * 1e3:.2f}ms")
    check(np.isfinite(base_losses).all(), "non-finite training loss")
    check(metrics.train_losses == base_losses,
          "filling changed the training trajectory")
    check(metrics.offline_tokens_generated > 0,
          "the bubbles produced no offline tokens")
    memory_report("colloc")


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the collocated phase, on a data=2 x "
                    "model=2 mesh of four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    device = device_report()
    if device["platform"] != "tpu":
        log("chip_smoke: JAX found no TPU; nothing was run")
        return 1
    need = 4 if args.four_chips else 1
    if device["count"] < need:
        log(f"chip_smoke: needs {need} chips, JAX sees {device['count']}")
        return 1

    from repro import configs
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_dev_mesh

    log(f"compile cache: {enable_compile_cache()}")
    t_start = time.monotonic()
    if args.four_chips:
        collocated_phase(make_dev_mesh(data=2, model=2),
                         layers=COLLOCATED_LAYERS, steps=4, seq_len=256,
                         global_batch=4)
    else:
        serving_phase(configs.get_config(ARCH), seed=args.seed, requests=8,
                      prompt_lens=(192, 320), max_new=32, max_seq=384)
        collocated_phase(make_dev_mesh(data=1, model=1),
                         layers=COLLOCATED_LAYERS, steps=4, seq_len=256,
                         global_batch=4)
    log(f"total {time.monotonic() - t_start:.1f}s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
