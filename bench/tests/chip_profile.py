"""Chip measurements a traffic file's bubble profile takes: the unfilled
train step of a cell on its mesh, and the engine's decode microstep with
every slot busy.

  python3 bench/tests/chip_profile.py <workload> <seed> [--steps 6] [--loops 6]

Prints one JSON line: ``compute_s`` (the median wall time of the jitted
train step, alone on the chips), ``decode_microstep_s`` (the median wall
time of the engine's fused decode loop at k = 8, over 8, every slot
decoding), and the samples.
The benchmark's own runs never run this.
"""
import json
import statistics
import sys
import time
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
import harness  # noqa: E402
from catalog import Catalog  # noqa: E402

K = 8


def main():
    args = sys.argv[1:]
    opts = {"--steps": 6, "--loops": 6}
    for o in list(opts):
        if o in args:
            i = args.index(o)
            opts[o] = int(args[i + 1])
            del args[i:i + 2]
    workload, seed = args[0], int(args[1])
    root = Path.cwd()
    sys.path.insert(0, str(root / "src"))
    import jax
    import jax.numpy as jnp
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_dev_mesh
    from repro.runtime.step import make_train_step
    from repro.serving.core import Priority, SamplingParams
    from repro.serving.engine import InferenceEngine

    enable_compile_cache()
    cat = Catalog(root)
    cell = cat.workload(workload)
    c = cat.config(cell["config"])
    traf = cat.traffic(cell["traffic"])
    ref = cat.reference(c["reference"])
    mcfg, tcfg, t = harness.model_config(c), harness.train_config(c), c["train"]
    mesh = make_dev_mesh(data=t["mesh"]["data"], model=t["mesh"]["model"])
    art = make_train_step(mcfg, tcfg, mesh)
    step_fn = art.jitted(donate=False)

    def make_state(key):
        p = ref.init_params(c, key)
        z = lambda x: jnp.zeros(x.shape, jnp.float32)
        return {"params": p, "opt": {"mu": jax.tree.map(z, p), "nu": jax.tree.map(z, p),
                                     "step": jnp.zeros((), jnp.int32)}}

    state = jax.jit(make_state, out_shardings=art.state_shardings())(harness.seed_key(seed))
    rng = np.random.default_rng([seed, 5])
    toks = rng.integers(0, c["vocab_size"], (t["global_batch"], t["seq_len"] + 1)).astype(np.int32)
    sh = art.batch_shardings()
    batch = {"inputs": jax.device_put(toks[:, :-1], sh["inputs"]),
             "labels": jax.device_put(toks[:, 1:], sh["labels"])}
    steps = []
    for i in range(opts["--steps"] + 1):
        t0 = time.perf_counter()
        state, m = step_fn(state, batch)
        jax.block_until_ready(m["loss"])
        if i:  # the first call compiles
            steps.append(time.perf_counter() - t0)

    es = traf["engine"]
    engine = InferenceEngine(mcfg, state["params"], max_slots=es["max_slots"],
                             max_seq=es["max_seq"])
    core = engine.core
    prompt = int(traf["offline"]["prompt"]["median"])
    for _ in range(es["max_slots"]):
        core.submit(rng.integers(0, c["vocab_size"], prompt), SamplingParams(
            max_new_tokens=es["max_seq"] - prompt - 1), priority=Priority.OFFLINE)
    while engine.num_active < es["max_slots"] or engine.num_prefilling:
        core.step()
    loops = []
    for i in range(opts["--loops"] + 1):
        t0 = time.perf_counter()
        engine._drive_decode_loop(K)
        if i:  # the first call at k = 8 may compile
            loops.append(time.perf_counter() - t0)
    print(json.dumps({
        "workload": workload, "seed": seed, "slots": es["max_slots"],
        "compute_s": statistics.median(steps),
        "decode_microstep_s": statistics.median(loops) / K,
        "train_step_s": steps, "decode_loop_s": loops,
        "memory_peak_bytes": [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                              for d in jax.devices()[: cell["chips"]]],
    }), flush=True)


if __name__ == "__main__":
    main()
