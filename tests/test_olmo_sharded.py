"""OLMo-shaped collocation on a data 2 × model 2 trainer (four host CPU
devices, so each case runs in a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=4``):

* the benchmark's harness, unchanged, runs a tiny OLMo-shaped cell trained
  with FSDP + ZeRO-1 on the 2 × 2 mesh: correct, the float8 control caught,
  no compilation and no placement move inside the window;
* an engine handed params sharded over the four devices gathers one
  compute-dtype copy onto device 0, once, keeps it through
  ``release_params``, moves mesh-placed arrays onto its device, and serves
  the tokens the float32 reference ranks first;
* ``bench/reference/dense_blocked.py`` (row-at-a-time gradients, scanned
  layers, arrays over the chips) reads what ``dense.py`` reads.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
BENCH = REPO / "bench"
SEED = 2**32 + 15
#: served tokens of a sound bf16 engine sit at most this far (in f32
#: logits) below the reference's best at the tiny size; the tiny cell's
#: limit is 0.05
SERVE_TOL = 0.05


def _load(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def tiny_olmo(mesh=(2, 2)) -> dict:
    """The tiny rehearsal configuration, OLMo-shaped: non-parametric
    LayerNorm, no qk-norm, as many KV heads as query heads."""
    tiny = _load(BENCH / "tests" / "tiny.py", "bench_tests_tiny")
    c = dict(tiny.TINY_CONFIG, name="tiny-olmo", norm="layernorm", qk_norm=False,
             num_key_value_heads=4, layer_norm_eps=1e-5, reference="dense_blocked")
    c.pop("rms_norm_eps")
    c["train"] = dict(c["train"], mesh={"data": mesh[0], "model": mesh[1]},
                      fsdp=mesh != (1, 1), zero1=mesh != (1, 1))
    return c


def _subprocess(case: str, *args: str) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(REPO / "src"), os.environ.get("PYTHONPATH", "")]))
    p = subprocess.run([sys.executable, __file__, case, *args], capture_output=True, text=True,
                       env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return p


# ---------------------------------------------------------------------------
# (a) the harness, unchanged, on a tiny OLMo-shaped 2 × 2 cell
# ---------------------------------------------------------------------------

MOVES_READER = '''"""Arrays the engine's programs moved onto its device in the window."""


def read(w):
    return w.counters.get("engine/placement_moves", 0)
'''


def make_root(tmp: Path) -> Path:
    """A scratch root: the tiny rehearsal root plus a tiny OLMo-shaped
    four-device cell on the new cell's metrics, and a reader of the
    engine's placement moves."""
    tiny = _load(BENCH / "tests" / "tiny.py", "bench_tests_tiny")
    root = tiny.make_root(tmp, online=False)
    cfg = tiny_olmo()
    (root / "bench" / "configs" / "tiny-olmo.json").write_text(json.dumps(cfg))
    (root / "bench" / "metrics" / "engine.placement_moves.py").write_text(MOVES_READER)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny-olmo", "source": "tiny", "reduced": [],
                            "file": "bench/configs/tiny-olmo.json", "why": "CPU rehearsal"})
    spec["workloads"].append({"name": "tiny-olmo-cell", "config": "tiny-olmo",
                              "traffic": "tiny_mix", "chips": 4, "why": "CPU rehearsal"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "olmo-colloc-dp2tp2" in m.get("workloads", []):
            m["workloads"].append("tiny-olmo-cell")
    spec["end_to_end"].append({"name": "engine.placement_moves", "unit": "arrays",
                               "better": "lower", "source": "host_clock", "bound": 0,
                               "workloads": ["tiny-olmo-cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def case_harness(root: str) -> None:
    import time

    sys.path.insert(0, str(Path(root) / "bench"))
    import harness

    res = harness.run_cell("tiny-olmo-cell", SEED, 2.0, False, root=Path(root),
                           t_start=time.perf_counter(), require_tpu=False, controls=True)
    print(json.dumps(res))


def test_tiny_olmo_cell_on_a_2x2_mesh_is_correct_through_the_harness(tmp_path):
    root = make_root(tmp_path / "root")
    p = _subprocess("harness", str(root))
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["correct"], res["checks"]
    assert res["device"]["count"] == 4
    limits = {k: c["limit"] for k, c in res["checks"].items()}
    control = res["readings"]["control"]
    assert control["train.loss_gap"] > limits["train.loss_gap"], control
    assert control["serve.logit_gap"] > limits["serve.logit_gap"], control
    m = res["metrics"]
    assert m["engine.placement_moves"]["value"] == 0
    assert m["offline_tokens_per_s"]["value"] > 0
    window = re.search(r"compilations inside the window: (\{[^}]*\})", p.stderr)
    assert window, p.stderr[-2000:]
    assert json.loads(window.group(1).replace("'", '"')) == {"traces": 0, "compiles": 0}
    shutil.rmtree(root, ignore_errors=True)


# ---------------------------------------------------------------------------
# (b) an engine handed a 2 × 2 trainer's sharded params
# ---------------------------------------------------------------------------


def case_engine() -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    sys.path.insert(0, str(BENCH))
    from harness import model_config, train_config
    from repro.launch.mesh import make_dev_mesh
    from repro.models import transformer as T
    from repro.runtime.step import make_train_step
    from repro.serving.core import Priority, SamplingParams
    from repro.serving.engine import InferenceEngine

    c = tiny_olmo()
    dense = _load(BENCH / "reference" / "dense.py", "bench_reference_dense")
    cfg = model_config(c)
    mesh = make_dev_mesh(data=2, model=2)
    art = make_train_step(cfg, train_config(c), mesh)
    given = dense.init_params(c, jax.random.PRNGKey(SEED % 2**31))
    sharded = jax.device_put(given, art.state_shardings()["params"])
    assert any(len(x.sharding.device_set) == 4 for x in jax.tree.leaves(sharded))

    eng = InferenceEngine(cfg, sharded, max_slots=2, max_seq=64)
    m = eng.obs.metrics
    d0 = jax.devices()[0]
    served = eng.params
    want = T.cast_params(given, jnp.bfloat16)
    out = {"on_device_0": all(x.sharding.device_set == {d0} for x in jax.tree.leaves(served)),
           "equal_to_cast": all(
               x.dtype == w.dtype and np.array_equal(np.asarray(x, np.float32),
                                                     np.asarray(w, np.float32))
               for x, w in zip(jax.tree.leaves(served), jax.tree.leaves(want))),
           "float32_matrices": sum(x.dtype == jnp.float32 and x.ndim > 1
                                   for t in (eng._given["target"], served)
                                   for x in jax.tree.leaves(t)),
           "gather_span_ns": m.counter("host_ns/engine.gather_params").value}
    for _ in range(3):
        eng.release_params()
    out["same_copy_after_release"] = eng.params is served

    # mesh-placed block tables are moved onto the engine's device, once per
    # array; arrays already there are not
    moves = m.counter("engine/placement_moves")
    cache = jax.tree.map(jnp.copy, eng.cache)
    cache["block_tables"] = jax.device_put(np.asarray(eng.cache["block_tables"]),
                                           NamedSharding(mesh, P()))
    jax.block_until_ready(eng._decode_loop(eng.params, jnp.copy(eng.tokens), cache,
                                           jnp.zeros((2,), jnp.int32), k=1))
    out["moves_mesh_tables"] = moves.value

    # prefill then paged decode: every served token is one the float32
    # reference ranks (within round-off) first
    rng = np.random.default_rng(SEED)
    reqs = [eng.core.submit(rng.integers(0, c["vocab_size"], n),
                            SamplingParams(max_new_tokens=12), priority=Priority.OFFLINE)
            for n in (9, 40)]
    while not all(r.state.finished for r in reqs):
        eng.core.step()
    gaps = [dense.serve_gaps(c, given, r.prompt, r.output_tokens, 64)[0] for r in reqs]
    out.update(moves_after_serving=moves.value, gaps=gaps,
               served_tokens=[len(r.output_tokens) for r in reqs],
               gathers=m.counter("engine/serving_param_gathers").value,
               casts=m.counter("engine/serving_param_casts").value)
    print(json.dumps(out))


def test_engine_gathers_one_bf16_copy_of_sharded_params_and_serves_it():
    out = json.loads(_subprocess("engine").stdout.strip().splitlines()[-1])
    assert out["on_device_0"] and out["equal_to_cast"], out
    assert out["float32_matrices"] == 0, "no float32 tree may be left on device 0"
    assert out["gather_span_ns"] > 0
    assert out["same_copy_after_release"]
    assert out["gathers"] == 1 and out["casts"] == 0
    assert out["moves_mesh_tables"] == 1
    assert out["moves_after_serving"] == 1, "the engine's own arrays need no move"
    assert out["served_tokens"] == [12, 12]
    assert max(out["gaps"]) <= SERVE_TOL, out["gaps"]


# ---------------------------------------------------------------------------
# (c) the blocked reference reads what dense.py reads
# ---------------------------------------------------------------------------

#: relative gap allowed between the two references' float32 readings: they
#: differ by summation order only (rows, scan); read 1e-7 – 4e-6 at the
#: tiny size
REF_TOL = 5e-5


@pytest.mark.parametrize("shape", ["qwen", "olmo"])
def test_blocked_reference_matches_dense_on_one_device(shape):
    import jax
    import jax.numpy as jnp

    dense = _load(BENCH / "reference" / "dense.py", "bench_reference_dense")
    blocked = _load(BENCH / "reference" / "dense_blocked.py", "bench_reference_dense_blocked")
    c = tiny_olmo(mesh=(1, 1))
    if shape == "qwen":
        tiny = _load(BENCH / "tests" / "tiny.py", "bench_tests_tiny")
        c = dict(tiny.TINY_CONFIG, name="tiny")
    p0 = dense.init_params(c, jax.random.PRNGKey(3))
    rng = np.random.default_rng(SEED)
    t = c["train"]
    batches = []
    for _ in range(3):
        toks = rng.integers(0, c["vocab_size"], (t["global_batch"], t["seq_len"] + 1))
        batches.append({"inputs": jnp.asarray(toks[:, :-1], jnp.int32),
                        "labels": jnp.asarray(toks[:, 1:], jnp.int32)})
    want = dense.train_readings(c, p0, batches, "f32")
    got = blocked.train_readings(c, p0, batches, "f32")
    for k in want:
        np.testing.assert_allclose(got[k], want[k], rtol=REF_TOL, err_msg=k)
    prompt, served = rng.integers(0, c["vocab_size"], 10), rng.integers(0, c["vocab_size"], 6)
    np.testing.assert_allclose(blocked.serve_gaps(c, p0, prompt, served, 64, control=True),
                               dense.serve_gaps(c, p0, prompt, served, 64, control=True),
                               rtol=REF_TOL, atol=1e-5)


if __name__ == "__main__":
    {"harness": case_harness, "engine": case_engine}[sys.argv[1]](*sys.argv[2:])
