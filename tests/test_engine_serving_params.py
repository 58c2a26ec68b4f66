"""The engine serves a compute-dtype copy of its params, cast by one
program on first use and kept until it is released (DESIGN.md §3, "Serving
params").

* the served trees: matrices in the compute dtype, vectors float32, buffers
  of the engine's own, one ``engine/serving_param_casts`` per cast tree and
  none while serving until ``release_params`` frees them;
* numerics: across the dense, MoE, SSM and hybrid families, the served copy
  gives the logits and tokens the programs give when fed the float32 tree
  and cast it themselves;
* the programs: fed the served copy, the decode loop and the chunked
  prefill hold no conversion of a float32 weight.
"""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.configs.base import draft_config
from repro.models import transformer as T
from repro.serving.core import Priority, SamplingParams
from repro.serving.engine import InferenceEngine, Request

CFG = configs.smoke_config("qwen3-1.7b")
PARAMS = T.init_params(CFG, jax.random.PRNGKey(0))
DCFG = draft_config(CFG)
DPARAMS = T.init_params(DCFG, jax.random.PRNGKey(5))
CASTS = "engine/serving_param_casts"


def _buffers(tree) -> set:
    return {x.unsafe_buffer_pointer() for x in jax.tree.leaves(tree)}


def _f32(x) -> np.ndarray:
    return np.asarray(x, np.float32)


@pytest.mark.parametrize("draft", [False, True], ids=["plain", "draft"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_engine_serves_each_tree_in_its_compute_dtype_cast_once(dtype, draft):
    kw = {"draft_cfg": DCFG, "draft_params": DPARAMS} if draft else {}
    eng = InferenceEngine(CFG, PARAMS, max_slots=2, max_seq=64,
                          compute_dtype=dtype, **kw)
    casts = eng.obs.metrics.counter(CASTS)
    assert casts.value == 0  # nothing is cast before it is served
    served = [(PARAMS, eng.params)]
    if draft:
        served.append((DPARAMS, eng.draft_params))
    cast = dtype == jnp.bfloat16
    for given, tree in served:
        assert jax.tree.structure(tree) == jax.tree.structure(given)
        for g, s in zip(jax.tree.leaves(given), jax.tree.leaves(tree)):
            assert s.shape == g.shape
            assert s.dtype == (dtype if g.ndim > 1 else jnp.float32)
            np.testing.assert_array_equal(_f32(s), _f32(g.astype(s.dtype)))
        if cast:  # a copy of the engine's own, not the caller's buffers
            assert not _buffers(tree) & _buffers(given)
        else:  # a float32 engine serves the tree as it is
            assert tree is given
    expected = 1 + draft if cast else 0
    assert casts.value == expected
    core = eng.core
    core.submit(np.arange(1, 20) % CFG.vocab_size,
                SamplingParams(max_new_tokens=8), priority=Priority.OFFLINE)
    for _ in range(3):
        core.step()
    assert eng.generated_tokens_total > 0
    assert casts.value == expected, "serving must never recast"


def test_release_frees_the_copy_and_the_next_call_casts_it_again():
    eng = InferenceEngine(CFG, PARAMS, max_slots=2, max_seq=64)
    casts = eng.obs.metrics.counter(CASTS)
    first = eng.params
    held = eng.memory_bytes()
    eng.release_params()
    assert casts.value == 1 and not eng._served
    core = eng.core
    core.submit(np.arange(1, 9), SamplingParams(max_new_tokens=4),
                priority=Priority.OFFLINE)
    core.step()
    assert casts.value == 2
    second = eng.params
    assert second is not first and eng.memory_bytes() == held
    for a, b in zip(jax.tree.leaves(first), jax.tree.leaves(second)):
        np.testing.assert_array_equal(_f32(a), _f32(b))


FAMILIES = {
    "dense": "qwen3-1.7b",
    "moe": "moonshot-v1-16b-a3b",
    "ssm": "falcon-mamba-7b",
    "hybrid": "zamba2-2.7b",
}


def _serve(engine, cases) -> list:
    reqs = [Request(prompt=np.arange(1, n + 1), max_new_tokens=m)
            for n, m in cases]
    for r in reqs:
        assert engine.add_request(r)
    guard = 100
    while engine.num_active and guard:
        engine.decode_loop(4)
        guard -= 1
    assert engine.num_active == 0
    return [r.generated for r in reqs]


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_served_copy_matches_the_programs_fed_float32_params(family):
    """The served copy changes where the cast happens, not what is served:
    logits equal to atol=0, and greedy streams (chunked prefill on the
    attention families, bucket prefill on the recurrent ones, then the fused
    decode loop) identical to an engine whose programs cast the float32
    tree on every call."""
    cfg = configs.smoke_config(FAMILIES[family])
    params = T.init_params(cfg, jax.random.PRNGKey(3))
    eng = InferenceEngine(cfg, params, max_slots=2, max_seq=64)
    assert jax.tree.leaves(eng.params)[0].dtype == jnp.bfloat16

    prompt = (jnp.arange(24, dtype=jnp.int32) * 7 % cfg.vocab_size)[None]
    prefill = jax.jit(functools.partial(T.prefill, cfg, max_seq=64))
    decode = jax.jit(functools.partial(T.decode_step, cfg))
    out = {}
    for name, tree in (("served", eng.params), ("f32", params)):
        logits, cache = prefill(tree, prompt)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        step_logits, _ = decode(tree, tok, cache)
        out[name] = (logits, step_logits)
    for a, b in zip(out["served"], out["f32"]):
        np.testing.assert_allclose(_f32(a), _f32(b), rtol=0, atol=0)

    # an engine whose programs are fed the float32 tree it was given casts
    # it inside every program, on every call
    ref = InferenceEngine(cfg, params, max_slots=2, max_seq=64)
    ref._serving = lambda model: ref._given[model]
    cases = [(5, 12), (40, 9)]  # 40 streams as two 32-token chunks
    assert _serve(eng, cases) == _serve(ref, cases)


def _f32_weight_converts(hlo: str, shapes: set) -> list:
    """``stablehlo.convert`` operations whose operand is a float32 tensor
    of one of ``shapes``."""
    found = []
    for m in re.finditer(r"stablehlo\.convert[^\n]*\(tensor<([0-9x]+)xf32>\)",
                         hlo):
        shape = tuple(int(d) for d in m.group(1).split("x"))
        if shape in shapes:
            found.append(shape)
    return found


def test_engine_programs_convert_no_float32_weight():
    """Lowered with the engine's params, the decode loop and the chunked
    prefill hold no conversion of a float32 embedding or stacked-layer
    matrix; lowered with the float32 tree, they do (the check can fail)."""
    b = 3  # != num_layers, so no [B, ...] activation shares a weight's shape
    eng = InferenceEngine(CFG, PARAMS, max_slots=b, max_seq=64)
    shapes = {x.shape for x in jax.tree.leaves(
        {"embed": PARAMS["embed"], "layers": PARAMS["layers"]}) if x.ndim > 1}

    def lowered(tree):
        decode = eng._decode_loop.lower(
            tree, eng.tokens, eng.cache, jnp.zeros((b,), jnp.int32), k=2,
        ).as_text()
        prefill = eng._prefill_chunks.lower(
            tree, jnp.zeros((b, eng.prefill_chunk), jnp.int32),
            jnp.zeros((b,), jnp.int32), eng.cache,
        ).as_text()
        return decode, prefill

    for hlo in lowered(eng.params):
        assert _f32_weight_converts(hlo, shapes) == []
    for hlo in lowered(PARAMS):
        assert PARAMS["embed"].shape in _f32_weight_converts(hlo, shapes)
        assert PARAMS["layers"]["attn"]["wq"].shape in _f32_weight_converts(
            hlo, shapes)
