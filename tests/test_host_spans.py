"""Host spans in a profiler trace, and the names of the engine's programs.

A tiny collocated run (virtual-clock SpecInF fill over a real engine) under
``jax.profiler`` must show every ``specinf.*`` span of ``repro.obs.trace``
on the host plane, each nested in the span that calls it, with every
quantum record joined by its ``seq`` to the ``core.step`` that collected
it.  The engine's jitted programs carry their model function's name
(``jit_decode_loop``, not ``jit__unknown``) with their buffer donation
intact.
"""
import glob
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.configs.base import SpecInFConfig
from repro.core import SpecInFRuntime
from repro.core.profiles import dp_profile
from repro.models import transformer as T
from repro.obs.trace import SPAN_PREFIX
from repro.serving.core import Priority, SamplingParams
from repro.serving.engine import InferenceEngine

#: span -> the spans it may sit directly inside (None: outermost)
PARENTS = {
    "runtime.train_step": {None},
    "runtime.fill": {None},
    "runtime.monitor": {None, "runtime.fill"},
    "core.step": {"runtime.fill"},
    "core.plan": {"core.step"},
    "core.admit": {"core.step"},
    "core.collect": {"core.step"},
    "core.record": {"core.step"},
    "engine.prefill": {"core.step"},
    "engine.decode": {"core.step"},
    "engine.fetch": {"core.step", "core.admit", "engine.decode"},
    "engine.tables": {"engine.decode", "engine.prefill", "core.admit",
                      "core.collect"},
}


@pytest.fixture(scope="module")
def engine():
    cfg = configs.smoke_config("olmo-1b")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    return InferenceEngine(cfg, params, max_slots=2, max_seq=64)


def _submit(core, n, seed):
    rng = np.random.default_rng(seed)
    for _ in range(n):
        core.submit(rng.integers(0, 256, 40), SamplingParams(max_new_tokens=6),
                    priority=Priority.OFFLINE, arrival_time=0.0)


def _host_spans(trace_dir):
    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    out.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                ev.name[len(SPAN_PREFIX):], dict(ev.stats)))
    return sorted(out, key=lambda e: (e[0], -e[1]))


def test_profile_holds_every_span_nested_and_joined_by_seq(engine, tmp_path):
    rt = SpecInFRuntime(
        train_step=lambda s, b: (s, {"loss": jnp.float32(0)}), train_state=None,
        batch_iter=itertools.repeat({}),
        profile=dp_profile("tiny", compute_s=0.03, comm_s=0.04),
        engine=engine, cfg=SpecInFConfig(busy_hold_ms=5.0),
        decode_microstep_s=0.002,
    )
    core = engine.core
    _submit(core, 2, 0)  # warm-up: compiles what the profiled window runs
    while core.has_unfinished:
        rt.run(1)
    _submit(core, 2, 1)
    tracer = engine.obs.tracer
    ev0 = len(tracer.events)
    jax.profiler.start_trace(str(tmp_path))
    try:
        while core.has_unfinished:
            rt.run(1)
    finally:
        jax.profiler.stop_trace()
    spans = _host_spans(tmp_path)
    assert {name for _, _, name, _ in spans} == set(PARENTS)
    stack = []
    for s, e, name, _ in spans:
        while stack and stack[-1][1] < e:
            stack.pop()
        parent = stack[-1][2] if stack else None
        assert parent in PARENTS[name], (name, parent)
        stack.append((s, e, name))
    # the fill keeps a quantum in flight: a core.step span carries the seq
    # of the first quantum record it collected and how many, and each
    # record is collected in exactly one
    seqs = [ev["seq"] for ev in tracer.events[ev0:] if ev["type"] == "quantum"]
    joined = []
    for _, _, name, st in spans:
        if name == "core.step" and "seq" in st:
            i = seqs.index(st["seq"])
            joined += seqs[i:i + st["quanta"]]
    assert seqs and joined == seqs


def test_engine_programs_carry_their_model_function_names(engine):
    b = engine.max_slots
    decode = engine._decode_loop.lower(
        engine.params, engine.tokens, engine.cache, jnp.zeros((b,), jnp.int32),
        k=2,
    ).as_text()
    assert "module @jit_decode_loop" in decode
    assert "tf.aliasing_output" in decode, "donation must survive the name"
    prefill = engine._prefill_chunks.lower(
        engine.params, jnp.zeros((b, engine.prefill_chunk), jnp.int32),
        jnp.zeros((b,), jnp.int32), engine.cache,
    ).as_text()
    assert "module @jit_prefill_chunks_into_slots" in prefill
    assert "tf.aliasing_output" in prefill
    names = {prog.__wrapped__.__name__ for prog in (
        engine._decode, engine._prefill_slot, engine._suffix_prefill)}
    assert names == {"decode_step", "prefill_into_slot_paged",
                     "prefill_suffix_into_slot"}
