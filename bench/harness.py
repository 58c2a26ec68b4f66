"""One run of one cell: set-up, the measured window, the check of what the
window produced, and the result line.

The window drives ``SpecInFRuntime.run``, one training iteration per call.
The runtime is built as ``launch.train.collocated_runtime`` builds it: the
train step of ``runtime.step.make_train_step`` jitted as ``Trainer`` jits
it, and an ``InferenceEngine`` serving the same params in the bubbles of the
traffic file's profile (``core.profiles.<kind>_profile``), with Algorithm 1
(``SpecInFPolicy``) deciding each quantum.
The harness only wraps the train step to time it, submits requests through
``EngineCore.submit``, and reads request state between iterations.

Set-up makes the weights on the device from the seed in one jitted call,
places the training batches on the device, warms every program the window uses (the train step, the decode loop at
each ``k`` bucket, the prefill-chunk program) with requests that are not
the window's, and drives the first three training iterations through the
window's own call: their losses, first gradient and parameter change are
what the check compares with the reference.

Every metric, end to end or per layer, is read by its own file
``metrics/<name>.py`` from one record of the window (see ``run_cell``).
"""
from __future__ import annotations

import contextlib
import gc
import json
import shutil
import sys
import tempfile
import time
import types
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import devtrace as tr  # noqa: E402
import generator  # noqa: E402
import work  # noqa: E402
from catalog import Catalog  # noqa: E402

CHECK_STEPS = 3
#: distinct training batches made and placed on the device in set-up; the
#: window's steps take them in turn, the first CHECK_STEPS are the checked
FEED_RING = 256


class NoChip(RuntimeError):
    pass


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def seed_key(seed: int):
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), seed >> 32)


# ---------------------------------------------------------------------------
# Configuration file -> the program's configuration objects
# ---------------------------------------------------------------------------


def model_config(c: dict):
    from repro.configs.base import ModelConfig

    return ModelConfig(
        name=c["name"], family=c["family"], num_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"], head_dim=c["head_dim"], qk_norm=c.get("qk_norm", False),
        rope_theta=float(c["rope_theta"]),
        norm_type="rmsnorm" if c["norm"] == "rmsnorm" else "layernorm",
        parametric_norm=c["norm"] == "rmsnorm", tie_embeddings=c["tie_word_embeddings"],
    )


def train_config(c: dict):
    from repro.configs.base import TrainConfig

    t = c["train"]
    keys = ("learning_rate", "warmup_steps", "total_steps", "weight_decay", "beta1", "beta2",
            "eps", "grad_clip_norm", "schedule", "param_dtype", "compute_dtype",
            "remat_policy", "zero1", "fsdp")
    return TrainConfig(**{k: t[k] for k in keys})


# ---------------------------------------------------------------------------
# Compilations inside the window
# ---------------------------------------------------------------------------


class CompileCounter:
    EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "traces",
              "/jax/core/compile/backend_compile_duration": "compiles"}

    def __init__(self):
        import jax

        self.armed = False
        self.counts = {"traces": 0, "compiles": 0}
        self.names = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.armed and event in self.EVENTS:
            self.counts[self.EVENTS[event]] += 1
            self.names.append(kw.get("fun_name"))


# ---------------------------------------------------------------------------
# The run
# ---------------------------------------------------------------------------


def counters(engine) -> dict:
    return {k: v["value"] for k, v in engine.obs.metrics.snapshot().items()
            if v["type"] == "counter"}


def pctl(xs, q):
    return float(np.percentile(np.asarray(xs, np.float64), q)) if xs else float("nan")


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *, root: Path,
             t_start: float, require_tpu: bool = True, controls: bool = False,
             keep_trace: str = None) -> dict:
    import jax
    import jax.numpy as jnp

    cat = Catalog(root)
    cell = cat.workload(workload)
    cfgd = cat.config(cell["config"])
    traf = cat.traffic(cell["traffic"])
    devices = jax.devices()
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind, "count": len(devices)}
    log(f"device: platform={d0.platform} kind={d0.device_kind} count={len(devices)}")
    if require_tpu and d0.platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {d0.platform!r})")
    if len(devices) < cell["chips"]:
        raise NoChip(f"the cell needs {cell['chips']} chips, JAX sees {len(devices)}")
    peaks = cat.peaks(d0.device_kind) if require_tpu else None

    sys.path.insert(0, str(Path(root) / "src"))
    from repro.core import SpecInFRuntime, profiles
    from repro.configs.base import SpecInFConfig
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_dev_mesh
    from repro.runtime.step import make_train_step
    from repro.serving.core import Grant, Priority, SamplingParams
    from repro.serving.engine import InferenceEngine

    log(f"compile cache: {enable_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = CompileCounter()
    ref = cat.reference(cfgd["reference"])
    mcfg, tcfg = model_config(cfgd), train_config(cfgd)
    t = cfgd["train"]
    B, S = t["global_batch"], t["seq_len"]
    mesh = make_dev_mesh(data=t["mesh"]["data"], model=t["mesh"]["model"])
    gen = generator.Traffic(traf, cfgd["vocab_size"])

    # -- the train step, jitted as Trainer jits it, and its state from the seed
    art = make_train_step(mcfg, tcfg, mesh)
    step_fn = art.jitted(donate=False)

    def make_state(key):
        p = ref.init_params(cfgd, key)
        z = lambda x: jnp.zeros(x.shape, jnp.float32)
        return {"params": p, "opt": {"mu": jax.tree.map(z, p), "nu": jax.tree.map(z, p),
                                     "step": jnp.zeros((), jnp.int32)}}

    want = art.abstract_state()
    got = jax.eval_shape(make_state, seed_key(0))
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise RuntimeError("the reference's weights do not match the program's train state")
    state = jax.jit(make_state, out_shardings=art.state_shardings())(seed_key(seed))
    params0 = state["params"]

    rec = types.SimpleNamespace(train_s=[], losses=[], gnorms=[], grad_leaf=None,
                                change_leaf=None, check_batches=[])
    leaf_norms = jax.jit(lambda tree: [jnp.sqrt(jnp.sum(jnp.square(x)))
                                       for x in jax.tree.leaves(tree)])
    change_norms = jax.jit(lambda a, b: [jnp.sqrt(jnp.sum(jnp.square(x - y)))
                                         for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))])
    def annotate(name):
        if not trace:
            return contextlib.nullcontext()
        return jax.profiler.TraceAnnotation(tr.SPAN_PREFIX + name)

    # -- the input pipeline: every batch made from the seed and placed with
    # the step's batch shardings before the window, as a prefetching loader
    # would have it ready
    rng = np.random.default_rng([seed, 5])
    sh = art.batch_shardings()
    ring = []
    for i in range(FEED_RING):
        toks = rng.integers(0, cfgd["vocab_size"], (B, S + 1)).astype(np.int32)
        if i < CHECK_STEPS:
            rec.check_batches.append({"inputs": toks[:, :-1], "labels": toks[:, 1:]})
        ring.append({"inputs": jax.device_put(toks[:, :-1], sh["inputs"]),
                     "labels": jax.device_put(toks[:, 1:], sh["labels"])})
    jax.block_until_ready(ring)

    def feed():
        i = 0
        while True:
            yield ring[i % FEED_RING]
            i += 1

    def train_step(st, batch):
        with annotate("train_step"):
            t0 = time.perf_counter()
            new, m = step_fn(st, batch)
            jax.block_until_ready(m["loss"])
            rec.train_s.append(time.perf_counter() - t0)
        n = len(rec.losses)
        if n < CHECK_STEPS:  # set-up only: the readings the check compares
            rec.losses.append(float(m["loss"]))
            rec.gnorms.append(float(m["grad_norm"]))
            if n == 0:  # AdamW's first moment after one step is (1 - b1) g
                rec.grad_leaf = [float(x) / (1 - t["beta1"])
                                 for x in leaf_norms(new["opt"]["mu"])]
            if n == CHECK_STEPS - 1:
                rec.change_leaf = [float(x) for x in change_norms(new["params"], params0)]
        return new, m

    # -- the engine and the runtime, as launch.train.collocated_runtime builds them
    es = traf["engine"]
    engine = InferenceEngine(mcfg, state["params"], max_slots=es["max_slots"],
                             max_seq=es["max_seq"])
    # the bubble profile by its kind: ``core.profiles.<kind>_profile`` with
    # the traffic file's parameters
    pf = dict(traf["profile"])
    profile = getattr(profiles, pf.pop("kind") + "_profile")(mcfg.name, **pf)
    rt = SpecInFRuntime(
        train_step=train_step, train_state=state, batch_iter=feed(),
        profile=profile,
        engine=engine, cfg=SpecInFConfig(), decode_microstep_s=traf["decode_microstep_s"],
    )
    del state
    core = rt.core
    mt = core.policy.microstep_tokens

    # -- warm-up: every k bucket and the prefill-chunk program, on requests
    # that are not the window's
    warm = [core.submit(r.prompt, SamplingParams(max_new_tokens=r.max_new),
                        priority=Priority.OFFLINE) for r in gen.warmup(seed)]
    ks = list(traf["warmup"]["k_buckets"])
    while not all(w.state.finished for w in warm):
        k = ks.pop(0) if ks else max(traf["warmup"]["k_buckets"])
        core.step(Grant(tokens=k * mt, online_ok=False, now=rt._vnow, max_cost_steps=k))
    if ks:
        raise RuntimeError(f"warm-up requests finished before k buckets {ks} ran")
    if traf.get("online"):
        # online traffic preempts offline slots and resumes them from their
        # cached prompt pages: fill every slot, then admit an online request
        warm = [core.submit(r.prompt, SamplingParams(max_new_tokens=r.max_new),
                            priority=Priority.OFFLINE)
                for r in gen.warmup(seed + 1, es["max_slots"])]
        core.step(Grant(tokens=mt, online_ok=False, now=rt._vnow, max_cost_steps=1))
        r = gen.warmup(seed + 2, 1)[0]
        warm.append(core.submit(r.prompt, SamplingParams(max_new_tokens=r.max_new),
                                priority=Priority.ONLINE))
        while not all(w.state.finished for w in warm):
            core.step(Grant(tokens=8 * mt, online_ok=True, now=rt._vnow, max_cost_steps=8))
        if not any(w.preemptions for w in warm):
            raise RuntimeError("the warm-up preempted no offline request")

    warm_decode_placements(engine, mesh, traf["warmup"]["k_buckets"])

    offline = gen.offline(seed)
    submitted = []  # every window-side EngineRequest, in submission order

    def top_up():
        while len(core.waiting[Priority.OFFLINE]) < traf["offline"]["queue_depth"]:
            r = next(offline)
            submitted.append(core.submit(r.prompt, SamplingParams(max_new_tokens=r.max_new),
                                         priority=Priority.OFFLINE, arrival_time=rt._vnow))

    # -- the first iterations, through the window's own call and feed
    for _ in range(CHECK_STEPS):
        top_up()
        rt.run(1)
    del params0

    # -- the window
    online = gen.online(seed, seconds + traf.get("online", {}).get("drain_s", 0) + 600)
    onl = []  # [due, submitted_at, first_seen, done_seen, EngineRequest]
    tracer = engine.obs.tracer
    outs_hist = {}  # request id -> [(tracer event index, outputs so far)]

    def poll(now):
        for o in onl:
            cr = o[4]
            if o[2] is None and cr.output_tokens:
                o[2] = now
            if o[3] is None and cr.state.finished:
                o[3] = now
                o[2] = now if o[2] is None else o[2]
        ev = len(tracer.events)
        for cr in list(core.slot_requests.values()) + list(core.waiting[Priority.OFFLINE]) + \
                list(core.waiting[Priority.ONLINE]):
            h = outs_hist.setdefault(cr.request_id, [])
            if not h or h[-1][1] != len(cr.output_tokens):
                h.append((ev, len(cr.output_tokens)))

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        jax.profiler.start_trace(trace_dir)
    top_up()
    poll(0.0)
    c0 = counters(engine)
    ev0 = len(tracer.events)
    start_outs = {cr.request_id: len(cr.output_tokens) for cr in submitted}
    it_s = []
    next_due = 0
    compiles.armed = True
    jax.config.update("jax_explain_cache_misses", True)  # say why, should one trace
    win_span = annotate("window")
    win_span.__enter__()
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    while True:
        ti = time.perf_counter()
        now = ti - t0
        if now >= seconds:
            break
        with annotate("harness"):
            while next_due < len(online) and online[next_due].due_s <= now:
                r = online[next_due]
                cr = core.submit(r.prompt, SamplingParams(max_new_tokens=r.max_new),
                                 priority=Priority.ONLINE, arrival_time=rt._vnow)
                onl.append([r.due_s, now, None, None, cr])
                next_due += 1
            top_up()
        with annotate("run"):
            rt.run(1)
        with annotate("harness"):
            poll(time.perf_counter() - t0)
        it_s.append(time.perf_counter() - ti)
    t1 = time.perf_counter()
    win_span.__exit__(None, None, None)
    compiles.armed = False
    jax.config.update("jax_explain_cache_misses", False)
    window_s = t1 - t0
    c1 = counters(engine)
    ev1 = len(tracer.events)
    end_outs = {cr.request_id: len(cr.output_tokens) for cr in submitted + [o[4] for o in onl]}
    iters = len(it_s)
    steps = len(rec.train_s) - CHECK_STEPS
    if trace:
        jax.profiler.stop_trace()
    win_train_s = rec.train_s[CHECK_STEPS:]

    # -- online requests due in the window that have not finished: keep the
    # runtime going (arrivals too) until they do, at most drain_s
    in_window = list(onl)
    drain_s = traf.get("online", {}).get("drain_s", 0)
    while any(o[3] is None for o in in_window) and time.perf_counter() - t1 < drain_s:
        now = time.perf_counter() - t0
        while next_due < len(online) and online[next_due].due_s <= now:
            r = online[next_due]
            cr = core.submit(r.prompt, SamplingParams(max_new_tokens=r.max_new),
                             priority=Priority.ONLINE, arrival_time=rt._vnow)
            onl.append([r.due_s, now, None, None, cr])
            next_due += 1
        top_up()
        rt.run(1)
        poll(time.perf_counter() - t0)
    mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices[: cell["chips"]])
    device["memory_peak_bytes"] = int(mem)

    # -- what the window did
    delta = {k: c1.get(k, 0) - c0.get(k, 0) for k in c1}
    log(f"window: {window_s:.3f}s, {iters} iterations, {steps} train steps, "
        f"{delta.get('core/generated_tokens/offline', 0)} offline tokens, "
        f"{delta.get('core/generated_tokens/online', 0)} online tokens, "
        f"{delta.get('core/preemptions', 0)} preemptions")
    log(f"compilations inside the window: {compiles.counts} (expected 0) {compiles.names}")
    if it_s:
        i = int(np.argmax(it_s))
        log(f"longest iteration: {it_s[i] * 1e3:.1f} ms at {sum(it_s[:i]):.1f} s (median "
            f"{pctl(it_s, 50) * 1e3:.1f} ms), its train step "
            f"{win_train_s[i] * 1e3 if i < steps else float('nan'):.1f} ms")
    log(f"peak device memory: {mem} bytes ({mem / 2**30:.2f} GiB)")
    ttft = [(o[2] - o[0]) * 1e3 for o in in_window if o[2] is not None]
    lat = [(o[3] - o[0]) * 1e3 for o in in_window if o[3] is not None]
    late = [(o[1] - o[0]) * 1e3 for o in in_window]
    if in_window:
        log(f"online: {len(in_window)} due in the window, {len(lat)} finished; submitted late "
            f"by p50 {pctl(late, 50):.2f} ms, max {max(late):.2f} ms; TTFT p50 "
            f"{pctl(ttft, 50):.1f} p95 {pctl(ttft, 95):.1f} ms; latency p50 "
            f"{pctl(lat, 50):.1f} p95 {pctl(lat, 95):.1f} ms")
        half = len(in_window) // 2
        first = [(o[2] - o[0]) * 1e3 for o in in_window[:half] if o[2] is not None]
        second = [(o[2] - o[0]) * 1e3 for o in in_window[half:] if o[2] is not None]
        log(f"online TTFT p50 of the first half of arrivals {pctl(first, 50):.1f} ms, of the "
            f"second half {pctl(second, 50):.1f} ms (a growing queue reads higher later)")

    # -- what the readers (``metrics/<name>.py``) see
    reqs = {cr.request_id: cr for cr in submitted + [o[4] for o in onl]}
    spans = [] if tracer.dropped else tracer.events[ev0:ev1]  # the core's records
    win = types.SimpleNamespace(
        window_s=window_s, setup_s=setup_s, iterations=iters, train_steps=steps,
        chips=cell["chips"], tokens_per_step=B * S, counters=delta,
        iteration_s=it_s, train_s=win_train_s, max_slots=es["max_slots"],
        page=engine.kv_page_size, chunk=engine.prefill_chunk, config=cfgd, peaks=peaks,
        online_ttft_ms=ttft, online_latency_ms=lat, requests=reqs, spans=spans,
        decoded=[] if tracer.dropped else work.decoded_lengths(reqs, start_outs, end_outs),
        prefilled=work.prefill_ranges(spans, ev0, reqs, outs_hist),
        decode_microsteps=work.decode_microsteps(spans), trace=None)
    gen_tokens = (delta.get("core/generated_tokens/offline", 0)
                  + delta.get("core/generated_tokens/online", 0))
    log(f"accounting: {len(win.decoded)} decoded + {len(win.prefilled)} prefill first tokens "
        f"vs {gen_tokens} generated (counter); {sum(e - s for s, e in win.prefilled)} "
        f"prefilled vs {delta.get('engine/prefill_metered_tokens', 0)} metered (counter); "
        f"{win.decode_microsteps} decode microsteps")
    summary = None
    if trace:
        t_rd = time.perf_counter()
        events = tr.read_xspace(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        log(f"trace read in {time.perf_counter() - t_rd:.1f}s")
        if keep_trace:
            Path(keep_trace).write_text(json.dumps(events))
        summary = win.trace = tr.summarize(events)
        log(f"trace: planes {tr.device_planes(events)}, {len(events)} events; programs "
            f"{summary and summary['programs']}")
        if summary is not None:
            device["busy_s"] = summary["busy_s"]
            device["window_s"] = summary["window_s"]
    metrics = {}
    for m in cat.per_layer(workload) if trace else cat.end_to_end(workload):
        v = cat.reader(m["name"]).read(win)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    # -- the check: free the program's state, then the reference
    sample = serve_sample(seed, submitted + [o[4] for o in onl], n=8)
    prog = {"loss": rec.losses, "gnorm": rec.gnorms, "grad_leaf": rec.grad_leaf,
            "change_leaf": rec.change_leaf}
    batches = rec.check_batches
    del rt, engine, core, warm
    gc.collect()
    import oracle

    t_chk = time.perf_counter()
    checks, control = oracle.check(cfgd, ref, seed_key(seed), prog, batches, sample,
                                   es["max_seq"], controls=controls)
    log(f"check: {time.perf_counter() - t_chk:.1f}s over {len(sample)} served requests, "
        f"{sum(len(s[1]) for s in sample)} served tokens")
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    attempted = steps + len(in_window) + (delta.get("core/finished/offline", 0))
    failed = sum(1 for o in in_window if o[3] is None) + sum(
        1 for o in in_window if o[4].state.finished and o[4].finish_reason != "length")
    result = {"correct": correct, "attempted": int(attempted), "failed": int(failed),
              "metrics": metrics, "device": device}
    if summary is not None:
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    result["checks"] = checks
    if controls:
        result["readings"] = {"control": control}
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} limit {c['limit']!r}")
    return result


def warm_decode_placements(engine, mesh, k_buckets) -> None:
    """Compile the engine's decode loop at every k bucket for each placement
    its block tables take in a run.  The engine's programs, fed the
    trainer's params, return the tables placed on the trainer's mesh
    (``P()`` from a prefill, ``P(None, None)`` from the decode loop), while a
    host upload places them on the default device; the jit traces each
    combination anew, so one that the warm-up requests happened not to
    reach would compile inside the window.  The calls run on copies of the
    engine's arrays, which the loop consumes, with no token budget left."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    loop = getattr(engine, "_decode_loop", None)
    if loop is None or "block_tables" not in engine.cache:
        return
    bt = np.asarray(engine.cache["block_tables"])
    placements = (jnp.asarray(bt), jax.device_put(bt, NamedSharding(mesh, P())),
                  jax.device_put(bt, NamedSharding(mesh, P(None, None))))
    for k in k_buckets:
        for tables in placements:
            cache = jax.tree.map(jnp.copy, engine.cache)
            cache["block_tables"] = jnp.copy(tables)
            jax.block_until_ready(loop(engine.params, jnp.copy(engine.tokens), cache,
                                       jnp.zeros((engine.max_slots,), jnp.int32), k=k))


def serve_sample(seed: int, reqs: list, n: int) -> list:
    """The finished requests the check compares: the one with the most
    served tokens, and others drawn from the seed."""
    done = [r for r in reqs if r.state.finished and r.output_tokens]
    done.sort(key=lambda r: r.request_id)
    if not done:
        return []
    longest = max(done, key=lambda r: len(r.output_tokens))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([seed, 6])
    pick = [longest] + [rest[i] for i in rng.permutation(len(rest))[: n - 1]]
    return [(np.asarray(r.prompt), list(r.output_tokens)) for r in pick]
