"""Serving CLI — the EngineCore request-lifecycle surface under Poisson load.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b --smoke \\
      --requests 32 --mean-interval-ms 20

Crash-safe serving (DESIGN.md §11) — journal every request lifecycle to an
append-only write-ahead log, and replay a previous (killed) run's journal
before submitting fresh work:

  PYTHONPATH=src python -m repro.launch.serve --smoke --requests 16 \\
      --journal /tmp/serve.journal.jsonl
  # ... kill it mid-run, then finish the survivors byte-identically:
  PYTHONPATH=src python -m repro.launch.serve --smoke --requests 0 \\
      --journal /tmp/serve.journal.jsonl --restore

All requests are submitted up front (``EngineCore.submit``, ONLINE
priority, explicit arrival times) and the loop just calls
``core.step()``: each quantum drains every admissible arrived request
(the old loop busy-polled ``pending[0]`` and admitted at most one per
pass), picks a responsive k bucket while arrivals are outstanding, and
streams per-request deltas/TTFT/finish reasons back in ``StepOutputs``.

The end-of-run summary reads the metrics registry (DESIGN.md §8): latency
and TTFT percentiles come from the core-recorded histograms, finish
reasons and peak queue depth / pool occupancy from the counters and
per-quantum gauges.  ``--trace PREFIX`` additionally writes the structured
step trace as ``PREFIX.jsonl`` plus a ``PREFIX.chrome.json`` Chrome trace
(open in https://ui.perfetto.dev).
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro import configs
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer as T
from repro.serving.core import Priority, SamplingParams
from repro.serving.engine import InferenceEngine


def summarize(engine: InferenceEngine) -> list:
    """Render the registry's end-of-run summary lines."""
    m = engine.obs.metrics
    lines = []
    reasons = {
        r: m.counter(f"core/finish_reason/{r}").value
        for r in ("stop", "length", "abort", "expired", "error")
    }
    lines.append(
        "[serve] finish reasons: "
        + " ".join(f"{k}={v}" for k, v in reasons.items())
        + f"; preemptions={m.counter('core/preemptions').value}"
    )
    shed = (m.counter("fault/shed/online").value
            + m.counter("fault/shed/offline").value)
    if reasons["expired"] or shed:
        lines.append(
            f"[serve] degradation: expired={reasons['expired']} "
            f"(shed {shed}); starved_quanta="
            f"{m.counter('core/starved_quanta').value}"
        )
    peaks = []
    for name in (
        "core/queue_depth/online", "core/queue_depth/offline",
        "engine/slots_active", "engine/pool/pages_in_use",
    ):
        gauge = m.gauge(name)
        if gauge.samples:
            peaks.append(f"{name.split('/', 1)[1]} peak={gauge.max:g}")
    if peaks:
        lines.append("[serve] gauges: " + "; ".join(peaks))
    for name in ("core/online_latency_s", "core/online_ttft_s"):
        h = m.histogram(name)
        if h.count:
            label = name.rsplit("/", 1)[1].replace("_s", "")
            lines.append(
                f"[serve] {label}: n={h.count} "
                f"p50={h.percentile(50)*1e3:.1f}ms "
                f"p95={h.percentile(95)*1e3:.1f}ms "
                f"max={h.max*1e3:.1f}ms"
            )
    # one row per proposer that actually ran (DESIGN.md §10)
    for prop in ("draft", "ngram", "suffix"):
        rounds = m.counter(f"spec/proposer/rounds/{prop}").value
        if rounds:
            lines.append(
                f"[serve] proposer {prop}: rounds={rounds} "
                f"proposed={m.counter(f'spec/proposer/proposed/{prop}').value} "
                f"accepted={m.counter(f'spec/proposer/accepted/{prop}').value} "
                f"acceptance={m.gauge(f'spec/proposer/acceptance/{prop}').value:.3f}"
            )
    switches = m.counter("spec/proposer/router_switches").value
    fallbacks = m.counter("spec/proposer/no_match_fallbacks").value
    if switches or fallbacks:
        lines.append(
            f"[serve] proposer routing: switches={switches} "
            f"no_match_fallbacks={fallbacks}"
        )
    # crash durability (DESIGN.md §11): journal I/O + replay recovery
    appends = m.counter("journal/appends").value
    if appends:
        lines.append(
            f"[serve] journal: appends={appends} "
            f"fsyncs={m.counter('journal/fsyncs').value} "
            f"bytes={m.counter('journal/bytes').value}"
        )
    restores = m.counter("recovery/restores").value
    if restores:
        lines.append(
            f"[serve] recovery: restores={restores} "
            f"requeued={m.counter('recovery/requeued_waiting').value} "
            f"resumed={m.counter('recovery/resumed_inflight').value} "
            f"replayed_tokens={m.counter('recovery/replayed_tokens').value} "
            f"skipped_finished="
            f"{m.counter('recovery/skipped_finished').value}"
        )
    return lines


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=list(configs.ARCH_IDS), default="qwen3-1.7b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--mean-interval-ms", type=float, default=20.0)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new-tokens", type=int, default=16)
    ap.add_argument(
        "--deadline-ms", type=float, default=None,
        help="queue TTL per request; WAITING past it finishes 'expired'",
    )
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=64)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--trace", metavar="PREFIX", default=None,
        help="write the step trace to PREFIX.jsonl + PREFIX.chrome.json",
    )
    ap.add_argument(
        "--journal", metavar="PATH", default=None,
        help="write-ahead request journal (append-only JSONL, DESIGN.md "
        "§11): submits, transitions, token deltas, and finishes are "
        "logged so a killed run can be recovered with --restore",
    )
    ap.add_argument(
        "--journal-fsync-interval", type=int, default=8,
        help="group-commit interval: fsync the journal every N records "
        "(a crash loses at most the last N appends)",
    )
    ap.add_argument(
        "--restore", action="store_true",
        help="replay the --journal file into the engine before submitting "
        "fresh work: a previous run's unfinished requests re-enter the "
        "queue (mid-flight ones as PREEMPTED) and finish byte-identically",
    )
    ap.add_argument(
        "--proposer", choices=("auto", "draft", "ngram", "none"),
        default="none",
        help="speculation source: 'ngram' is host-only (no draft model); "
        "'draft'/'auto' additionally build a draft pairing; 'auto' routes "
        "between them per quantum (DESIGN.md §10)",
    )
    args = ap.parse_args()
    enable_compile_cache()

    cfg = configs.smoke_config(args.arch) if args.smoke else configs.get_config(args.arch)
    params = T.init_params(cfg, jax.random.PRNGKey(args.seed))
    spec_kw = {}
    if args.proposer != "none":
        from repro.configs.base import SpecDecodeConfig, draft_config

        spec = SpecDecodeConfig(proposer=args.proposer)
        spec_kw["spec"] = spec
        if args.proposer in ("auto", "draft"):
            dcfg = draft_config(cfg, spec)
            spec_kw["draft_cfg"] = dcfg
            spec_kw["draft_params"] = T.init_params(
                dcfg, jax.random.PRNGKey(args.seed + 1)
            )
    t0 = time.monotonic()
    # single clock source: engine timestamps share the arrival timebase
    engine = InferenceEngine(cfg, params, max_slots=args.slots,
                             max_seq=args.max_seq,
                             clock=lambda: time.monotonic() - t0,
                             **spec_kw)
    engine.obs.tracer.enabled = args.trace is not None
    core = engine.core

    journal = None
    if args.journal is not None:
        from repro.resilience import RequestJournal

        journal = RequestJournal(
            args.journal, fsync_interval=args.journal_fsync_interval
        )
        if args.restore:
            report = journal.recover_into(core)
            print(
                f"[serve] restored {report.restored} requests "
                f"({report.resumed_inflight} mid-flight, "
                f"{report.replayed_tokens} tokens replayed, "
                f"{report.skipped_finished} already finished) from "
                f"{args.journal}"
            )
        journal.attach(core)
    elif args.restore:
        raise SystemExit("--restore requires --journal PATH")

    rng = np.random.default_rng(args.seed)
    arrivals = np.cumsum(
        rng.exponential(args.mean_interval_ms / 1e3, args.requests)
    )
    requests = [
        core.submit(
            rng.integers(0, cfg.vocab_size, args.prompt_len),
            SamplingParams(
                max_new_tokens=args.max_new_tokens,
                deadline_s=(
                    None if args.deadline_ms is None
                    else args.deadline_ms / 1e3
                ),
            ),
            priority=Priority.ONLINE,
            arrival_time=float(arrivals[i]),
        )
        for i in range(args.requests)
    ]
    while core.has_unfinished:
        out = core.step()
        if out.k == 0 and not out.admitted:
            time.sleep(0.001)  # idle until the next arrival
    if journal is not None:
        journal.close()
    total_tokens = sum(len(r.output_tokens) for r in requests)
    dt = time.monotonic() - t0
    print(
        f"[serve] {len(requests)} requests, {total_tokens} tokens in "
        f"{dt:.2f}s ({total_tokens/dt:.1f} tok/s)"
    )
    for line in summarize(engine):
        print(line)
    if args.trace is not None:
        tr = engine.obs.tracer
        tr.write_jsonl(
            args.trace + ".jsonl", metrics=engine.obs.metrics.snapshot()
        )
        tr.write_chrome(args.trace + ".chrome.json")
        print(
            f"[serve] trace: {args.trace}.jsonl "
            f"({len(tr.events)} events, {tr.dropped} dropped); "
            f"{args.trace}.chrome.json (load in https://ui.perfetto.dev)"
        )


if __name__ == "__main__":
    main()
