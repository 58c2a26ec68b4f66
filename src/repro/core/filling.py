"""Speculative-filling executors binding real JAX compute to the paper's
control plane (monitor -> Algorithm 1 -> barrier / pull-and-execute).

Two modes (DESIGN.md §2):

* ``SpecInFRuntime`` — host-interleaved: each training iteration dispatches
  the real jitted train step, then the collective window (the bubble, whose
  span comes from the iteration profile) is filled with real inference-engine
  microsteps admitted by Algorithm 1.  On CPU the device serializes, so
  *timing* flows on a virtual clock driven by the profile while *compute* is
  real — functional truth with calibrated time (documented limitation).

* ``make_collocated_step`` — the beyond-paper fused program: train_step and
  k decode microsteps compiled into ONE jitted function with no data
  dependence between them, so the XLA scheduler may overlap inference compute
  with training collectives.  k is bucketed to avoid recompiles; Algorithm 1
  picks the bucket each iteration.

Engines built with a draft/target pairing route every quantum through the
speculative loop instead, and the token grant is spent in *verified*
tokens: the gamma controller (``spec.controller``) maps Algorithm-1's
phase + observed acceptance to a draft length, and the k bucket is sized
by the expected verified-token yield per round (DESIGN.md §4).

Since the EngineCore redesign (DESIGN.md §6) Algorithm 1 is ONE pluggable
``SchedulerPolicy`` (``SpecInFPolicy``): the runtime feeds each monitor
window's decision to ``EngineCore.step(grant)`` and the policy decides
admission (online pull-and-execute, preempting RUNNING offline slots when
capacity blocks), the offline token metering, and the k/gamma quantum
shape.
"""
from __future__ import annotations

import math
from typing import Any, Callable, Optional

import jax
import numpy as np

from repro.configs.base import SpecInFConfig
from repro.core.bubble_monitor import BubbleMonitor
from repro.core.profiles import IterationProfile
from repro.core.scheduler import AdaptiveKernelScheduler, Status
from repro.obs import Observability
from repro.obs.trace import _num as _jnum
from repro.resilience.faults import FaultInjector
from repro.serving.core import (
    Grant,
    Priority,
    RequestState,
    RevocationSignal,
    SamplingParams,
    SchedulerPolicy,
    StepOutputs,
    StepPlan,
    largest_bucket,
)
from repro.serving.engine import InferenceEngine, Request
from repro.spec.controller import AdaptiveGammaController


class FillingMetrics:
    """Run-level metrics for one SpecInF filling run.

    Since the observability layer (DESIGN.md §8) the latency/TTFT
    distributions and the lifecycle counters are DERIVED VIEWS over the
    engine's metrics registry: the core records every sample once, as it
    happens, on the engine's single clock, and this class projects the
    run's slice of it.  Baselines snapshot the registry at construction, so
    a pre-warmed engine never leaks earlier activity into a fresh run.

    The old unbounded ``online_latencies_s`` / ``online_ttft_s`` list
    fields survive as properties over the registry's streaming histograms:
    while a histogram still holds its raw samples (up to its exact cap) the
    lists — and therefore every percentile — reproduce the historical
    values bit-for-bit; past the cap memory stays bounded and percentiles
    are bin-interpolated (the lists are gone and raise instead of lying).

    Quantities that are *run-local* rather than engine-level (train
    iterations/losses, phase counts, virtual time, offline microsteps,
    spec rounds) stay plain attributes."""

    def __init__(self, obs: Optional[Observability] = None):
        #: engine-less runs (bubble accounting only) get a private registry
        self.obs = obs if obs is not None else Observability(tracing=False)
        m = self.obs.metrics
        self._ttft = m.histogram("core/online_ttft_s")
        self._lat = m.histogram("core/online_latency_s")
        self._ttft_base = self._ttft.count
        self._lat_base = self._lat.count
        self._served = m.counter("core/finished/online")
        self._served_base = self._served.value
        self._offline_tok = m.counter("core/generated_tokens/offline")
        self._offline_tok_base = self._offline_tok.value
        self._preempt = m.counter("core/preemptions")
        self._preempt_base = self._preempt.value
        self.train_iterations = 0
        self.train_losses: list = []
        self.offline_microsteps = 0
        self.virtual_time_s = 0.0
        self.phase_counts: dict = {}
        self.spec_rounds = 0

    # -- registry-backed views -----------------------------------------
    @property
    def online_served(self) -> int:
        return self._served.value - self._served_base

    @property
    def offline_tokens_generated(self) -> int:
        return self._offline_tok.value - self._offline_tok_base

    @property
    def preemptions(self) -> int:
        return self._preempt.value - self._preempt_base

    @property
    def online_latencies_s(self) -> list:
        """Online end-to-end latencies this run (exact list while the
        histogram is under its cap; past it, query the percentiles)."""
        return self._lat.values()[self._lat_base:]

    @property
    def online_ttft_s(self) -> list:
        """Time-to-first-token per online request (arrival -> first output
        token), stamped by the core on the step that produced it — prefill
        skips from prefix-cache hits show up here, where end-to-end latency
        alone would hide them."""
        return self._ttft.values()[self._ttft_base:]

    def _percentile(self, hist, base: int, q: float) -> float:
        if hist.count - base <= 0:
            return float("nan")
        if hist.exact:
            return float(np.percentile(hist.values()[base:], q))
        return hist.percentile(q)

    def p95_latency_s(self) -> float:
        return self._percentile(self._lat, self._lat_base, 95)

    def ttft_percentile_s(self, q: float) -> float:
        return self._percentile(self._ttft, self._ttft_base, q)

    def p95_ttft_s(self) -> float:
        return self.ttft_percentile_s(95)


class SpecInFPolicy(SchedulerPolicy):
    """Algorithm 1 as a ``SchedulerPolicy`` (paper §3.3 -> DESIGN.md §6).

    * ONLINE admission is the pull-and-execute path: gated on the IDLE
      status and arrival time.  When capacity blocks (no free slot, or no
      pool pages), admission preempts a RUNNING OFFLINE slot instead of
      queueing behind it — the paper's p95 protection inside bubbles.
    * OFFLINE quanta spend the Kernel-Barrier token grant, and only run
      when the grant covers one whole quantum (speculative engines spend
      grants in *verified* tokens, so the bar is the expected yield of one
      round at the phase's draft length).
    * Online execution, once admitted, is never token-metered — only its
      admission is gated.
    """

    def __init__(
        self,
        *,
        microstep_tokens: float = 1.0,
        gamma_ctrl: Optional[AdaptiveGammaController] = None,
        preemption: bool = True,
        prefill_token_cost_steps: float = 0.0,
    ):
        #: Kernel-Barrier token cost of one plain microstep (1 token/ms).
        self.microstep_tokens = microstep_tokens
        self.gamma_ctrl = gamma_ctrl
        self.preemption = preemption
        #: profiled per-prefill-token step cost in microstep-equivalents
        #: (DESIGN.md §7): converts a bubble window into a prefill token
        #: budget, so a grant can never be overrun by a long prompt.  0
        #: keeps prefill free in the cost model (the historical behavior).
        self.prefill_token_cost_steps = prefill_token_cost_steps

    def _spec(self, core) -> bool:
        return (
            core.engine.spec_enabled or core.engine.host_spec_enabled
        ) and self.gamma_ctrl is not None

    def min_offline_grant(self, core, phase) -> float:
        """Smallest grant that pays for one whole offline quantum."""
        if self._spec(core):
            g = self.gamma_ctrl.gamma_for(phase)
            return self.gamma_ctrl.expected_tokens_per_round(g)
        return self.microstep_tokens

    def plan(self, core, grant: Grant) -> StepPlan:
        admit = []
        if grant.online_ok:
            admit += [
                cr for cr in core.waiting[Priority.ONLINE]
                if self.eligible(cr, grant)
            ]
        offline_grant_ok = grant.tokens >= self.min_offline_grant(
            core, grant.phase
        )
        if offline_grant_ok:
            admit += [
                cr for cr in core.waiting[Priority.OFFLINE]
                if self.eligible(cr, grant)
            ]
        plan = StepPlan(admit=admit, preempt_to_admit=self.preemption)
        online = [
            cr for cr in list(core.slot_requests.values()) + admit
            if cr.priority is Priority.ONLINE
        ]
        room = max(int(grant.max_cost_steps), 1)
        if online:
            # dedicated quantum: size by the online work's remaining budget
            want = max(max(cr.remaining_budget for cr in online), 1)
            self._size_quantum(plan, core, grant, want)
        elif core.slot_requests or admit:
            # offline quantum: the grant must cover it whole
            if offline_grant_ok:
                if self._spec(core):
                    self._size_quantum(plan, core, grant, grant.tokens)
                else:
                    steps = int(grant.tokens // self.microstep_tokens)
                    plan.k = largest_bucket(min(steps, room))
                    plan.cost_steps = float(plan.k)
        # unified token-budget step (DESIGN.md §7): clamp decode rounds to
        # the grant's token budget, then spend what remains — of both the
        # budget and the bubble room, priced at the profiled per-token
        # cost — on streaming prefill chunks
        decode_tokens = self._clamp_k_to_budget(plan, core, grant)
        self.plan_prefill(core, grant, plan, decode_tokens)
        return plan

    def _size_quantum(self, plan, core, grant, want_tokens: float) -> None:
        """Pick k (and gamma) so the quantum's expected token yield stays
        within ``want_tokens`` and its cost within the bubble room."""
        if self._spec(core):
            g = self.gamma_ctrl.gamma_for(grant.phase)
            exp = self.gamma_ctrl.expected_tokens_per_round(g)
            # grant-aware routing (DESIGN.md §10): model-free host rounds
            # spend ~1 bubble step where a draft round spends
            # 1 + (gamma+1)*cost_ratio — Algorithm-1 grants are priced by
            # what will actually run
            plan.proposer = core.engine.route_proposer(g)
            rc = (
                core.engine.proposer_round_cost(plan.proposer, g)
                if plan.proposer is not None
                else self.gamma_ctrl.round_cost_steps(g)
            )
            afford = max(int(want_tokens / max(exp, 1e-9)), 1)
            left = max(int(grant.max_cost_steps / rc), 1)
            plan.k = largest_bucket(min(afford, left))
            plan.gamma = g
            plan.cost_steps = plan.k * rc
        else:
            room = max(int(grant.max_cost_steps), 1)
            plan.k = largest_bucket(min(room, int(max(want_tokens, 1))))
            plan.cost_steps = float(plan.k)

    def observe(self, outputs: StepOutputs) -> None:
        if self.gamma_ctrl is not None and outputs.spec_proposed:
            self.gamma_ctrl.observe(
                outputs.spec_accepted, outputs.spec_proposed
            )


class SpecInFRuntime:
    """Collocates one training driver with inference engines on a device set,
    running the deployable Algorithm-1 control plane over real JAX compute."""

    def __init__(
        self,
        *,
        train_step: Callable[[Any, Any], tuple[Any, Any]],  # (state, batch) -> (state, metrics)
        train_state: Any,
        batch_iter,
        profile: IterationProfile,
        engine: Optional[InferenceEngine] = None,
        online_requests: Optional[list[Request]] = None,
        cfg: SpecInFConfig = SpecInFConfig(),
        decode_microstep_s: float = 0.005,
        gamma_controller: Optional[AdaptiveGammaController] = None,
        faults: Optional[FaultInjector] = None,
        journal=None,
    ):
        self.train_step = train_step
        self.state = train_state
        self.batch_iter = batch_iter
        self.profile = profile
        self.engine = engine
        self.cfg = cfg
        # Seeded chaos (DESIGN.md §9): one injector shared by every fault
        # point in the stack — the runtime consults ``runtime/early_resume``
        # per bubble, and the same instance is handed down to the engine and
        # page pool so a single seed reproduces the whole fault schedule.
        self.faults = faults
        if faults is not None and engine is not None:
            faults.metrics = engine.obs.metrics
            if engine.fault_injector is None:
                engine.fault_injector = faults
                if engine.pool is not None:
                    engine.pool.fault_injector = faults
        self.monitor = BubbleMonitor(cfg)
        self.scheduler = AdaptiveKernelScheduler(cfg, num_instances=1)
        # metrics share the engine's registry (DESIGN.md §8): the core
        # records TTFT/latency/preemptions as they happen and FillingMetrics
        # is this run's view over them
        self.metrics = FillingMetrics(
            obs=engine.obs if engine is not None else None
        )
        #: host spans (``runtime.train_step`` / ``runtime.fill`` /
        #: ``runtime.monitor``) land on the same bundle
        self.obs = self.metrics.obs
        self.decode_microstep_s = decode_microstep_s
        # Speculative engines spend grants in verified tokens: the gamma
        # controller sizes each round from phase + observed acceptance,
        # parameterized by the engine's draft/target pairing config.
        self.gamma_ctrl = gamma_controller
        if (
            self.gamma_ctrl is None
            and engine is not None
            and (engine.spec_enabled or engine.host_spec_enabled)
        ):
            sc = engine.spec_cfg
            self.gamma_ctrl = AdaptiveGammaController(
                sc.gamma_buckets, ewma=sc.accept_ewma,
                draft_cost_ratio=sc.draft_cost_ratio,
            )
        self._window_s = cfg.window_ms / 1e3
        # Bind the engine to the runtime's virtual clock: every request
        # timestamp then comes from ONE timebase (never mixed with
        # time.monotonic), and latencies are internally consistent.
        self._vnow = 0.0
        #: quanta dispatched whose outputs are not folded in yet
        self._uncollected: list = []
        self.core = None
        self.recovery = None
        if engine is not None:
            engine.clock = lambda: self._vnow
            # Algorithm 1 as the engine core's scheduler policy.  Reusing
            # ``engine.core`` keeps requests admitted through the legacy
            # shim (add_request) in the same lifecycle the runtime steps.
            self.core = engine.core
            self.core.policy = SpecInFPolicy(
                microstep_tokens=decode_microstep_s / 1e-3,
                gamma_ctrl=self.gamma_ctrl,
                prefill_token_cost_steps=cfg.prefill_token_cost_steps,
            )
            # Requests submitted/admitted before this point were stamped on
            # the engine's OLD clock (usually wall time).  Restamp them to
            # the virtual epoch so they are pullable from the first bubble
            # — the same "no mixed timebases" rule the legacy add_request
            # applied to default-arrival offline work.  RUNNING slots are
            # restamped too: a wall-clock arrival would otherwise never
            # satisfy the policy's arrival gate if the slot is preempted
            # and must be re-admitted on the virtual clock.
            tr = engine.obs.tracer
            for q in self.core.waiting.values():
                for cr in q:
                    cr.arrival_time = 0.0
                    tr.restamp_arrival(cr.request_id, 0.0)
            for cr in self.core.slot_requests.values():
                cr.arrival_time = 0.0
                tr.restamp_arrival(cr.request_id, 0.0)
            # Crash durability (DESIGN.md §11): replay any existing journal
            # BEFORE fresh submissions, so a restarted runtime re-arms
            # bubble filling with the previous incarnation's surviving
            # requests already queued (restamped onto the virtual clock —
            # replay runs after the restamp loop above, so its shift-based
            # stamps are not clobbered back to 0), then attach so this
            # incarnation's lifecycle is journaled in turn.
            if journal is not None:
                self.recovery = journal.recover_into(self.core)
                journal.attach(self.core)
            for r in sorted(
                online_requests or [], key=lambda r: r.arrival_time
            ):
                self.core.submit(
                    r.prompt,
                    SamplingParams(max_new_tokens=r.max_new_tokens),
                    priority=(
                        Priority.ONLINE if r.online else Priority.OFFLINE
                    ),
                    arrival_time=r.arrival_time,
                )
        self.journal = journal

    # ------------------------------------------------------------------
    def _observe_windows(self, n: int, activity: int = 0):
        """Feed monitor + Algorithm 1 for ``n`` windows; returns the last
        decision.  One observe per window keeps accounting identical whether
        microsteps run fused or one-by-one.  Runs in the ``runtime.monitor``
        host span."""
        d = None
        with self.obs.span("runtime.monitor"):
            for _ in range(n):
                zc = self.monitor.observe(activity)
                d = self.scheduler.update(zc)
                ph = d.phase.value
                self.metrics.phase_counts[ph] = (
                    self.metrics.phase_counts.get(ph, 0) + 1
                )
        return d

    def _advance_windows(self, span_s: float, activity: int) -> None:
        """Feed the monitor/scheduler for every 2 ms window inside a span."""
        self._observe_windows(
            max(1, int(round(span_s / self._window_s))), activity
        )

    def _fill_bubble(self, bubble_s: float) -> None:
        """Fill a virtual bubble of ``bubble_s`` with real engine compute,
        one quantum at a time (``EngineCore.step_overlapped``: each call
        dispatches a quantum and collects the ones dispatched before it).

        Each pass observes one 2 ms monitor window, converts the
        Algorithm-1 decision into a ``Grant`` (token grant, IDLE gate for
        online admission, phase for the gamma controller, and the bubble
        room as ``max_cost_steps``), and lets ``SpecInFPolicy`` decide what
        the quantum does: admit (preempting offline slots when an online
        arrival is capacity-blocked), pick the k bucket / draft length, and
        drive the fused loop.  The step's cost in microstep-equivalents,
        priced when it is dispatched, advances the virtual clock and the
        monitor window count — the same accounting whether the quantum was
        plain or speculative; its outputs are folded in when collected.

        Revocation (DESIGN.md §9): when the bubble's ``RevocationSignal``
        is armed (seeded early-resume chaos) every grant carries it — a
        revoked quantum ends the fill immediately, the overrun past the
        resume instant is recorded, and the rest of the span is fed to the
        monitor as training activity."""
        if self.engine is None:
            self.metrics.virtual_time_s += bubble_s
            self._advance_windows(bubble_s, activity=0)
            return
        now = self.metrics.virtual_time_s
        tracer = self.engine.obs.tracer
        tracer.span("bubble", "train", now, now + bubble_s, span_s=bubble_s)
        sig, resume_at = self._arm_revocation(now, bubble_s)
        spent = 0.0
        step_cost = self.decode_microstep_s
        revoked = False
        while spent < bubble_s:
            base = now + spent
            if sig is not None and sig.check(base):
                revoked = True  # revoked on a quantum boundary: run nothing
                break
            d = self._observe_windows(1)
            self._vnow = base  # admission/TTFT stamps land at quantum start
            # the monitor/Algorithm-1 state behind this quantum's grant —
            # the core folds it into the quantum trace event
            tracer.window_state = {
                **self.monitor.state(),
                "status": d.status.value,
                "phase": d.phase.value,
                "tokens": _jnum(d.tokens),
            }
            grant = Grant(
                tokens=d.tokens,
                online_ok=d.status is Status.IDLE,
                phase=d.phase,
                now=base,
                max_cost_steps=max((bubble_s - spent) / step_cost, 1.0),
                token_budget=self.cfg.step_token_budget or math.inf,
                # retirement stamps land at quantum END: the core advances
                # the clock once the plan's cost is known, before the loop
                advance_clock=lambda steps, _b=base: setattr(
                    self, "_vnow", _b + steps * step_cost
                ),
                revocation=sig,
                revoke_check_steps=max(self.cfg.revocation_check_steps, 1),
            )
            out = self.core.step_overlapped(grant)
            self._uncollected.append(out)
            self._fold_collected()
            if out.cost_steps <= 0:
                if out.revoked:
                    revoked = True
                    break
                spent += self._window_s
                continue
            dt = out.cost_steps * step_cost
            spent += dt
            self._vnow = base + dt
            # the outer observe covered the quantum's first window
            quanta = max(out.k, int(round(out.cost_steps)))
            self._observe_windows(quanta - 1)
            if out.revoked or (sig is not None and sig.check(self._vnow)):
                # cut mid-plan, or tripped right as the quantum completed
                revoked = True
                break
        if not revoked and sig is not None and sig.check(now + bubble_s):
            # armed inside the span but no quantum was running to cut
            # (tiny bubble, or no grant) — the early resume still happened
            revoked = True
        if revoked:
            m = self.engine.obs.metrics
            m.counter("fault/early_resume").inc()
            m.histogram("fault/revocation_overrun_s").record(
                max(0.0, self._vnow - resume_at)
            )
            self.monitor.notice_activity()
            remaining = bubble_s - spent
            if remaining > 0:
                # training owns the rest of the span: the monitor sees it
                # as active windows, so Algorithm 1 stops granting
                self._advance_windows(remaining, activity=1)
        self.metrics.virtual_time_s += bubble_s
        self._vnow = self.metrics.virtual_time_s

    def _arm_revocation(self, now: float, bubble_s: float):
        """Build this bubble's revocation signal (DESIGN.md §9).

        Chaos: when the injector fires ``runtime/early_resume``, training
        is declared to resume at a seeded fraction (25–75%) of the
        profiled bubble — the signal is armed at that virtual instant,
        and ``EngineCore.step`` must yield within the documented token
        bound once it trips.  Without a fault, a signal is still attached
        whenever ``cfg.revocation_check_steps > 0`` (unarmed, never
        fires) so the sub-dispatch path is exercised; under the default
        config grants carry no signal and the single-dispatch quantum is
        byte-identical to pre-§9 behavior."""
        faults = self.faults
        if faults is not None and faults.should_fire("runtime/early_resume"):
            frac = 0.25 + 0.5 * faults.uniform("runtime/early_resume")
            resume_at = now + frac * bubble_s
            sig = RevocationSignal()
            sig.arm(resume_at, reason="early_resume")
            return sig, resume_at
        if self.cfg.revocation_check_steps > 0:
            return RevocationSignal(), math.inf
        return None, math.inf

    def _fold_collected(self) -> None:
        """Fold the quanta collected since the last call into the run-local
        metrics (``_record_step``), in dispatch order."""
        while self._uncollected and self._uncollected[0].collected:
            self._record_step(self._uncollected.pop(0))

    def _record_step(self, out: StepOutputs) -> None:
        """Fold one quantum's StepOutputs into the RUN-LOCAL metrics.  The
        engine-level quantities the old version stamped here (TTFT/latency
        samples, preemptions, served/offline-token counts) are now recorded
        by the core into the shared registry as they happen —
        ``FillingMetrics`` reads them back as derived views."""
        online_active = any(
            ro.priority is Priority.ONLINE
            and (ro.new_tokens or ro.state is RequestState.RUNNING)
            for ro in out.outputs
        )
        if out.gamma is not None:
            self.metrics.spec_rounds += out.k
        if not online_active:
            self.metrics.offline_microsteps += out.k

    # ------------------------------------------------------------------
    def run(self, num_iterations: int) -> FillingMetrics:
        """Run ``num_iterations`` training iterations: the train step (the
        ``runtime.train_step`` host span, its loss's fetch included), then
        the profile's segments, each bubble filled in a ``runtime.fill``
        span.  The fill keeps a quantum's device work in flight
        (``EngineCore.step_overlapped``), across bubbles too; each
        iteration ends by collecting what is in flight, in a
        ``runtime.fill`` span, so
        every token is on the host when ``run`` returns and no decode loop
        runs beside the next train step.  The engine's serving copy of the
        params is freed before each train step, which then has the memory
        it has without filling, and made again by the iteration's first
        quantum (DESIGN.md §3)."""
        span = self.obs.span
        for _ in range(num_iterations):
            batch = next(self.batch_iter)
            if self.engine is not None:
                self.engine.release_params()
            with span("runtime.train_step"):
                self.state, step_metrics = self.train_step(self.state, batch)
                loss = step_metrics.get("loss")
                if loss is not None:
                    self.metrics.train_losses.append(float(loss))
            for kind, dur in self.profile.segments:
                if kind == "compute":
                    t0 = self.metrics.virtual_time_s
                    self.metrics.virtual_time_s += dur
                    if self.engine is not None:
                        self.engine.obs.tracer.span(
                            "train_compute", "train", t0, t0 + dur
                        )
                    self._advance_windows(dur, activity=1)
                else:
                    with span("runtime.fill"):
                        self._fill_bubble(dur)
            if self.core is not None:
                with span("runtime.fill"):
                    self.core.drain()
                    self._fold_collected()
            self.metrics.train_iterations += 1
        return self.metrics


# ---------------------------------------------------------------------------
# Beyond-paper: fused collocated step (bucketed k)
# ---------------------------------------------------------------------------


def make_collocated_step(
    train_step_fn: Callable,
    decode_step_fn: Callable,
    *,
    k_buckets: tuple[int, ...] = (0, 1, 2, 4, 8),
    decode_loop_fn: Optional[Callable] = None,
):
    """Build jitted fused programs ``{k: fn}`` where fn runs the train step
    plus k chained decode microsteps in one XLA program.  The decode chain
    has no data dependence on the train step, so the latency-hiding scheduler
    overlaps it with the training collectives (verified in §Perf by the
    fused program's collective/compute schedule).

    The decode chain is a ``lax.scan`` over microsteps (the engine's
    ``decode_loop`` shape), so the fused program's HLO stays O(1) in k
    instead of unrolling — all buckets share the same compile-size budget.
    Pass ``decode_loop_fn(params, tokens, cache, k) -> (tokens, cache)`` to
    supply a custom loop (e.g. ``transformer.decode_loop`` with masking);
    by default the chain is built from ``decode_step_fn``.
    """
    if decode_loop_fn is None:

        def decode_loop_fn(params, tokens, cache, k):
            def body(carry, _):
                t, c = carry
                logits, c = decode_step_fn(params, t, c)
                t = jax.numpy.argmax(logits, axis=-1).astype(jax.numpy.int32)
                return (t, c), None

            (t, c), _ = jax.lax.scan(body, (tokens, cache), None, length=k)
            return t, c

    def fused(k):
        def fn(train_state, batch, infer_params, tokens, cache):
            new_state, metrics = train_step_fn(train_state, batch)
            t, c = decode_loop_fn(infer_params, tokens, cache, k)
            return new_state, metrics, t, c

        return jax.jit(fn, donate_argnums=(0, 4))

    return {k: fused(k) for k in k_buckets}


def pick_bucket(tokens: float, microstep_tokens: float, buckets=(0, 1, 2, 4, 8)) -> int:
    """Largest bucket affordable under the current Algorithm-1 token grant.

    Thin wrapper over ``serving.core.largest_bucket`` (one bucket-floor
    implementation); a leading 0 bucket means "grant affords nothing"."""
    return largest_bucket(int(tokens // max(microstep_tokens, 1e-9)), buckets)
