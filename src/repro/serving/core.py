r"""Request-lifecycle engine core (DESIGN.md §6).

``EngineCore`` re-founds the serving surface around an iteration-level
``step()``: every call is ONE scheduling quantum — consult a separable
``SchedulerPolicy`` (admit / preempt / pick the k bucket and gamma), drive
the engine's fused decode or speculative loop, and return ``StepOutputs``
carrying per-request token deltas, TTFT stamps, and finish reasons.  The
paper's headline guarantee (online p95 protected while offline work soaks
up training bubbles) needs exactly this shape: an ONLINE arrival may
*preempt* a RUNNING OFFLINE slot mid-flight instead of queueing behind it.

Lifecycle::

    WAITING --admit--> [PREFILLING] --> RUNNING --budget--> FINISHED_LENGTH
       ^                    |              |  \--stop-----> FINISHED_STOPPED
       |                    |              |   \--abort()-> FINISHED_ABORTED
       +------<--preempt----+--------------+   (WAITING/PREEMPTED/
            (PREEMPTED)                         PREFILLING abort too)

PREFILLING exists on chunked-prefill engines only (DESIGN.md §7):
admission reserves the slot and the prompt streams as fixed-width chunks
across token-budgeted steps; monolithic engines go straight to RUNNING.

Preemption evicts the slot's KV pages back to the ``PagePool`` (the prompt's
full pages stay radix-cached, so resume recomputes only the uncovered
suffix via the existing prefix-hit path) and re-queues the request at the
FRONT of its priority class.  Resume re-prefills ``prompt + generated`` and
continues greedy decode — deterministic, so the resumed stream is
byte-identical to an uninterrupted run (property-tested for dense + paged,
spec on/off).

The legacy ``InferenceEngine.add_request / decode_loop / spec_decode_loop``
surface survives as a thin deprecated shim delegating to this core
(``add_legacy`` / ``run_legacy``), so pre-existing callers and tests run
unchanged through the new lifecycle.  ``scripts/check_api_surface.py``
fails CI if the shim's signature drifts from the core's delegates.
"""
from __future__ import annotations

import collections
import dataclasses
import enum
import math
from typing import Any, Callable, Iterator, Optional, Union

import numpy as np

from repro.obs.trace import _num as _jnum
from repro.obs.trace import wall_ns
from repro.serving.engine import DECODE_K_BUCKETS, InferenceEngine, Request

__all__ = [
    "EngineCore",
    "Grant",
    "Priority",
    "PriorityPolicy",
    "EngineRequest",
    "RequestOutput",
    "RequestState",
    "RevocationSignal",
    "SamplingParams",
    "SchedulerPolicy",
    "StepOutputs",
    "StepPlan",
    "largest_bucket",
]


class Priority(enum.Enum):
    """Request class: ONLINE is latency-sensitive (may preempt), OFFLINE is
    throughput work that soaks up spare capacity.  Replaces the old
    ``Request.online`` bool on the new surface."""

    ONLINE = "online"
    OFFLINE = "offline"


class RequestState(enum.Enum):
    WAITING = "waiting"
    #: admitted to a slot on a chunked-prefill engine, prompt still
    #: streaming in fixed-width chunks across token-budgeted steps
    #: (DESIGN.md §7); monolithic engines go straight to RUNNING
    PREFILLING = "prefilling"
    RUNNING = "running"
    PREEMPTED = "preempted"
    FINISHED_STOPPED = "finished_stopped"
    FINISHED_LENGTH = "finished_length"
    FINISHED_ABORTED = "finished_aborted"
    #: deadline (``SamplingParams.deadline_s``) elapsed while WAITING, or
    #: the overload ladder shed the request before it took a slot
    #: (DESIGN.md §9) — the request never consumed device compute
    FINISHED_EXPIRED = "finished_expired"
    #: fault-containment gave up: the request was quarantined more times
    #: than the core's retry budget allows (DESIGN.md §9)
    FINISHED_ERROR = "finished_error"

    @property
    def finished(self) -> bool:
        return self.name.startswith("FINISHED")


#: finish_reason strings per terminal state (vLLM-style short names).
FINISH_REASONS = {
    RequestState.FINISHED_STOPPED: "stop",
    RequestState.FINISHED_LENGTH: "length",
    RequestState.FINISHED_ABORTED: "abort",
    RequestState.FINISHED_EXPIRED: "expired",
    RequestState.FINISHED_ERROR: "error",
}


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request generation parameters.

    The engine decodes greedily (argmax); ``stop_token_ids`` are checked
    host-side after each fused loop, so a stop can land up to ``k - 1``
    device microsteps late — the surplus tokens are trimmed from the
    stream, never delivered."""

    max_new_tokens: int = 16
    stop_token_ids: tuple[int, ...] = ()
    #: queue TTL in engine-clock seconds, measured from ``arrival_time``.
    #: A WAITING request whose deadline elapses finishes FINISHED_EXPIRED
    #: without ever taking a slot; a request already in a slot is never
    #: expired mid-flight.  None = no deadline.
    deadline_s: Optional[float] = None


@dataclasses.dataclass(eq=False)
class EngineRequest:
    """One request's lifecycle record.  ``output_tokens`` is the canonical
    stream: it survives preemption/resume (the per-admission engine-side
    ``Request`` only ever holds the tokens since the last admission).

    ``eq=False``: requests compare by identity.  Field equality would make
    queue membership tests compare ndarray prompts elementwise — two
    same-prompt requests must still be distinct queue entries."""

    prompt: np.ndarray  # [prompt_len] int32
    sampling: SamplingParams
    priority: Priority
    request_id: int
    arrival_time: float
    state: RequestState = RequestState.WAITING
    output_tokens: list = dataclasses.field(default_factory=list)
    first_token_time: Optional[float] = None
    finish_time: Optional[float] = None
    finish_reason: Optional[str] = None
    preemptions: int = 0
    #: wall-clock stamps (``repro.obs.wall_ns``, for observability only;
    #: the ``*_time`` fields above are on the engine's clock, which the
    #: policy reads): submission, the FIRST admission, the host's receipt
    #: of the first token (right after the fetch that delivered it), and
    #: the finish
    arrival_wall_ns: Optional[int] = None
    admit_wall_ns: Optional[int] = None
    first_token_wall_ns: Optional[int] = None
    finish_wall_ns: Optional[int] = None
    #: fault-containment bookkeeping (DESIGN.md §9): quarantines survived,
    #: and the engine-clock instant before which admission must not retry
    #: (exponential backoff after each quarantine)
    faults: int = 0
    retry_at: float = 0.0
    # -- core internals --
    _internal: Optional[Request] = None  # engine-side record while RUNNING
    _consumed: int = 0  # tokens of _internal.generated already absorbed
    _reported: int = 0  # tokens of output_tokens already in a StepOutputs
    _ttft_reported: bool = False
    #: consecutive clean decode quanta since the last quarantine — once it
    #: reaches ``EngineCore.fault_decay_quanta`` the fault counter resets,
    #: so transient faults spread across a long life never accumulate into
    #: FINISHED_ERROR (DESIGN.md §9)
    _clean_quanta: int = 0

    @property
    def remaining_budget(self) -> int:
        """Tokens the request may still produce, less those in flight."""
        pending = self._internal.pending if self._internal is not None else 0
        return self.sampling.max_new_tokens - len(self.output_tokens) - pending


class RevocationSignal:
    """A grant's kill switch (DESIGN.md §9).

    The runtime raises it — immediately via ``revoke()``, or ahead of time
    via ``arm(at)`` when it knows the engine-clock instant training resumes
    — and ``EngineCore.step()`` re-checks it between decode sub-dispatches
    (``Grant.revoke_check_steps`` microsteps apart), yielding the GPU within
    a bounded number of tokens instead of running the quantum to
    completion.  Latching: once ``check()`` has observed the revocation it
    stays revoked for the signal's lifetime."""

    def __init__(self) -> None:
        self._revoked = False
        self.revoke_at = math.inf
        self.reason: Optional[str] = None

    def revoke(self, reason: str = "revoked") -> None:
        self._revoked = True
        self.reason = self.reason or reason

    def arm(self, at: float, reason: str = "early_resume") -> None:
        """Schedule revocation at engine-clock instant ``at`` (earliest
        armed instant wins)."""
        if at < self.revoke_at:
            self.revoke_at = at
            self.reason = reason

    def check(self, now: float) -> bool:
        if not self._revoked and now >= self.revoke_at:
            self._revoked = True
        return self._revoked

    @property
    def revoked(self) -> bool:
        return self._revoked


@dataclasses.dataclass
class Grant:
    """One quantum's scheduling inputs (the Algorithm-1 decision, or the
    permissive defaults for a dedicated serving engine).

    ``tokens`` is the Kernel-Barrier grant metering OFFLINE work (online
    execution is never token-metered, only its *admission* is gated by
    ``online_ok``).  ``now`` gates arrivals; ``None`` reads the engine
    clock.  ``max_cost_steps`` caps the quantum in microstep-equivalents
    (the remaining bubble span).  ``token_budget`` caps the step's MIXED
    batch — prefill chunk tokens plus decode / spec-verify tokens — so the
    worst-case step latency is bounded regardless of prompt length
    (DESIGN.md §7; monolithic engines ignore it at admission, which is
    exactly the overrun chunked prefill fixes).  ``advance_clock``, when
    set, is called with the step's cost right before the device work runs,
    so virtual-clock runtimes stamp retirements at quantum end."""

    tokens: float = math.inf
    online_ok: bool = True
    phase: Any = None
    now: Optional[float] = None
    max_cost_steps: float = math.inf
    token_budget: float = math.inf
    advance_clock: Optional[Callable[[float], None]] = None
    #: revocation kill switch (DESIGN.md §9).  None (the default) keeps
    #: the historical contract — a grant, once issued, runs its quantum to
    #: completion in one fused dispatch.  Set, the decode loop splits into
    #: sub-dispatches of ``revoke_check_steps`` microsteps and re-checks
    #: the signal between them, so ``step()`` yields within
    #: ``revoke_check_steps * slots * (gamma + 1)`` tokens of the signal
    #: being raised (plus at most the quantum's already-planned prefill
    #: chunk tokens when revoked mid-wave).
    revocation: Optional[RevocationSignal] = None
    revoke_check_steps: int = 1


@dataclasses.dataclass
class StepPlan:
    """A SchedulerPolicy's decision for one quantum."""

    admit: list = dataclasses.field(default_factory=list)  # EngineRequests
    preempt: list = dataclasses.field(default_factory=list)  # slot indices
    preempt_to_admit: bool = False  # may admission evict OFFLINE victims?
    k: int = 0
    gamma: Optional[int] = None  # None -> plain decode loop
    #: routed candidate source for the speculative quantum (DESIGN.md §10):
    #: None keeps the historical draft-pairing dispatch; a name drives the
    #: engine's ``_drive_proposed_loop`` (the draft model delegates back to
    #: the fused loop, host proposers run tree-verify rounds)
    proposer: Optional[str] = None
    cost_steps: float = 0.0  # DECODE cost in microstep-equivalents
    #: prefill-token budget for this quantum (chunked engines stream up to
    #: this many metered prompt tokens; inf = drain all pending, the
    #: permissive dedicated-serving default)
    prefill_tokens: float = math.inf
    #: microstep-equivalents charged per prefill token (0 = prefill is
    #: free in the cost model, the historical behavior)
    prefill_token_cost: float = 0.0


@dataclasses.dataclass
class RequestOutput:
    """Per-request delta for one step."""

    request_id: int
    priority: Priority
    new_tokens: list
    state: RequestState
    finish_reason: Optional[str]
    #: seconds from arrival to first token — set ONLY on the step that
    #: produced the request's first output token, None afterwards.
    ttft_s: Optional[float]


@dataclasses.dataclass
class StepOutputs:
    outputs: list = dataclasses.field(default_factory=list)
    finished: list = dataclasses.field(default_factory=list)  # EngineRequests
    admitted: list = dataclasses.field(default_factory=list)  # request ids
    preempted: list = dataclasses.field(default_factory=list)  # request ids
    k: int = 0
    gamma: Optional[int] = None
    #: the candidate source the speculative quantum ran with (None for
    #: plain decode or the un-routed draft dispatch)
    proposer: Optional[str] = None
    cost_steps: float = 0.0
    #: prefill tokens this step computed — chunk tokens streamed (chunked
    #: engines) or whole-prompt compute at admission (monolithic), so
    #: ``prefill_tokens + generated-token delta`` is the step's mixed-batch
    #: token count either way
    prefill_tokens: int = 0
    spec_accepted: int = 0
    spec_proposed: int = 0
    #: True when the grant's revocation signal cut this quantum short —
    #: ``k`` and ``cost_steps`` then reflect the microsteps actually run,
    #: not the plan (exact partial-quantum accounting, DESIGN.md §9)
    revoked: bool = False
    #: ``seq`` of this step's ``quantum`` trace record (None when the
    #: tracer kept none); the ``core.step`` host span carries it too
    seq: Optional[int] = None
    #: True once the quantum was collected: ``outputs``, ``finished``,
    #: ``seq`` and the spec counts are final (at once after ``step()``, one
    #: call later after ``step_overlapped()``)
    collected: bool = False


@dataclasses.dataclass(eq=False)
class _Quantum:
    """One quantum between its dispatch half (``EngineCore._begin``) and
    its collect half (``EngineCore._end``)."""

    g: Grant
    w0: int  # wall_ns at the dispatch half's start
    window: Any = None  # the monitor window state behind the grant
    plan: StepPlan = dataclasses.field(default_factory=StepPlan)
    out: StepOutputs = dataclasses.field(default_factory=StepOutputs)
    #: request_id -> request whose delta the quantum reports
    touched: dict = dataclasses.field(default_factory=dict)
    #: slot -> request_id the fused loop decoded
    ran_slots: dict = dataclasses.field(default_factory=dict)
    #: slot -> (prefill tokens, request_id) of the chunk waves
    prefill_slots: dict = dataclasses.field(default_factory=dict)
    readback: Any = None  # the engine's ``Readback``
    #: engine clock at the quantum's end: recorded at dispatch when the
    #: quantum is collected later, else read at collection
    t1: Optional[float] = None
    finished: list = dataclasses.field(default_factory=list)

    @property
    def busy(self) -> bool:
        """The quantum dispatched device work: a decode loop or prefill."""
        return self.readback.packed is not None or self.out.prefill_tokens > 0

    @property
    def ready(self) -> bool:
        """Collecting the quantum waits on nothing: its outputs are on the
        device already."""
        rb = self.readback
        arrays = [t for _, _, t in rb.first]
        if rb.packed is not None:
            arrays.append(rb.packed)
        return all(a.is_ready() for a in arrays)


def largest_bucket(n: int, buckets: tuple = DECODE_K_BUCKETS) -> int:
    """Largest compile bucket <= n, floored at the smallest bucket."""
    best = buckets[0]
    for b in buckets:
        if b <= n:
            best = b
    return best


# ---------------------------------------------------------------------------
# Scheduler policies
# ---------------------------------------------------------------------------


class SchedulerPolicy:
    """Separable scheduling brain ``EngineCore.step()`` consults.

    Implementations decide admission order, preemption appetite, and the
    quantum shape (k bucket, draft length gamma) from a ``Grant``; the core
    executes the plan against the engine.  ``plan`` must not mutate core
    state — failed admissions simply stay queued."""

    #: microstep-equivalents charged per prefill token by ``plan_prefill``
    #: (0 = prefill is free in the cost model, the historical behavior;
    #: SpecInF runtimes set it from the profiled per-token step cost so a
    #: bubble grant can never be overrun by a long prompt — DESIGN.md §7)
    prefill_token_cost_steps: float = 0.0

    def plan(self, core: "EngineCore", grant: Grant) -> StepPlan:
        raise NotImplementedError

    @staticmethod
    def eligible(cr: EngineRequest, grant: Grant) -> bool:
        """Admission eligibility shared by every policy: the request has
        arrived AND any fault-quarantine backoff (``retry_at``) has
        elapsed — a quarantined request must not be re-admitted into the
        very next quantum (DESIGN.md §9)."""
        return cr.arrival_time <= grant.now and cr.retry_at <= grant.now

    def _clamp_k_to_budget(
        self, plan: StepPlan, core: "EngineCore", grant: Grant
    ) -> float:
        """Clamp ``plan.k`` so the quantum's worst-case decode tokens
        (1/slot, or gamma+1/slot for spec rounds) fit the grant's
        ``token_budget``; returns the decode-token allowance consumed.

        PREFILLING slots count toward the reserve: any of them may land
        its final chunk this step and decode the full k alongside the
        RUNNING slots — sizing on running slots alone let exactly that
        step overshoot the grant."""
        eng = core.engine
        slots = min(max(eng.num_active + len(plan.admit), 1), eng.max_slots)
        per_k = slots * (1 if plan.gamma is None else plan.gamma + 1)
        if math.isfinite(grant.token_budget) and plan.k > 0:
            max_k = int(grant.token_budget // per_k)
            buckets = getattr(self, "k_buckets", DECODE_K_BUCKETS)
            if max_k < min(buckets):
                plan.k, plan.cost_steps = 0, 0.0
            elif plan.k > max_k:
                per_cost = plan.cost_steps / plan.k
                plan.k = largest_bucket(max_k, buckets)
                plan.cost_steps = plan.k * per_cost
        return plan.k * per_k

    def plan_prefill(
        self,
        core: "EngineCore",
        grant: Grant,
        plan: StepPlan,
        decode_tokens: float = 0.0,
    ) -> None:
        """Budget this quantum's prefill stream (chunked engines): at most
        the grant's ``token_budget`` minus the decode tokens already
        planned, and at most what the remaining step room can pay for at
        ``prefill_token_cost_steps`` per token — the conversion that turns
        a bubble window into an un-overrunnable token budget."""
        eng = core.engine
        # monolithic engines run no chunk waves, but their admission-time
        # prefill compute is still priced at the same per-token cost — the
        # step cost model must not depend on the prefill layout
        plan.prefill_token_cost = self.prefill_token_cost_steps
        if not getattr(eng, "prefill_chunk", 0):
            plan.prefill_tokens = 0.0
            return
        # a slot whose prompt completes mid-step emits its first generated
        # token on top of the chunk stream; reserve that slack so the
        # step's TOTAL mixed batch stays within the grant
        slack = eng.num_prefilling + len(plan.admit)
        budget = grant.token_budget - decode_tokens - slack
        ptc = self.prefill_token_cost_steps
        plan.prefill_token_cost = ptc
        if ptc > 0 and math.isfinite(grant.max_cost_steps):
            room = grant.max_cost_steps - plan.cost_steps
            budget = min(budget, room / ptc)
        plan.prefill_tokens = max(budget, 0.0)

    def pick_victim(
        self, core: "EngineCore", for_request: EngineRequest
    ) -> Optional[int]:
        """Slot to evict so ``for_request`` can be admitted, or None.

        Default: only an ONLINE admission may preempt, and the victim is
        the RUNNING OFFLINE slot with the shortest total sequence — the
        cheapest resume recompute (resume re-prefills prompt+generated)."""
        if for_request.priority is not Priority.ONLINE:
            return None
        best = None
        for slot, cr in core.slot_requests.items():
            if cr.priority is not Priority.OFFLINE:
                continue
            cost = len(cr.prompt) + len(cr.output_tokens)
            if best is None or cost < best[0]:
                best = (cost, slot)
        return None if best is None else best[1]

    def observe(self, outputs: StepOutputs) -> None:
        """Post-step feedback hook (e.g. acceptance EWMA updates)."""


class PriorityPolicy(SchedulerPolicy):
    """Priority-aware FCFS with preemption — the dedicated-serving default.

    Admits every arrived ONLINE request first (evicting OFFLINE slots when
    capacity blocks, if ``preemption``), then arrived OFFLINE requests
    while the grant allows.  Picks a small k while requests are waiting
    (admission stays responsive — the old serve loop's ``k=1`` heuristic),
    the largest useful bucket otherwise."""

    def __init__(
        self,
        *,
        preemption: bool = True,
        k_buckets: tuple = DECODE_K_BUCKETS,
        gamma_ctrl=None,
        prefill_token_cost_steps: float = 0.0,
    ):
        self.preemption = preemption
        self.k_buckets = tuple(k_buckets)
        self.gamma_ctrl = gamma_ctrl
        self.prefill_token_cost_steps = prefill_token_cost_steps

    def _gamma_ctrl_for(self, engine: InferenceEngine):
        if self.gamma_ctrl is None and (
            engine.spec_enabled or engine.host_spec_enabled
        ):
            from repro.spec.controller import AdaptiveGammaController

            sc = engine.spec_cfg
            self.gamma_ctrl = AdaptiveGammaController(
                sc.gamma_buckets, ewma=sc.accept_ewma,
                draft_cost_ratio=sc.draft_cost_ratio,
            )
        return self.gamma_ctrl

    def plan(self, core: "EngineCore", grant: Grant) -> StepPlan:
        admit = []
        if grant.online_ok:
            admit += [
                cr for cr in core.waiting[Priority.ONLINE]
                if self.eligible(cr, grant)
            ]
        if grant.tokens > 0:
            admit += [
                cr for cr in core.waiting[Priority.OFFLINE]
                if self.eligible(cr, grant)
            ]
        running = list(core.slot_requests.values())
        want = 0
        for cr in running + admit:
            want = max(want, cr.remaining_budget)
        if want <= 0:
            plan = StepPlan(admit=admit, preempt_to_admit=self.preemption)
            self.plan_prefill(core, grant, plan)
            return plan
        leftover = sum(len(q) for q in core.waiting.values()) > len(admit)
        steps = 1 if leftover else min(want, grant.max_cost_steps)
        plan = StepPlan(admit=admit, preempt_to_admit=self.preemption)
        eng = core.engine
        ctrl = self._gamma_ctrl_for(eng)
        if (eng.spec_enabled or eng.host_spec_enabled) and ctrl is not None:
            g = ctrl.gamma_for(grant.phase if grant.phase is not None else "stable")
            # grant-aware routing (DESIGN.md §10): the routed proposer sets
            # the round price — a model-free host proposal spends ~1 step
            # where a draft-model round spends 1 + (gamma+1)*cost_ratio
            plan.proposer = eng.route_proposer(g)
            round_cost = (
                eng.proposer_round_cost(plan.proposer, g)
                if plan.proposer is not None else ctrl.round_cost_steps(g)
            )
            rounds = max(int(steps / ctrl.expected_tokens_per_round(g)), 1)
            plan.k = largest_bucket(rounds, self.k_buckets)
            plan.gamma = g
            plan.cost_steps = plan.k * round_cost
        else:
            plan.k = largest_bucket(int(steps), self.k_buckets)
            plan.cost_steps = float(plan.k)
        decode_tokens = self._clamp_k_to_budget(plan, core, grant)
        self.plan_prefill(core, grant, plan, decode_tokens)
        return plan

    def observe(self, outputs: StepOutputs) -> None:
        if self.gamma_ctrl is not None and outputs.spec_proposed:
            self.gamma_ctrl.observe(outputs.spec_accepted, outputs.spec_proposed)


# ---------------------------------------------------------------------------
# EngineCore
# ---------------------------------------------------------------------------


class EngineCore:
    """Iteration-level request-lifecycle core over an ``InferenceEngine``.

    Owns the WAITING queues (one FIFO per priority class; preempted
    requests resume from the front), the slot -> request map, and the
    canonical per-request output streams.  All device compute still runs
    through the engine's fused drive loops — the core only decides *what*
    each quantum does."""

    def __init__(
        self,
        engine: InferenceEngine,
        policy: Optional[SchedulerPolicy] = None,
    ):
        self.engine = engine
        # An engine has exactly ONE lifecycle core: retirements inside the
        # fused loops notify ``engine._core``, so constructing a core binds
        # it.  Rebinding while the old core still has unfinished requests
        # (RUNNING slots or queued WAITING/PREEMPTED work) would orphan
        # them in a queue nothing steps — refuse instead.
        if engine._core is not None and engine._core.has_unfinished:
            raise RuntimeError(
                "engine already has a lifecycle core with unfinished "
                "requests; drain it before attaching a new EngineCore"
            )
        engine._core = self
        #: the engine's observability bundle (DESIGN.md §8): the core
        #: records lifecycle transitions, per-quantum trace events, and the
        #: latency/TTFT histograms into it
        self.obs = engine.obs
        self.policy = policy or PriorityPolicy()
        self.waiting: dict = {
            Priority.ONLINE: collections.deque(),
            Priority.OFFLINE: collections.deque(),
        }
        self.requests: dict = {}  # request_id -> EngineRequest
        self.slot_requests: dict = {}  # slot index -> EngineRequest (RUNNING)
        self._finished_buffer: list = []
        #: optional graceful-degradation ladder (``repro.resilience``):
        #: consulted each quantum to shed load and downshift the plan
        #: under registry pressure (DESIGN.md §9)
        self.ladder = None
        #: fault containment (DESIGN.md §9): quarantines a request may
        #: survive before FINISHED_ERROR, and the backoff base — retry n
        #: waits ``fault_backoff_s * 2**(n-1)`` engine-clock seconds
        self.max_fault_retries = 3
        self.fault_backoff_s = 0.01
        #: consecutive clean decode quanta after which a request's fault
        #: counter resets (0 disables decay — the pre-decay lifetime-
        #: counter behaviour)
        self.fault_decay_quanta = 8
        #: optional write-ahead request journal
        #: (``repro.resilience.journal.RequestJournal.attach``): submits,
        #: transitions, token deltas, and finishes are logged append-only
        #: so a killed engine can replay them into a fresh core
        #: (DESIGN.md §11)
        self.journal = None
        self._quanta = self.obs.metrics.counter("core/quanta")
        #: decode readbacks collected after the next quantum was dispatched
        self._overlapped = self.obs.metrics.counter(
            "engine/overlapped_readbacks"
        )
        #: quanta ``step_overlapped`` dispatched and has not collected, in
        #: dispatch order
        self._in_flight: collections.deque = collections.deque()
        #: ``seq`` of the quantum records collected in the open ``core.step``
        self._collected: list = []

    # ------------------------------------------------------------------
    # Submission / queries
    # ------------------------------------------------------------------
    def submit(
        self,
        prompt,
        sampling: Optional[SamplingParams] = None,
        *,
        priority: Priority = Priority.OFFLINE,
        arrival_time: Optional[float] = None,
    ) -> EngineRequest:
        """Queue a request (WAITING).  Raises ``ValueError`` when the
        request could NEVER be admitted on this engine (prompt beyond
        ``max_seq``, or worst-case page need beyond the whole pool) —
        failing loudly at submission instead of starving the queue head."""
        sampling = sampling or SamplingParams()
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        probe = Request(prompt=prompt, max_new_tokens=sampling.max_new_tokens)
        if not self.engine.request_fits(probe):
            raise ValueError(
                f"request can never be admitted on this engine "
                f"(prompt {len(prompt)} tokens, "
                f"max_new={sampling.max_new_tokens}, "
                f"max_seq={self.engine.max_seq})"
            )
        if arrival_time is None:
            arrival_time = self.engine.clock()
        cr = EngineRequest(
            prompt=prompt, sampling=sampling, priority=priority,
            request_id=probe.request_id, arrival_time=arrival_time,
            arrival_wall_ns=wall_ns(),
        )
        self.waiting[priority].append(cr)
        self.requests[cr.request_id] = cr
        self.obs.tracer.transition(
            cr.request_id, None, "waiting", arrival_time,
            priority=priority.value,
        )
        if self.journal is not None:
            self.journal.record_submit(cr, self.engine.clock())
        return cr

    def slot_of(self, req: EngineRequest) -> Optional[int]:
        for slot, cr in self.slot_requests.items():
            if cr is req:
                return slot
        return None

    @property
    def num_waiting(self) -> int:
        return sum(len(q) for q in self.waiting.values())

    @property
    def has_unfinished(self) -> bool:
        return bool(self.num_waiting or self.slot_requests)

    @property
    def preemption_count(self) -> int:
        """Total ``preempt()`` evictions — a view of the registry's
        ``core/preemptions`` counter (the historical attribute surface)."""
        return self.obs.metrics.counter("core/preemptions").value

    # ------------------------------------------------------------------
    # One scheduling quantum
    # ------------------------------------------------------------------
    def step(self, grant: Optional[Grant] = None) -> StepOutputs:
        """Run ONE scheduling quantum: policy plan -> preempt -> admit ->
        prefill chunk waves -> fused loop -> collect deltas/finishes.

        On a chunked-prefill engine the quantum is the unified token-budget
        step (DESIGN.md §7): admissions only *reserve* their slot, the
        plan's ``prefill_tokens`` budget streams prompt chunks (PREFILLING
        slots), and the fused loop decodes the RUNNING slots — a slot whose
        prompt completes mid-step starts decoding in the same quantum.  The
        whole mixed batch is priced deterministically BEFORE any device
        work runs, so virtual-clock callers stamp retirements at the true
        quantum end and no step can exceed its granted budget.

        The quantum's dispatch half (``_begin``) and its collect half
        (``_end``) run back to back, after any quantum ``step_overlapped``
        left in flight is collected.  The step runs inside the
        ``core.step`` host span, annotated with the ``seq`` of its quantum
        record (``repro.obs.trace``)."""
        self.drain()
        self._quanta.inc()
        with self.obs.span("core.step") as span:
            q = self._begin(grant, overlap=False)
            self._end(q)
            self._annotate(span)
        return q.out

    def step_overlapped(self, grant: Grant) -> StepOutputs:
        """One quantum in the overlapped order (DESIGN.md §6): dispatch this
        quantum's device work, then collect the quanta dispatched before
        it, so collecting them, tracing them and the caller's work until
        the next call run while the device computes.  Both halves run
        inside one ``core.step`` span, annotated with the ``seq`` of the
        first quantum record collected in it and how many (``quanta``).

        A quantum that dispatched device work stays in flight.  One that
        dispatched none (its grant paid for nothing, or nothing can run
        until the quantum in flight is collected) collects only quanta whose
        outputs are on the device already (``_Quantum.ready``): waiting there
        would leave the device with nothing queued.  Returns this quantum's
        ``StepOutputs``: priced at once (``k``, ``cost_steps``,
        ``prefill_tokens``, ``admitted``, ``preempted``), its token deltas
        and finishes filled in when it is collected (``collected``).
        ``drain()`` collects every quantum in flight.

        The order needs stamps that are known at dispatch, so it runs only
        where the caller prices the quantum onto its clock
        (``Grant.advance_clock``) and the quantum is a plain decode loop;
        elsewhere — speculative engines, a revocable grant, an attached
        fault injector, journal or overload ladder — this is ``step()``.  A
        quantum whose admission may preempt collects the quanta in flight
        before it admits."""
        eng = self.engine
        if (grant.advance_clock is None or grant.revocation is not None
                or eng.fault_injector is not None or self.journal is not None
                or self.ladder is not None or eng.spec_enabled
                or eng.host_spec_enabled):
            return self.step(grant)
        self._quanta.inc()
        with self.obs.span("core.step") as span:
            q = self._begin(grant, overlap=True)
            self._in_flight.append(q)
            while self._in_flight:
                head = self._in_flight[0]
                if head is q and q.busy or not (q.busy or head.ready):
                    break
                self._in_flight.popleft()
                if q.busy and head.readback.packed is not None:
                    self._overlapped.inc()  # q's work is queued behind it
                self._end(head)
            self._annotate(span)
        return q.out

    def drain(self) -> None:
        """Collect the quanta ``step_overlapped`` left in flight, if any,
        in a ``core.step`` span of its own (or in the one open around
        this call).  Counts no quantum."""
        if not self._in_flight:
            return
        span = self.obs.span("core.step")
        if span.open:
            while self._in_flight:
                self._end(self._in_flight.popleft())
            return
        with span as ann:
            while self._in_flight:
                self._end(self._in_flight.popleft())
            self._annotate(ann)

    def _annotate(self, span) -> None:
        """Join the open ``core.step`` span to the quantum records collected
        in it: the first one's ``seq`` and their count."""
        if self._collected:
            span.set_metadata(seq=self._collected[0],
                              quanta=len(self._collected))
        self._collected = []

    def _begin(self, grant: Optional[Grant], overlap: bool) -> "_Quantum":
        """The dispatch half of a quantum: plan, preempt, admit, dispatch
        the prefill waves and the fused loop, and advance the caller's
        clock by the planned cost.  The synchronous order delivers the
        completing prompts' first tokens before the loop is dispatched;
        ``overlap`` leaves them on the quantum's readback, and records the
        clock at the quantum's end for its stamps."""
        w0 = wall_ns()
        g = grant if grant is not None else Grant()
        if g.now is None:
            g = dataclasses.replace(g, now=self.engine.clock())
        eng = self.engine
        if (eng.fault_injector is not None
                and eng.fault_injector.should_fire("process/kill")):
            # lazy import: repro.resilience's package init imports this
            # module, so a top-level import would cycle
            from repro.resilience.faults import ProcessKilled

            raise ProcessKilled("injected process death between quanta")
        tr = self.obs.tracer
        q = _Quantum(g=g, w0=w0, window=tr.window_state)
        tr.window_state = None
        out = q.out
        self._finished_buffer = q.finished
        q.touched = {cr.request_id: cr for cr in self.slot_requests.values()}
        # monolithic engines run prefill compute inside admission; the
        # engine's layout-independent meter prices it identically to the
        # chunk waves, so cost accounting never depends on the layout
        m0 = eng.prefill_metered_tokens
        with self.obs.span("core.plan"):
            self._expire_deadlines(g.now)
            if g.token_budget <= 0:
                # degenerate grant (DESIGN.md §9): an explicit no-op quantum —
                # nothing is planned or driven, but the expiries above still
                # land, the trace still records the quantum, and the
                # starvation is counted instead of falling through to planning
                self.obs.metrics.counter("core/starved_quanta").inc()
                plan = StepPlan(prefill_tokens=0.0)
            else:
                if self.ladder is not None:
                    self.ladder.update(self, g)
                plan = self.policy.plan(self, g)
                if self.ladder is not None:
                    self.ladder.apply(self, g, plan)
        q.plan = plan
        if self._in_flight and (plan.preempt or (
                plan.preempt_to_admit and any(
                    self.policy.pick_victim(self, cr) is not None
                    for cr in plan.admit))):
            # a preemption reads the victim's stream: collect the quanta in
            # flight first (their retirements may also free the room)
            self.drain()
        with self.obs.span("core.admit"):
            for slot in list(plan.preempt):
                cr = self.preempt(slot)
                if cr is not None:
                    out.preempted.append(cr.request_id)
            for cr in plan.admit:
                q.touched.setdefault(cr.request_id, cr)
                if self._try_admit(
                    cr,
                    allow_preempt=plan.preempt_to_admit,
                    on_preempt=lambda victim: (
                        out.preempted.append(victim.request_id),
                        q.touched.setdefault(victim.request_id, victim),
                    ),
                ):
                    out.admitted.append(cr.request_id)
        pf_take, completing = 0, []
        if eng.prefill_chunk and plan.prefill_tokens > 0:
            # deterministic preview: price the chunk waves before driving
            _, pf_take, completing = eng._plan_prefill_waves(
                plan.prefill_tokens
            )
        # decode only runs when some slot will be RUNNING after the waves
        # with tokens left to make: a slot whose budget the quantum in
        # flight spends retires when that quantum is collected
        still_prefilling = {
            i for i in range(eng.max_slots) if eng.slot_prefilling(i)
        } - set(completing)
        runnable = sum(
            1 for i, r in enumerate(eng.slots)
            if r is not None and i not in still_prefilling and not (
                r.pending and r.max_new_tokens - len(r.generated) <= r.pending
            )
        )
        k = plan.k if runnable > 0 else 0
        if k == 0 and plan.k > 0 and eng.prefill_chunk:
            # the planned decode can't run (every slot still mid-prefill):
            # release its token reserve back to the chunk stream instead of
            # throttling prefill below the grant for nothing.  plan.admit
            # is cleared first — those requests are already admitted (and
            # counted in num_prefilling), so re-planning must not count
            # their completion slack twice
            plan.k, plan.cost_steps = 0, 0.0
            plan.admit = []
            self.policy.plan_prefill(self, g, plan, 0.0)
            if plan.prefill_tokens > 0:
                _, pf_take, completing = eng._plan_prefill_waves(
                    plan.prefill_tokens
                )
        a0, p0 = eng.spec_accepted, eng.spec_drafted
        # prefill runs BEFORE the clock advances: a completing prompt's
        # first token stamps at quantum start, the same convention as a
        # monolithic admission's (retirements still stamp at quantum end)
        if pf_take > 0:
            eng._dispatch_prefill_waves(plan.prefill_tokens)
            if not overlap:
                eng._absorb(eng._readback())
        out.prefill_tokens = eng.prefill_metered_tokens - m0
        if out.prefill_tokens:
            for slot, ntok in eng.last_prefill_slot_tokens.items():
                cr = self.slot_requests.get(slot)
                q.prefill_slots[slot] = (
                    ntok, None if cr is None else cr.request_id
                )
        pf_cost = out.prefill_tokens * plan.prefill_token_cost
        rb = None
        if k > 0:
            # the slots the fused loop will decode (the quantum record's
            # ``decoded``); captured now because retirements mutate the
            # map mid-loop
            q.ran_slots = {
                slot: cr.request_id
                for slot, cr in self.slot_requests.items()
                if not eng.slot_prefilling(slot)
            }
        if g.revocation is None:
            cost = (plan.cost_steps if k > 0 else 0.0) + pf_cost
            if (k > 0 or out.prefill_tokens > 0) \
                    and g.advance_clock is not None:
                g.advance_clock(cost)
            if k > 0:
                out.k = k
                if plan.gamma is not None and plan.proposer is not None:
                    out.gamma = plan.gamma
                    out.proposer = plan.proposer
                    eng._drive_proposed_loop(k, plan.gamma, plan.proposer)
                elif plan.gamma is not None and eng.spec_enabled:
                    out.gamma = plan.gamma
                    eng._drive_spec_loop(k, plan.gamma)
                else:
                    rb = eng._dispatch_decode_loop(k)
        else:
            # revocable quantum (DESIGN.md §9): pay the prefill cost
            # first, then decode in sub-dispatches, re-checking the
            # signal between them — the quantum can stop mid-plan, with
            # the clock and the plan re-priced to what actually ran
            if out.prefill_tokens > 0 and g.advance_clock is not None:
                g.advance_clock(pf_cost)
            ran = self._drive_revocable(g, plan, k, out, pf_cost)
            plan.cost_steps = ran * (plan.cost_steps / k) if k > 0 else 0.0
            cost = plan.cost_steps + pf_cost
        inj = eng.fault_injector
        if (
            inj is not None
            and (out.k > 0 or out.prefill_tokens)
            and inj.should_fire("core/step_overrun")
        ):
            # slow-step fault (DESIGN.md §9): the quantum takes 25-75%
            # longer than priced — the overrun eats real bubble span, so
            # the step-time bound checks see it
            cost *= 1.25 + 0.5 * inj.uniform("core/step_overrun")
            if g.advance_clock is not None:
                g.advance_clock(cost)
        if out.k > 0 or out.prefill_tokens:
            out.cost_steps = cost
        out.spec_accepted = eng.spec_accepted - a0
        out.spec_proposed = eng.spec_drafted - p0
        q.readback = eng._readback(rb)
        if overlap:
            q.t1 = eng.clock()
            q.readback.first_at, q.readback.done_at = g.now, q.t1
        return q

    def _end(self, q: "_Quantum") -> None:
        """The collect half of a quantum: absorb its readback (stamped at the
        quantum's own start and end where ``_begin`` recorded the end), then
        the per-request deltas, finishes, the journal append and the trace
        record.  Fills ``q.out``."""
        eng = self.engine
        out, g = q.out, q.g
        outer, self._finished_buffer = self._finished_buffer, q.finished
        if q.readback.packed is not None:
            # the decode loop's outputs reach the host through
            # ``_drive_decode_loop`` in either order, the quantum's first
            # tokens with them: a wrapper of it (the benchmark's altered-token
            # control) sees every decoded token
            eng._ahead = q.readback
            eng._drive_decode_loop(q.readback.k)
        else:
            eng._absorb(q.readback)
        if q.t1 is None:
            q.t1 = eng.clock()
        inj = eng.fault_injector
        with self.obs.span("core.collect"):
            for slot, cr in list(self.slot_requests.items()):
                self._absorb_running(slot, cr, q.t1)
            if inj is not None and inj.should_fire("process/kill"):
                # mid-quantum death: device work ran and its tokens were
                # absorbed into host state, but the journal append below never
                # happens — replay-resume regenerates them byte-identically
                from repro.resilience.faults import ProcessKilled

                raise ProcessKilled("injected process death mid-quantum")
            m = self.obs.metrics
            if self.fault_decay_quanta and out.k > 0:
                # fault-counter decay (DESIGN.md §9): a quarantined request
                # that then decodes N consecutive clean quanta earns its
                # retry budget back — transient faults spread across a long
                # life must not escalate to FINISHED_ERROR
                for cr in self.slot_requests.values():
                    if cr.faults and cr.state is RequestState.RUNNING:
                        cr._clean_quanta += 1
                        if cr._clean_quanta >= self.fault_decay_quanta:
                            cr.faults = 0
                            cr._clean_quanta = 0
                            m.counter("fault/decays").inc()
            out.finished = list(q.finished)
            touched = q.touched
            for cr in out.finished:
                touched.setdefault(cr.request_id, cr)
                pri = cr.priority.value
                m.counter("core/finished/" + pri).inc()
                if cr.finish_reason != "expired":
                    # served latency means completed work; shed/expired
                    # requests never ran and would poison the p95
                    m.histogram(f"core/{pri}_latency_s").record(
                        cr.finish_time - cr.arrival_time
                    )
            for rid, cr in touched.items():
                if cr.state.finished and cr not in q.finished:
                    # finished in the quantum collected before this one,
                    # which reported its last delta
                    continue
                new = cr.output_tokens[cr._reported:]
                cr._reported = len(cr.output_tokens)
                ttft = None
                if cr.first_token_time is not None and not cr._ttft_reported:
                    cr._ttft_reported = True
                    ttft = cr.first_token_time - cr.arrival_time
                    self.obs.tracer.instant(
                        "first_token", cr.first_token_time, request_id=rid,
                        priority=cr.priority.value,
                    )
                    if cr.priority is Priority.ONLINE:
                        m.histogram("core/online_ttft_s").record(ttft)
                if new:
                    m.counter(
                        "core/generated_tokens/" + cr.priority.value
                    ).inc(len(new))
                out.outputs.append(RequestOutput(
                    request_id=rid, priority=cr.priority, new_tokens=list(new),
                    state=cr.state, finish_reason=cr.finish_reason, ttft_s=ttft,
                ))
            if self.journal is not None:
                self.journal.record_step(self, out)
        out.seq = self._record_quantum(q)
        out.collected = True
        if out.seq is not None:
            self._collected.append(out.seq)
        self._finished_buffer = outer
        self.policy.observe(out)

    # ------------------------------------------------------------------
    def _drive_revocable(
        self, g: Grant, plan: StepPlan, k: int, out: StepOutputs,
        pf_cost: float = 0.0,
    ) -> int:
        """Decode portion of a revocable quantum (DESIGN.md §9): run the
        ``k`` planned microsteps as sub-dispatches of at most
        ``g.revoke_check_steps`` microsteps, re-checking the revocation
        signal (on the engine clock, which the per-sub-dispatch
        ``advance_clock`` calls keep current for virtual-clock runtimes)
        before each one.  Returns the microsteps actually run and stamps
        ``out.k`` / ``out.gamma`` / ``out.revoked``.  The extra d2h sync
        per sub-dispatch is the price of revocability — dedicated engines
        keep the single-dispatch path by leaving ``Grant.revocation``
        unset."""
        eng = self.engine
        sig = g.revocation
        inj = eng.fault_injector
        per_cost = (plan.cost_steps / k) if k > 0 else 0.0
        spec = plan.gamma is not None and (
            eng.spec_enabled or plan.proposer is not None
        )
        buckets = getattr(self.policy, "k_buckets", DECODE_K_BUCKETS)
        check = max(int(g.revoke_check_steps), 1)
        ran = 0
        while ran < k and eng.num_active > eng.num_prefilling:
            if inj is not None and inj.should_fire("core/revoke_mid_quantum"):
                sig.revoke(reason="injected_revocation")
            if sig.check(eng.clock()):
                break
            k_sub = min(largest_bucket(min(check, k - ran), buckets),
                        k - ran)
            if g.advance_clock is not None:
                # absolute from quantum start: cumulative cost so far
                g.advance_clock(pf_cost + (ran + k_sub) * per_cost)
            if spec and plan.proposer is not None:
                eng._drive_proposed_loop(k_sub, plan.gamma, plan.proposer)
            elif spec:
                eng._drive_spec_loop(k_sub, plan.gamma)
            else:
                eng._drive_decode_loop(k_sub)
            ran += k_sub
        out.k = ran
        if spec and ran > 0:
            out.gamma = plan.gamma
            out.proposer = plan.proposer
        if sig.revoked and ran < k:
            out.revoked = True
            self.obs.metrics.counter("fault/revocations").inc()
        return ran

    # ------------------------------------------------------------------
    def stream(
        self, req: EngineRequest, grant: Optional[Grant] = None
    ) -> Iterator[int]:
        """Yield ``req``'s tokens as they are produced, driving ``step()``
        (with ``grant``, or the permissive default) whenever the stream
        runs dry.  Returns once the request reaches a terminal state."""
        sent = 0
        stalls = 0
        while True:
            while sent < len(req.output_tokens):
                yield req.output_tokens[sent]
                sent += 1
            if req.state.finished:
                return
            out = self.step(grant)
            if (out.k == 0 and not out.admitted and not out.preempted
                    and not out.prefill_tokens):
                stalls += 1
                if stalls > 2:
                    raise RuntimeError(
                        f"stream stalled: request {req.request_id} is "
                        f"{req.state.value} and the policy scheduled no work"
                    )
            else:
                stalls = 0

    # ------------------------------------------------------------------
    def abort(self, req: EngineRequest) -> None:
        """Terminal ABORT from any non-finished state.  A RUNNING request
        is evicted immediately — its pages return to the pool and its
        draft-cache slot state is reset (mid-decode abort never leaks).
        The quanta in flight are collected first."""
        if req.state.finished:
            return
        self.drain()
        if req.state in (RequestState.RUNNING, RequestState.PREFILLING):
            slot = self.slot_of(req)
            self._collect(req)
            del self.slot_requests[slot]
            self.engine.evict_slot(slot)
            req._internal = None
        else:
            try:
                self.waiting[req.priority].remove(req)
            except ValueError:
                pass
        self._finish(req, RequestState.FINISHED_ABORTED, self.engine.clock())
        if self.journal is not None:
            # abort() runs outside step(), so the end-of-quantum journal
            # hook never sees this finish
            self.journal.record_finish(req, self.engine.clock())

    # ------------------------------------------------------------------
    def preempt(self, target: Union[int, EngineRequest]) -> Optional[EngineRequest]:
        """Evict a RUNNING slot and re-queue its request (PREEMPTED) at the
        front of its priority class.  Pages go back to the pool; the
        radix-cached prompt pages survive, so resume recomputes only the
        suffix.  Returns the preempted request (None if the slot is empty).
        The quanta in flight are collected first, so no token is lost.
        """
        self.drain()
        slot = target if isinstance(target, int) else self.slot_of(target)
        cr = self.slot_requests.pop(slot, None) if slot is not None else None
        if cr is None:
            return None
        frm = cr.state.value
        new = self._collect(cr)
        self.engine.evict_slot(slot)
        cr._internal = None
        if self._apply_stop(cr, new):
            # the tail the eviction salvaged already carried a stop token
            self._finish(cr, RequestState.FINISHED_STOPPED, self.engine.clock())
            return cr
        cr.state = RequestState.PREEMPTED
        cr.preemptions += 1
        self.obs.metrics.counter("core/preemptions").inc()
        self.obs.tracer.transition(
            cr.request_id, frm, "preempted", self.engine.clock(),
            priority=cr.priority.value,
        )
        self.waiting[cr.priority].appendleft(cr)
        return cr

    # ------------------------------------------------------------------
    # Legacy shim surface (InferenceEngine delegates here)
    # ------------------------------------------------------------------
    def add_legacy(self, req: Request) -> bool:
        """Deprecated ``InferenceEngine.add_request`` contract: admit
        ``req`` immediately (no queueing), returning False on capacity.
        The request still joins the core lifecycle, so shim- and
        core-driven streams share one bookkeeping path."""
        self.drain()
        if not self.engine._admit_request(req):
            return False
        cr = EngineRequest(
            prompt=np.asarray(req.prompt, np.int32).reshape(-1),
            sampling=SamplingParams(max_new_tokens=req.max_new_tokens),
            priority=Priority.ONLINE if req.online else Priority.OFFLINE,
            request_id=req.request_id,
            arrival_time=req.arrival_time,
            state=RequestState.RUNNING,
            arrival_wall_ns=wall_ns(),
        )
        cr.admit_wall_ns = cr.arrival_wall_ns
        cr._internal = req
        cr.first_token_time = req.first_token_time
        cr.first_token_wall_ns = req.first_token_wall_ns
        slot = next(
            i for i, r in enumerate(self.engine.slots) if r is req
        )
        self.slot_requests[slot] = cr
        self.requests[cr.request_id] = cr
        tr = self.obs.tracer
        tr.transition(
            cr.request_id, None, "waiting", cr.arrival_time,
            priority=cr.priority.value,
        )
        tr.transition(
            cr.request_id, "waiting", "running", self.engine.clock(),
            priority=cr.priority.value,
        )
        return True

    def run_legacy(self, k: int, gamma: Optional[int] = None) -> list:
        """Deprecated ``decode_loop`` / ``spec_decode_loop`` contract: run
        exactly one fused loop (no admission, no preemption) and return the
        engine-side ``Request`` records that finished."""
        self.drain()
        if self.engine.num_active == 0 or k <= 0:
            return []
        self._finished_buffer = []
        if gamma is None:
            finished = self.engine._drive_decode_loop(k)
        else:
            finished = self.engine._drive_spec_loop(k, gamma)
        for slot, cr in list(self.slot_requests.items()):
            self._absorb_running(slot, cr)
        return finished

    # ------------------------------------------------------------------
    # Internals
    # ------------------------------------------------------------------
    def _record_quantum(self, q: "_Quantum") -> Optional[int]:
        """Per-quantum observability (DESIGN.md §8), in the ``core.record``
        host span: sample the gauges and emit the structured trace events
        for this step — per-slot prefill spans and one ``quantum`` record,
        which names the slots the fused loop decoded (``decoded``, drawn
        per slot at export) and the step's wall-clock interval
        (``wall_ns``, from its dispatch to this record).  Span boundaries
        are the engine clock's quantum endpoints; the prefill/decode split
        inside the quantum follows the plan's deterministic cost model
        (prefill runs first, before the clock advance).  Returns the
        quantum record's ``seq``."""
        with self.obs.span("core.record"):
            eng = self.engine
            m = self.obs.metrics
            m.gauge("core/queue_depth/online").set(
                len(self.waiting[Priority.ONLINE])
            )
            m.gauge("core/queue_depth/offline").set(
                len(self.waiting[Priority.OFFLINE])
            )
            m.gauge("engine/slots_active").set(eng.num_active)
            m.gauge("engine/slots_prefilling").set(eng.num_prefilling)
            if eng.pool is not None:
                for key, v in eng.pool.occupancy().items():
                    m.gauge(f"engine/pool/{key}").set(v)
            tr = self.obs.tracer
            if not tr.enabled:
                return None
            g, plan, out = q.g, q.plan, q.out
            t0, t1 = g.now, q.t1
            pf_cost = out.prefill_tokens * plan.prefill_token_cost
            dec_cost = plan.cost_steps if out.k > 0 else 0.0
            total = pf_cost + dec_cost
            t_mid = t0 + (t1 - t0) * (pf_cost / total if total > 0 else 0.0)
            if out.prefill_tokens:
                if eng.prefill_chunk:
                    for slot, (ntok, rid) in q.prefill_slots.items():
                        tr.span(
                            "prefill_chunk", f"slot{slot}", t0, t_mid,
                            tokens=ntok, request_id=rid,
                        )
                else:
                    for rid in out.admitted:
                        cr = self.requests.get(rid)
                        slot = None if cr is None else self.slot_of(cr)
                        if slot is not None:
                            tr.span(
                                "prefill", f"slot{slot}", t0, t_mid,
                                request_id=rid,
                            )
            return tr.quantum(
                t0, t1,
                grant={
                    "tokens": _jnum(g.tokens), "online_ok": g.online_ok,
                    "phase": (
                        None if g.phase is None
                        else str(getattr(g.phase, "value", g.phase))
                    ),
                    "max_cost_steps": _jnum(g.max_cost_steps),
                    "token_budget": _jnum(g.token_budget),
                },
                k=out.k, gamma=out.gamma, proposer=out.proposer,
                cost_steps=out.cost_steps,
                prefill_tokens=out.prefill_tokens, revoked=out.revoked,
                admitted=list(out.admitted), preempted=list(out.preempted),
                finished=[cr.request_id for cr in out.finished],
                spec_accepted=out.spec_accepted,
                spec_proposed=out.spec_proposed,
                window=q.window,
                decoded=q.ran_slots, decode_t0=t_mid,
                wall_ns=[q.w0, wall_ns()],
            )

    def _collect(self, cr: EngineRequest) -> list:
        """Absorb tokens the engine produced since the last collection into
        the canonical stream; returns just the new ones.  Also propagates
        the engine-side TTFT stamps, which a chunked-prefill admission only
        produces once the prompt's final chunk lands (monolithic admission
        stamped them inside ``_try_admit``)."""
        if (cr.first_token_time is None
                and cr._internal.first_token_time is not None):
            cr.first_token_time = cr._internal.first_token_time
            cr.first_token_wall_ns = cr._internal.first_token_wall_ns
        gen = cr._internal.generated
        new = [int(t) for t in gen[cr._consumed:]]
        cr._consumed = len(gen)
        cr.output_tokens.extend(new)
        return new

    def _apply_stop(self, cr: EngineRequest, new: list) -> bool:
        """Host-side stop-token scan over this step's delta; trims the
        stream past the first stop (stop token included)."""
        stops = cr.sampling.stop_token_ids
        if not stops:
            return False
        for j, t in enumerate(new):
            if t in stops:
                cut = len(cr.output_tokens) - len(new) + j + 1
                del cr.output_tokens[cut:]
                return True
        return False

    def _finish(
        self, cr: EngineRequest, state: RequestState, now: float
    ) -> None:
        frm = cr.state.value
        cr.state = state
        cr.finish_reason = FINISH_REASONS[state]
        cr.finish_time = now
        cr.finish_wall_ns = wall_ns()
        self._finished_buffer.append(cr)
        self.obs.metrics.counter(
            "core/finish_reason/" + cr.finish_reason
        ).inc()
        self.obs.tracer.transition(
            cr.request_id, frm, state.value, now, priority=cr.priority.value,
        )

    def _absorb_running(
        self, slot: int, cr: EngineRequest, now: Optional[float] = None
    ) -> None:
        """Absorb a slot's new tokens; a PREFILLING request whose first
        token arrived turns RUNNING, stamped at that token's time, and a
        stop token finishes the request at ``now`` (None: the clock)."""
        new = self._collect(cr)
        if cr.state is RequestState.PREFILLING and cr._internal.generated:
            cr.state = RequestState.RUNNING
            self.obs.tracer.transition(
                cr.request_id, "prefilling", "running",
                cr._internal.first_token_time, priority=cr.priority.value,
            )
        if self._apply_stop(cr, new):
            del self.slot_requests[slot]
            self.engine.evict_slot(slot)
            cr._internal = None
            self._finish(
                cr, RequestState.FINISHED_STOPPED,
                self.engine.clock() if now is None else now,
            )

    def _expire_deadlines(self, now: float) -> None:
        """Deadline sweep at quantum start (DESIGN.md §9): WAITING or
        PREEMPTED requests whose ``SamplingParams.deadline_s`` elapsed
        finish FINISHED_EXPIRED without ever taking a slot.  Requests
        already in a slot are never expired mid-flight — their deadline
        only mattered while they queued."""
        for q in self.waiting.values():
            expired = [
                cr for cr in q
                if cr.sampling.deadline_s is not None
                and now >= cr.arrival_time + cr.sampling.deadline_s
            ]
            for cr in expired:
                q.remove(cr)
                self._finish(cr, RequestState.FINISHED_EXPIRED, now)

    def shed(self, cr: EngineRequest, now: float, kind: str) -> None:
        """Load-shed a queued request (overload ladder, DESIGN.md §9):
        remove it from its WAITING queue and finish it FINISHED_EXPIRED.
        ``kind`` labels the ``fault/shed/<kind>`` counter."""
        try:
            self.waiting[cr.priority].remove(cr)
        except ValueError:
            return
        self.obs.metrics.counter("fault/shed/" + kind).inc()
        self._finish(cr, RequestState.FINISHED_EXPIRED, now)

    def _on_slot_fault(self, slot: int, internal: Request) -> None:
        """Engine quarantine callback (DESIGN.md §9): the fused loop's
        per-slot NaN screen flagged this slot, the engine scrubbed and
        freed its KV, and the request must now be re-queued (front of its
        class, exponential backoff) or — once its retry budget is spent —
        finished FINISHED_ERROR.  Tokens from the poisoned dispatch were
        never absorbed, so the retry's resumed stream stays byte-identical
        to a fault-free run."""
        cr = self.slot_requests.pop(slot, None)
        if cr is None:
            return
        frm = cr.state.value
        new = self._collect(cr)
        cr._internal = None
        cr.faults += 1
        cr._clean_quanta = 0
        now = self.engine.clock()
        if self._apply_stop(cr, new):
            # the good tokens absorbed before the fault carried a stop
            self._finish(cr, RequestState.FINISHED_STOPPED, now)
            return
        m = self.obs.metrics
        if cr.faults > self.max_fault_retries:
            m.counter("fault/retry_exhausted").inc()
            self._finish(cr, RequestState.FINISHED_ERROR, now)
            return
        cr.retry_at = now + self.fault_backoff_s * 2 ** (cr.faults - 1)
        cr.state = RequestState.PREEMPTED
        m.counter("fault/requeues").inc()
        self.obs.tracer.transition(
            cr.request_id, frm, "preempted", now, priority=cr.priority.value,
        )
        self.waiting[cr.priority].appendleft(cr)

    def _on_slot_finished(self, slot: int, internal: Request) -> None:
        """Engine retirement callback (budget exhausted or max_seq horizon
        reached) — also covers retirements driven through the legacy
        ``decode_microstep`` path."""
        cr = self.slot_requests.pop(slot, None)
        if cr is None:
            return
        new = self._collect(cr)
        cr._internal = None
        state = (
            RequestState.FINISHED_STOPPED
            if self._apply_stop(cr, new) else RequestState.FINISHED_LENGTH
        )
        self._finish(cr, state, internal.finish_time)

    def _try_admit(
        self,
        cr: EngineRequest,
        *,
        allow_preempt: bool = False,
        on_preempt: Optional[Callable[[EngineRequest], Any]] = None,
    ) -> bool:
        """Admit ``cr`` (prefill into a slot), evicting policy-chosen
        OFFLINE victims while admission fails and ``allow_preempt``.  On
        failure the request simply stays where it was in its queue."""
        frm = cr.state.value
        if cr.remaining_budget <= 0:
            # a preempted request whose budget was exactly exhausted
            self.waiting[cr.priority].remove(cr)
            self._finish(cr, RequestState.FINISHED_LENGTH, self.engine.clock())
            return False
        prompt = cr.prompt
        if cr.output_tokens:
            prompt = np.concatenate(
                [prompt, np.asarray(cr.output_tokens, np.int32)]
            )
        internal = Request(
            prompt=prompt, max_new_tokens=cr.remaining_budget,
            arrival_time=cr.arrival_time,
            online=cr.priority is Priority.ONLINE,
        )
        while not self.engine._admit_request(internal, stream_prefill=True):
            victim_slot = (
                self.policy.pick_victim(self, cr) if allow_preempt else None
            )
            if victim_slot is None:
                return False
            victim = self.preempt(victim_slot)
            if victim is not None and on_preempt is not None:
                on_preempt(victim)
        slot = next(
            i for i, r in enumerate(self.engine.slots) if r is internal
        )
        self.slot_requests[slot] = cr
        try:
            self.waiting[cr.priority].remove(cr)
        except ValueError:
            pass  # legacy/externally-managed request not in a queue
        cr._internal = internal
        cr._consumed = 0
        # chunked engines leave the slot PREFILLING: the prompt streams in
        # token-budgeted chunk waves and the state flips to RUNNING on the
        # step that lands the final chunk
        cr.state = (
            RequestState.PREFILLING if self.engine.slot_prefilling(slot)
            else RequestState.RUNNING
        )
        if cr.admit_wall_ns is None:
            cr.admit_wall_ns = wall_ns()
        if cr.first_token_time is None:
            cr.first_token_time = internal.first_token_time
            cr.first_token_wall_ns = internal.first_token_wall_ns
        self.obs.tracer.transition(
            cr.request_id, frm, cr.state.value, self.engine.clock(),
            priority=cr.priority.value,
        )
        return True
