"""Continuous-batching inference engine.

The engine is the *schedulable unit producer* for SpecInF: every public
operation is a short jitted microstep (one prefill, or one decode step over
all active slots), which is exactly the quantum the Kernel Barrier meters
tokens against (DESIGN.md §2, "admission quanta").

Slots: a fixed-capacity decode batch (size ``max_slots``) with per-slot KV
index, so requests of different lengths run concurrently (continuous
batching).  Finished slots are refilled from the queue by the caller
(``core/filling.py`` or the standalone serve loop).

Paged KV cache (DESIGN.md §5): attention-family engines store KV in a
shared pool of fixed-size physical pages addressed through per-slot block
tables (``kv_page_size``; 0 forces the legacy dense ``[B, S_max]`` layout,
kept for recurrent families and A/B benchmarks).  Admission is
capacity-based — a request is admitted iff the pool can cover its
worst-case page need, so ``max_slots`` may exceed what the dense layout
could hold — and a radix tree over page-aligned prompt chunks serves shared
prefixes straight from cached pages: a prefix hit increfs the pages, skips
prefill compute for the covered length, and prefills only the suffix
through the chunk-verify path.  Pages are topped up lazily ahead of each
fused loop, trimmed back after speculative rollback, and released (not
index-reset) at retirement.

Fast path (DESIGN.md §3):

* ``decode_loop(k)`` fuses k microsteps into one jitted ``lax.scan`` with
  per-slot active/done masking and donated cache buffers — exactly ONE
  device->host transfer per loop, whatever k is.
* Chunked prefill (DESIGN.md §7, default for attention families): admission
  only *reserves* a slot; the prompt streams as fixed-width chunks
  (``prefill_chunk``) through ONE compiled batched program per model —
  replacing both the power-of-two bucket family and the per-request draft
  prefill dispatch — so a long prompt never monopolizes a step and the
  EngineCore can meter prefill against a token budget.  The legacy
  ``add_request`` contract drives the chunks to completion at admission;
  ``prefill_chunk=0`` restores monolithic bucket prefill
  (``prefill_into_slot`` writes K/V straight into the batch cache on
  device, prompts padded to power-of-two buckets), which recurrent
  families always use.

Speculative fast path (DESIGN.md §4): constructing the engine with a
``draft_cfg``/``draft_params`` pairing (``configs.base.draft_config``)
enables ``spec_decode_loop(k, gamma)`` — k fused draft-propose /
chunk-verify rounds that emit up to ``gamma + 1`` *verified* tokens per slot
per round under the same one-transfer-per-loop discipline.

Lifecycle (DESIGN.md §6): the request-management surface now lives in
``serving/core.py`` — ``EngineCore.step()`` with priority classes,
preemption, and streaming outputs.  ``add_request`` / ``decode_loop`` /
``spec_decode_loop`` remain as thin DEPRECATED shims delegating to the
core (``scripts/check_api_surface.py`` pins them); the engine keeps only
the compute primitives: ``_admit_request`` (one prefill microstep into a
free slot), ``_drive_decode_loop`` / ``_drive_spec_loop`` (the fused
device loops), and ``evict_slot`` (release a slot's pages and cache
indices WITHOUT finishing — the preempt/abort path).

Timebase: all request timestamps come from ONE clock chosen at construction
(``clock=``, default ``time.monotonic``).  Collocated runtimes rebind it to
their virtual clock so latencies never mix timebases.  Offline requests
added with the default ``arrival_time == 0.0`` are stamped from the engine
clock at admission, so latency metrics never mix an epoch-zero arrival with
a monotonic/virtual now (online requests keep their explicit arrivals —
including a genuine virtual ``t == 0`` — so queueing delay is preserved).
Beside them, for observability only, ``first_token_wall_ns`` is stamped on
the wall clock (``repro.obs.wall_ns``) right after the fetch that delivered
the token, and the drivers run in host spans (``engine.prefill``,
``engine.decode``, ``engine.fetch``, ``engine.tables``; ``repro.obs.trace``).
"""
from __future__ import annotations

import dataclasses
import functools
import itertools
import math
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, SpecDecodeConfig
from repro.models import transformer as T
from repro.obs import Observability, wall_ns
from repro.serving.kv_pool import PageAllocError, PagePool, RadixCache

_req_counter = itertools.count()


def advance_request_ids(floor: int) -> None:
    """Ensure future auto-assigned request ids are ``>= floor``.

    Journal recovery (DESIGN.md §11) re-creates requests with their
    journaled ids; without bumping the process-wide counter past them, a
    fresh ``submit()`` could collide with a replayed id."""
    global _req_counter
    nxt = next(_req_counter)
    _req_counter = itertools.count(max(nxt, int(floor)))

#: Fused-loop sizes the engine compiles on demand; callers bucket their k so
#: the set of compiled programs stays bounded (DESIGN.md §2).
DECODE_K_BUCKETS = (1, 2, 4, 8)

#: Default physical page size (tokens) for the paged KV pool.  A power of
#: two, so power-of-two prefill buckets stay page-aligned; >= 8 sublanes so
#: one page is a legal Pallas KV tile (DESIGN.md §5).
DEFAULT_KV_PAGE_SIZE = 16

#: Default chunked-prefill width (tokens per slot per wave, DESIGN.md §7).
#: One compiled program at this fixed width replaces the whole power-of-two
#: prefill bucket family for attention-family engines.
DEFAULT_PREFILL_CHUNK = 32

_ATTENTION_FAMILIES = ("dense", "moe", "audio", "vlm")


def _on_one_device(tree: Any) -> bool:
    """Whether every array of ``tree`` lives on one device.  The engine runs
    on one device: its cache and token arrays are made on the default
    device, and the TPU lowering cannot partition its Pallas kernels over a
    mesh.  A tree on one device is served as ``InferenceEngine._serving``
    says; a tree sharded over several (a collocated trainer's) is gathered
    once, already in the compute dtype, into a copy the engine keeps
    (``InferenceEngine._gathered``)."""
    return not any(
        isinstance(x, jax.Array) and len(x.sharding.device_set) > 1
        for x in jax.tree.leaves(tree)
    )


def _program(fn: Callable, *args, **kwargs) -> Callable:
    """``functools.partial(fn, *args, **kwargs)`` named after ``fn``, so its
    jitted program is ``jit_<fn name>`` in HLO dumps, compile logs and
    profiles (a bare partial's is ``jit__unknown``).  Only the name is
    copied: a ``__wrapped__`` would make ``inspect.signature`` report
    ``fn``'s own parameters, and jit would resolve donated and static
    argument names against the wrong positions."""
    p = functools.partial(fn, *args, **kwargs)
    p.__name__ = fn.__name__
    p.__qualname__ = fn.__qualname__
    return p


def decode_loop(cfg, params, tokens, cache, remaining, *, k: int, **kwargs):
    """``transformer.decode_loop`` with what the host reads of it packed
    into ONE int32 array ``[k + 4, slots]``: the token after each microstep
    (rows ``0 .. k-1``), then each slot's microsteps run, budget left, cache
    index and NaN flag.  The array is a fresh output of the program, so it
    aliases no buffer that the next loop donates (the cache's index does).
    Returns ``(tokens, cache, packed)``."""
    tokens, cache, rem, toks_seq, steps, bad = T.decode_loop(
        cfg, params, tokens, cache, remaining, k=k, **kwargs
    )
    tail = jnp.stack([steps, rem, cache["index"], bad.astype(jnp.int32)])
    return tokens, cache, jnp.concatenate([toks_seq, tail])


def put_first_tokens(tokens, first, mask):
    """The decode batch's tokens with the slots in ``mask`` taking their
    first generated token from a completing prefill wave's ``first``."""
    return jnp.where(mask, first, tokens)


@dataclasses.dataclass(eq=False)
class Readback:
    """One quantum's device outputs on their way to the host (DESIGN.md
    §6).  Their copies start when they are dispatched; ``_absorb`` reads
    them in one fetch.

    ``first``: ``(slot, request, tokens)`` for each slot whose prefill
    completed, ``tokens`` the completing wave's ``[slots]`` argmax.
    ``packed``: the decode loop's ``[k + 4, slots]`` array (``decode_loop``),
    ``reqs`` the request each slot decoded for (None: frozen or empty),
    ``steps`` the microsteps predicted for it at dispatch.  ``first_at`` and
    ``done_at`` stamp its first tokens and finishes on the engine clock
    (None: the clock when the outputs arrive)."""

    first: list = dataclasses.field(default_factory=list)
    packed: Optional[jax.Array] = None
    k: int = 0
    reqs: list = dataclasses.field(default_factory=list)
    steps: Optional[np.ndarray] = None
    first_at: Optional[float] = None
    done_at: Optional[float] = None

    @property
    def empty(self) -> bool:
        return not self.first and self.packed is None


class RegistryCounterView:
    """Thin view (DESIGN.md §8): a historical ``InferenceEngine`` counter
    attribute backed by a ``repro.obs`` registry counter under a stable
    name.  ``engine.d2h_transfers += 1`` and
    ``engine.obs.metrics.counter("engine/d2h_transfers")`` are the SAME
    cell, so the legacy attribute surface and the registry can never
    diverge — ``scripts/check_api_surface.py`` pins the mapping.  The
    counter object is cached on the instance after the first access, so
    hot paths pay one ``getattr`` plus an integer add."""

    def __init__(self, name: str):
        self.name = name
        self._cache_attr = "_ctr_" + name.replace("/", "_")

    def _cell(self, obj):
        cell = getattr(obj, self._cache_attr, None)
        if cell is None:
            cell = obj.obs.metrics.counter(self.name)
            setattr(obj, self._cache_attr, cell)
        return cell

    def __get__(self, obj, objtype=None):
        if obj is None:
            return self
        return self._cell(obj).value

    def __set__(self, obj, value):
        self._cell(obj).set(value)


@dataclasses.dataclass
class Request:
    prompt: np.ndarray  # [prompt_len] int32
    max_new_tokens: int
    request_id: int = dataclasses.field(default_factory=lambda: next(_req_counter))
    arrival_time: float = 0.0
    online: bool = False
    # -- filled by the engine --
    generated: list = dataclasses.field(default_factory=list)
    first_token_time: Optional[float] = None
    #: ``repro.obs.wall_ns`` right after the fetch that delivered the
    #: first token
    first_token_wall_ns: Optional[int] = None
    finish_time: Optional[float] = None
    #: tokens dispatched for this request whose readback the host has not
    #: absorbed yet (dense engines: a bound where a loop reaches max_seq)
    pending: int = 0


class InferenceEngine:
    # Historical perf-counter attributes, now thin views over the metrics
    # registry (stable names: repro.obs.metrics.STABLE_NAMES; mapping
    # pinned by scripts/check_api_surface.py).  Reads/writes hit the same
    # cell as obs.metrics.counter(name).
    d2h_transfers = RegistryCounterView("engine/d2h_transfers")
    steps_executed = RegistryCounterView("engine/steps_executed")
    generated_tokens_total = RegistryCounterView("engine/generated_tokens")
    prefill_prompt_tokens = RegistryCounterView("engine/prefill_prompt_tokens")
    prefill_skipped_tokens = RegistryCounterView(
        "engine/prefill_skipped_tokens"
    )
    prefill_metered_tokens = RegistryCounterView(
        "engine/prefill_metered_tokens"
    )
    spec_rounds = RegistryCounterView("engine/spec_rounds")
    spec_drafted = RegistryCounterView("engine/spec_drafted")
    spec_accepted = RegistryCounterView("engine/spec_accepted")

    def __init__(
        self,
        cfg: ModelConfig,
        params: Any,
        *,
        max_slots: int = 4,
        max_seq: int = 128,
        compute_dtype=jnp.bfloat16,
        decode_impl: str = "auto",
        prefill_impl: str = "xla",
        clock: Optional[Callable[[], float]] = None,
        min_prefill_bucket: int = 8,
        draft_cfg: Optional[ModelConfig] = None,
        draft_params: Any = None,
        spec: Optional[SpecDecodeConfig] = None,
        spec_seed: int = 0,
        kv_page_size: Optional[int] = None,
        kv_pool_pages: Optional[int] = None,
        enable_prefix_cache: bool = True,
        prefill_chunk: Optional[int] = None,
        obs: Optional[Observability] = None,
        fault_injector=None,
    ):
        # observability bundle FIRST: the counter attributes below are
        # RegistryCounterView descriptors whose backing cells live in
        # ``self.obs.metrics``, so it must exist before any ``= 0`` lands
        self.obs = obs or Observability()
        #: optional seeded ``FaultInjector`` (DESIGN.md §9): consulted at
        #: the ``engine/nan_logits`` point before each fused dispatch (and
        #: handed to the page pool for ``pool/alloc_fail``); None = inert
        self.fault_injector = fault_injector
        if fault_injector is not None:
            fault_injector.metrics = self.obs.metrics
        self.cfg = cfg
        self.max_slots = max_slots
        self.max_seq = max_seq
        self.compute_dtype = compute_dtype
        #: the device every program runs on
        self.device = jax.devices()[0]
        self._cast = jax.jit(_program(T.cast_params, dtype=compute_dtype))
        #: the trees the engine was handed if on one device, else the
        #: compute-dtype copies gathered from them (``_resident``, kept for
        #: the engine's life); the programs are fed ``params`` and
        #: ``draft_params``
        self._resident: set = set()
        self._given = {}
        for model, tree in (("target", params), ("draft", draft_params)):
            if tree is not None and not _on_one_device(tree):
                tree = self._gathered(tree)
                self._resident.add(model)
            self._given[model] = tree
        self._served: dict = {}
        self.clock: Callable[[], float] = clock or time.monotonic
        self.min_prefill_bucket = min_prefill_bucket
        #: decode-path attention impl, kept for programs built after
        #: construction (the host-proposed tree-verify rounds)
        self._attn_impl = decode_impl

        # --- chunked prefill (DESIGN.md §7): None -> auto (on for attention
        # families, whose chunk attention is the verify shape; recurrent
        # families keep the monolithic dt-masked bucket prefill); 0 -> off.
        if prefill_chunk is None:
            prefill_chunk = (
                DEFAULT_PREFILL_CHUNK if cfg.family in _ATTENTION_FAMILIES
                else 0
            )
        if prefill_chunk:
            assert cfg.family in _ATTENTION_FAMILIES, (
                f"chunked prefill needs an attention family, not "
                f"{cfg.family!r}"
            )
        self.prefill_chunk = prefill_chunk
        #: per-slot pending prompt-token streams while PREFILLING (target
        #: and draft progress differ under prefix hits: the draft has no
        #: prefix pool and always streams the whole prompt)
        self._prefill_left: list[Optional[np.ndarray]] = [None] * max_slots
        self._draft_prefill_left: list[Optional[np.ndarray]] = (
            [None] * max_slots
        )
        #: device [B] next-token array from the wave that completed each
        #: slot's target prefill
        self._prefill_tok: list = [None] * max_slots
        #: ``Readback.first`` entries of the slots whose prefill completed
        #: since the last ``_readback``
        self._landed: list = []
        #: a dispatched decode loop's readback for ``_drive_decode_loop`` to
        #: absorb instead of dispatching one
        self._ahead: Optional[Readback] = None
        #: slot -> metered tokens taken by the LAST _drive_prefill_chunks
        #: call (the core turns these into per-slot prefill-chunk spans)
        self.last_prefill_slot_tokens: dict[int, int] = {}

        # --- KV layout: paged pool (attention families) or dense rows ---
        if kv_page_size is None:
            kv_page_size = (
                DEFAULT_KV_PAGE_SIZE if cfg.family in _ATTENTION_FAMILIES
                else 0
            )
        self.paged = kv_page_size > 0
        self.kv_page_size = kv_page_size
        self.pool: Optional[PagePool] = None
        self.prefix_cache: Optional[RadixCache] = None
        if self.paged:
            assert cfg.family in _ATTENTION_FAMILIES, (
                f"paged KV cache needs an attention family, not {cfg.family!r}"
            )
            assert kv_page_size & (kv_page_size - 1) == 0, (
                "kv_page_size must be a power of two (page-aligned buckets)"
            )
            self.pages_per_slot = -(-max_seq // kv_page_size)
            # default pool: dense-equivalent logical capacity (+ sentinel);
            # callers shrink it (or raise max_slots) to trade layout slack
            # for concurrency
            num_pages = kv_pool_pages or (
                max_slots * self.pages_per_slot + 1
            )
            self.pool = PagePool(num_pages, kv_page_size)
            self.pool.fault_injector = fault_injector
            if enable_prefix_cache:
                self.prefix_cache = RadixCache(self.pool)
            cache = T.init_paged_cache(
                cfg, max_slots, num_pages, kv_page_size,
                self.pages_per_slot, compute_dtype,
            )
            # prefill buckets must stay page-aligned for the page scatter
            # (round up: doubling then preserves page multiples)
            self.min_prefill_bucket = kv_page_size * (
                -(-max(min_prefill_bucket, 1) // kv_page_size)
            )
            self._slot_pages: list[list[int]] = [[] for _ in range(max_slots)]
            self._slot_reserved = [0] * max_slots
            self._slot_idx = [0] * max_slots
            self._slot_horizon = [0] * max_slots
            # host mirror of the device block tables: mutations land here
            # and ship as ONE whole-table h2d transfer (the table is tiny;
            # per-entry device scatters cost more in dispatch than the copy)
            self._bt_host = np.zeros(
                (max_slots, self.pages_per_slot + 1), np.int32
            )
            self._bt_dirty = False
        else:
            cache = T.init_cache(cfg, max_slots, max_seq, compute_dtype)
            cache["index"] = jnp.zeros((max_slots,), jnp.int32)
        self.cache = cache
        self._core = None  # lazily-built EngineCore (the .core property)
        self.slots: list[Optional[Request]] = [None] * max_slots
        self.tokens = jnp.zeros((max_slots,), jnp.int32)
        self.steps_executed = 0
        # perf counters (registry views; the tests and bench/ read these)
        self.d2h_transfers = 0  # device->host syncs issued by engine code
        self.generated_tokens_total = 0
        #: (model, impl) -> distinct program widths compiled, where model is
        #: "target"/"draft" and impl is "bucket" (monolithic power-of-two),
        #: "suffix" (prefix-hit suffix prefill), or "chunk" (the one
        #: fixed-width chunked-prefill program).  ``prefill_compile_count``
        #: sums the buckets; ``prefill_compile_counts`` reports them.
        self._prefill_programs: dict[tuple[str, str], set] = {}
        # prefix-cache counters (prefill_skip_fraction reads these)
        self.prefill_prompt_tokens = 0
        self.prefill_skipped_tokens = 0
        #: layout-independent prefill meter (DESIGN.md §7): per admission,
        #: the max of the target's computed tokens (prompt minus prefix
        #: skip) and the draft's (always the whole prompt — no draft prefix
        #: pool), the same per-slot-per-wave metric the chunked driver
        #: charges, so ``EngineCore.step`` prices monolithic and chunked
        #: prefill identically
        self.prefill_metered_tokens = 0
        # speculative-decoding counters (spec_acceptance_rate reads these)
        self.spec_rounds = 0
        self.spec_drafted = 0
        self.spec_accepted = 0

        self._decode_loop = jax.jit(
            _program(
                decode_loop, cfg, compute_dtype=compute_dtype,
                attn_impl=decode_impl, max_seq=max_seq,
            ),
            static_argnames=("k",),
            donate_argnames=("tokens", "cache"),
        )
        self._put_first = jax.jit(
            _program(put_first_tokens), donate_argnames=("tokens",)
        )
        if self._resident and self.paged:
            self._decode_loop = self._tables_on_device(self._decode_loop)
        if self.paged:
            self._prefill_slot = jax.jit(
                _program(
                    T.prefill_into_slot_paged, cfg,
                    impl=prefill_impl, compute_dtype=compute_dtype,
                ),
                donate_argnames=("cache",),
            )
            self._suffix_prefill = jax.jit(
                _program(
                    T.prefill_suffix_into_slot, cfg,
                    compute_dtype=compute_dtype, attn_impl=decode_impl,
                ),
                donate_argnames=("cache",),
            )
        else:
            self._prefill_slot = jax.jit(
                _program(
                    T.prefill_into_slot, cfg, max_seq=max_seq,
                    impl=prefill_impl, compute_dtype=compute_dtype,
                ),
                donate_argnames=("cache",),
            )
        if self.prefill_chunk:
            # the ONE chunked-prefill program: every argument is traced, so
            # a single compile serves every mix of slots / chunk lengths /
            # prefill offsets (dense and paged branch on the cache layout)
            self._prefill_chunks = jax.jit(
                _program(
                    T.prefill_chunks_into_slots, cfg,
                    compute_dtype=compute_dtype, attn_impl=decode_impl,
                ),
                donate_argnames=("cache",),
            )

        # --- speculative decoding (draft/target pairing) ---------------
        self.draft_cfg = draft_cfg
        self.draft_cache = None
        self.spec_cfg = spec or SpecDecodeConfig()
        #: PRNG stream for simulated-acceptance modes (spec loop AND the
        #: host-proposed tree rounds, which exist without a draft pairing)
        self._spec_key = jax.random.PRNGKey(spec_seed)
        if self.spec_enabled:
            assert draft_cfg is not None, "draft_params without draft_cfg"
            assert draft_cfg.vocab_size == cfg.vocab_size, (
                "draft and target must share a vocabulary"
            )
            dcache = T.init_cache(draft_cfg, max_slots, max_seq, compute_dtype)
            dcache["index"] = jnp.zeros((max_slots,), jnp.int32)
            self.draft_cache = dcache
            from repro.spec.loop import spec_decode_loop as _spec_fn

            self._spec_loop = jax.jit(
                _program(
                    _spec_fn, cfg, draft_cfg, mode=self.spec_cfg.mode,
                    max_seq=max_seq, sim_accept_p=self.spec_cfg.sim_accept_p,
                    compute_dtype=compute_dtype, attn_impl=decode_impl,
                ),
                static_argnames=("k", "gamma"),
                donate_argnames=(
                    "tokens", "cache", "draft_cache", "remaining", "key"
                ),
            )
            self._draft_prefill = jax.jit(
                _program(
                    T.prefill_into_slot, draft_cfg, max_seq=max_seq,
                    impl=prefill_impl, compute_dtype=compute_dtype,
                ),
                donate_argnames=("cache",),
            )
            if self.prefill_chunk:
                # draft prefill folds into the same admission wave as the
                # target's (one batched dispatch per model per wave, not
                # one per admitted request); its first-token logits are
                # never read, so the program skips the vocab projection
                self._draft_prefill_chunks = jax.jit(
                    _program(
                        T.prefill_chunks_into_slots, draft_cfg,
                        compute_dtype=compute_dtype, attn_impl=decode_impl,
                        need_logits=False,
                    ),
                    donate_argnames=("cache",),
                )

        # --- pluggable proposers + routing (DESIGN.md §10) --------------
        #: name -> Proposer.  ``spec_cfg.proposer`` selects the initial
        #: set: "auto" registers every applicable source on a DRAFT-PAIRED
        #: engine (the draft model plus prompt-lookup n-gram on attention
        #: families) but nothing on a plain engine — speculation stays
        #: opt-in, so engines built without a draft pairing behave exactly
        #: as before.  "draft"/"ngram" pin one ("ngram" enables host-only
        #: speculation on a plain engine); "suffix" starts empty (a
        #: corpus-backed ``StaticSuffixProposer`` needs the corpus —
        #: callers attach it via ``register_proposer``); "none" disables
        #: routing entirely.
        self._proposers: dict = {}
        self._router = None
        self._tree_round_cache: dict = {}
        #: per-slot (accepted, proposed) from the LAST fused spec loop —
        #: the router's draft-path feedback
        self._last_spec_slot_stats: dict = {}
        pchoice = self.spec_cfg.proposer
        if pchoice != "none":
            from repro.spec.proposers import DraftModelProposer, NgramProposer

            if self.spec_enabled and pchoice in ("auto", "draft"):
                self._proposers["draft"] = DraftModelProposer(
                    draft_cost_ratio=self.spec_cfg.draft_cost_ratio
                )
            if cfg.family in _ATTENTION_FAMILIES and (
                pchoice == "ngram"
                or (pchoice == "auto" and self.spec_enabled)
            ):
                self._proposers["ngram"] = NgramProposer(
                    order=self.spec_cfg.ngram_order
                )
            if self._proposers:
                self._rebuild_router()

    # ------------------------------------------------------------------
    @property
    def params(self) -> Any:
        """The target params the programs are fed (``_serving``)."""
        return self._serving("target")

    @property
    def draft_params(self) -> Any:
        """The draft params the programs are fed, None without a draft."""
        return self._serving("draft")

    def _serving(self, model: str) -> Any:
        """The serving copy of a given tree: its float32 matrices cast to
        the compute dtype by ONE program on first use, instead of by
        ``T.cast_params`` inside every program on every call (their casts
        of the copy are no-ops), then kept until ``release_params``.  The
        values are the ones those casts compute, and the copy shares no
        buffer with the given tree (DESIGN.md §3, "Serving params").  A
        float32 engine serves the given tree as it is, and a gathered
        copy (``_resident``) is served as it is, always."""
        tree = self._served.get(model)
        if tree is None:
            tree = self._given[model]
            if tree is not None and model not in self._resident and (
                jnp.dtype(self.compute_dtype) != jnp.float32
            ):
                self.obs.metrics.counter("engine/serving_param_casts").inc()
                tree = self._cast(tree)
            self._served[model] = tree
        return tree

    def _gathered(self, tree: Any) -> Any:
        """The engine's own copy of a tree sharded over a mesh, on its
        device, in the compute dtype: ``jit_cast_params`` casts each shard
        where it lives, then the compute-dtype shards are moved onto the
        engine's device, so that device never holds the float32 tree.
        Made once, at construction, and counted on
        ``engine/serving_param_gathers``."""
        with self.obs.span("engine.gather_params"):
            ours = jax.device_put(self._cast(tree), self.device)
            jax.block_until_ready(ours)
        self.obs.metrics.counter("engine/serving_param_gathers").inc()
        return ours

    def _tables_on_device(self, loop: Callable) -> Callable:
        """``loop`` (the jitted decode loop) taking a cache whose block
        tables may be placed on a trainer's mesh, as a sharded trainer's
        harness warms them: such tables are moved onto the engine's device
        first, one count of ``engine/placement_moves`` each; tables already
        there pass as they are."""
        moves = self.obs.metrics.counter("engine/placement_moves")

        def run(params, tokens, cache, *args, **kwargs):
            bt = cache["block_tables"]
            if bt.sharding.device_set != {self.device}:
                cache = dict(cache,
                             block_tables=jax.device_put(bt, self.device))
                moves.inc()
            return loop(params, tokens, cache, *args, **kwargs)

        return run

    def release_params(self) -> None:
        """Free the serving copies cast from trees on one device; the next
        program call makes them again.  A collocated runtime calls this
        before each train step, so the step runs with the device memory it
        has without filling.  A gathered copy stays: it shares no buffer
        with the trainer and is never made again."""
        self._served.clear()

    @property
    def spec_enabled(self) -> bool:
        return self._given["draft"] is not None

    @property
    def host_spec_enabled(self) -> bool:
        """True when a host-side (model-free) proposer is registered — the
        tree-verify path is available even without a draft pairing."""
        return any(p.kind == "host" for p in self._proposers.values())

    @property
    def proposer_router(self):
        return self._router

    def register_proposer(self, proposer) -> None:
        """Attach an additional candidate source (e.g. a corpus-backed
        ``StaticSuffixProposer``) and rebuild the router over the new set.
        Host proposers need an attention family (tree verification needs
        parallel position scoring)."""
        if proposer.kind == "host":
            assert self.cfg.family in _ATTENTION_FAMILIES, (
                f"host proposers need an attention family, not "
                f"{self.cfg.family!r}"
            )
        self._proposers[proposer.name] = proposer
        self._rebuild_router()

    def _rebuild_router(self) -> None:
        from repro.spec.proposers import ProposerRouter

        device = tuple(
            n for n, p in self._proposers.items() if p.kind == "device"
        )
        self._router = ProposerRouter(
            list(self._proposers),
            device_names=device,
            ewma=self.spec_cfg.router_ewma,
            init_acceptance=self.spec_cfg.router_init_acceptance,
            draft_cost_ratio=self.spec_cfg.draft_cost_ratio,
        )

    def route_proposer(self, gamma: int):
        """Route the coming quantum: ONE proposer for the whole batch (the
        engine dispatches one fused program per quantum), picked by summed
        per-slot score.  Returns the name, or None when no proposer is
        registered (callers fall back to the historical dispatch)."""
        if self._router is None:
            return None
        slots = [
            i for i, r in enumerate(self.slots)
            if r is not None and not self.slot_prefilling(i)
        ]
        name = self._router.pick_majority(slots, gamma)
        self.obs.metrics.counter("spec/proposer/router_switches").set(
            self._router.switches
        )
        return name

    def proposer_round_cost(self, name: str, gamma: int) -> float:
        """Quantum steps one routed round will spend (grant pricing)."""
        return self._router.round_cost(name, gamma)

    @property
    def spec_acceptance_rate(self) -> float:
        """Observed draft-token acceptance across all spec rounds (pre
        budget-clamp: measures draft quality, not budget truncation)."""
        if self.spec_drafted == 0:
            return float("nan")
        return self.spec_accepted / self.spec_drafted

    # ------------------------------------------------------------------
    def free_slots(self) -> list[int]:
        return [i for i, r in enumerate(self.slots) if r is None]

    @property
    def num_active(self) -> int:
        return sum(r is not None for r in self.slots)

    def slot_prefilling(self, i: int) -> bool:
        """True while slot ``i`` still has prompt chunks to stream (target
        or draft side) — such a slot is frozen in the fused loops and never
        retires mid-prefill."""
        return (
            self._prefill_left[i] is not None
            or self._draft_prefill_left[i] is not None
        )

    @property
    def num_prefilling(self) -> int:
        return sum(
            self.slot_prefilling(i) for i in range(self.max_slots)
        )

    def _record_prefill_program(
        self, model: str, impl: str, width: int
    ) -> None:
        self._prefill_programs.setdefault((model, impl), set()).add(width)

    @property
    def prefill_compile_count(self) -> int:
        """Distinct prefill programs compiled across models and impls (one
        per (model, impl, width) triple).  Chunked prefill pins this to a
        small constant — one fixed-width program per model — where the
        bucket family grew with the prompt-length distribution."""
        return sum(len(v) for v in self._prefill_programs.values())

    def prefill_compile_counts(self) -> dict[str, int]:
        """Per-model (target/draft), per-impl (bucket/suffix/chunk) prefill
        program counts — the unconflated view of
        ``prefill_compile_count``."""
        return {
            f"{model}/{impl}": len(widths)
            for (model, impl), widths in sorted(self._prefill_programs.items())
        }

    def _bucket_len(self, n: int, page_aligned: Optional[bool] = None) -> int:
        """Power-of-two compile bucket for a prompt of length ``n``.

        Page-aligned buckets (the paged default) cap at ``max_seq`` rounded
        UP to a page multiple — the bucket-page scatter needs alignment
        even when ``max_seq`` itself is not page-aligned, and positions
        past ``max_seq`` are pad, scattered into the sentinel.  Dense
        consumers (the legacy layout, and a spec pairing's dense draft
        cache on an otherwise-paged engine) must pass
        ``page_aligned=False``: their prefill pads K/V to exactly
        ``max_seq`` and cannot take a larger bucket."""
        if page_aligned is None:
            page_aligned = self.paged
        b = self.min_prefill_bucket
        while b < n:
            b *= 2
        if page_aligned:
            return min(b, self.pages_per_slot * self.kv_page_size)
        return min(b, self.max_seq)

    # ------------------------------------------------------------------
    # Paged-pool bookkeeping
    # ------------------------------------------------------------------
    def _page_need(self, req: Request) -> tuple[int, int]:
        """(worst-case total pages, prompt pages) for ``req`` — the
        Principle-I capacity question admission answers."""
        n = len(req.prompt)
        horizon = min(n + req.max_new_tokens, self.max_seq)
        return self.pool.pages_for(horizon), self.pool.pages_for(n)

    def _shared_prefix(self, prompt: np.ndarray, record: bool = True):
        """Longest radix-cached full-page prefix of ``prompt``, capped one
        token short of the whole prompt so at least one suffix token remains
        to produce the first-token logits."""
        if self.prefix_cache is None:
            return []
        return self.prefix_cache.match(prompt[: len(prompt) - 1],
                                       record=record)

    def _ensure_capacity(self, need: int) -> bool:
        """Make ``need`` pages promisable, evicting LRU cached prefixes."""
        while self.pool.available < need:
            if self.prefix_cache is None:
                return False
            if self.prefix_cache.evict(need - self.pool.available) == 0:
                return False
        return True

    def request_fits(self, req: Request) -> bool:
        """Structural admissibility: could ``req`` EVER be admitted, even on
        an idle engine?  False means waiting will not help (prompt exceeds
        max_seq, or its worst-case page need exceeds the whole pool) —
        queue managers should fail such a request loudly instead of letting
        it starve the head of the line."""
        if len(req.prompt) > self.max_seq:
            return False
        if self.paged:
            total_pages, _ = self._page_need(req)
            return total_pages <= self.pool.num_pages - 1
        return True

    def can_admit(self, req: Request) -> bool:
        """Capacity probe for Algorithm-1 admission: a free slot exists AND
        (paged engines) the pool can cover the request's worst-case page
        need, counting evictable cached prefixes but never the pages the
        request itself would share.  Non-mutating."""
        if not self.free_slots() or not self.request_fits(req):
            return False
        if not self.paged:
            return True
        total_pages, _ = self._page_need(req)
        prompt = np.asarray(req.prompt, np.int32)
        shared = self._shared_prefix(prompt, record=False)
        evictable = 0
        if self.prefix_cache is not None:
            evictable = self.prefix_cache.evictable_pages() - sum(
                1 for p in shared if self.pool.refcount[p] == 1
            )
        return total_pages - len(shared) <= self.pool.available + evictable

    def export_prefix_pages(self):
        """Warm-state snapshot export (DESIGN.md §11): the radix cache's
        tree structure plus the KV contents of its pages, as
        ``(nodes, k, v)`` with ``k``/``v`` shaped ``[L, N, page, kvH, hd]``
        gathered in node order.  None on dense engines or when the cache
        is empty — the snapshot is strictly optional warm state."""
        if self.prefix_cache is None:
            return None
        nodes = self.prefix_cache.export_nodes()
        if not nodes:
            return None
        pages = jnp.asarray([page for _, _, page in nodes], jnp.int32)
        layers = self.cache["layers"]
        return nodes, layers["k"][:, pages], layers["v"][:, pages]

    def import_prefix_pages(self, nodes, k, v) -> int:
        """Warm the radix cache from an exported snapshot: allocate fresh
        pages (evicting colder entries if needed), write the saved KV
        contents into them, and rebuild the tree.  Nodes that don't fit
        are dropped from the tail — warm state is best-effort, never
        required for correctness.  Returns the nodes loaded."""
        if self.prefix_cache is None or not nodes:
            return 0
        keep = len(nodes)
        if not self._ensure_capacity(keep):
            # drop whole subtrees from the tail: export order is
            # parents-first, so a prefix of it is still a valid forest
            keep = self.pool.available
            nodes = nodes[:keep]
        if keep == 0:
            return 0
        pages = self.pool.alloc(keep)
        idx = jnp.asarray(pages, jnp.int32)
        dtype = self.cache["layers"]["k"].dtype
        layers = self.cache["layers"]
        layers["k"] = layers["k"].at[:, idx].set(
            jnp.asarray(k[:, :keep], dtype)
        )
        layers["v"] = layers["v"].at[:, idx].set(
            jnp.asarray(v[:, :keep], dtype)
        )
        return self.prefix_cache.load_nodes(nodes, pages)

    def _sync_block_tables(self) -> None:
        with self.obs.span("engine.tables"):
            self.cache["block_tables"] = jnp.asarray(self._bt_host)
            self._bt_dirty = False

    def _fetch(self, tree):
        """Blocking device->host copy of ``tree``: the host waiting on the
        device, timed as the ``engine.fetch`` span."""
        with self.obs.span("engine.fetch"):
            return jax.device_get(tree)

    def _set_block_table_row(
        self, slot: int, pages: list[int], sync: bool = True
    ) -> None:
        self._bt_host[slot] = 0
        self._bt_host[slot, : len(pages)] = pages
        self._bt_dirty = True
        if sync:
            self._sync_block_tables()

    def _top_up_pages(self, steps: int) -> None:
        """Extend every active slot's block table to cover the next
        ``steps`` token writes (converting admission reservations into
        physical pages) — the fused loops then never need a host alloc.

        A ``PageAllocError`` (injected transient allocator fault,
        DESIGN.md §9) is contained per slot: the failing slot is evicted
        and its request re-queued through the core's fault path; the
        other slots keep decoding."""
        with self.obs.span("engine.tables"):
            for i, r in enumerate(self.slots):
                if r is None:
                    continue
                cover = min(self._slot_idx[i] + steps, self._slot_horizon[i])
                need = self.pool.pages_for(cover)
                cur = len(self._slot_pages[i])
                if need > cur:
                    try:
                        got = self.pool.alloc(need - cur, reserved=True)
                    except PageAllocError:
                        self.obs.metrics.counter("fault/alloc_failures").inc()
                        req = self.evict_slot(i, sync=False)
                        if self._core is not None:
                            self._core._on_slot_fault(i, req)
                        continue
                    self._slot_reserved[i] -= len(got)
                    self._bt_host[i, cur: cur + len(got)] = got
                    self._slot_pages[i].extend(got)
                    self._bt_dirty = True
        if self._bt_dirty:
            self._sync_block_tables()

    def _trim_slot_pages(self, i: int) -> None:
        """Release pages past the page holding the slot's next write
        position — speculative rollback's freed capacity returns to the
        pool (as restored reservation) instead of idling.  Marks the block
        tables dirty; the caller syncs once per sweep."""
        keep = self._slot_idx[i] // self.kv_page_size + 1
        pages = self._slot_pages[i]
        if len(pages) <= keep:
            return
        drop = pages[keep:]
        del pages[keep:]
        freed = self.pool.decref(drop)
        # trimmed pages sit past the prompt (idx >= prompt length), so the
        # radix tree never holds them: every drop frees
        assert len(freed) == len(drop), "trimmed a shared page"
        self.pool.reserve(len(drop))
        self._slot_reserved[i] += len(drop)
        self._bt_host[i, keep: keep + len(drop)] = 0
        self._bt_dirty = True

    def evict_slot(self, i: int, sync: bool = True) -> Request:
        """Release slot ``i``'s resources — pages back to the pool, BOTH
        cache indices reset (the draft index too, which the plain-loop
        paths previously left stale) — WITHOUT finishing the request.

        This is the preempt/abort primitive: the request keeps its
        generated tokens and may be re-admitted later (resume re-prefills
        ``prompt + generated``; the radix tree still holds the prompt's
        full pages, so a paged resume recomputes only the suffix).
        ``sync=False`` defers the block-table upload to the caller's sweep
        (the retirement paths batch one upload over all evictions)."""
        req = self.slots[i]
        assert req is not None, f"evict of empty slot {i}"
        self.slots[i] = None
        if self._router is not None:
            # recycled slots start from the optimistic prior again
            self._router.reset_slot(i)
        # a mid-PREFILLING eviction drops the pending chunk streams: resume
        # re-prefills from the radix-covered prefix (partial chunk work past
        # it is recomputed — its pages were released with the slot)
        self._prefill_left[i] = None
        self._draft_prefill_left[i] = None
        self._prefill_tok[i] = None
        req.pending = 0
        self.cache["index"] = self.cache["index"].at[i].set(0)
        if self.spec_enabled:
            self.draft_cache["index"] = (
                self.draft_cache["index"].at[i].set(0)
            )
        if self.paged:
            self.pool.decref(self._slot_pages[i])
            self.pool.unreserve(self._slot_reserved[i])
            self._slot_pages[i] = []
            self._slot_reserved[i] = 0
            self._slot_idx[i] = 0
            self._slot_horizon[i] = 0
            self._bt_host[i] = 0
            self._bt_dirty = True
            if sync:
                self._sync_block_tables()
        return req

    def _retire_slot(self, i: int, now: float) -> Request:
        """Single retirement path for the fused loops: evict the slot,
        stamp the finish time, and notify the lifecycle core (if one is
        attached) so the request's state machine advances to FINISHED."""
        req = self.evict_slot(i, sync=False)
        req.finish_time = now
        if self._core is not None:
            self._core._on_slot_finished(i, req)
        return req

    # ------------------------------------------------------------------
    def _embed_or_pass(self, params, buf: np.ndarray):
        if self.cfg.embed_inputs:
            # stub frontend: embed prompt tokens through the output table
            return params["embed"][jnp.asarray(buf)].astype(
                self.compute_dtype
            )
        return jnp.asarray(buf)

    def _bucket_buf(
        self,
        tokens: np.ndarray,
        page_aligned: Optional[bool] = None,
        model: str = "target",
        impl: str = "bucket",
    ) -> np.ndarray:
        sb = self._bucket_len(len(tokens), page_aligned)
        self._record_prefill_program(model, impl, sb)
        buf = np.zeros((1, sb), np.int32)
        buf[0, : len(tokens)] = tokens
        return buf

    def _paged_reserve(
        self, slot: int, req: Request
    ) -> Optional[tuple[list[int], int]]:
        """The bookkeeping half of paged admission, shared by monolithic
        prefill and chunked streaming: match the radix prefix, make room
        (evicting LRU cached prefixes if needed), allocate prompt pages now
        and reserve the decode horizon.  Returns ``(block-table row, shared
        token count)``, or None on capacity.  Leaves the block tables dirty
        — callers batch the h2d upload before their first dispatch."""
        n = len(req.prompt)
        prompt = np.asarray(req.prompt, np.int32)
        total_pages, prompt_pages = self._page_need(req)
        shared_pages = self._shared_prefix(prompt)
        if shared_pages:
            # hold the matched pages before eviction can reclaim them
            self.pool.incref(shared_pages)
        if not self._ensure_capacity(total_pages - len(shared_pages)):
            if shared_pages:
                self.pool.decref(shared_pages)
            return None
        try:
            new_pages = self.pool.alloc(prompt_pages - len(shared_pages))
        except PageAllocError:
            # exhaustion or an injected allocator fault: unwind the
            # prefix hold and report "no capacity" — admission blocks
            # (the request stays queued) instead of crashing
            self.obs.metrics.counter("fault/alloc_failures").inc()
            if shared_pages:
                self.pool.decref(shared_pages)
            return None
        self.pool.reserve(total_pages - prompt_pages)
        row = shared_pages + new_pages
        self._slot_pages[slot] = list(row)
        self._slot_reserved[slot] = total_pages - prompt_pages
        self._slot_horizon[slot] = min(n + req.max_new_tokens, self.max_seq)
        self._slot_idx[slot] = len(shared_pages) * self.kv_page_size
        self._set_block_table_row(slot, row, sync=False)
        return row, len(shared_pages) * self.kv_page_size

    def _paged_admit(self, slot: int, req: Request) -> Optional[int]:
        """Capacity-based paged MONOLITHIC admission: reserve pages, then
        prefill in one dispatch — the whole prompt on a radix miss, only
        the suffix on a hit.  (Chunked engines stream instead:
        ``_begin_chunked_admit`` + ``_drive_prefill_chunks``.)"""
        res = self._paged_reserve(slot, req)
        if res is None:
            return None
        row, shared = res
        self._sync_block_tables()  # the prefill dispatch reads the tables
        n = len(req.prompt)
        prompt = np.asarray(req.prompt, np.int32)
        self._slot_idx[slot] = n
        if shared:
            suffix = prompt[shared:]
            buf = self._bucket_buf(suffix, impl="suffix")
            tok, self.cache = self._suffix_prefill(
                self.params, jnp.asarray(buf), jnp.int32(len(suffix)),
                jnp.int32(shared), jnp.int32(slot), self.cache,
            )
            self.prefill_skipped_tokens += shared
        else:
            buf = self._bucket_buf(prompt)
            tok, self.cache = self._prefill_slot(
                self.params, self._embed_or_pass(self.params, buf),
                jnp.int32(n), jnp.int32(slot), self.cache,
            )
        self.prefill_prompt_tokens += n
        self.prefill_metered_tokens += n if self.spec_enabled else n - shared
        if self.prefix_cache is not None:
            # cache the prompt's full pages for future admissions (the tree
            # takes its own reference; they outlive this slot)
            self.prefix_cache.insert(prompt, row[: n // self.kv_page_size])
        if self.spec_enabled:
            # the dense draft cache has no prefix pool: it prefill-tracks
            # the full prompt (cheap by construction; first-token output is
            # never fetched — no extra device->host transfer).  Its bucket
            # caps at max_seq, not the page-aligned roundup.
            dbuf = self._bucket_buf(prompt, page_aligned=False, model="draft")
            _, self.draft_cache = self._draft_prefill(
                self.draft_params, self._embed_or_pass(self.draft_params, dbuf),
                jnp.int32(n), jnp.int32(slot), self.draft_cache,
            )
        return tok

    def _dense_admit(self, slot: int, req: Request) -> int:
        n = len(req.prompt)
        buf = self._bucket_buf(np.asarray(req.prompt, np.int32))
        tok, self.cache = self._prefill_slot(
            self.params, self._embed_or_pass(self.params, buf),
            jnp.int32(n), jnp.int32(slot), self.cache,
        )
        self.prefill_prompt_tokens += n
        self.prefill_metered_tokens += n
        if self.spec_enabled:
            # draft cache tracks the same prefix; its first-token output is
            # never fetched (no extra device->host transfer)
            dbuf = self._bucket_buf(
                np.asarray(req.prompt, np.int32), model="draft"
            )
            _, self.draft_cache = self._draft_prefill(
                self.draft_params, self._embed_or_pass(self.draft_params, dbuf),
                jnp.int32(n), jnp.int32(slot), self.draft_cache,
            )
        return tok

    # ------------------------------------------------------------------
    # Chunked prefill (DESIGN.md §7): admission reserves, waves stream
    # ------------------------------------------------------------------
    def _begin_chunked_admit(self, slot: int, req: Request) -> bool:
        """Chunked admission: reserve the slot's capacity (paged: prompt
        pages + decode-horizon reservation, radix prefix matched and held)
        WITHOUT running any prefill compute — the prompt streams into the
        slot as fixed-width chunks across subsequent
        ``_drive_prefill_chunks`` waves.  Block-table mutations stay host-
        side; the first wave ships them as ONE h2d upload covering every
        admission in the step."""
        n = len(req.prompt)
        prompt = np.asarray(req.prompt, np.int32)
        shared = 0
        if self.paged:
            res = self._paged_reserve(slot, req)
            if res is None:
                return False
            _, shared = res
            if shared:
                # the slot's device-side progress starts past the radix-
                # covered prefix; chunk attention reads those shared pages
                # directly, so the skip costs zero FLOPs as before
                self.cache["index"] = self.cache["index"].at[slot].set(shared)
        self._prefill_left[slot] = prompt[shared:]
        if self.spec_enabled:
            self._draft_prefill_left[slot] = prompt  # no draft prefix pool
        self._prefill_tok[slot] = None
        self.prefill_prompt_tokens += n
        self.prefill_skipped_tokens += shared
        self.slots[slot] = req
        return True

    def _plan_prefill_waves(self, budget: float):
        """Host-side preview of ``_drive_prefill_chunks``: greedy slot-order
        allocation of chunk takes, wave by wave, under ``budget`` metered
        tokens.  Returns ``(waves, consumed, completing)`` where each wave
        is a list of ``(slot, target_take, draft_take)`` — deterministic,
        so schedulers can price a step's prefill cost BEFORE driving it."""
        chunk = self.prefill_chunk
        left: dict[int, list[int]] = {}
        for i in range(self.max_slots):
            t = self._prefill_left[i]
            d = self._draft_prefill_left[i]
            t_n = len(t) if t is not None else 0
            d_n = len(d) if d is not None else 0
            if t_n or d_n:
                left[i] = [t_n, d_n]
            elif self.slot_prefilling(i):
                # fully-streamed but not yet finalized (shouldn't persist)
                left[i] = [0, 0]
        waves, consumed, completing = [], 0, []
        budget_left = budget
        while left:
            wave = []
            # shortest-pending-first: a just-admitted short (online) prompt
            # completes ahead of a long stream instead of starving behind
            # it when the budget runs dry mid-wave
            order = sorted(left, key=lambda i: (max(left[i]), i))
            for i in order:
                if budget_left <= 0:
                    break
                t_n, d_n = left[i]
                tt, dd = min(chunk, t_n), min(chunk, d_n)
                cost = max(tt, dd)
                if cost > budget_left:
                    cap = int(budget_left)
                    tt, dd = min(tt, cap), min(dd, cap)
                    cost = max(tt, dd)
                if cost <= 0:
                    continue
                wave.append((i, tt, dd))
                left[i] = [t_n - tt, d_n - dd]
                budget_left -= cost
                consumed += cost
                if left[i] == [0, 0]:
                    completing.append(i)
                    del left[i]
            if not wave:
                break
            waves.append(wave)
        return waves, consumed, completing

    def _drive_prefill_chunks(self, budget: float = math.inf) -> int:
        """Stream chunk waves into every PREFILLING slot, consuming at most
        ``budget`` metered tokens (per slot per wave: max of the target and
        draft takes), and deliver the first token of every slot whose
        prompt completed, in ONE batched d2h transfer.  Returns tokens
        consumed.  The dispatch half is ``_dispatch_prefill_waves``."""
        consumed = self._dispatch_prefill_waves(budget)
        self._absorb(self._readback())
        return consumed

    def _dispatch_prefill_waves(self, budget: float) -> int:
        """The packing and dispatch half of ``_drive_prefill_chunks``,
        in the ``engine.prefill`` host span: each wave is ONE batched target
        dispatch plus — when a draft pairing is attached — ONE batched
        draft dispatch, replacing the per-request prefill (and per-request
        draft prefill) dispatches of the monolithic path.  Slots whose
        prompt completes start RUNNING at once (``_land``).  Returns tokens
        consumed."""
        self.last_prefill_slot_tokens = {}
        if not self.prefill_chunk:
            return 0
        waves, consumed, _ = self._plan_prefill_waves(budget)
        if not waves:
            return 0
        with self.obs.span("engine.prefill"):
            for wave in waves:
                for i, tt, dd in wave:
                    self.last_prefill_slot_tokens[i] = (
                        self.last_prefill_slot_tokens.get(i, 0) + max(tt, dd)
                    )
            if self.paged and self._bt_dirty:
                self._sync_block_tables()  # one h2d wave covers every admission
            chunk = self.prefill_chunk
            completed: list[int] = []
            for wave in waves:
                t_lens = np.zeros((self.max_slots,), np.int32)
                d_lens = np.zeros((self.max_slots,), np.int32)
                t_toks = np.zeros((self.max_slots, chunk), np.int32)
                d_toks = np.zeros((self.max_slots, chunk), np.int32)
                t_done: list[int] = []
                for i, tt, dd in wave:
                    if tt:
                        buf = self._prefill_left[i]
                        t_toks[i, :tt] = buf[:tt]
                        t_lens[i] = tt
                        self._prefill_left[i] = buf[tt:]
                        if len(self._prefill_left[i]) == 0:
                            t_done.append(i)
                        if self.paged:
                            self._slot_idx[i] += tt
                    if dd:
                        dbuf = self._draft_prefill_left[i]
                        d_toks[i, :dd] = dbuf[:dd]
                        d_lens[i] = dd
                        self._draft_prefill_left[i] = dbuf[dd:]
                if t_lens.any():
                    self._record_prefill_program("target", "chunk", chunk)
                    next_toks, self.cache = self._prefill_chunks(
                        self.params, jnp.asarray(t_toks), jnp.asarray(t_lens),
                        self.cache,
                    )
                    for i in t_done:
                        # hold the completing wave's device logits-argmax; the
                        # slot may still owe draft chunks before finalizing
                        self._prefill_tok[i] = next_toks
                if d_lens.any():
                    self._record_prefill_program("draft", "chunk", chunk)
                    _, self.draft_cache = self._draft_prefill_chunks(
                        self.draft_params, jnp.asarray(d_toks),
                        jnp.asarray(d_lens), self.draft_cache,
                    )
                self.steps_executed += 1
                for i, _, _ in wave:
                    t = self._prefill_left[i]
                    d = self._draft_prefill_left[i]
                    if (t is not None and len(t) == 0) and (
                        d is None or len(d) == 0
                    ):
                        completed.append(i)
            self._land(completed)
        self.prefill_metered_tokens += consumed
        return consumed

    def _land(self, slots: list[int]) -> None:
        """Turn PREFILLING slots whose prompt streamed in whole RUNNING
        without waiting for the device: each slot's first generated token
        (its completing wave's argmax) goes into the decode batch's tokens
        on the device, one ``put_first_tokens`` program per wave, and its
        copy to the host starts.  The host appends it when the quantum's
        readback is absorbed (``Readback.first``); until then it counts in
        ``pending``.  Paged: the prompt's full pages enter the radix tree —
        the same shape monolithic admission produced in one shot."""
        waves: dict = {}
        for i in slots:
            req = self.slots[i]
            toks = self._prefill_tok[i]
            self._prefill_left[i] = None
            self._draft_prefill_left[i] = None
            self._prefill_tok[i] = None
            req.pending += 1
            self._landed.append((i, req, toks))
            waves.setdefault(id(toks), (toks, []))[1].append(i)
            if self.paged and self.prefix_cache is not None:
                prompt = np.asarray(req.prompt, np.int32)
                self.prefix_cache.insert(
                    prompt,
                    self._slot_pages[i][: len(prompt) // self.kv_page_size],
                )
        for toks, idx in waves.values():
            mask = np.zeros((self.max_slots,), bool)
            mask[idx] = True
            self.tokens = self._put_first(self.tokens, toks, mask)
            toks.copy_to_host_async()

    def _restore_draft_prefill_indices(self) -> None:
        """Re-pin the draft cache index of PREFILLING slots to their draft
        progress: the fused speculative loop keeps draft and target indices
        EQUAL for every slot (frozen ones included), which is wrong exactly
        while a slot's two prefill streams sit at different offsets.  One
        batched scatter, regardless of how many slots are mid-prefill."""
        slots, values = [], []
        for i in range(self.max_slots):
            if not self.slot_prefilling(i):
                continue
            d = self._draft_prefill_left[i]
            slots.append(i)
            values.append(
                len(self.slots[i].prompt) - (len(d) if d is not None else 0)
            )
        if slots:
            self.draft_cache["index"] = self.draft_cache["index"].at[
                np.asarray(slots)
            ].set(np.asarray(values, np.int32))

    # ------------------------------------------------------------------
    # Lifecycle core + deprecated shim surface
    # ------------------------------------------------------------------
    @property
    def core(self):
        """The engine's lazily-built ``EngineCore`` (serving/core.py) — the
        request-lifecycle surface (``submit``/``step``/``stream``/``abort``)
        all public admission and decode now routes through."""
        if self._core is None:
            from repro.serving.core import EngineCore

            self._core = EngineCore(self)
        return self._core

    def add_request(self, req: Request) -> bool:
        """DEPRECATED shim — delegates to ``EngineCore.add_legacy``.

        Prefer ``engine.core.submit(prompt, SamplingParams(...),
        priority=...)``: queued admission with priority classes, preemption,
        and streaming outputs.  This shim admits immediately (no queueing)
        and returns False on capacity, the historical contract."""
        return self.core.add_legacy(req)

    def decode_loop(self, k: int) -> list[Request]:
        """DEPRECATED shim — delegates to ``EngineCore.run_legacy``: one
        fused plain-decode loop, returning the requests that finished.
        Prefer ``engine.core.step(grant)``."""
        return self.core.run_legacy(k)

    def spec_decode_loop(self, k: int, gamma: int) -> list[Request]:
        """DEPRECATED shim — delegates to ``EngineCore.run_legacy``: one
        fused speculative loop, returning the requests that finished.
        Prefer ``engine.core.step(grant)``."""
        return self.core.run_legacy(k, gamma=gamma)

    # ------------------------------------------------------------------
    def _admit_request(self, req: Request, *, stream_prefill: bool = False) -> bool:
        """Admit ``req`` into a free slot.

        Monolithic engines (``prefill_chunk == 0``) prefill the whole
        prompt in one microstep, as ever.  Chunked engines only *reserve*
        the slot (pages, block-table row, pending chunk streams):

          * ``stream_prefill=True`` (the EngineCore path) leaves the slot
            PREFILLING — ``_drive_prefill_chunks`` streams the prompt
            across subsequent token-budgeted steps.
          * ``stream_prefill=False`` (the legacy shim contract) drives the
            chunks to completion before returning, preserving the
            historical "first token at admission" behavior bit-for-bit.
            NOTE: the completion drive is unmetered and batches over ALL
            PREFILLING slots — mixing the deprecated shim with core-driven
            budgeted streaming force-completes the core's pending streams
            outside any step's accounting; drive everything through
            ``EngineCore.step`` when budgets matter.

        Returns False when no slot is free — or, on paged engines, when the
        pool cannot cover the request's worst-case page need even after
        evicting unreferenced cached prefixes (capacity-based admission)."""
        free = self.free_slots()
        if not free:
            return False
        slot = free[0]
        n = len(req.prompt)
        if n > self.max_seq:
            raise ValueError(
                f"prompt of {n} tokens exceeds engine max_seq={self.max_seq}; "
                "refusing to truncate silently"
            )
        if req.arrival_time == 0.0 and not req.online:
            # default epoch-zero arrival on an offline request: stamp from
            # the engine clock so latency metrics never mix timebases.
            # Online requests keep an explicit 0.0 — on a virtual clock that
            # is a real arrival instant, and restamping it at admission
            # would erase the request's queueing delay.
            req.arrival_time = self.clock()
        if self.prefill_chunk:
            if not self._begin_chunked_admit(slot, req):
                return False
            if not stream_prefill:
                self._drive_prefill_chunks()
            return True
        if self.paged:
            tok = self._paged_admit(slot, req)
            if tok is None:
                return False
        else:
            tok = self._dense_admit(slot, req)
        tok = int(self._fetch(tok))
        got_ns = wall_ns()
        req.generated.append(tok)
        self.d2h_transfers += 1
        self.generated_tokens_total += 1
        if req.first_token_time is None:
            req.first_token_time = self.clock()
            req.first_token_wall_ns = got_ns
        self.tokens = self.tokens.at[slot].set(tok)
        self.slots[slot] = req
        self.steps_executed += 1
        return True

    # ------------------------------------------------------------------
    # Fault injection + containment (DESIGN.md §9)
    # ------------------------------------------------------------------
    def _maybe_inject_nan(self) -> None:
        """Consult the ``engine/nan_logits`` fault point before a fused
        dispatch; on fire, poison one decodable slot's KV so the next
        attention read produces NaN logits for exactly that slot.

        The poison lands on the slot's LAST WRITTEN position — always
        past the prompt's full pages (the victim must have generated at
        least one token), so a radix-cached prefix is never poisoned and
        prefix-sharing peers stay clean.  Attention families only: the
        recurrent families carry no per-position KV to poison."""
        inj = self.fault_injector
        if inj is None or not inj.should_fire("engine/nan_logits"):
            return
        if not (isinstance(self.cache["layers"], dict)
                and "k" in self.cache["layers"]):
            return
        cands = [
            i for i, r in enumerate(self.slots)
            if r is not None and not self.slot_prefilling(i)
            and len(r.generated) > 0
        ]
        if not cands:
            return
        slot = cands[inj.choice("engine/nan_logits", len(cands))]
        layers = self.cache["layers"]
        if self.paged:
            pos = self._slot_idx[slot] - 1
            page = self._slot_pages[slot][pos // self.kv_page_size]
            off = pos % self.kv_page_size
            layers["k"] = layers["k"].at[0, page, off].set(jnp.nan)
        else:
            pos = int(self._fetch(self.cache["index"])[slot]) - 1
            layers["k"] = layers["k"].at[0, slot, pos].set(jnp.nan)

    def _scrub_slot_kv(self, i: int) -> None:
        """Zero the KV a quarantined slot wrote, BEFORE its pages/rows are
        released.  Freeing poisoned KV un-scrubbed is not safe: a masked
        attention position still contributes ``0 * NaN = NaN`` to the
        weighted sum, so the stale-overwrite invariant only holds for
        finite stale data.  Shared (radix-held) pages are left alone —
        the poison never lands on them (see ``_maybe_inject_nan``), and
        zeroing a shared prefix would corrupt its other holders."""
        layers = self.cache["layers"]
        if not (isinstance(layers, dict) and "k" in layers):
            return
        if self.paged:
            private = [
                p for p in self._slot_pages[i]
                if self.pool.refcount[p] == 1
            ]
            if private:
                idx = jnp.asarray(private, jnp.int32)
                layers["k"] = layers["k"].at[:, idx].set(0)
                layers["v"] = layers["v"].at[:, idx].set(0)
        else:
            layers["k"] = layers["k"].at[:, i].set(0)
            layers["v"] = layers["v"].at[:, i].set(0)

    def _quarantine_slot(self, i: int) -> Request:
        """Containment for a NaN-screened slot: count it, scrub its KV,
        evict it (pages freed, draft state reset), and hand the request
        to the core's fault path (bounded-retry requeue).  The poisoned
        dispatch's tokens were never absorbed, so a retry regenerates
        them and the final stream stays byte-identical to a fault-free
        run."""
        self.obs.metrics.counter("fault/nan_quarantines").inc()
        self._scrub_slot_kv(i)
        req = self.evict_slot(i, sync=False)
        if self._core is not None:
            self._core._on_slot_fault(i, req)
        return req

    # ------------------------------------------------------------------
    def _budgets(self) -> np.ndarray:
        """Per-slot token budgets for the next fused loop: what each RUNNING
        slot's request may still generate, less what is already in flight
        (``Request.pending``); zero for empty and PREFILLING slots, which
        the loop freezes."""
        remaining = np.zeros((self.max_slots,), np.int32)
        for i, r in enumerate(self.slots):
            if r is not None and not self.slot_prefilling(i):
                remaining[i] = max(
                    r.max_new_tokens - len(r.generated) - r.pending, 0
                )
        return remaining

    def _drive_decode_loop(self, k: int) -> list[Request]:
        """Run ``k`` fused decode microsteps on-device; returns requests that
        finished.  One device->host transfer total, regardless of ``k``: the
        dispatch half (``_dispatch_decode_loop``), then its absorption.  A
        loop already dispatched and handed back in ``_ahead`` (the core's
        collect half does so, in either order: DESIGN.md §6) is absorbed
        instead, so every decoded token reaches the host here.

        Finished slots freeze mid-loop on device (token, index, and budget
        held in place), so the host never needs to intervene between
        microsteps — PREFILLING slots of a chunked engine freeze the same
        way (zero budget) and never retire mid-prefill.  Callers should
        pick ``k`` from ``DECODE_K_BUCKETS`` to bound the number of
        compiled programs."""
        rb, self._ahead = self._ahead, None
        if rb is None:
            rb = self._dispatch_decode_loop(k)
            if rb is None:
                return []
            rb = self._readback(rb)
        return self._absorb(rb)

    def _dispatch_decode_loop(self, k: int) -> Optional[Readback]:
        """Dispatch ``k`` fused decode microsteps and start the copy of their
        packed outputs to the host; returns the loop's ``Readback`` (None if
        nothing can decode).  Reads nothing back: the budgets come from
        ``_budgets``, and each decoding slot's ``pending`` (and, paged, its
        host index, which the next top-up reads) advance by the microsteps
        the loop will run, so the next loop can be dispatched before this
        one is absorbed."""
        if self.num_active == 0 or k <= 0:
            return None
        if self.num_active == self.num_prefilling:
            return None  # every slot is mid-prefill: nothing to decode
        with self.obs.span("engine.decode"):
            if self.paged:
                # extend block tables to cover the loop's k writes per slot
                self._top_up_pages(k)
                if self.num_active == 0:
                    return None  # every slot fell to an allocator fault
            self._maybe_inject_nan()
            remaining = self._budgets()
            self.tokens, self.cache, packed = self._decode_loop(
                self.params, self.tokens, self.cache, jnp.asarray(remaining),
                k=k,
            )
            packed.copy_to_host_async()
            self.steps_executed += k
            steps = np.minimum(remaining, k)
            if self.paged:
                # the loop's own freeze rule: budget spent, or max_seq - 1
                idx = np.asarray(self._slot_idx, np.int64)
                steps = np.minimum(steps, np.maximum(self.max_seq - 1 - idx, 0))
                self._slot_idx = (idx + steps).tolist()
            reqs = [
                None if r is None or self.slot_prefilling(i) else r
                for i, r in enumerate(self.slots)
            ]
            for r, n in zip(reqs, steps.tolist()):
                if r is not None:
                    r.pending += n
        return Readback(packed=packed, k=k, reqs=reqs, steps=steps)

    def _readback(self, rb: Optional[Readback] = None) -> Readback:
        """``rb`` (or an empty readback) carrying the first tokens landed
        since the last call."""
        rb = rb if rb is not None else Readback()
        rb.first, self._landed = self._landed, []
        return rb

    def _absorb(self, rb: Readback) -> list[Request]:
        """Read a quantum's outputs back in ONE blocking fetch (the copies
        started at dispatch) and fold them into host state: first tokens,
        then each decoding slot's tokens, NaN quarantines and retirements.
        Returns the requests that finished.  A slot whose request changed
        since dispatch (retired or evicted meanwhile) is skipped."""
        if rb.empty:
            return []
        arrays = {id(t): t for _, _, t in rb.first}
        if rb.packed is not None:
            arrays[id(rb.packed)] = rb.packed
        host = dict(zip(arrays, self._fetch(list(arrays.values()))))
        got_ns = wall_ns()
        self.d2h_transfers += 1  # one fetch covers the whole quantum
        now = self.clock()
        first_at = now if rb.first_at is None else rb.first_at
        done_at = now if rb.done_at is None else rb.done_at
        finished = []
        span = "engine.decode" if rb.packed is not None else "engine.prefill"
        with self.obs.span(span):
            for i, req, toks in rb.first:
                if self.slots[i] is not req:
                    continue
                req.pending -= 1
                req.generated.append(int(host[id(toks)][i]))
                self.generated_tokens_total += 1
                if req.first_token_time is None:
                    req.first_token_time = first_at
                    req.first_token_wall_ns = got_ns
            if rb.packed is not None:
                packed, k = host[id(rb.packed)], rb.k
                toks_np, (steps, rem, idx, bad) = packed[:k], packed[k:]
                for i, req in enumerate(rb.reqs):
                    if req is None or self.slots[i] is not req:
                        continue
                    req.pending -= int(rb.steps[i])
                    if bad[i]:
                        # NaN screen (DESIGN.md §9): this slot's tokens from
                        # the loop are garbage — drop them all (the screen
                        # can't say which microstep went bad) and quarantine
                        # the slot; its on-device index/remaining are garbage
                        # too, so no retire check either
                        self._quarantine_slot(i)
                        continue
                    n = int(steps[i])
                    req.generated.extend(toks_np[:n, i].tolist())
                    self.generated_tokens_total += n
                    if rem[i] == 0 or idx[i] >= self.max_seq - 1:
                        finished.append(self._retire_slot(i, done_at))
            if self.paged and self._bt_dirty:
                self._sync_block_tables()  # one upload covers every retirement
        return finished

    # ------------------------------------------------------------------
    def _drive_spec_loop(self, k: int, gamma: int) -> list[Request]:
        """Run ``k`` fused speculative rounds (draft-propose + chunk-verify);
        returns requests that finished.  One device->host transfer total.

        Each round spends one schedulable quantum and emits up to
        ``gamma + 1`` *verified* tokens per slot (greedy mode: byte-identical
        to the plain greedy ``decode_loop`` stream).  Pick ``k`` from
        ``DECODE_K_BUCKETS`` and ``gamma`` from the pairing's gamma buckets
        to bound the number of compiled programs.  A slot needs room for a
        whole chunk, so it retires once ``index + gamma >= max_seq`` —
        slightly earlier than the plain loop's ``max_seq - 1`` horizon."""
        assert self.spec_enabled, "engine built without a draft pairing"
        if self.num_active == 0 or k <= 0:
            return []
        if self.num_active == self.num_prefilling:
            return []  # every slot is mid-prefill: nothing to verify
        with self.obs.span("engine.decode"):
            if self.paged:
                # worst case every round accepts the whole chunk: cover
                # k * (gamma + 1) writes per slot
                self._top_up_pages(k * (gamma + 1))
                if self.num_active == 0:
                    return []  # every slot fell to an allocator fault
            self._maybe_inject_nan()
            remaining = self._budgets()
            (
                self.tokens, self.cache, self.draft_cache, rem, self._spec_key,
                out_toks, n_out, accepted, proposed, bad,
            ) = self._spec_loop(
                self.params, self.draft_params, self.tokens, self.cache,
                self.draft_cache, jnp.asarray(remaining), self._spec_key,
                k=k, gamma=gamma,
            )
        toks_np, n_np, acc_np, prop_np, rem_np, idx_np, bad_np = self._fetch((
            out_toks, n_out, accepted, proposed, rem, self.cache["index"], bad,
        ))
        with self.obs.span("engine.decode"):
            self.d2h_transfers += 1  # the single fused fetch above
            self.steps_executed += k
            self.spec_rounds += k
            now = self.clock()
            finished = []
            self._last_spec_slot_stats = {}
            for i, req in enumerate(self.slots):
                if req is None or self.slot_prefilling(i):
                    continue
                if bad_np[i]:
                    # NaN screen (DESIGN.md §9): every round's acceptance for
                    # this slot is suspect — drop the whole loop's output and
                    # quarantine (no acceptance-EWMA pollution either)
                    self._quarantine_slot(i)
                    continue
                for j in range(k):
                    n = int(n_np[j, i])
                    req.generated.extend(int(t) for t in toks_np[j, i, :n])
                    self.generated_tokens_total += n
                slot_acc = int(acc_np[:, i].sum())
                slot_prop = int(prop_np[:, i].sum())
                self._last_spec_slot_stats[i] = (slot_acc, slot_prop)
                self.spec_accepted += slot_acc
                self.spec_drafted += slot_prop
                if self.paged:
                    self._slot_idx[i] = int(idx_np[i])
                if rem_np[i] == 0 or idx_np[i] + gamma >= self.max_seq:
                    finished.append(self._retire_slot(i, now))
                elif self.paged:
                    # rollback freed tokens past the accepted prefix: release
                    # the pages the worst-case top-up provisioned beyond them
                    self._trim_slot_pages(i)
            if self.num_prefilling:
                # the fused loop pinned every frozen slot's draft index to its
                # TARGET index; mid-prefill the two streams sit at different
                # offsets, so restore the draft's own progress
                self._restore_draft_prefill_indices()
            if self.paged and self._bt_dirty:
                self._sync_block_tables()  # one upload covers trims + retires
            return finished

    # ------------------------------------------------------------------
    # Host-proposed tree verification (DESIGN.md §10)
    # ------------------------------------------------------------------
    def _tree_round_fn(self, parents: tuple, mode: str):
        """Jitted ``tree_verify_round`` for one static topology.  Topologies
        come from the gamma/width buckets, so the program set stays bounded
        the same way the k/gamma buckets bound the fused loops."""
        fn = self._tree_round_cache.get((parents, mode))
        if fn is None:
            from repro.spec.tree import tree_verify_round as _tree_fn

            fn = jax.jit(
                _program(
                    _tree_fn, self.cfg, parents=parents, mode=mode,
                    max_seq=self.max_seq,
                    sim_accept_p=self.spec_cfg.sim_accept_p,
                    compute_dtype=self.compute_dtype,
                    attn_impl=self._attn_impl,
                ),
                donate_argnames=("tokens", "cache", "remaining", "key"),
            )
            self._tree_round_cache[(parents, mode)] = fn
        return fn

    def _note_proposer_round(
        self, name: str, rounds: int, accepted: int, proposed: int
    ) -> None:
        m = self.obs.metrics
        m.counter(f"spec/proposer/rounds/{name}").inc(rounds)
        m.counter(f"spec/proposer/proposed/{name}").inc(proposed)
        m.counter(f"spec/proposer/accepted/{name}").inc(accepted)
        ptot = m.counter(f"spec/proposer/proposed/{name}").value
        if ptot:
            m.gauge(f"spec/proposer/acceptance/{name}").set(
                m.counter(f"spec/proposer/accepted/{name}").value / ptot
            )

    def _drive_proposed_loop(
        self, k: int, gamma: int, proposer: Optional[str] = None
    ) -> list[Request]:
        """Run ``k`` routed speculative rounds; returns requests that
        finished.

        The routed proposer decides the machinery: the device-resident
        draft model delegates to the fused ``_drive_spec_loop`` (k rounds,
        one transfer), while a host proposer (n-gram / static-suffix) runs
        ``k`` tree-verify rounds at ONE dispatch and one device->host
        transfer EACH — the host must see a round's accepted tokens before
        it can propose the next tree.  A round where the proposer has
        nothing to offer (no history match anywhere) falls back to one
        plain fused decode step instead of paying a doomed verify pass."""
        from repro.spec.proposers.base import ProposeContext

        if proposer is None:
            proposer = self.route_proposer(gamma)
        assert proposer is not None and proposer in self._proposers, (
            f"no proposer routed (got {proposer!r})"
        )
        prop = self._proposers[proposer]
        if prop.kind == "device":
            a0, p0 = self.spec_accepted, self.spec_drafted
            r0 = self.spec_rounds
            finished = self._drive_spec_loop(k, gamma)
            self._note_proposer_round(
                proposer, self.spec_rounds - r0,
                self.spec_accepted - a0, self.spec_drafted - p0,
            )
            for i, (acc, prp) in self._last_spec_slot_stats.items():
                if self.slots[i] is not None:  # retired slots were reset
                    self._router.observe(i, proposer, acc, prp)
            return finished
        width = max(1, self.spec_cfg.tree_width)
        mode = "simulated" if self.spec_cfg.mode == "simulated" else "greedy"
        finished: list[Request] = []
        span = self.obs.span
        for _ in range(k):
            if self.num_active == 0 or (
                self.num_active == self.num_prefilling
            ):
                break
            with span("engine.decode"):
                remaining = self._budgets()
                hists: list[list[int]] = [[] for _ in range(self.max_slots)]
                for i, r in enumerate(self.slots):
                    if r is not None and not self.slot_prefilling(i):
                        hists[i] = [int(t) for t in r.prompt] + r.generated
                if not remaining.any():
                    break
                tree = prop.propose(ProposeContext(
                    histories=hists, active=remaining > 0, gamma=gamma,
                    width=width,
                ))
                if tree is not None:
                    n_nodes = len(tree.parents)
                    if self.paged:
                        # worst case the round accepts a whole root-to-leaf
                        # path; node-index K/V slots need n_nodes positions
                        # regardless
                        self._top_up_pages(n_nodes)
                        if self.num_active == 0:
                            break  # every slot fell to an allocator fault
                    self._maybe_inject_nan()
                    (
                        self.tokens, self.cache, rem, self._spec_key,
                        out, n_out, accepted, proposed, bad,
                    ) = self._tree_round_fn(tree.parents, mode)(
                        self.params, self.tokens, self.cache,
                        jnp.asarray(tree.tail), jnp.asarray(remaining),
                        self._spec_key,
                    )
            if tree is None:
                # no slot matched: the round IS zero-acceptance evidence —
                # without it the optimistic prior would route a useless
                # proposer forever (the counters stay clean: nothing was
                # actually drafted or verified)
                self.obs.metrics.counter(
                    "spec/proposer/no_match_fallbacks"
                ).inc()
                for i in np.flatnonzero(remaining > 0):
                    self._router.observe(int(i), proposer, 0, gamma)
                finished.extend(self._drive_decode_loop(1))
                continue
            toks_np, n_np, acc_np, prop_np, rem_np, idx_np, bad_np = (
                self._fetch((
                    out, n_out, accepted, proposed, rem,
                    self.cache["index"], bad,
                ))
            )
            with span("engine.decode"):
                self.d2h_transfers += 1  # one per round: proposals need history
                self.steps_executed += 1
                self.spec_rounds += 1
                self.obs.metrics.gauge("spec/proposer/tree_nodes").set(n_nodes)
                round_acc = round_prop = 0
                now = self.clock()
                for i, req in enumerate(self.slots):
                    if req is None or self.slot_prefilling(i):
                        continue
                    if bad_np[i]:
                        self._quarantine_slot(i)
                        continue
                    n = int(n_np[i])
                    req.generated.extend(int(t) for t in toks_np[i, :n])
                    self.generated_tokens_total += n
                    if self.paged:
                        self._slot_idx[i] = int(idx_np[i])
                    if tree.matched[i]:
                        acc, prp = int(acc_np[i]), int(prop_np[i])
                        round_acc += acc
                        round_prop += prp
                        self._router.observe(i, proposer, acc, prp)
                        prop.observe(i, acc, prp)
                    elif remaining[i] > 0:
                        # the proposer declined THIS slot while serving others:
                        # zero-acceptance routing evidence for the slot, but
                        # not a drafted proposal (its filler row was always
                        # going to be rejected), so the counters stay clean
                        self._router.observe(i, proposer, 0, gamma)
                    if rem_np[i] == 0 or idx_np[i] + (
                        n_nodes - 1
                    ) >= self.max_seq:
                        finished.append(self._retire_slot(i, now))
                    elif self.paged:
                        # rejected siblings past the accepted path: release the
                        # pages the worst-case top-up provisioned beyond it
                        self._trim_slot_pages(i)
                self.spec_accepted += round_acc
                self.spec_drafted += round_prop
                self._note_proposer_round(proposer, 1, round_acc, round_prop)
                if self.paged and self._bt_dirty:
                    self._sync_block_tables()
        return finished

    # ------------------------------------------------------------------
    @property
    def prefill_skip_fraction(self) -> float:
        """Fraction of admitted prompt tokens served from cached prefix
        pages instead of prefill compute."""
        return self.prefill_skipped_tokens / max(self.prefill_prompt_tokens, 1)

    def kv_cache_bytes(self) -> int:
        """Device bytes held by the KV cache (pool or dense rows) alone."""
        return sum(
            x.size * x.dtype.itemsize for x in jax.tree.leaves(self.cache)
        )

    def memory_bytes(self) -> int:
        """Weights + cache footprint (Principle-I input).

        Counts the target params as served and the KV cache (dense rows or
        paged pool + block tables) AND — when a draft pairing is attached —
        the draft params and draft cache, which earlier revisions omitted,
        understating the capacity Algorithm 1 budgets against."""
        leaves = list(jax.tree.leaves(self.params)) + list(
            jax.tree.leaves(self.cache)
        )
        if self.spec_enabled:
            leaves += list(jax.tree.leaves(self.draft_params))
            leaves += list(jax.tree.leaves(self.draft_cache))
        return sum(x.size * x.dtype.itemsize for x in leaves)
