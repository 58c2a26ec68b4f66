"""Decoder-only LM supporting all assigned families.

One parameter pytree, ``lax.scan`` over stacked layer weights (keeps HLO and
compile time depth-independent), three entry points:

  * ``forward``           -- train / full-sequence logits (tokens or
                             embeddings in)
  * ``prefill``           -- forward + build decode cache (bucket-padded
                             prompts via ``length``)
  * ``prefill_into_slot`` -- prefill one prompt straight into a batch cache
                             slot (jitted; no host-side cache splice)
  * ``decode_step``       -- one token with KV cache / SSM state
  * ``decode_loop``       -- k fused microsteps via lax.scan with per-slot
                             active masking (sync-free serving fast path)

Hybrid (Zamba2) runs an outer scan over cycles: one *shared* attention+MLP
block (single weight set) followed by ``shared_attn_every`` Mamba2 layers per
cycle.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import moe as MOE
from repro.models import ssm as SSM
from repro.models.act_sharding import shard

Params = Any


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _init_layer(cfg: ModelConfig, key, dtype) -> Params:
    ks = jax.random.split(key, 4)
    p: dict = {}
    if cfg.family in ("dense", "moe", "audio", "vlm"):
        p["attn"] = L.init_attention(cfg, ks[0], cfg.d_model, dtype)
        if cfg.family == "moe":
            p["ffn"] = MOE.init_moe(cfg, ks[1], dtype)
        else:
            p["ffn"] = L.init_mlp(ks[1], cfg.d_model, cfg.d_ff, dtype)
        if cfg.parametric_norm:
            p["ln1"] = jnp.ones((cfg.d_model,), dtype)
            p["ln2"] = jnp.ones((cfg.d_model,), dtype)
    elif cfg.family == "ssm":
        p["mixer"] = SSM.init_mamba1(cfg, ks[0], dtype)
        if cfg.parametric_norm:
            p["ln"] = jnp.ones((cfg.d_model,), dtype)
    elif cfg.family == "hybrid":
        p["mixer"] = SSM.init_mamba2(cfg, ks[0], dtype)
        if cfg.parametric_norm:
            p["ln"] = jnp.ones((cfg.d_model,), dtype)
    else:
        raise ValueError(cfg.family)
    return p


def init_params(cfg: ModelConfig, key, dtype=jnp.float32) -> Params:
    keys = jax.random.split(key, cfg.num_layers + 3)
    params: dict = {
        "embed": jax.random.normal(keys[0], (cfg.vocab_size, cfg.d_model), dtype)
        * cfg.d_model**-0.5
    }
    stacked = jax.tree.map(
        lambda *xs: jnp.stack(xs),
        *[_init_layer(cfg, keys[1 + i], dtype) for i in range(cfg.num_layers)],
    )
    if cfg.family == "hybrid":
        n_cyc = cfg.num_layers // cfg.shared_attn_every
        stacked = jax.tree.map(
            lambda x: x.reshape(n_cyc, cfg.shared_attn_every, *x.shape[1:]), stacked
        )
        kk = jax.random.split(keys[-1], 2)
        params["shared"] = {
            "attn": L.init_attention(cfg, kk[0], cfg.d_model, dtype),
            "ffn": L.init_mlp(kk[1], cfg.d_model, cfg.d_ff, dtype),
        }
        if cfg.parametric_norm:
            params["shared"]["ln1"] = jnp.ones((cfg.d_model,), dtype)
            params["shared"]["ln2"] = jnp.ones((cfg.d_model,), dtype)
    params["layers"] = stacked
    if cfg.parametric_norm:
        params["final_norm"] = jnp.ones((cfg.d_model,), dtype)
    if not cfg.tie_embeddings:
        params["lm_head"] = (
            jax.random.normal(keys[-2], (cfg.d_model, cfg.vocab_size), dtype)
            * cfg.d_model**-0.5
        )
    return params


# ---------------------------------------------------------------------------
# Layer bodies (full sequence)
# ---------------------------------------------------------------------------


def _dense_layer(cfg: ModelConfig, p: Params, x: jax.Array, impl: str):
    h = L.norm(cfg, x, p.get("ln1"))
    x = x + L.attention_block(cfg, p["attn"], h, impl=impl)
    h = L.norm(cfg, x, p.get("ln2"))
    if cfg.family == "moe":
        y, aux, dropped = MOE.moe_block(cfg, p["ffn"], h)
        return x + y, aux, dropped
    return x + L.mlp_block(p["ffn"], h), jnp.float32(0), jnp.float32(0)


def _ssm_layer(cfg: ModelConfig, p: Params, x: jax.Array, impl: str):
    h = L.norm(cfg, x, p.get("ln"))
    if cfg.family == "hybrid":
        return x + SSM.mamba2_block(cfg, p["mixer"], h)
    return x + SSM.mamba1_block(cfg, p["mixer"], h, impl=impl)


def _shared_block(cfg: ModelConfig, p: Params, x: jax.Array, impl: str):
    h = L.norm(cfg, x, p.get("ln1"))
    x = x + L.attention_block(cfg, p["attn"], h, impl=impl)
    h = L.norm(cfg, x, p.get("ln2"))
    return x + L.mlp_block(p["ffn"], h)


def _maybe_remat(fn, policy: str):
    if policy == "none":
        return fn
    if policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        )
    return jax.checkpoint(fn)  # "full": save nothing


# ---------------------------------------------------------------------------
# Forward (train / logits over full sequence)
# ---------------------------------------------------------------------------


def cast_params(tree: Params, dtype) -> Params:
    """The float32 matrices of a params subtree in the compute ``dtype``
    (vectors stay float32), under the ``cast_params`` name scope, so a
    profile names the conversion wherever a program does it."""
    with jax.named_scope("cast_params"):
        return jax.tree.map(
            lambda a: a.astype(dtype)
            if a.dtype == jnp.float32 and a.ndim > 1 else a, tree)


def embed_tokens(cfg: ModelConfig, params: Params, tokens: jax.Array, dtype):
    with jax.named_scope("cast_params"):
        table = params["embed"].astype(dtype)
    return table[tokens]


def unembed(cfg: ModelConfig, params: Params, x: jax.Array) -> jax.Array:
    with jax.named_scope("cast_params"):
        head = (
            params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        ).astype(x.dtype)
    return jnp.einsum("bsd,dv->bsv", x, head)


def forward(
    cfg: ModelConfig,
    params: Params,
    inputs: jax.Array,
    *,
    impl: str = "xla",
    remat_policy: str = "none",
    compute_dtype=jnp.bfloat16,
) -> tuple[jax.Array, dict]:
    """inputs: int tokens [B, S] or (embed_inputs archs) embeddings [B, S, d].
    Returns (logits [B, S, V], metrics)."""
    if inputs.dtype in (jnp.int32, jnp.int64):
        x = embed_tokens(cfg, params, inputs, compute_dtype)
    else:
        assert cfg.embed_inputs, f"{cfg.name} does not take embedding inputs"
        x = inputs.astype(compute_dtype)
    x = shard(x, "btd")

    cast = functools.partial(cast_params, dtype=compute_dtype)

    if cfg.family == "hybrid":
        shared = cast(params["shared"])

        def cycle(xc, cyc_params):
            xc = _shared_block(cfg, shared, xc, impl)

            def inner(xi, lp):
                return shard(_ssm_layer(cfg, lp, xi, impl), "btd"), None

            xc, _ = jax.lax.scan(inner, xc, cyc_params)
            return xc, None

        body = _maybe_remat(cycle, remat_policy)
        x, _ = jax.lax.scan(body, x, cast(params["layers"]))
        aux = dropped = jnp.float32(0)
    elif cfg.family == "ssm":

        def body(xc, lp):
            return shard(_ssm_layer(cfg, lp, xc, impl), "btd"), None

        x, _ = jax.lax.scan(_maybe_remat(body, remat_policy), x, cast(params["layers"]))
        aux = dropped = jnp.float32(0)
    else:

        def body(xc, lp):
            xc, a, dr = _dense_layer(cfg, lp, xc, impl)
            return shard(xc, "btd"), (a, dr)

        x, (auxs, drops) = jax.lax.scan(
            _maybe_remat(body, remat_policy), x, cast(params["layers"])
        )
        aux, dropped = auxs.mean(), drops.mean()

    x = L.norm(cfg, x, params.get("final_norm"))
    logits = shard(unembed(cfg, params, x), "btv")
    return logits, {"moe_aux": aux, "moe_dropped": dropped}


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------


def lm_loss(
    cfg: ModelConfig,
    params: Params,
    inputs: jax.Array,
    labels: jax.Array,
    *,
    impl: str = "xla",
    remat_policy: str = "none",
    compute_dtype=jnp.bfloat16,
    moe_aux_weight: float = 0.01,
) -> tuple[jax.Array, dict]:
    logits, metrics = forward(
        cfg, params, inputs, impl=impl, remat_policy=remat_policy,
        compute_dtype=compute_dtype,
    )
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    ce = (logz - gold).mean()
    loss = ce + moe_aux_weight * metrics["moe_aux"]
    metrics = dict(metrics, ce=ce, loss=loss)
    return loss, metrics


# ---------------------------------------------------------------------------
# Decode cache
# ---------------------------------------------------------------------------


def init_cache(
    cfg: ModelConfig, batch: int, max_seq: int, dtype=jnp.bfloat16
) -> Params:
    l, hd = cfg.num_layers, cfg.resolved_head_dim
    if cfg.family in ("dense", "moe", "audio", "vlm"):
        kv = lambda: jnp.zeros((l, batch, max_seq, cfg.num_kv_heads, hd), dtype)
        layer_state = {"k": kv(), "v": kv()}
    elif cfg.family == "ssm":
        st = SSM.mamba1_init_state(cfg, batch, dtype)
        layer_state = jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (l, *a.shape)), st
        )
    elif cfg.family == "hybrid":
        n_cyc = l // cfg.shared_attn_every
        st = SSM.mamba2_init_state(cfg, batch, dtype)
        layer_state = {
            "mamba": jax.tree.map(
                lambda a: jnp.broadcast_to(
                    a[None, None], (n_cyc, cfg.shared_attn_every, *a.shape)
                ),
                st,
            ),
            "shared_k": jnp.zeros(
                (n_cyc, batch, max_seq, cfg.num_kv_heads, hd), dtype
            ),
            "shared_v": jnp.zeros(
                (n_cyc, batch, max_seq, cfg.num_kv_heads, hd), dtype
            ),
        }
    else:
        raise ValueError(cfg.family)
    return {"index": jnp.int32(0), "layers": layer_state}


def init_paged_cache(
    cfg: ModelConfig,
    batch: int,
    num_pages: int,
    page_size: int,
    max_pages_per_slot: int,
    dtype=jnp.bfloat16,
) -> Params:
    """Paged decode cache: physical page pools + per-slot block tables.

    ``layers.k/v`` are [L, P, page, kvH, hd] pools of physical pages shared
    across slots (prefix-shared pages appear in several block tables);
    ``block_tables`` is [B, W] int32 with ``W = max_pages_per_slot + 1`` —
    the extra last column stays permanently at the sentinel page 0 so
    overflow writes clamp onto a page nobody reads (``L.paged_kv_write``).
    Attention families only: SSM/hybrid state is O(1) per slot and keeps the
    dense layout."""
    assert cfg.family in ("dense", "moe", "audio", "vlm"), (
        f"paged KV cache is for attention families, not {cfg.family!r}"
    )
    l, hd = cfg.num_layers, cfg.resolved_head_dim
    kv = lambda: jnp.zeros(
        (l, num_pages, page_size, cfg.num_kv_heads, hd), dtype
    )
    return {
        "index": jnp.zeros((batch,), jnp.int32),
        "block_tables": jnp.zeros((batch, max_pages_per_slot + 1), jnp.int32),
        "layers": {"k": kv(), "v": kv()},
    }


def is_paged_cache(cache: Params) -> bool:
    return isinstance(cache, dict) and "block_tables" in cache


def _scan_paged_layers(layer_fn: Callable, x: jax.Array, layers: Params,
                       cache: Params) -> tuple[jax.Array, Params]:
    """``lax.scan`` of ``layer_fn(x, lp, (k_pool, v_pool), tables) -> (x,
    (k_pool, v_pool))`` over the layers of a paged cache, its pools carried
    whole.  The [L, P, page, kvH, hd] pools are viewed as one [L * P, ...]
    pool and layer ``l`` reads and writes it through its block table offset
    by ``l * P``, so every layer's K/V write lands in place in the one
    carried buffer.  Scanning the pools as per-layer slices instead makes
    XLA slice each layer's pool out and stack the updated ones into a new
    pool: a copy of the whole pool a call.  Each layer's page 0 stays its
    sentinel page."""
    kp, vp = cache["layers"]["k"], cache["layers"]["v"]
    n, pages = kp.shape[:2]
    flat = lambda a: a.reshape((n * pages,) + a.shape[2:])
    bt = cache["block_tables"]

    def body(carry, per_layer):
        xc, kv = carry
        lp, layer = per_layer
        return layer_fn(xc, lp, kv, bt + layer * pages), None

    (x, (k_all, v_all)), _ = jax.lax.scan(
        body, (x, (flat(kp), flat(vp))),
        (layers, jnp.arange(n, dtype=jnp.int32)),
    )
    return x, {"k": k_all.reshape(kp.shape), "v": v_all.reshape(vp.shape)}


# ---------------------------------------------------------------------------
# Decode step
# ---------------------------------------------------------------------------


def decode_step(
    cfg: ModelConfig,
    params: Params,
    tokens: jax.Array,
    cache: Params,
    *,
    compute_dtype=jnp.bfloat16,
    attn_impl: str = "auto",
) -> tuple[jax.Array, Params]:
    """tokens: [B] int32 (last generated).  Returns (logits [B, V], cache).

    ``cache["index"]`` may be scalar (uniform batch) or [B] per-slot
    positions (continuous batching).  ``attn_impl`` picks the decode
    attention core (see ``ops.decode_attention``)."""
    x = embed_tokens(cfg, params, tokens, compute_dtype)[:, None, :]  # [B, 1, d]
    idx = cache["index"]
    cast = functools.partial(cast_params, dtype=compute_dtype)

    if cfg.family in ("dense", "moe", "audio", "vlm"):

        def layer(xc, lp, kv, tables):
            h = L.norm(cfg, xc, lp.get("ln1"))
            if tables is not None:  # paged cache: [B, W] page map
                y, kv = L.attention_decode_paged(
                    cfg, lp["attn"], h, kv, tables, idx, impl=attn_impl
                )
            else:
                y, kv = L.attention_decode(
                    cfg, lp["attn"], h, kv, idx, impl=attn_impl
                )
            xc = xc + y
            h = L.norm(cfg, xc, lp.get("ln2"))
            if cfg.family == "moe":
                y2, _, _ = MOE.moe_block(cfg, lp["ffn"], h)
            else:
                y2 = L.mlp_block(lp["ffn"], h)
            return xc + y2, kv

        if is_paged_cache(cache):
            x, new_layers = _scan_paged_layers(
                layer, x, cast(params["layers"]), cache
            )
        else:
            x, (k_new, v_new) = jax.lax.scan(
                lambda xc, per: layer(xc, per[0], per[1:], None), x,
                (cast(params["layers"]), cache["layers"]["k"], cache["layers"]["v"]),
            )
            new_layers = {"k": k_new, "v": v_new}
    elif cfg.family == "ssm":

        def body(xc, per_layer):
            lp, st = per_layer
            h = L.norm(cfg, xc, lp.get("ln"))
            y, st = SSM.mamba1_step(cfg, lp["mixer"], h[:, 0], st)
            return xc + y[:, None], st

        x, new_layers = jax.lax.scan(
            body, x, (cast(params["layers"]), cache["layers"])
        )
    else:  # hybrid
        shared = cast(params["shared"])

        def cycle(xc, per_cycle):
            cyc_params, mamba_st, k_c, v_c = per_cycle
            h = L.norm(cfg, xc, shared.get("ln1"))
            y, (k_c, v_c) = L.attention_decode(
                cfg, shared["attn"], h, (k_c, v_c), idx, impl=attn_impl
            )
            xc = xc + y
            h = L.norm(cfg, xc, shared.get("ln2"))
            xc = xc + L.mlp_block(shared["ffn"], h)

            def inner(xi, per_layer):
                lp, st = per_layer
                hh = L.norm(cfg, xi, lp.get("ln"))
                yy, st = SSM.mamba2_step(cfg, lp["mixer"], hh[:, 0], st)
                return xi + yy[:, None], st

            xc, mamba_st = jax.lax.scan(inner, xc, (cyc_params, mamba_st))
            return xc, (mamba_st, k_c, v_c)

        x, (m_new, k_new, v_new) = jax.lax.scan(
            cycle,
            x,
            (
                cast(params["layers"]),
                cache["layers"]["mamba"],
                cache["layers"]["shared_k"],
                cache["layers"]["shared_v"],
            ),
        )
        new_layers = {"mamba": m_new, "shared_k": k_new, "shared_v": v_new}

    x = L.norm(cfg, x, params.get("final_norm"))
    logits = shard(unembed(cfg, params, x), "btv")[:, 0]
    new_cache = dict(cache, index=idx + 1, layers=new_layers)
    return logits, new_cache


# ---------------------------------------------------------------------------
# Chunk-verify decode (speculative decoding target pass)
# ---------------------------------------------------------------------------


def recurrent_state_batch_axis(cfg: ModelConfig) -> int:
    """Batch-axis position inside the *recurrent* per-layer state pytree
    (``chunk_states`` leaves carry one extra leading step axis on top)."""
    return 2 if cfg.family == "hybrid" else 1


def chunk_recurrent_states(cfg: ModelConfig, layers: Params) -> Optional[Params]:
    """The rollback-relevant slice of a cache's ``layers`` pytree: SSM/conv
    state for recurrent families, ``None`` for pure-KV families (their
    rollback is an index rewind — stale entries are overwritten before ever
    being read, DESIGN.md §4)."""
    if cfg.family == "ssm":
        return layers
    if cfg.family == "hybrid":
        return layers["mamba"]
    return None


def merge_recurrent_states(cfg: ModelConfig, layers: Params, states) -> Params:
    """Inverse of ``chunk_recurrent_states``: graft rolled-back recurrent
    state back into a cache's ``layers`` pytree."""
    if cfg.family == "ssm":
        return states
    if cfg.family == "hybrid":
        return dict(layers, mamba=states)
    return layers


def decode_chunk(
    cfg: ModelConfig,
    params: Params,
    tokens: jax.Array,
    cache: Params,
    *,
    compute_dtype=jnp.bfloat16,
    attn_impl: str = "auto",
    logits_at: Optional[jax.Array] = None,
    anc: Optional[jax.Array] = None,
    depths: Optional[jax.Array] = None,
) -> tuple[jax.Array, Params, Optional[Params]]:
    """Score a T = gamma+1 speculative chunk in ONE fused pass.

    tokens: [B, T] int32 — current token + gamma draft tokens per slot.
    Returns ``(logits [B, T, V], cache, chunk_states)`` with the cache index
    advanced by T and the cache's K/V (or SSM state) consumed.

    Tree mode (attention families only): ``anc`` [B, T] int32 ancestor
    bitmasks + ``depths`` [T] int32 per-node depths switch the attention
    core to ``tree_verify_attention`` — tokens then hold one packed-tree
    node each (node 0 = root = the current token) and every layer applies
    the same ancestor visibility and depth-based RoPE.  ``None`` (default)
    is bit-identical to the linear-chunk path.

    ``logits_at`` ([] int32, traced) restricts the unembedding to one chunk
    position — logits come back [B, 1, V].  Chunk-based suffix prefill
    needs only the last real position's logits, and the vocab projection
    over a full pad bucket would otherwise dominate its cost.

    Attention families score all T positions in parallel through
    ``attention_verify`` (the chunk-verify kernel path) — no sequential
    scan, so the pass costs one cache sweep instead of T.  Recurrent
    families (ssm/hybrid) cannot parallelize the state recurrence; they run
    a ``lax.scan`` of ``decode_step`` *inside the same jitted program* and
    additionally return ``chunk_states``: the recurrent per-layer state
    stacked after each chunk step (leading axis T), which acceptance uses to
    rewind a slot's SSM/conv state past rejected tokens
    (``spec.rollback.select_step_state``).  Pure-KV families return ``None``
    there — rewinding ``index`` alone is a complete rollback for them."""
    b, t = tokens.shape
    if anc is not None and cfg.family not in ("dense", "moe", "audio", "vlm"):
        raise ValueError(
            f"tree verification needs an attention family, got {cfg.family!r}"
        )
    if cfg.family in ("dense", "moe", "audio", "vlm"):
        x = embed_tokens(cfg, params, tokens, compute_dtype)  # [B, T, d]
        idx = cache["index"]
        bt = cache.get("block_tables")  # paged cache: [B, W] page map
        cast = functools.partial(cast_params, dtype=compute_dtype)

        def body(xc, per_layer):
            lp, k_c, v_c = per_layer
            h = L.norm(cfg, xc, lp.get("ln1"))
            if bt is not None:
                y, (k_c, v_c) = L.attention_verify_paged(
                    cfg, lp["attn"], h, (k_c, v_c), bt, idx, impl=attn_impl,
                    anc=anc, depths=depths,
                )
            else:
                y, (k_c, v_c) = L.attention_verify(
                    cfg, lp["attn"], h, (k_c, v_c), idx, impl=attn_impl,
                    anc=anc, depths=depths,
                )
            xc = xc + y
            h = L.norm(cfg, xc, lp.get("ln2"))
            if cfg.family == "moe":
                y2, _, _ = MOE.moe_block(cfg, lp["ffn"], h)
            else:
                y2 = L.mlp_block(lp["ffn"], h)
            return xc + y2, (k_c, v_c)

        x, (k_new, v_new) = jax.lax.scan(
            body, x,
            (cast(params["layers"]), cache["layers"]["k"], cache["layers"]["v"]),
        )
        x = L.norm(cfg, x, params.get("final_norm"))
        if logits_at is not None:
            x = jax.lax.dynamic_slice_in_dim(
                x, jnp.asarray(logits_at, jnp.int32), 1, axis=1
            )
        logits = shard(unembed(cfg, params, x), "btv")
        new_cache = dict(
            cache, index=idx + t, layers={"k": k_new, "v": v_new}
        )
        return logits, new_cache, None

    # Recurrent families: fused sequential scan with per-step state capture.
    def step(c, tok_t):
        logits_t, c = decode_step(
            cfg, params, tok_t, c, compute_dtype=compute_dtype,
            attn_impl=attn_impl,
        )
        return c, (logits_t, chunk_recurrent_states(cfg, c["layers"]))

    cache, (logits_seq, states_seq) = jax.lax.scan(step, cache, tokens.T)
    logits = logits_seq.transpose(1, 0, 2)
    if logits_at is not None:
        logits = jax.lax.dynamic_slice_in_dim(
            logits, jnp.asarray(logits_at, jnp.int32), 1, axis=1
        )
    return logits, cache, states_seq


# ---------------------------------------------------------------------------
# Fused decode loop (sync-free serving fast path)
# ---------------------------------------------------------------------------


def decode_loop(
    cfg: ModelConfig,
    params: Params,
    tokens: jax.Array,
    cache: Params,
    remaining: Optional[jax.Array] = None,
    *,
    k: int,
    max_seq: Optional[int] = None,
    compute_dtype=jnp.bfloat16,
    attn_impl: str = "auto",
) -> tuple[jax.Array, Params, jax.Array, jax.Array, jax.Array]:
    """Run ``k`` greedy decode microsteps entirely on-device via ``lax.scan``.

    ``remaining``: [B] int32 per-slot token budgets.  A slot is *active* while
    ``remaining > 0`` and (when ``max_seq`` is set) its cache index is below
    ``max_seq - 1``.  Inactive slots are frozen in place — token, cache index,
    and budget untouched — so finished requests never need a host round-trip
    mid-loop.  ``remaining=None`` runs all slots unconditionally (uniform
    batch; used by the fused collocated train+decode step, where the cache
    index may be scalar).

    Returns ``(tokens, cache, remaining, toks_seq, steps, bad)`` where
    ``toks_seq[j]`` is the [B] token vector after microstep ``j`` (frozen
    slots repeat their last token), ``steps[i]`` counts microsteps slot
    ``i`` was active for, and ``bad[i]`` is the per-slot NaN screen
    (DESIGN.md §9): True if any microstep produced a non-finite logit for
    an *active* slot ``i`` — its tokens from this loop are garbage and the
    caller must quarantine the slot instead of absorbing them.  Inactive
    slots are never flagged (an empty slot's logits are unread noise).
    The caller fetches everything it needs with ONE device->host transfer
    after the loop.
    """
    b = tokens.shape[0]
    masked = remaining is not None

    def body(carry, _):
        toks, c, rem, bad = carry
        idx = c["index"]
        logits, new_c = decode_step(
            cfg, params, toks, c, compute_dtype=compute_dtype,
            attn_impl=attn_impl,
        )
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        finite = jnp.isfinite(logits).all(axis=-1)
        if masked:
            active = rem > 0
            if max_seq is not None:
                active = active & (idx < max_seq - 1)
            toks = jnp.where(active, next_tok, toks)
            # dict(new_c, ...) keeps cache keys beyond index/layers (the
            # paged cache's block_tables) flowing through the scan carry
            c = dict(new_c, index=jnp.where(active, new_c["index"], idx))
            rem = jnp.where(active, rem - 1, rem)
        else:
            toks, c = next_tok, new_c
            active = jnp.ones((b,), bool)
        bad = bad | (active & ~finite)
        return (toks, c, rem, bad), (toks, active)

    rem0 = remaining if masked else jnp.zeros((b,), jnp.int32)
    bad0 = jnp.zeros((b,), bool)
    (tokens, cache, rem, bad), (toks_seq, active_seq) = jax.lax.scan(
        body, (tokens, cache, rem0, bad0), None, length=k
    )
    steps = active_seq.sum(axis=0).astype(jnp.int32) if k else jnp.zeros(
        (b,), jnp.int32
    )
    return tokens, cache, rem, toks_seq, steps, bad


# ---------------------------------------------------------------------------
# Prefill: forward + cache construction
# ---------------------------------------------------------------------------


def prefill(
    cfg: ModelConfig,
    params: Params,
    inputs: jax.Array,
    max_seq: int,
    *,
    impl: str = "xla",
    compute_dtype=jnp.bfloat16,
    cache_dtype=None,
    length: Optional[jax.Array] = None,
) -> tuple[jax.Array, Params]:
    """Full-sequence prefill.  Returns (last-position logits [B, V], cache).
    ``cache_dtype`` stores the KV cache quantized (e.g. fp8).

    ``length`` (traced [] int32) marks the true prompt length when ``inputs``
    is zero-padded to a compile bucket: logits are taken at ``length - 1`` and
    the cache index starts at ``length``.  Pad positions only ever produce
    K/V entries *beyond* the cache index, which decode overwrites before
    reading (see DESIGN.md §3), so padding never leaks into results."""
    cache_dtype = cache_dtype or compute_dtype
    if inputs.dtype in (jnp.int32, jnp.int64):
        b, s = inputs.shape
        x = embed_tokens(cfg, params, inputs, compute_dtype)
    else:
        b, s, _ = inputs.shape
        x = inputs.astype(compute_dtype)
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    cast = functools.partial(cast_params, dtype=compute_dtype)

    def attn_prefill(lp, h):
        q, k, v = L._project_qkv(cfg, lp, h, positions)
        from repro.kernels import ops

        out = ops.attention(q, k, v, causal=True, impl=impl)
        mask = L.head_mask(cfg, out.dtype)
        if mask is not None:
            out = out * mask[None, None, :, None]
        return jnp.einsum("bshk,hkd->bsd", out, lp["wo"]), k, v

    pad_kv = lambda t: jnp.pad(t, ((0, 0), (0, max_seq - s), (0, 0), (0, 0)))

    if cfg.family in ("dense", "moe", "audio", "vlm"):

        def body(xc, lp):
            h = L.norm(cfg, xc, lp.get("ln1"))
            y, k, v = attn_prefill(lp["attn"], h)
            xc = xc + y
            h = L.norm(cfg, xc, lp.get("ln2"))
            if cfg.family == "moe":
                y2, _, _ = MOE.moe_block(cfg, lp["ffn"], h)
            else:
                y2 = L.mlp_block(lp["ffn"], h)
            return xc + y2, (pad_kv(k).astype(cache_dtype),
                             pad_kv(v).astype(cache_dtype))

        x, (ks, vs) = jax.lax.scan(body, x, cast(params["layers"]))
        new_layers = {"k": ks, "v": vs}
    elif cfg.family == "ssm":

        def body(xc, lp):
            h = L.norm(cfg, xc, lp.get("ln"))
            # run block while capturing final state via the chunked scan
            y, st = _mamba1_with_state(cfg, lp["mixer"], h, impl, length=length)
            return xc + y, st

        x, new_layers = jax.lax.scan(body, x, cast(params["layers"]))
        new_layers = jax.tree.map(
            lambda a, proto: a.astype(proto.dtype),
            new_layers,
            init_cache(cfg, b, max_seq, cache_dtype)["layers"],
        )
    else:  # hybrid
        shared = cast(params["shared"])

        def cycle(xc, cyc_params):
            h = L.norm(cfg, xc, shared.get("ln1"))
            y, k, v = attn_prefill(shared["attn"], h)
            xc = xc + y
            h = L.norm(cfg, xc, shared.get("ln2"))
            xc = xc + L.mlp_block(shared["ffn"], h)

            def inner(xi, lp):
                hh = L.norm(cfg, xi, lp.get("ln"))
                yy, st = _mamba2_with_state(cfg, lp["mixer"], hh, length=length)
                return xi + yy, st

            xc, m_st = jax.lax.scan(inner, xc, cyc_params)
            return xc, (m_st, pad_kv(k).astype(cache_dtype),
                        pad_kv(v).astype(cache_dtype))

        x, (m_new, ks, vs) = jax.lax.scan(cycle, x, cast(params["layers"]))
        proto = init_cache(cfg, b, max_seq, cache_dtype)["layers"]["mamba"]
        m_new = jax.tree.map(lambda a, pr: a.astype(pr.dtype), m_new, proto)
        new_layers = {"mamba": m_new, "shared_k": ks, "shared_v": vs}

    x = L.norm(cfg, x, params.get("final_norm"))
    if length is None:
        last, index = x[:, -1:, :], jnp.int32(s)
    else:
        index = jnp.asarray(length, jnp.int32)
        last = jax.lax.dynamic_slice_in_dim(x, index - 1, 1, axis=1)
    logits = shard(unembed(cfg, params, last), "btv")[:, 0]
    return logits, {"index": index, "layers": new_layers}


def _ssm_tail_state(x, length, n):
    """Last ``n`` timesteps before ``length`` with implicit left zero-pad —
    the decode conv state for a bucket-padded prompt of true ``length``."""
    if length is None:
        return x[:, -n:, :]
    xp = jnp.pad(x, ((0, 0), (n, 0), (0, 0)))
    return jax.lax.dynamic_slice_in_dim(
        xp, jnp.asarray(length, jnp.int32), n, axis=1
    )


def _ssm_dt_mask(dt, length):
    """Zero the SSM step size at pad positions (>= length): ``dt == 0`` makes
    the recurrence a no-op (decay exp(0*A) == 1, input term 0), so a bucket-
    padded prompt leaves the state exactly where the real tokens left it."""
    if length is None:
        return dt
    valid = jnp.arange(dt.shape[1]) < jnp.asarray(length, jnp.int32)
    return dt * valid[None, :, None]


def _mamba1_with_state(cfg, p, x, impl, length=None):
    """mamba1_block but also returning the final SSM + conv state."""
    b, s, _ = x.shape
    di, ds, dtr = cfg.d_inner, cfg.ssm_state, cfg.resolved_dt_rank
    xz = jnp.einsum("bsd,de->bse", x, p["in_proj"])
    xi_raw, z = jnp.split(xz, 2, axis=-1)
    conv_state = _ssm_tail_state(xi_raw, length, cfg.ssm_conv - 1)
    xi = jax.nn.silu(SSM.causal_conv(xi_raw, p["conv_w"], p["conv_b"]))
    dbc = jnp.einsum("bse,ef->bsf", xi, p["x_proj"])
    dt_r, B_, C_ = jnp.split(dbc, [dtr, dtr + ds], axis=-1)
    dt = jax.nn.softplus(
        jnp.einsum("bsr,re->bse", dt_r, p["dt_proj"]) + p["dt_bias"]
    ).astype(jnp.float32)
    dt = _ssm_dt_mask(dt, length)
    A = -jnp.exp(p["A_log"])
    h0 = jnp.zeros((b, di, ds), jnp.float32)
    y, h_fin = SSM.selective_scan_chunked(
        xi.astype(jnp.float32), dt, B_.astype(jnp.float32), C_.astype(jnp.float32),
        A, h0, impl=impl,
    )
    y = y.astype(x.dtype) + p["D"].astype(x.dtype) * xi
    y = y * jax.nn.silu(z)
    return jnp.einsum("bse,ed->bsd", y, p["out_proj"]), {
        "conv": conv_state, "h": h_fin,
    }


def prefill_into_slot(
    cfg: ModelConfig,
    params: Params,
    inputs: jax.Array,
    length: jax.Array,
    slot: jax.Array,
    cache: Params,
    *,
    max_seq: int,
    impl: str = "xla",
    compute_dtype=jnp.bfloat16,
) -> tuple[jax.Array, Params]:
    """Prefill one bucket-padded prompt and write its K/V (or SSM state)
    directly into the batch decode cache at ``slot`` — one jitted program,
    no host-side cache splice.

    inputs: [1, S_bucket] int32 tokens (or [1, S_bucket, d] embeddings),
    zero-padded to a power-of-two bucket; length: [] int32 true prompt
    length; slot: [] int32 target batch slot (traced, so one compiled
    program serves every slot).  ``cache`` should be donated by the caller's
    jit so the slot write is performed in place.

    Returns ``(first generated token [] int32, updated batch cache)``.
    """
    logits, cache1 = prefill(
        cfg, params, inputs, max_seq, impl=impl, compute_dtype=compute_dtype,
        cache_dtype=jax.tree.leaves(cache["layers"])[0].dtype, length=length,
    )
    tok = jnp.argmax(logits[0]).astype(jnp.int32)
    slot = jnp.asarray(slot, jnp.int32)

    def upd(axis):
        return lambda b, s: jax.lax.dynamic_update_index_in_dim(
            b, jnp.squeeze(s, axis).astype(b.dtype), slot, axis=axis
        )

    # Batch axis is 1 for [L, B, ...] leaves; the hybrid family's per-cycle
    # mamba state is [n_cyc, shared_attn_every, B, ...] — batch on axis 2.
    if cfg.family == "hybrid":
        new_layers = {
            "mamba": jax.tree.map(
                upd(2), cache["layers"]["mamba"], cache1["layers"]["mamba"]
            ),
            "shared_k": upd(1)(
                cache["layers"]["shared_k"], cache1["layers"]["shared_k"]
            ),
            "shared_v": upd(1)(
                cache["layers"]["shared_v"], cache1["layers"]["shared_v"]
            ),
        }
    else:
        new_layers = jax.tree.map(
            upd(1), cache["layers"], cache1["layers"]
        )
    index = cache["index"].at[slot].set(jnp.asarray(length, jnp.int32))
    return tok, {"index": index, "layers": new_layers}


def prefill_into_slot_paged(
    cfg: ModelConfig,
    params: Params,
    inputs: jax.Array,
    length: jax.Array,
    slot: jax.Array,
    cache: Params,
    *,
    impl: str = "xla",
    compute_dtype=jnp.bfloat16,
) -> tuple[jax.Array, Params]:
    """Cold-path prefill straight into the paged pool.

    Runs the ordinary full-sequence prefill over the [1, S_bucket] prompt —
    against a *bucket-sized* scratch cache rather than a dense max_seq row —
    then scatters the K/V bucket page-by-page into the slot's block-table
    pages.  The bucket must be page-aligned (the engine raises its minimum
    prefill bucket to the page size).  Bucket-pad positions past ``length``
    scatter into either the slot's last page beyond ``index`` (stale,
    overwritten before read) or unallocated table entries, which hold the
    sentinel page — a write sink nobody attends to.

    Returns ``(first generated token [] int32, updated paged cache)``."""
    k_pool = cache["layers"]["k"]  # [L, P, page, kvH, hd]
    page = k_pool.shape[2]
    sb = inputs.shape[1]
    assert sb % page == 0, f"prefill bucket {sb} not page-aligned ({page})"
    nbp = sb // page
    logits, cache1 = prefill(
        cfg, params, inputs, sb, impl=impl, compute_dtype=compute_dtype,
        cache_dtype=k_pool.dtype, length=length,
    )
    tok = jnp.argmax(logits[0]).astype(jnp.int32)
    slot = jnp.asarray(slot, jnp.int32)
    pages = jax.lax.dynamic_slice(
        cache["block_tables"], (slot, 0), (1, nbp)
    )[0]  # [nbp] physical page per bucket page

    def scatter(pool, new):  # new: [L, 1, SB, kvH, hd]
        l = pool.shape[0]
        newp = new[:, 0].reshape(l, nbp, page, *pool.shape[3:])
        return pool.at[:, pages].set(newp.astype(pool.dtype))

    new_layers = {
        "k": scatter(cache["layers"]["k"], cache1["layers"]["k"]),
        "v": scatter(cache["layers"]["v"], cache1["layers"]["v"]),
    }
    index = cache["index"].at[slot].set(jnp.asarray(length, jnp.int32))
    return tok, dict(cache, index=index, layers=new_layers)


def prefill_chunks_into_slots(
    cfg: ModelConfig,
    params: Params,
    tokens: jax.Array,
    chunk_lens: jax.Array,
    cache: Params,
    *,
    compute_dtype=jnp.bfloat16,
    attn_impl: str = "auto",
    need_logits: bool = True,
) -> tuple[jax.Array, Params]:
    """One unified chunked-prefill microstep over ALL slots (DESIGN.md §7).

    tokens: [B, C] int32 — one fixed-width prompt chunk per slot,
    zero-padded past ``chunk_lens``; chunk_lens: [B] int32 real tokens per
    slot (ragged: 0 freezes a slot — no K/V write, no index advance);
    cache: the batch decode cache (dense rows or paged pool) with
    ``index`` [B] holding each slot's prefill progress.  Because every
    quantity is traced, ONE compiled program serves every mix of slots,
    chunk lengths, and prefill offsets — this is the program that replaces
    the power-of-two prefill bucket zoo.

    Each layer writes the chunk's real K/V at ``index .. index +
    chunk_lens - 1`` and attends it to the previously-written prefix
    (radix-shared pages included, so prefix hits compose with chunking for
    free) plus the chunk's own causal triangle; ``index`` advances by
    ``chunk_lens`` per slot.

    Returns ``(next_tokens [B] int32, cache)``: ``next_tokens[b]`` is the
    argmax over the logits at chunk position ``chunk_lens[b] - 1`` — the
    model's next-token prediction after the chunk, meaningful only for the
    chunk that completes a slot's prompt (the engine fetches it exactly
    then).  ``need_logits=False`` (draft-model prefill, whose first-token
    logits are never read) skips the vocab projection entirely.

    Attention families only: recurrent (ssm/hybrid) prefill keeps the
    monolithic dt-masked bucket path — their state recurrence cannot skip
    ahead chunk-by-chunk without carrying per-chunk state host-side."""
    assert cfg.family in ("dense", "moe", "audio", "vlm"), (
        f"chunked prefill needs an attention family, not {cfg.family!r}"
    )
    b, c = tokens.shape
    x = embed_tokens(cfg, params, tokens, compute_dtype)  # [B, C, d]
    idx = cache["index"]
    lens = jnp.asarray(chunk_lens, jnp.int32)
    cast = functools.partial(cast_params, dtype=compute_dtype)

    def layer(xc, lp, kv, tables):
        h = L.norm(cfg, xc, lp.get("ln1"))
        if tables is not None:  # paged cache: [B, W] page map
            y, kv = L.attention_prefill_chunk_paged(
                cfg, lp["attn"], h, kv, tables, idx, lens, impl=attn_impl,
            )
        else:
            y, kv = L.attention_prefill_chunk(
                cfg, lp["attn"], h, kv, idx, lens, impl=attn_impl
            )
        xc = xc + y
        h = L.norm(cfg, xc, lp.get("ln2"))
        if cfg.family == "moe":
            y2, _, _ = MOE.moe_block(cfg, lp["ffn"], h)
        else:
            y2 = L.mlp_block(lp["ffn"], h)
        return xc + y2, kv

    if is_paged_cache(cache):
        x, new_layers = _scan_paged_layers(
            layer, x, cast(params["layers"]), cache
        )
    else:
        x, (k_new, v_new) = jax.lax.scan(
            lambda xc, per: layer(xc, per[0], per[1:], None), x,
            (cast(params["layers"]), cache["layers"]["k"], cache["layers"]["v"]),
        )
        new_layers = {"k": k_new, "v": v_new}
    index = idx + lens
    new_cache = dict(cache, index=index, layers=new_layers)
    if not need_logits:
        return jnp.zeros((b,), jnp.int32), new_cache
    x = L.norm(cfg, x, params.get("final_norm"))
    # per-slot last real chunk position (frozen slots clamp to row 0 and
    # produce garbage nobody fetches)
    pos = jnp.maximum(lens - 1, 0)[:, None, None]
    last = jnp.take_along_axis(x, pos, axis=1)  # [B, 1, d]
    logits = shard(unembed(cfg, params, last), "btv")[:, 0]
    next_tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return next_tokens, new_cache


def prefill_suffix_into_slot(
    cfg: ModelConfig,
    params: Params,
    tokens: jax.Array,
    suffix_len: jax.Array,
    shared_len: jax.Array,
    slot: jax.Array,
    cache: Params,
    *,
    compute_dtype=jnp.bfloat16,
    attn_impl: str = "auto",
) -> tuple[jax.Array, Params]:
    """Prefix-hit prefill: score only the prompt *suffix* against shared
    prefix pages already resident in the pool.

    tokens: [1, T_bucket] int32 suffix tokens zero-padded to a compile
    bucket; suffix_len: [] int32 true suffix length; shared_len: [] int32
    prefix length served from the radix cache (a page multiple, >= 1 page);
    slot: [] int32 target slot whose block table already maps the shared
    pages (refcounted) plus freshly-allocated suffix pages.

    The heavy lifting is ``decode_chunk`` on a one-row view of the paged
    cache: the chunk-verify path attends suffix queries to the shared
    prefix plus the chunk's own causal triangle and scatters suffix K/V into
    the slot's private pages — so prefill compute is O(suffix), ZERO FLOPs
    for the shared length.  Bucket-pad rows write stale/sentinel K/V and
    attend garbage, but the returned logits row ``suffix_len - 1`` attends
    real positions only.

    Returns ``(first generated token [] int32, updated paged cache)``."""
    slot = jnp.asarray(slot, jnp.int32)
    shared = jnp.asarray(shared_len, jnp.int32)
    row = jax.lax.dynamic_slice_in_dim(
        cache["block_tables"], slot, 1, axis=0
    )  # [1, W]
    view = {
        "index": shared[None],
        "block_tables": row,
        "layers": cache["layers"],
    }
    pos = jnp.asarray(suffix_len, jnp.int32) - 1
    logits, view, _ = decode_chunk(
        cfg, params, tokens, view, compute_dtype=compute_dtype,
        attn_impl=attn_impl, logits_at=pos,
    )
    tok = jnp.argmax(logits[0, 0]).astype(jnp.int32)
    index = cache["index"].at[slot].set(shared + suffix_len)
    return tok, dict(cache, index=index, layers=view["layers"])


def _mamba2_with_state(cfg, p, x, length=None):
    from repro.models.layers import rms_norm

    b, s, _ = x.shape
    di, ds, nh, hp = cfg.d_inner, cfg.ssm_state, cfg.ssm_num_heads, cfg.ssm_head_dim
    zx = jnp.einsum("bsd,de->bse", x, p["in_proj_zx"])
    z, xr = jnp.split(zx, 2, axis=-1)
    bcdt = jnp.einsum("bsd,de->bse", x, p["in_proj_bcdt"])
    bc_raw, dt = jnp.split(bcdt, [2 * ds], axis=-1)
    conv_x_state = _ssm_tail_state(xr, length, cfg.ssm_conv - 1)
    conv_bc_state = _ssm_tail_state(bc_raw, length, cfg.ssm_conv - 1)
    xi = jax.nn.silu(SSM.causal_conv(xr, p["conv_x_w"], p["conv_x_b"]))
    bc = jax.nn.silu(SSM.causal_conv(bc_raw, p["conv_bc_w"], p["conv_bc_b"]))
    B_, C_ = jnp.split(bc, 2, axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    dt = _ssm_dt_mask(dt, length)
    A = -jnp.exp(p["A_log"])
    xh = xi.reshape(b, s, nh, hp).astype(jnp.float32)
    h0 = jnp.zeros((b, nh, hp, ds), jnp.float32)
    y, h_fin = SSM.ssd_chunked(
        xh, dt, B_.astype(jnp.float32), C_.astype(jnp.float32), A, h0
    )
    y = y + p["D"][:, None] * xh
    y = y.reshape(b, s, di).astype(x.dtype)
    y = rms_norm(y * jax.nn.silu(z), p["gate_norm"])
    return jnp.einsum("bse,ed->bsd", y, p["out_proj"]), {
        "conv_x": conv_x_state, "conv_bc": conv_bc_state, "h": h_fin,
    }
