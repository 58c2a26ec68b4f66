"""Shared neural layers: norms, RoPE, GQA attention (full / blocked / decode),
SwiGLU MLP.  Pure functions over explicit parameter pytrees.

Attention exposes three execution paths:
  * ``xla``       -- plain einsum softmax (small sequences)
  * ``xla_flash`` -- lax.scan blocked online-softmax (long prefill; no S^2 buffer)
  * ``pallas``    -- Pallas TPU flash kernel (kernels/flash_attention.py),
                     forward only
The path is chosen by ``repro.kernels.ops.attention`` unless forced; the
train step forces the XLA family ("xla_auto") because the kernel has no
backward pass.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.act_sharding import shard

Params = Any


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, weight: Optional[jax.Array], eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    y = x32 * inv
    if weight is not None:
        y = y * weight.astype(jnp.float32)
    return y.astype(dtype)


def layer_norm(x: jax.Array, weight: Optional[jax.Array], eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    if weight is not None:
        y = y * weight.astype(jnp.float32)
    return y.astype(dtype)


def norm(cfg: ModelConfig, x: jax.Array, weight: Optional[jax.Array]) -> jax.Array:
    if cfg.norm_type == "layernorm":
        return layer_norm(x, weight if cfg.parametric_norm else None)
    return rms_norm(x, weight if cfg.parametric_norm else None)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    """Inverse frequencies, shape [head_dim // 2]."""
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta**exponent)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [..., S, H, hd]; positions: broadcastable to [..., S]."""
    hd = x.shape[-1]
    inv_freq = rope_frequencies(hd, theta)
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # [..., S, hd/2]
    cos = jnp.cos(angles)[..., None, :]  # [..., S, 1, hd/2]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention cores
# ---------------------------------------------------------------------------


def _repeat_kv(k: jax.Array, q_heads: int) -> jax.Array:
    """[B, S, kvH, hd] -> [B, S, qH, hd] by group broadcast."""
    b, s, kvh, hd = k.shape
    if kvh == q_heads:
        return k
    reps = q_heads // kvh
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, kvh, reps, hd)).reshape(
        b, s, q_heads, hd
    )


def attention_xla(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    q_offset: int | jax.Array = 0,
    length_mask: Optional[jax.Array] = None,
) -> jax.Array:
    """Plain softmax attention.  q: [B,Sq,H,hd], k/v: [B,Sk,kvH,hd]."""
    qh = q.shape[2]
    k = _repeat_kv(k, qh)
    v = _repeat_kv(v, qh)
    scale = q.shape[-1] ** -0.5
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    sq, sk = q.shape[1], k.shape[1]
    if causal:
        qpos = jnp.arange(sq) + q_offset
        kpos = jnp.arange(sk)
        mask = kpos[None, :] <= qpos[:, None]
        scores = jnp.where(mask[None, None], scores, -1e30)
    if length_mask is not None:  # [B, Sk] valid-key mask (decode)
        scores = jnp.where(length_mask[:, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def attention_xla_flash(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    block_k: int = 1024,
) -> jax.Array:
    """Blocked online-softmax attention (no S^2 buffer) via lax.scan over KV
    blocks.  Used for long-prefill shapes where materializing scores is
    infeasible.  Matches attention_xla to fp32 accumulation error."""
    b, sq, qh, hd = q.shape
    k = _repeat_kv(k, qh)
    v = _repeat_kv(v, qh)
    sk = k.shape[1]
    nblocks = max(1, (sk + block_k - 1) // block_k)
    pad = nblocks * block_k - sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kb = k.reshape(b, nblocks, block_k, qh, hd).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(b, nblocks, block_k, qh, hd).transpose(1, 0, 2, 3, 4)
    scale = hd**-0.5
    qpos = jnp.arange(sq)

    def body(carry, blk):
        acc, m, l = carry
        kblk, vblk, start = blk
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kblk).astype(jnp.float32) * scale
        kpos = start + jnp.arange(block_k)
        valid = kpos[None, :] < sk
        if causal:
            valid = valid & (kpos[None, :] <= qpos[:, None])
        s = jnp.where(valid[None, None], s, -1e30)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p.astype(q.dtype), vblk
        ).astype(jnp.float32)
        # pin the carry's sharding: scan carries silently lose it, which
        # replicates the fp32 accumulators over the model axis (observed:
        # +GBs of temp on the 32k prefill dry-runs)
        acc_new = shard(acc_new, "bhtd")
        m_new = shard(m_new, "bht")
        l_new = shard(l_new, "bht")
        return (acc_new, m_new, l_new), None

    acc0 = jnp.zeros((b, qh, sq, hd), jnp.float32)
    m0 = jnp.full((b, qh, sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, qh, sq), jnp.float32)
    starts = jnp.arange(nblocks) * block_k
    (acc, m, l), _ = jax.lax.scan(body, (acc0, m0, l0), (kb, vb, starts))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


# ---------------------------------------------------------------------------
# Attention block (projections + rope + core), train/prefill + decode
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class AttnDims:
    num_heads: int
    num_kv_heads: int
    head_dim: int


def head_mask(cfg: ModelConfig, dtype) -> Optional[jax.Array]:
    """[H_phys] 1/0 mask selecting real q-head slots (None when unpadded).
    With per-group padding, slot ``s`` is real iff ``s % group_phys`` is
    below the logical group size, keeping GQA's head->kv mapping exact."""
    if not cfg.padded_heads:
        return None
    kv = max(cfg.num_kv_heads, 1)
    group_phys = cfg.num_heads_physical // kv
    group_log = cfg.num_heads // kv
    m = (jnp.arange(cfg.num_heads_physical) % group_phys) < group_log
    return m.astype(dtype)


def init_attention(cfg: ModelConfig, key, d_model: int, dtype) -> Params:
    hd = cfg.resolved_head_dim
    h = cfg.num_heads_physical
    ks = jax.random.split(key, 4)
    scale = d_model**-0.5
    p = {
        "wq": jax.random.normal(ks[0], (d_model, h, hd), dtype) * scale,
        "wk": jax.random.normal(ks[1], (d_model, cfg.num_kv_heads, hd), dtype) * scale,
        "wv": jax.random.normal(ks[2], (d_model, cfg.num_kv_heads, hd), dtype) * scale,
        "wo": jax.random.normal(ks[3], (h, hd, d_model), dtype)
        * (cfg.num_heads * hd) ** -0.5,
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h, hd), dtype)
        p["bk"] = jnp.zeros((cfg.num_kv_heads, hd), dtype)
        p["bv"] = jnp.zeros((cfg.num_kv_heads, hd), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p


def _project_qkv(cfg: ModelConfig, p: Params, x: jax.Array, positions: jax.Array):
    q = shard(jnp.einsum("bsd,dhk->bshk", x, p["wq"]), "bthd")
    k = shard(jnp.einsum("bsd,dhk->bshk", x, p["wk"]), "btkv")
    v = shard(jnp.einsum("bsd,dhk->bshk", x, p["wv"]), "btkv")
    if cfg.qkv_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def attention_block(
    cfg: ModelConfig,
    p: Params,
    x: jax.Array,
    *,
    impl: str = "xla",
    positions: Optional[jax.Array] = None,
) -> jax.Array:
    """Full-sequence causal attention (train / prefill). x: [B, S, d]."""
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    q, k, v = _project_qkv(cfg, p, x, positions)
    from repro.kernels import ops  # local import to avoid cycles

    out = shard(ops.attention(q, k, v, causal=True, impl=impl), "bthd")
    mask = head_mask(cfg, out.dtype)
    if mask is not None:  # zero padded head slots (and their gradients)
        out = out * mask[None, None, :, None]
    return shard(jnp.einsum("bshk,hkd->bsd", out, p["wo"]), "btd")


def attention_decode(
    cfg: ModelConfig,
    p: Params,
    x: jax.Array,
    kv_cache: tuple[jax.Array, jax.Array],
    cache_index: jax.Array,
    *,
    impl: str = "auto",
) -> tuple[jax.Array, tuple[jax.Array, jax.Array]]:
    """One-token decode.  x: [B, 1, d]; cache k/v: [B, S_max, kvH, hd];
    cache_index: [] or [B] int32 current length(s) — per-slot indices allow
    continuous batching (each slot at its own position).

    The attention core is the flash-decode path (``ops.decode_attention``):
    length-aware over the ragged batch instead of dense over S_max."""
    b = x.shape[0]
    idx = jnp.broadcast_to(jnp.asarray(cache_index, jnp.int32), (b,))
    positions = idx[:, None]
    q, k_new, v_new = _project_qkv(cfg, p, x, positions)
    k_cache, v_cache = kv_cache
    upd = jax.vmap(
        lambda c, n, i: jax.lax.dynamic_update_slice_in_dim(c, n, i, axis=0)
    )
    k_cache = upd(k_cache, k_new.astype(k_cache.dtype), idx)
    v_cache = upd(v_cache, v_new.astype(v_cache.dtype), idx)
    from repro.kernels import ops  # local import to avoid cycles

    out = shard(
        ops.decode_attention(q[:, 0], k_cache, v_cache, idx + 1, impl=impl)[
            :, None
        ],
        "bthd",
    )
    mask = head_mask(cfg, out.dtype)
    if mask is not None:
        out = out * mask[None, None, :, None]
    y = shard(jnp.einsum("bshk,hkd->bsd", out, p["wo"]), "btd")
    return y, (k_cache, v_cache)


def attention_verify(
    cfg: ModelConfig,
    p: Params,
    x: jax.Array,
    kv_cache: tuple[jax.Array, jax.Array],
    cache_index: jax.Array,
    *,
    impl: str = "auto",
    anc: Optional[jax.Array] = None,
    depths: Optional[jax.Array] = None,
) -> tuple[jax.Array, tuple[jax.Array, jax.Array]]:
    """Chunk-verify decode: score T = gamma+1 chunk tokens in one pass.

    x: [B, T, d] — embeddings of the speculative chunk (current token +
    gamma draft tokens); cache k/v: [B, S_max, kvH, hd]; cache_index: [] or
    [B] int32 per-slot prefix length(s).  Writes the chunk's K/V at
    positions ``index .. index + T - 1`` and attends each chunk token to the
    prefix plus the chunk's own causal triangle (``ops.verify_attention``).
    Rollback after acceptance only rewinds ``index`` — rejected positions'
    K/V entries sit beyond the rewound index and are rewritten before ever
    being attended to (the same stale-overwrite invariant bucket-padded
    prefill relies on, DESIGN.md §3/§4).

    Tree mode (``anc`` + ``depths`` given): x holds one embedding per
    packed-tree node; ``anc`` [B, T] int32 ancestor bitmasks select the
    intra-chunk visibility (``ops.tree_verify_attention``); ``depths`` [T]
    int32 per-node tree depth replaces ``arange(T)`` as the RoPE offset so
    sibling branches rotate at the same sequence position.  K/V still
    writes at node-index positions — the slot each bitmask bit refers to.
    A linear chain (depths == arange, anc == cumulative bits) is
    bit-identical to the default path."""
    b, t, _ = x.shape
    idx = jnp.broadcast_to(jnp.asarray(cache_index, jnp.int32), (b,))
    offs = jnp.arange(t) if depths is None else depths.astype(jnp.int32)
    positions = idx[:, None] + offs[None, :]  # [B, T] RoPE positions
    q, k_new, v_new = _project_qkv(cfg, p, x, positions)
    k_cache, v_cache = kv_cache
    upd = jax.vmap(
        lambda c, n, i: jax.lax.dynamic_update_slice_in_dim(c, n, i, axis=0)
    )
    k_cache = upd(k_cache, k_new.astype(k_cache.dtype), idx)
    v_cache = upd(v_cache, v_new.astype(v_cache.dtype), idx)
    from repro.kernels import ops  # local import to avoid cycles

    if anc is None:
        core = ops.verify_attention(q, k_cache, v_cache, idx + t, impl=impl)
    else:
        core = ops.tree_verify_attention(
            q, k_cache, v_cache, idx + t, anc, impl=impl
        )
    out = shard(core, "bthd")
    mask = head_mask(cfg, out.dtype)
    if mask is not None:
        out = out * mask[None, None, :, None]
    y = shard(jnp.einsum("bshk,hkd->bsd", out, p["wo"]), "btd")
    return y, (k_cache, v_cache)


def attention_prefill_chunk(
    cfg: ModelConfig,
    p: Params,
    x: jax.Array,
    kv_cache: tuple[jax.Array, jax.Array],
    cache_index: jax.Array,
    chunk_lens: jax.Array,
    *,
    impl: str = "auto",
) -> tuple[jax.Array, tuple[jax.Array, jax.Array]]:
    """Chunked-prefill step (dense cache): C prompt tokens per slot in one
    pass.

    x: [B, C, d] — one fixed-width prefill chunk per slot, zero-padded past
    ``chunk_lens``; cache k/v: [B, S_max, kvH, hd]; cache_index: [B] int32
    per-slot prefill progress; chunk_lens: [B] int32 real tokens per chunk
    (0 == frozen slot).  Writes the chunk's *real* K/V at positions
    ``index .. index + chunk_lens - 1`` — pad rows scatter out of bounds
    and are DROPPED, so a chunk near the sequence horizon can never clamp
    onto (and corrupt) live entries — then attends each real row to the
    prefix plus the chunk's own causal triangle
    (``ops.prefill_chunk_attention``)."""
    b, c, _ = x.shape
    idx = jnp.broadcast_to(jnp.asarray(cache_index, jnp.int32), (b,))
    positions = idx[:, None] + jnp.arange(c)[None, :]  # [B, C]
    q, k_new, v_new = _project_qkv(cfg, p, x, positions)
    k_cache, v_cache = kv_cache
    s_max = k_cache.shape[1]
    valid = jnp.arange(c)[None, :] < chunk_lens[:, None]  # [B, C]
    pos_w = jnp.where(valid, positions, s_max)  # out of bounds -> dropped
    rows = jnp.broadcast_to(jnp.arange(b)[:, None], (b, c))
    k_cache = k_cache.at[rows, pos_w].set(
        k_new.astype(k_cache.dtype), mode="drop"
    )
    v_cache = v_cache.at[rows, pos_w].set(
        v_new.astype(v_cache.dtype), mode="drop"
    )
    from repro.kernels import ops  # local import to avoid cycles

    out = shard(
        ops.prefill_chunk_attention(
            q, k_cache, v_cache, idx, chunk_lens, impl=impl
        ),
        "bthd",
    )
    mask = head_mask(cfg, out.dtype)
    if mask is not None:
        out = out * mask[None, None, :, None]
    y = shard(jnp.einsum("bshk,hkd->bsd", out, p["wo"]), "btd")
    return y, (k_cache, v_cache)


def paged_kv_write(
    pool: jax.Array,
    new: jax.Array,
    block_tables: jax.Array,
    positions: jax.Array,
) -> jax.Array:
    """Scatter new K/V rows into the paged pool through the block table.

    pool: [P, page, kvH, hd]; new: [B, T, kvH, hd]; block_tables: [B, W]
    int32; positions: [B, T] int32 logical positions.  Positions whose
    logical page index falls past the table width clamp onto the last
    column, which the engine keeps permanently at the sentinel page — the
    fused loops' overflow writes (frozen slots at the sequence boundary,
    bucket-pad chunk tails) land there instead of corrupting live pages."""
    page = pool.shape[1]
    w = block_tables.shape[1]
    cols = jnp.minimum(positions // page, w - 1)
    pages = jnp.take_along_axis(block_tables, cols, axis=1)  # [B, T]
    return pool.at[pages, positions % page].set(new.astype(pool.dtype))


def attention_decode_paged(
    cfg: ModelConfig,
    p: Params,
    x: jax.Array,
    kv_pool: tuple[jax.Array, jax.Array],
    block_tables: jax.Array,
    cache_index: jax.Array,
    *,
    impl: str = "auto",
) -> tuple[jax.Array, tuple[jax.Array, jax.Array]]:
    """One-token decode against the paged KV pool.

    x: [B, 1, d]; pool k/v: [P, page, kvH, hd] physical pages shared across
    slots; block_tables: [B, W] int32 logical->physical page map;
    cache_index: [B] int32 per-slot lengths.  The new token's K/V scatters
    into the slot's own page at ``index`` (always a private page — shared
    prefix pages are never written after insertion), then the attention core
    gathers pages through the block table (``ops.paged_decode_attention``)."""
    b = x.shape[0]
    idx = jnp.broadcast_to(jnp.asarray(cache_index, jnp.int32), (b,))
    positions = idx[:, None]
    q, k_new, v_new = _project_qkv(cfg, p, x, positions)
    k_pool, v_pool = kv_pool
    k_pool = paged_kv_write(k_pool, k_new, block_tables, positions)
    v_pool = paged_kv_write(v_pool, v_new, block_tables, positions)
    from repro.kernels import ops  # local import to avoid cycles

    out = shard(
        ops.paged_decode_attention(
            q[:, 0], k_pool, v_pool, block_tables, idx + 1, impl=impl
        )[:, None],
        "bthd",
    )
    mask = head_mask(cfg, out.dtype)
    if mask is not None:
        out = out * mask[None, None, :, None]
    y = shard(jnp.einsum("bshk,hkd->bsd", out, p["wo"]), "btd")
    return y, (k_pool, v_pool)


def attention_verify_paged(
    cfg: ModelConfig,
    p: Params,
    x: jax.Array,
    kv_pool: tuple[jax.Array, jax.Array],
    block_tables: jax.Array,
    cache_index: jax.Array,
    *,
    impl: str = "auto",
    anc: Optional[jax.Array] = None,
    depths: Optional[jax.Array] = None,
) -> tuple[jax.Array, tuple[jax.Array, jax.Array]]:
    """Chunk-verify decode against the paged KV pool: T tokens in one pass.

    x: [B, T, d] chunk embeddings; the chunk's K/V scatters into the slot's
    pages at logical positions ``index .. index + T - 1`` before the fused
    prefix+triangle attention (``ops.paged_verify_attention``).  Rollback
    after acceptance only rewinds ``index``: rejected positions sit past the
    rewound index inside the slot's *private* pages and are rewritten before
    ever being attended to — the dense path's stale-overwrite invariant,
    unchanged by paging (DESIGN.md §5).

    Tree mode (``anc`` + ``depths``): same contract as
    ``attention_verify`` — ancestor-bitmask intra-chunk visibility
    (``ops.paged_tree_verify_attention``), depth-based RoPE offsets,
    node-index K/V scatter."""
    b, t, _ = x.shape
    idx = jnp.broadcast_to(jnp.asarray(cache_index, jnp.int32), (b,))
    pos_w = idx[:, None] + jnp.arange(t)[None, :]  # [B, T] write slots
    if depths is None:
        positions = pos_w
    else:
        positions = idx[:, None] + depths.astype(jnp.int32)[None, :]
    q, k_new, v_new = _project_qkv(cfg, p, x, positions)
    k_pool, v_pool = kv_pool
    k_pool = paged_kv_write(k_pool, k_new, block_tables, pos_w)
    v_pool = paged_kv_write(v_pool, v_new, block_tables, pos_w)
    from repro.kernels import ops  # local import to avoid cycles

    if anc is None:
        core = ops.paged_verify_attention(
            q, k_pool, v_pool, block_tables, idx + t, impl=impl
        )
    else:
        core = ops.paged_tree_verify_attention(
            q, k_pool, v_pool, block_tables, idx + t, anc, impl=impl
        )
    out = shard(core, "bthd")
    mask = head_mask(cfg, out.dtype)
    if mask is not None:
        out = out * mask[None, None, :, None]
    y = shard(jnp.einsum("bshk,hkd->bsd", out, p["wo"]), "btd")
    return y, (k_pool, v_pool)


def attention_prefill_chunk_paged(
    cfg: ModelConfig,
    p: Params,
    x: jax.Array,
    kv_pool: tuple[jax.Array, jax.Array],
    block_tables: jax.Array,
    cache_index: jax.Array,
    chunk_lens: jax.Array,
    *,
    impl: str = "auto",
) -> tuple[jax.Array, tuple[jax.Array, jax.Array]]:
    """Chunked-prefill step against the paged KV pool.

    x: [B, C, d] chunk embeddings; the chunk's *real* K/V scatters into the
    slot's pages at logical positions ``index .. index + chunk_lens - 1``
    before the fused prefix+triangle attention
    (``ops.paged_prefill_chunk_attention``).  Pad rows are steered onto the
    table's sentinel column (a write sink nobody attends to) instead of
    being dropped — the block-table analog of the dense path's out-of-bounds
    drop.  Earlier chunks' pages — including radix-shared prefix pages —
    are read, never written, so prefix sharing composes with chunking."""
    b, c, _ = x.shape
    idx = jnp.broadcast_to(jnp.asarray(cache_index, jnp.int32), (b,))
    positions = idx[:, None] + jnp.arange(c)[None, :]  # [B, C]
    q, k_new, v_new = _project_qkv(cfg, p, x, positions)
    k_pool, v_pool = kv_pool
    page = k_pool.shape[1]
    w = block_tables.shape[1]
    valid = jnp.arange(c)[None, :] < chunk_lens[:, None]  # [B, C]
    # invalid rows clamp onto the last table column == the sentinel page
    pos_w = jnp.where(valid, positions, w * page)
    k_pool = paged_kv_write(k_pool, k_new, block_tables, pos_w)
    v_pool = paged_kv_write(v_pool, v_new, block_tables, pos_w)
    from repro.kernels import ops  # local import to avoid cycles

    out = shard(
        ops.paged_prefill_chunk_attention(
            q, k_pool, v_pool, block_tables, idx, chunk_lens, impl=impl
        ),
        "bthd",
    )
    mask = head_mask(cfg, out.dtype)
    if mask is not None:
        out = out * mask[None, None, :, None]
    y = shard(jnp.einsum("bshk,hkd->bsd", out, p["wo"]), "btd")
    return y, (k_pool, v_pool)


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------


def init_mlp(key, d_model: int, d_ff: int, dtype) -> Params:
    ks = jax.random.split(key, 3)
    return {
        "wg": jax.random.normal(ks[0], (d_model, d_ff), dtype) * d_model**-0.5,
        "wu": jax.random.normal(ks[1], (d_model, d_ff), dtype) * d_model**-0.5,
        "wd": jax.random.normal(ks[2], (d_ff, d_model), dtype) * d_ff**-0.5,
    }


def mlp_block(p: Params, x: jax.Array) -> jax.Array:
    g = shard(jnp.einsum("bsd,df->bsf", x, p["wg"]), "btf")
    u = shard(jnp.einsum("bsd,df->bsf", x, p["wu"]), "btf")
    h = jax.nn.silu(g) * u
    return shard(jnp.einsum("bsf,fd->bsd", h, p["wd"]), "btd")
