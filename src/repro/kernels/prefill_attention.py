"""Ragged chunked-prefill Pallas TPU kernel: large-query-chunk attention.

Chunked prefill (DESIGN.md §7) splits an admitted prompt into fixed-width
chunks and streams them into a slot across successive engine steps, so no
single step ever pays a whole prompt's latency.  The attention each chunk
needs is the chunk-verify shape scaled up: every chunk query attends the
slot's *previously-written* cache prefix plus the chunk's own causal
triangle.  This kernel is ``verify_attention`` generalized from a
``gamma + 1`` speculative chunk to a prefill-sized query chunk, with one
extra grid axis so large chunks tile instead of loading one giant block.

Layout: q [B, C, H, hd] (C = chunk width), k/v [B, S_max, kvH, hd] — the
chunk's *real* K/V (rows ``t < chunk_lens``) has already been written at
positions ``starts .. starts + chunk_lens - 1``; starts [B] int32 = KV
entries before the chunk (the slot's prefill progress); chunk_lens [B]
int32 = real tokens in this chunk (ragged: the mixed batch runs every
slot's chunk at its own length, 0 = slot not prefilling).  Chunk query t
sits at sequence position ``starts + t`` and attends ``kpos <= starts + t``;
rows ``t >= chunk_lens`` return zeros.

Grid: (B, num_q_blocks, num_kv_blocks).  Each program owns one
``block_q``-row slice of one slot's chunk, folded per kv head to a single
``block_q * gp`` sublane axis exactly as in the verify kernel; the body
loops over the kv heads of each ``[block_k, kvH, hd]`` tile through the
decode kernel's shared ``tile_update``.  Both ragged-batch levers
generalize:

  * ``starts`` and ``chunk_lens`` ride in as scalar-prefetch operands; the
    KV BlockSpec index_map clamps the tile index at the q block's *causal*
    bound ``starts + min((qi + 1) * block_q, chunk_lens)`` — tiles past it
    re-address the same block and the pipeline skips their DMA.  A short
    chunk (``chunk_lens`` well below C) therefore skips the KV tiles its
    missing rows would have swept, not just their FLOPs.
  * the body early-exits for q blocks past ``chunk_lens`` and KV tiles past
    the causal bound; the intra-chunk causal mask is the per-row position
    bound ``kpos <= starts + t`` on top of the row-validity mask.

``chunk_lens == 0`` marks a frozen slot: every tile is skipped and the
output is zeros.  ``interpret=True`` is for tests off the TPU only (see
``decode_attention``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.decode_attention import (
    finalize,
    init_scratch,
    scratch_shapes,
    tile_update,
)


def _prefill_kernel(
    starts_ref,  # scalar prefetch: [B] int32
    lens_ref,  # scalar prefetch: [B] int32
    q_ref,  # [1, kvH, block_q * gp, hd]
    k_ref, v_ref,  # [1, block_k, kvH, hd]
    o_ref,  # [1, kvH, block_q * gp, hd]
    acc_ref, m_ref, l_ref,  # VMEM scratch (scratch_shapes)
    *,
    block_q: int,
    block_k: int,
    gp: int,  # sublane-padded GQA group size
    sm_scale: float,
):
    b = pl.program_id(0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    start = starts_ref[b]
    clen = lens_ref[b]
    q0 = qi * block_q  # first chunk row owned by this program

    @pl.when(ki == 0)
    def _init():
        init_scratch(acc_ref, m_ref, l_ref)

    k_start = ki * block_k
    # Exclusive KV bound of this q block: its last real row q0 + block_q - 1
    # (clamped at chunk_lens) attends kpos <= start + row.
    limit = start + jnp.minimum(q0 + block_q, clen)

    @pl.when((q0 < clen) & (k_start < limit))
    def _compute():
        shape = (q_ref.shape[2], block_k)  # [block_q * gp, block_k]
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        # Row r holds chunk query t = q0 + r // gp at sequence position
        # start + t: causal bound over prefix + intra-chunk triangle, and
        # rows past the slot's real chunk length are masked out entirely
        # (they finalize to zeros).
        t_row = q0 + jax.lax.broadcasted_iota(jnp.int32, shape, 0) // gp
        tile_update(
            q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref,
            (kpos <= start + t_row) & (t_row < clen), sm_scale,
        )

    @pl.when(ki == nk - 1)
    def _finalize():
        finalize(o_ref, acc_ref, l_ref)


def _fold_queries(q: jax.Array, kvh: int, group: int, gp: int, block_q: int):
    """[B, C, H, hd] -> [B, kvH, Cp * gp, hd] with C padded to a block_q
    multiple and the (chunk, group) axes folded to one sublane axis
    (row r = t * gp + g)."""
    b, c, h, hd = q.shape
    cp = -(-c // block_q) * block_q
    qr = q.reshape(b, c, kvh, group, hd)
    if cp != c:
        qr = jnp.pad(qr, ((0, 0), (0, cp - c), (0, 0), (0, 0), (0, 0)))
    if gp != group:
        qr = jnp.pad(qr, ((0, 0), (0, 0), (0, 0), (0, gp - group), (0, 0)))
    return qr.transpose(0, 2, 1, 3, 4).reshape(b, kvh, cp * gp, hd), cp


def _unfold_outputs(out, b, c, cp, kvh, group, gp, hd):
    out = out.reshape(b, kvh, cp, gp, hd)[:, :, :c, :group]
    return out.transpose(0, 2, 1, 3, 4).reshape(b, c, kvh * group, hd)


@functools.partial(
    jax.jit, static_argnames=("block_q", "block_k", "interpret")
)
def prefill_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    starts: jax.Array,
    chunk_lens: jax.Array,
    *,
    block_q: int = 32,
    block_k: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """q: [B, C, H, hd] chunk queries; k/v: [B, S_max, kvH, hd] with the
    chunk's real K/V already written at ``starts .. starts + chunk_lens - 1``;
    starts/chunk_lens: [B] int32.  Chunk query t attends
    ``kpos <= starts + t``.  Returns [B, C, H, hd]; rows ``t >= chunk_lens``
    (frozen slots included: ``chunk_lens == 0``) return zeros."""
    b, c, h, hd = q.shape
    s, kvh = k.shape[1], k.shape[2]
    assert h % kvh == 0, f"q heads {h} not a multiple of kv heads {kvh}"
    group = h // kvh
    gp = max(8, group)  # sublane-pad the tiny GQA-group axis
    block_q = min(block_q, c)
    block_k = min(block_k, s)
    nk = (s + block_k - 1) // block_k
    pad_s = nk * block_k - s
    if pad_s:
        k = jnp.pad(k, ((0, 0), (0, pad_s), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_s), (0, 0), (0, 0)))
    qr, cp = _fold_queries(q, kvh, group, gp, block_q)
    nq = cp // block_q
    starts = starts.astype(jnp.int32)
    # chunk rows never extend past the cache; rows past a clamped length
    # are pad by contract (the engine sizes chunks to fit)
    chunk_lens = jnp.minimum(chunk_lens.astype(jnp.int32), c)

    def q_map(bi, qi, ki, starts, lens):
        return (bi, 0, qi, 0)

    def kv_map(bi, qi, ki, starts, lens):
        # Clamp past-bound tiles onto the q block's last useful KV block:
        # the pipeline sees a repeated index and skips the DMA, so short
        # chunks skip the KV tiles their missing rows would have swept.
        limit = starts[bi] + jnp.minimum((qi + 1) * block_q, lens[bi])
        last = jnp.maximum(pl.cdiv(limit, block_k) - 1, 0)
        return (bi, jnp.minimum(ki, last), 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, nq, nk),
        in_specs=[
            pl.BlockSpec((1, kvh, block_q * gp, hd), q_map),
            pl.BlockSpec((1, block_k, kvh, hd), kv_map),
            pl.BlockSpec((1, block_k, kvh, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, kvh, block_q * gp, hd), q_map),
        scratch_shapes=scratch_shapes(kvh, block_q * gp, hd),
    )
    kernel = functools.partial(
        _prefill_kernel, block_q=block_q, block_k=block_k, gp=gp,
        sm_scale=hd**-0.5,
    )
    out = pl.pallas_call(
        kernel,
        name="prefill_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, cp * gp, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")
        ),
        interpret=interpret,
    )(starts, chunk_lens, qr, k, v)
    return _unfold_outputs(out, b, c, cp, kvh, group, gp, hd)
