"""Chunk-verify Pallas TPU kernel: multi-token speculative-verify attention.

Speculative decoding's target-side hot path scores all ``gamma + 1`` chunk
positions (current token + gamma draft tokens) in ONE pass over the KV cache
instead of ``gamma + 1`` sequential decode steps.  This kernel is
``decode_attention`` generalized from one query token per slot to a small
query *chunk* per slot.

Layout: q [B, T, H, hd] (T = gamma+1 chunk queries per slot), k/v
[B, S_max, kvH, hd] (the KV cache in its native engine layout — the chunk's
own K/V has already been written at positions ``lengths - T .. lengths - 1``),
lengths [B] int32 = valid KV entries per slot INCLUDING the chunk.  Chunk
query t sits at sequence position ``lengths - T + t`` and may attend to
``kpos <= lengths - T + t`` — prefix plus the chunk's own causal triangle.

Grid: (B, num_kv_blocks).  Each program owns one slot for ALL T chunk
queries: per kv head, the query rows fold to a single ``T * gp`` sublane
axis (``gp`` = sublane-padded group size), and the body loops over the kv
heads of each ``[block_k, kvH, hd]`` tile through the decode kernel's shared
``tile_update``.  The same two ragged-batch levers apply:

  * ``lengths`` rides in as a scalar-prefetch operand, so the KV BlockSpec
    index_map clamps the tile index at each slot's last useful block — tiles
    past the length re-address the same block and the pipeline skips their
    DMA entirely (the decode kernel's DMA-clamp machinery, reused verbatim).
  * the kernel body early-exits (``pl.when(k_start < length)``) for tiles
    past the length, skipping their FLOPs; the intra-chunk causal mask is a
    per-row position bound on top of the shared length mask.

``lengths == 0`` marks an empty slot: every tile is skipped and the output
is zeros.  ``interpret=True`` is for tests off the TPU only (see
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


def _verify_kernel(
    lengths_ref,  # scalar prefetch: [B] int32
    q_ref,  # [1, kvH, T * gp, hd]
    k_ref, v_ref,  # [1, bk, kvH, hd]
    o_ref,  # [1, kvH, T * gp, hd]
    acc_ref, m_ref, l_ref,  # VMEM scratch (scratch_shapes)
    *,
    block_k: int,
    chunk: int,  # T = gamma + 1
    gp: int,  # sublane-padded GQA group size
    sm_scale: float,
):
    b = pl.program_id(0)
    ki = pl.program_id(1)
    nk = pl.num_programs(1)
    length = lengths_ref[b]

    @pl.when(ki == 0)
    def _init():
        init_scratch(acc_ref, m_ref, l_ref)

    k_start = ki * block_k

    @pl.when(k_start < length)
    def _compute():
        shape = (q_ref.shape[2], block_k)  # [T*gp, bk]
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        # Row r holds chunk query t = r // gp at sequence position
        # length - chunk + t: causal bound over prefix + intra-chunk triangle.
        # A row whose window is empty (lengths < T) finalizes to zeros.
        t_row = jax.lax.broadcasted_iota(jnp.int32, shape, 0) // gp
        tile_update(
            q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref,
            kpos <= length - chunk + t_row, sm_scale,
        )

    @pl.when(ki == nk - 1)
    def _finalize():
        finalize(o_ref, acc_ref, l_ref)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def verify_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    lengths: jax.Array,
    *,
    block_k: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """q: [B, T, H, hd] chunk queries; k/v: [B, S_max, kvH, hd]; lengths: [B]
    int32 valid-KV counts *including* the T chunk positions (chunk query t
    attends to kpos <= lengths - T + t).  Returns [B, T, H, hd].  Slots with
    ``lengths == 0`` — and individual chunk rows whose causal window is
    empty (``lengths < T``) — return zeros."""
    b, t, h, hd = q.shape
    s, kvh = k.shape[1], k.shape[2]
    assert h % kvh == 0, f"q heads {h} not a multiple of kv heads {kvh}"
    group = h // kvh
    gp = max(8, group)  # sublane-pad the tiny GQA-group axis
    block_k = min(block_k, s)
    nk = (s + block_k - 1) // block_k
    pad_s = nk * block_k - s
    if pad_s:
        k = jnp.pad(k, ((0, 0), (0, pad_s), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_s), (0, 0), (0, 0)))
    # Fold (chunk, group) into one sublane axis: row r = t * gp + g.
    qr = q.reshape(b, t, kvh, group, hd)
    if gp != group:
        qr = jnp.pad(qr, ((0, 0), (0, 0), (0, 0), (0, gp - group), (0, 0)))
    qr = qr.transpose(0, 2, 1, 3, 4).reshape(b, kvh, t * gp, hd)
    lengths = jnp.minimum(lengths.astype(jnp.int32), s)

    def q_map(bi, ki, lens):
        return (bi, 0, 0, 0)

    def kv_map(bi, ki, lens):
        # Clamp past-length tiles onto the slot's last useful block: the
        # pipeline sees a repeated index and skips the DMA (ragged early-exit).
        last = jnp.maximum(pl.cdiv(lens[bi], block_k) - 1, 0)
        return (bi, jnp.minimum(ki, last), 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, nk),
        in_specs=[
            pl.BlockSpec((1, kvh, t * gp, hd), q_map),
            pl.BlockSpec((1, block_k, kvh, hd), kv_map),
            pl.BlockSpec((1, block_k, kvh, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, kvh, t * gp, hd), q_map),
        scratch_shapes=scratch_shapes(kvh, t * gp, hd),
    )
    kernel = functools.partial(
        _verify_kernel, block_k=block_k, chunk=t, gp=gp, sm_scale=hd**-0.5
    )
    out = pl.pallas_call(
        kernel,
        name="verify_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, t * gp, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(lengths, qr, k, v)
    out = out.reshape(b, kvh, t, gp, hd)[:, :, :, :group]
    return out.transpose(0, 2, 1, 3, 4).reshape(b, t, h, hd)
