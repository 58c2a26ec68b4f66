"""Paged chunk-verify Pallas TPU kernel: block-table KV gather.

``verify_attention`` generalized the flash-decode kernel from one query
token to a ``T = gamma + 1`` speculative chunk; this kernel adds a
block-table indirection in the KV index_map on top, so speculative
verification runs directly against the paged KV pool.  The chunk's own K/V
has already been scattered into the slot's pages at logical positions
``lengths - T .. lengths - 1``.

Layout: q [B, T, H, hd]; k/v pools [P, page, kvH, hd]; block_tables [B, W]
int32; lengths [B] int32 valid-KV counts INCLUDING the chunk.  Chunk query t
sits at sequence position ``lengths - T + t`` and attends to
``kpos <= lengths - T + t`` — prefix plus the chunk's own causal triangle.

Grid: (B, num_logical_pages); each step DMAs one whole page
``[page, kvH, hd]`` and query rows fold to a single ``T * gp`` sublane axis
per kv head exactly as in ``verify_attention``.  The scalar-prefetched
block table is dereferenced in the KV index_map after clamping the logical
page index at the slot's last useful page, preserving the DMA-skip behavior
for ragged batches.  ``interpret=True`` is for tests off the TPU only (see
``decode_attention``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.decode_attention import scratch_shapes
from repro.kernels.verify_attention import _verify_kernel


def _paged_verify_kernel(lengths_ref, tables_ref, *refs, **kw):
    # The body IS the dense chunk-verify kernel (single source of truth for
    # the online softmax / causal bound / fully-masked-row guard); the block
    # table only steers the BlockSpec index_map below and is unused inside
    # the body.
    _verify_kernel(lengths_ref, *refs, **kw)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_verify_attention(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    block_tables: jax.Array,
    lengths: jax.Array,
    *,
    interpret: bool = False,
) -> jax.Array:
    """q: [B, T, H, hd] chunk queries; k/v_pool: [P, page, kvH, hd];
    block_tables: [B, W] int32; lengths: [B] int32 valid-KV counts
    *including* the T chunk positions.  Returns [B, T, H, hd].  Slots with
    ``lengths == 0`` — and chunk rows whose causal window is empty — return
    zeros.  The block table's LAST column is the overflow sentinel (never
    live KV: ``lengths <= (W-1) * page``), so the grid iterates W-1 logical
    pages."""
    b, t, h, hd = q.shape
    page, kvh = k_pool.shape[1], k_pool.shape[2]
    nk = block_tables.shape[1] - 1
    assert h % kvh == 0, f"q heads {h} not a multiple of kv heads {kvh}"
    group = h // kvh
    gp = max(8, group)  # sublane-pad the tiny GQA-group axis
    # Fold (chunk, group) into one sublane axis: row r = t * gp + g.
    qr = q.reshape(b, t, kvh, group, hd)
    if gp != group:
        qr = jnp.pad(qr, ((0, 0), (0, 0), (0, 0), (0, gp - group), (0, 0)))
    qr = qr.transpose(0, 2, 1, 3, 4).reshape(b, kvh, t * gp, hd)
    # lengths are NOT clamped to the logical capacity: suffix prefill passes
    # lengths = shared + T_bucket, which may exceed it when the bucket's pad
    # tail spills past max_seq — clamping would shift the causal bound
    # (length - chunk + t_row) and silently mask real prefix positions.
    # kv_map's min(ki, last) already keeps every table lookup in-grid.
    lengths = lengths.astype(jnp.int32)
    block_tables = block_tables.astype(jnp.int32)

    def q_map(bi, ki, lens, tables):
        return (bi, 0, 0, 0)

    def kv_map(bi, ki, lens, tables):
        last = jnp.maximum(pl.cdiv(lens[bi], page) - 1, 0)
        return (tables[bi, jnp.minimum(ki, last)], 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, nk),
        in_specs=[
            pl.BlockSpec((1, kvh, t * gp, hd), q_map),
            pl.BlockSpec((1, page, kvh, hd), kv_map),
            pl.BlockSpec((1, page, kvh, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, kvh, t * gp, hd), q_map),
        scratch_shapes=scratch_shapes(kvh, t * gp, hd),
    )
    kernel = functools.partial(
        _paged_verify_kernel, block_k=page, chunk=t, gp=gp,
        sm_scale=hd**-0.5,
    )
    out = pl.pallas_call(
        kernel,
        name="paged_verify_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, t * gp, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(lengths, block_tables, qr, k_pool, v_pool)
    out = out.reshape(b, kvh, t, gp, hd)[:, :, :, :group]
    return out.transpose(0, 2, 1, 3, 4).reshape(b, t, h, hd)
