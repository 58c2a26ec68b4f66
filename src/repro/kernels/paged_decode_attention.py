"""Paged flash-decode Pallas TPU kernel: block-table KV gather.

The dense ``decode_attention`` kernel assumes each slot owns a contiguous
``[S_max]`` row of the KV cache.  Under the paged KV pool
(``serving/kv_pool.py``) a slot's cache is a list of fixed-size *physical
pages* scattered through a shared pool, named by a per-slot **block table**.
This kernel is the dense one with exactly one change: the KV BlockSpec
index_map dereferences the scalar-prefetched block table, so each grid step
DMAs the slot's ``ki``-th *logical* page from wherever it physically lives.

Layout: q [B, H, hd] (one query token per slot), k/v pools
[P, page, kvH, hd] (physical pages, shared across slots — prefix-shared
pages appear in several block tables), block_tables [B, W] int32 (logical
page ``j`` of slot ``b`` lives at physical page ``block_tables[b, j]``;
unused entries hold the sentinel page 0), lengths [B] int32 valid-KV counts.

Grid: (B, num_logical_pages).  One grid step DMAs one whole page
``[page, kvH, hd]`` — contiguous in the pool — and the dense kernel's body
loops over its kv heads.  Both ragged-batch levers of the dense kernel
survive the indirection:

  * ``lengths`` and ``block_tables`` ride in as scalar-prefetch operands, so
    the KV index_map clamps the logical page index at the slot's last useful
    page *before* dereferencing — tiles past a slot's length re-address the
    same physical page and the pipeline skips their DMA entirely.
  * the kernel body early-exits (``pl.when(k_start < length)``) for pages
    past the length, skipping their FLOPs.

``lengths == 0`` marks an empty slot (output zeros).  ``interpret=True``
is for tests off the TPU only (see ``decode_attention``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.decode_attention import _decode_kernel, scratch_shapes


def _paged_decode_kernel(lengths_ref, tables_ref, *refs, **kw):
    # The body IS the dense flash-decode kernel (single source of truth for
    # the online softmax / masking); the block table only steers the
    # BlockSpec index_map below and is unused inside the body.
    _decode_kernel(lengths_ref, *refs, **kw)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    block_tables: jax.Array,
    lengths: jax.Array,
    *,
    interpret: bool = False,
) -> jax.Array:
    """q: [B, H, hd]; k/v_pool: [P, page, kvH, hd]; block_tables: [B, W]
    int32 physical-page ids per logical page, whose LAST column is the
    overflow sentinel (never live KV: ``lengths <= (W-1) * page`` — see
    ``transformer.init_paged_cache``), so the grid iterates W-1 logical
    pages; lengths: [B] int32 valid-KV counts.  Returns [B, H, hd].  Slots
    with ``lengths == 0`` return zeros."""
    b, h, hd = q.shape
    page, kvh = k_pool.shape[1], k_pool.shape[2]
    nk = block_tables.shape[1] - 1
    assert h % kvh == 0, f"q heads {h} not a multiple of kv heads {kvh}"
    group = h // kvh
    gp = max(8, group)  # sublane-pad the tiny GQA-group axis
    qr = q.reshape(b, kvh, group, hd)
    if gp != group:
        qr = jnp.pad(qr, ((0, 0), (0, 0), (0, gp - group), (0, 0)))
    # lengths are NOT clamped to the logical capacity: kv_map's min(ki,
    # last) already keeps every table lookup in-grid, and positions past
    # the last logical page are simply never loaded.
    lengths = lengths.astype(jnp.int32)
    block_tables = block_tables.astype(jnp.int32)

    def q_map(bi, ki, lens, tables):
        return (bi, 0, 0, 0)

    def kv_map(bi, ki, lens, tables):
        # Clamp the *logical* page index at the slot's last useful page, then
        # dereference the block table: past-length tiles re-address the same
        # physical page and the pipeline skips their DMA (ragged early-exit).
        last = jnp.maximum(pl.cdiv(lens[bi], page) - 1, 0)
        return (tables[bi, jnp.minimum(ki, last)], 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, nk),
        in_specs=[
            pl.BlockSpec((1, kvh, gp, hd), q_map),
            pl.BlockSpec((1, page, kvh, hd), kv_map),
            pl.BlockSpec((1, page, kvh, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, kvh, gp, hd), q_map),
        scratch_shapes=scratch_shapes(kvh, gp, hd),
    )
    kernel = functools.partial(
        _paged_decode_kernel, block_k=page, sm_scale=hd**-0.5
    )
    out = pl.pallas_call(
        kernel,
        name="paged_decode_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, gp, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(lengths, block_tables, qr, k_pool, v_pool)
    return out[:, :, :group].reshape(b, h, hd)
