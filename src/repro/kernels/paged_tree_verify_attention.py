"""Paged tree-verify Pallas TPU kernel: ancestor-mask verification over the
block-table KV pool.

``tree_verify_attention`` generalizes the chunk-verify causal triangle to a
packed candidate tree; this kernel applies ``paged_verify_attention``'s
block-table indirection on top, so multi-branch speculative verification
runs directly against the paged KV pool in ONE pass.  Tree node j's K/V has
already been scattered into the slot's pages at logical position
``lengths - N + j`` (the node-index slot a linear chunk would use).

Layout: q [B, N, H, hd]; k/v pools [P, page, kvH, hd]; block_tables [B, W]
int32 (last column = overflow sentinel, so the grid iterates W-1 logical
pages); lengths [B] int32 INCLUDING the N tree positions; anc [B, N] int32
ancestor bitmasks riding as a THIRD scalar-prefetch operand after lengths
and the block table.  The body IS ``_tree_verify_kernel`` — the table only
steers the KV index_map, exactly as in ``paged_verify_attention``: grid
(B, num_logical_pages), one whole ``[page, kvH, hd]`` page per step.
``interpret=True`` is for tests off the TPU only (see ``decode_attention``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.decode_attention import scratch_shapes
from repro.kernels.tree_verify_attention import (
    MAX_TREE_NODES,
    _tree_verify_kernel,
)


def _paged_tree_verify_kernel(lengths_ref, tables_ref, anc_ref, *refs, **kw):
    # Single source of truth: the dense tree kernel body (online softmax,
    # ancestor-bitmask visibility, fully-masked-row guard).  The block table
    # only steers the BlockSpec index_map below.
    _tree_verify_kernel(lengths_ref, anc_ref, *refs, **kw)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_tree_verify_attention(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    block_tables: jax.Array,
    lengths: jax.Array,
    anc: jax.Array,
    *,
    interpret: bool = False,
) -> jax.Array:
    """q: [B, N, H, hd] one query per packed-tree node; k/v_pool: [P, page,
    kvH, hd]; block_tables: [B, W] int32; lengths: [B] int32 valid-KV counts
    *including* the N tree positions; anc: [B, N] int32 ancestor bitmasks.
    Returns [B, N, H, hd]."""
    b, t, h, hd = q.shape
    page, kvh = k_pool.shape[1], k_pool.shape[2]
    nk = block_tables.shape[1] - 1
    assert t <= MAX_TREE_NODES, f"tree has {t} nodes (> {MAX_TREE_NODES})"
    assert h % kvh == 0, f"q heads {h} not a multiple of kv heads {kvh}"
    group = h // kvh
    gp = max(8, group)  # sublane-pad the tiny GQA-group axis
    qr = q.reshape(b, t, kvh, group, hd)
    if gp != group:
        qr = jnp.pad(qr, ((0, 0), (0, 0), (0, 0), (0, gp - group), (0, 0)))
    qr = qr.transpose(0, 2, 1, 3, 4).reshape(b, kvh, t * gp, hd)
    # lengths NOT clamped — same rationale as paged_verify_attention: the
    # visibility base (lengths - N) must not shift; kv_map's min(ki, last)
    # keeps every table lookup in-grid.
    lengths = lengths.astype(jnp.int32)
    block_tables = block_tables.astype(jnp.int32)
    anc = anc.astype(jnp.int32)

    def q_map(bi, ki, lens, tables, ancs):
        return (bi, 0, 0, 0)

    def kv_map(bi, ki, lens, tables, ancs):
        last = jnp.maximum(pl.cdiv(lens[bi], page) - 1, 0)
        return (tables[bi, jnp.minimum(ki, last)], 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
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
        _paged_tree_verify_kernel, block_k=page, chunk=t, gp=gp,
        sm_scale=hd**-0.5,
    )
    out = pl.pallas_call(
        kernel,
        name="paged_tree_verify_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, t * gp, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(lengths, block_tables, anc, qr, k_pool, v_pool)
    out = out.reshape(b, kvh, t, gp, hd)[:, :, :, :group]
    return out.transpose(0, 2, 1, 3, 4).reshape(b, t, h, hd)
