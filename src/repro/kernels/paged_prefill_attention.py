"""Paged ragged chunked-prefill Pallas TPU kernel: block-table KV gather.

``prefill_attention`` generalized the chunk-verify kernel to prefill-sized
query chunks; this kernel applies the same block-table indirection as
``paged_verify_attention`` on top, so chunked
prefill streams straight into the paged KV pool: each chunk query attends
the slot's previously-written *pages* (including radix-shared prefix pages)
plus the chunk's own causal triangle.  The chunk's real K/V has already
been scattered into the slot's pages at positions
``starts .. starts + chunk_lens - 1``.

Layout: q [B, C, H, hd]; k/v pools [P, page, kvH, hd]; block_tables [B, W]
int32; starts / chunk_lens [B] int32 as in the dense kernel.

Grid: (B, num_q_blocks, num_logical_pages); each step DMAs one whole page
``[page, kvH, hd]`` and query rows fold to ``block_q * gp`` sublanes per kv
head exactly as in ``prefill_attention``.  The
scalar-prefetched block table is dereferenced in the KV index_map after
clamping the logical page at the q block's causal bound
``starts + min((qi + 1) * block_q, chunk_lens)`` — the DMA-skip lever now
scales with prefill *progress*: early chunks of a long prompt sweep only
the few pages written so far.  ``interpret=True`` is for tests off the TPU
only (see ``decode_attention``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.decode_attention import scratch_shapes
from repro.kernels.prefill_attention import (
    _fold_queries,
    _prefill_kernel,
    _unfold_outputs,
)


def _paged_prefill_kernel(starts_ref, lens_ref, tables_ref, *refs, **kw):
    # The body IS the dense chunked-prefill kernel (single source of truth
    # for the online softmax / causal bound / pad-row guard); the block
    # table only steers the BlockSpec index_map below and is unused inside
    # the body.
    _prefill_kernel(starts_ref, lens_ref, *refs, **kw)


@functools.partial(jax.jit, static_argnames=("block_q", "interpret"))
def paged_prefill_attention(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    block_tables: jax.Array,
    starts: jax.Array,
    chunk_lens: jax.Array,
    *,
    block_q: int = 32,
    interpret: bool = False,
) -> jax.Array:
    """q: [B, C, H, hd] chunk queries; k/v_pool: [P, page, kvH, hd];
    block_tables: [B, W] int32; starts / chunk_lens: [B] int32 — the chunk's
    real K/V sits in the slot's pages at ``starts .. starts + chunk_lens -
    1`` and query t attends ``kpos <= starts + t``.  Returns [B, C, H, hd];
    rows ``t >= chunk_lens`` return zeros.  The table's LAST column is the
    overflow sentinel (never live KV), so the grid iterates W-1 logical
    pages."""
    b, c, h, hd = q.shape
    page, kvh = k_pool.shape[1], k_pool.shape[2]
    nk = block_tables.shape[1] - 1
    assert h % kvh == 0, f"q heads {h} not a multiple of kv heads {kvh}"
    group = h // kvh
    gp = max(8, group)  # sublane-pad the tiny GQA-group axis
    block_q = min(block_q, c)
    qr, cp = _fold_queries(q, kvh, group, gp, block_q)
    nq = cp // block_q
    starts = starts.astype(jnp.int32)
    chunk_lens = jnp.minimum(chunk_lens.astype(jnp.int32), c)
    block_tables = block_tables.astype(jnp.int32)

    def q_map(bi, qi, ki, starts, lens, tables):
        return (bi, 0, qi, 0)

    def kv_map(bi, qi, ki, starts, lens, tables):
        limit = starts[bi] + jnp.minimum((qi + 1) * block_q, lens[bi])
        last = jnp.maximum(pl.cdiv(limit, page) - 1, 0)
        return (tables[bi, jnp.minimum(ki, last)], 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, nq, nk),
        in_specs=[
            pl.BlockSpec((1, kvh, block_q * gp, hd), q_map),
            pl.BlockSpec((1, page, kvh, hd), kv_map),
            pl.BlockSpec((1, page, kvh, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, kvh, block_q * gp, hd), q_map),
        scratch_shapes=scratch_shapes(kvh, block_q * gp, hd),
    )
    kernel = functools.partial(
        _paged_prefill_kernel, block_q=block_q, block_k=page, gp=gp,
        sm_scale=hd**-0.5,
    )
    out = pl.pallas_call(
        kernel,
        name="paged_prefill_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, cp * gp, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")
        ),
        interpret=interpret,
    )(starts, chunk_lens, block_tables, qr, k_pool, v_pool)
    return _unfold_outputs(out, b, c, cp, kvh, group, gp, hd)
