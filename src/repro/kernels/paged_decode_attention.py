"""Paged flash-decode Pallas TPU kernel: block-table KV gather, many pages
a block, a loop bounded by each slot's length.

The dense ``decode_attention`` kernel assumes each slot owns a contiguous
``[S_max]`` row of the KV cache.  Under the paged KV pool
(``serving/kv_pool.py``) a slot's cache is a list of fixed-size *physical
pages* scattered through a shared pool, named by a per-slot **block table**.

Layout: q [B, H, hd] (one query token per slot), k/v pools
[P, page, kvH, hd] (physical pages, shared across slots — prefix-shared
pages appear in several block tables), block_tables [B, W] int32 (logical
page ``j`` of slot ``b`` lives at physical page ``block_tables[b, j]``;
unused entries hold the sentinel page 0), lengths [B] int32 valid-KV counts.

Grid: (B,), one step a slot on the ``parallel`` axis.  The pools stay in
HBM (``memory_space=pl.ANY``); the step loops over its slot's KV in blocks
of ``pages_per_block`` logical pages, ``cdiv(live pages, pages_per_block)``
times — a dynamic bound, so no step, loop trip or DMA is spent past a
slot's length, and an empty slot (``lengths == 0``) copies nothing and
writes zeros.  A block's pages are copied one DMA a page (each page
``[page, kvH, hd]`` is contiguous in the pool) from wherever the table
puts them into a two-deep VMEM buffer ``[2, block_tokens, kvH, hd]``; the
next block's copies start before the current block is computed, and only
the live pages of a slot's last block are copied.

Block size: ``pages_per_block`` is chosen from the shapes alone (no knob):
enough pages for a block of at least ``BLOCK_TOKENS`` tokens (8 pages at a
16-token page), halved while the two buffers of K and V would pass
``VMEM_BUDGET``, and never more than the table's W-1 logical pages.

Compute: each block folds into the online softmax at once, through the
shared ``fold_head`` (one ``[gp, hd] x [hd, block_tokens]`` product a kv
head).  A head's rows come from one strided load of the block, two heads a
32-bit word for 16-bit pages (``_head_rows``).  Keys at or past the slot's
length are masked, and the V rows of pages not copied (stale buffer
contents) are zeroed.

Why it is faster than a grid of one page a step: that grid ran all W-1
pages of the table for every slot (2,048 steps a call at 16 slots and
``max_seq`` 2048), where a slot's live pages are a few tens; a step past
the length skipped its DMA and its compute but still paid the step.

``interpret=True`` is for tests off the TPU only (see ``decode_attention``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.decode_attention import (
    finalize,
    fold_head,
    init_scratch,
    scratch_shapes,
)

BLOCK_TOKENS = 128  # the least block: one MXU-wide product per kv head
VMEM_BUDGET = 8 << 20  # both buffers of K and V, under v5e's scoped 16 MiB


def pages_per_block(page: int, kvh: int, hd: int, itemsize: int,
                    logical_pages: int) -> int:
    """Pages a block: at least ``BLOCK_TOKENS`` tokens, halved while the
    double-buffered K and V blocks pass ``VMEM_BUDGET``, at most the
    table's logical pages."""
    n = -(-BLOCK_TOKENS // page)
    while n > 1 and 4 * n * page * kvh * hd * itemsize > VMEM_BUDGET:
        n //= 2
    return max(1, min(n, logical_pages))


def _head_rows(block, h: int):
    """Kv head ``h``'s rows ``[bk, hd]`` (f32) of a block ``[bk, kvH, hd]``
    ref, by one strided load of the block viewed as ``[bk * kvH, hd]``:
    indexing the head axis of a tile would instead relayout row by row.
    16-bit pages load 32-bit words holding heads ``2i`` (low half) and
    ``2i + 1`` (high half); a 16-bit bfloat is the top half of its f32."""
    bk, kvh, hd = block.shape
    flat = block.reshape(bk * kvh, hd)
    if flat.dtype.itemsize == 4 or kvh == 1:
        return flat[pl.ds(h, bk, stride=kvh), :].astype(jnp.float32)
    words = flat.bitcast(jnp.uint32)[pl.ds(h // 2, bk, stride=kvh // 2), :]
    top = words & jnp.uint32(0xFFFF0000) if h % 2 else words << 16
    return pltpu.bitcast(top, jnp.float32)


def _paged_decode_kernel(
    lengths_ref,  # scalar prefetch: [B] int32
    tables_ref,  # scalar prefetch: [B, W] int32
    q_ref,  # [1, kvH, gp, hd]
    k_hbm, v_hbm,  # [P, page, kvH, hd], in HBM
    o_ref,  # [1, kvH, gp, hd]
    k_buf, v_buf,  # [2, block_tokens, kvH, hd] VMEM double buffers
    sems,  # DMA semaphores [2 (K, V), 2 (buffer)]
    acc_ref, m_ref, l_ref,  # VMEM scratch (scratch_shapes)
    *,
    pages_per_block: int,
    sm_scale: float,
):
    b = pl.program_id(0)
    page, kvh = k_hbm.shape[1], k_hbm.shape[2]
    nk = tables_ref.shape[1] - 1  # the last column is the overflow sentinel
    ppb = pages_per_block
    bk = ppb * page
    length = lengths_ref[b]
    live = jnp.minimum(pl.cdiv(length, page), nk)  # live logical pages
    valid = jnp.minimum(length, live * page)  # keys that were copied
    nblocks = pl.cdiv(live, ppb)

    def page_copies(src_page, buf, i):
        rows = pl.ds(i * page, page)
        return (
            pltpu.make_async_copy(
                k_hbm.at[src_page], k_buf.at[buf, rows], sems.at[0, buf]),
            pltpu.make_async_copy(
                v_hbm.at[src_page], v_buf.at[buf, rows], sems.at[1, buf]),
        )

    def each_live_page(blk, fn):
        for i in range(ppb):
            j = blk * ppb + i
            pl.when(j < live)(functools.partial(fn, j, i))

    def start(blk, buf):
        def go(j, i):
            for c in page_copies(tables_ref[b, j], buf, i):
                c.start()

        each_live_page(blk, go)

    def wait(blk, buf):
        def go(j, i):
            for c in page_copies(0, buf, i):  # a wait needs only the size
                c.wait()

        each_live_page(blk, go)

    init_scratch(acc_ref, m_ref, l_ref)

    @pl.when(nblocks > 0)
    def _prefetch_first():
        start(0, 0)

    def block(blk, carry):
        buf = blk % 2

        @pl.when(blk + 1 < nblocks)
        def _prefetch_next():
            start(blk + 1, 1 - buf)

        wait(blk, buf)
        kpos = blk * bk + jax.lax.broadcasted_iota(
            jnp.int32, (q_ref.shape[2], bk), 1)
        # rows of pages not copied hold stale buffer contents, NaN maybe:
        # a zero weight times NaN is NaN, so their V rows are zeroed
        copied = (blk * bk
                  + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)) < valid
        k_blk, v_blk = k_buf.at[buf], v_buf.at[buf]
        for h in range(kvh):
            fold_head(
                h, q_ref[0, h].astype(jnp.float32), _head_rows(k_blk, h),
                lambda h=h: jnp.where(copied, _head_rows(v_blk, h), 0.0),
                acc_ref, m_ref, l_ref, kpos < valid, sm_scale,
            )
        return carry

    jax.lax.fori_loop(0, nblocks, block, 0)
    finalize(o_ref, acc_ref, l_ref)


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
    ``transformer.init_paged_cache``), so at most W-1 logical pages are
    read; lengths: [B] int32 valid-KV counts.  Returns [B, H, hd].  Slots
    with ``lengths == 0`` return zeros."""
    b, h, hd = q.shape
    page, kvh = k_pool.shape[1], k_pool.shape[2]
    nk = block_tables.shape[1] - 1
    assert h % kvh == 0, f"q heads {h} not a multiple of kv heads {kvh}"
    itemsize = k_pool.dtype.itemsize
    assert itemsize == 4 or kvh == 1 or (itemsize == 2 and kvh % 2 == 0), (
        f"{k_pool.dtype} pages of {kvh} kv heads: 16-bit pages load their "
        "heads in pairs")
    group = h // kvh
    gp = max(8, group)  # sublane-pad the tiny GQA-group axis
    qr = q.reshape(b, kvh, group, hd)
    if gp != group:
        qr = jnp.pad(qr, ((0, 0), (0, 0), (0, gp - group), (0, 0)))
    ppb = pages_per_block(page, kvh, hd, itemsize, nk)
    bk = ppb * page

    def q_map(bi, lens, tables):
        return (bi, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, kvh, gp, hd), q_map),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, kvh, gp, hd), q_map),
        scratch_shapes=[
            pltpu.VMEM((2, bk, kvh, hd), k_pool.dtype),
            pltpu.VMEM((2, bk, kvh, hd), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            *scratch_shapes(kvh, gp, hd),
        ],
    )
    kernel = functools.partial(
        _paged_decode_kernel, pages_per_block=ppb, sm_scale=hd**-0.5
    )
    out = pl.pallas_call(
        kernel,
        name="paged_decode_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, gp, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)
        ),
        interpret=interpret,
    )(lengths.astype(jnp.int32), block_tables.astype(jnp.int32), qr,
      k_pool, v_pool)
    return out[:, :, :group].reshape(b, h, hd)
