"""Tree-verify Pallas TPU kernel: multi-candidate speculative verification.

``verify_attention`` scores one *linear* draft chain per slot: chunk query t
attends the prefix plus intra-chunk positions ``<= t`` (a causal triangle).
This kernel generalizes that intra-chunk triangle to an ANCESTOR MASK so a
packed candidate *tree* — k branches sharing a root — verifies in ONE pass.
Node j of the tree occupies chunk position j (its K/V is written at cache
position ``lengths - N + j``, exactly where a linear chunk would put it);
``anc[b, j]`` is an int32 bitmask whose bit i is set iff node i is an
ancestor of node j *or j itself* (nodes are numbered so parents precede
children, hence ``N <= 31`` nodes fit one int32).  Query row j then attends

    kpos <  lengths - N          (the committed prefix), or
    kpos >= lengths - N  with bit ``kpos - (lengths - N)`` set in anc[b, j]

A linear chain (``anc[j]`` = bits 0..j) reproduces the triangle bound
``kpos <= lengths - N + j`` bit for bit, so this kernel is a strict
generalization of ``verify_attention`` (the equivalence a property test
pins down).

Layout mirrors ``verify_attention`` exactly: q [B, N, H, hd] (one query per
tree node), k/v [B, S_max, kvH, hd], lengths [B] int32 INCLUDING the N tree
positions, anc [B, N] int32 riding in as a second scalar-prefetch operand
next to lengths.  Grid (B, num_kv_blocks), one ``[block_k, kvH, hd]`` tile
per step; query rows fold to a ``N * gp`` sublane axis per kv head; the
DMA-clamp index_map and the shared ``tile_update`` (with its fully-masked-row
guard) are reused verbatim.  The per-row bitmask test is an unrolled Python
loop over the N chunk rows reading one SMEM scalar each — no gathers inside
the kernel body — and is built once per tile for all heads.
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
    init_scratch,
    scratch_shapes,
    tile_update,
)

#: Hard cap on packed-tree size: ancestor sets are int32 bitmasks.
MAX_TREE_NODES = 31


def _tree_verify_kernel(
    lengths_ref,  # scalar prefetch: [B] int32
    anc_ref,  # scalar prefetch: [B, N] int32 ancestor bitmasks
    q_ref,  # [1, kvH, N * gp, hd]
    k_ref, v_ref,  # [1, bk, kvH, hd]
    o_ref,  # [1, kvH, N * gp, hd]
    acc_ref, m_ref, l_ref,  # VMEM scratch (scratch_shapes)
    *,
    block_k: int,
    chunk: int,  # N = tree nodes
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
        shape = (q_ref.shape[2], block_k)  # [N*gp, bk]
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        t_row = jax.lax.broadcasted_iota(jnp.int32, shape, 0) // gp
        # Intra-chunk node index of each key position (negative = prefix,
        # >= chunk = beyond the tree).  Shifts are clamped into [0, 31] so
        # out-of-range lanes stay defined; ``in_chunk`` gates them off.
        jpos = kpos - (length - chunk)
        jc = jnp.clip(jpos, 0, 31)
        in_chunk = (jpos >= 0) & (jpos < chunk)
        # Row r holds tree node t = r // gp.  Visibility of key node j from
        # query node t is bit j of anc[b, t]; each of the N rows reads its
        # one SMEM scalar in an unrolled loop (no in-kernel gathers).  The
        # bits stay int32 until the final compare: Mosaic cannot truncate a
        # constant to a bool vector.
        bits = jnp.zeros(shape, jnp.int32)
        for t in range(chunk):
            bits = jnp.where(t_row == t, (anc_ref[b, t] >> jc) & 1, bits)
        # Fully-masked rows (empty slots, lengths < N) finalize to zeros.
        tile_update(
            q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref,
            (kpos < length - chunk) | (in_chunk & (bits == 1)), sm_scale,
        )

    @pl.when(ki == nk - 1)
    def _finalize():
        finalize(o_ref, acc_ref, l_ref)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def tree_verify_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    lengths: jax.Array,
    anc: jax.Array,
    *,
    block_k: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """q: [B, N, H, hd] one query per packed-tree node; k/v: [B, S_max, kvH,
    hd] with node j's K/V already written at position ``lengths - N + j``;
    lengths: [B] int32 valid-KV counts *including* the N tree positions;
    anc: [B, N] int32 ancestor bitmasks (bit i of anc[b, j] = node i visible
    from node j; self bit set).  Returns [B, N, H, hd].  Slots with
    ``lengths == 0`` — and rows whose visibility set is empty — return
    zeros."""
    b, t, h, hd = q.shape
    s, kvh = k.shape[1], k.shape[2]
    assert t <= MAX_TREE_NODES, f"tree has {t} nodes (> {MAX_TREE_NODES})"
    assert h % kvh == 0, f"q heads {h} not a multiple of kv heads {kvh}"
    group = h // kvh
    gp = max(8, group)  # sublane-pad the tiny GQA-group axis
    block_k = min(block_k, s)
    nk = (s + block_k - 1) // block_k
    pad_s = nk * block_k - s
    if pad_s:
        k = jnp.pad(k, ((0, 0), (0, pad_s), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_s), (0, 0), (0, 0)))
    qr = q.reshape(b, t, kvh, group, hd)
    if gp != group:
        qr = jnp.pad(qr, ((0, 0), (0, 0), (0, 0), (0, gp - group), (0, 0)))
    qr = qr.transpose(0, 2, 1, 3, 4).reshape(b, kvh, t * gp, hd)
    lengths = jnp.minimum(lengths.astype(jnp.int32), s)
    anc = anc.astype(jnp.int32)

    def q_map(bi, ki, lens, ancs):
        return (bi, 0, 0, 0)

    def kv_map(bi, ki, lens, ancs):
        last = jnp.maximum(pl.cdiv(lens[bi], block_k) - 1, 0)
        return (bi, jnp.minimum(ki, last), 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
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
        _tree_verify_kernel, block_k=block_k, chunk=t, gp=gp,
        sm_scale=hd**-0.5,
    )
    out = pl.pallas_call(
        kernel,
        name="tree_verify_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, t * gp, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(lengths, anc, qr, k, v)
    out = out.reshape(b, kvh, t, gp, hd)[:, :, :, :group]
    return out.transpose(0, 2, 1, 3, 4).reshape(b, t, h, hd)
