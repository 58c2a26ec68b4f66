"""Flash-decode Pallas TPU kernel: single-token batched decode attention.

Serving decode is the hottest path in the repo — every engine microstep runs
it once per layer per slot batch — so it gets its own kernel instead of the
masked dense ``attention_xla`` over the full ``S_max`` KV cache.

Layout: q [B, H, hd] (one query token per slot), k/v [B, S_max, kvH, hd]
(the KV cache in its native engine layout — no transpose copy on the hot
path), lengths [B] int32 (valid KV entries per slot; 0 marks an empty slot).

Grid: (B, num_kv_blocks).  Each program owns one slot.  A KV tile is
``[block_k, kvH, hd]`` — all kv heads in one contiguous DMA; the TPU
lowering requires a block's two trailing dims to be (8, 128)-aligned or the
array's own, and a one-head ``(block_k, 1, hd)`` slice is neither.  The body
loops over the kv heads (static sublane slices of the tile); each head's
GQA group (``H // kvH`` query heads) accumulates the online softmax in its
own VMEM scratch row block, exactly like ``flash_attention.py``.  Two
length-awareness levers make the kernel ragged-batch fast:

  * ``lengths`` rides in as a scalar-prefetch operand
    (``PrefetchScalarGridSpec``), so the KV BlockSpec index_map can clamp the
    tile index to the slot's last useful block — tiles past a slot's length
    re-address the same block and the pipeline skips their DMA entirely.
  * the kernel body early-exits (``pl.when(k_start < length)``) for tiles
    past the length, so their FLOPs are skipped too.

The per-tile helpers below (``init_scratch`` / ``tile_update`` /
``fold_head`` / ``finalize`` / ``scratch_shapes``) are shared by every
attention kernel of this package; each kernel only supplies its visibility
mask.  The paged verify, tree-verify and prefill kernels also share their
dense kernel's body.  The paged decode kernel has its own (it copies its
pages by hand, many a block, in a loop bounded by the slot's length, and
loads each kv head's rows by strided loads) and folds each head through
``fold_head``.

``interpret=True`` runs the body in the Pallas interpreter off the TPU, for
tests only: the interpreter does not apply the TPU lowering rules, which
``tests/test_tpu_compile.py`` checks by compiling for a described v5e.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def scratch_shapes(kvh: int, rows: int, hd: int) -> list:
    """fp32 VMEM scratch for ``kvh`` heads of ``rows`` query rows each: the
    online-softmax accumulator, running max and running denominator."""
    return [
        pltpu.VMEM((kvh, rows, hd), jnp.float32),
        pltpu.VMEM((kvh, rows, 1), jnp.float32),
        pltpu.VMEM((kvh, rows, 1), jnp.float32),
    ]


def init_scratch(acc_ref, m_ref, l_ref) -> None:
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)


def tile_update(q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref, mask, sm_scale):
    """Fold one KV tile into the online softmax of every kv head.

    q_ref [1, kvH, R, hd] (R query rows per head); k_ref / v_ref
    [1, bk, kvH, hd]; mask [R, bk] bool — which (row, key) pairs are
    visible, the same for every head."""
    for h in range(k_ref.shape[2]):
        fold_head(
            h, q_ref[0, h].astype(jnp.float32),  # [R, hd]
            k_ref[0, :, h].astype(jnp.float32),  # [bk, hd]
            lambda h=h: v_ref[0, :, h].astype(jnp.float32),
            acc_ref, m_ref, l_ref, mask, sm_scale,
        )


def fold_head(h, q, k, load_v, acc_ref, m_ref, l_ref, mask, sm_scale):
    """Fold kv head ``h``'s tile into its online softmax.

    q [R, hd] and k [bk, hd] f32; ``load_v()`` gives the head's V rows
    [bk, hd] f32, loaded once the scores are done; mask [R, bk] bool.
    Masked pairs contribute exactly 0, so a row whose window is empty keeps
    ``l == 0`` and finalizes to zeros (without the guard ``exp(s - m_new)``
    would be 1 for a fully-masked row and the output an unweighted mean of
    V)."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * sm_scale  # [R, bk]
    s = jnp.where(mask, s, NEG_INF)
    m_prev = m_ref[h]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_ref[h] = l_ref[h] * corr + jnp.sum(p, axis=1, keepdims=True)
    pv = jax.lax.dot_general(
        p, load_v(), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    acc_ref[h] = acc_ref[h] * corr + pv
    m_ref[h] = m_new


def finalize(o_ref, acc_ref, l_ref) -> None:
    # rows that never accumulated (empty slots, empty windows): l stays 0,
    # clamped -> output 0
    l = jnp.maximum(l_ref[...], 1e-30)
    o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _decode_kernel(
    lengths_ref,  # scalar prefetch: [B] int32
    q_ref,  # [1, kvH, gp, hd]
    k_ref, v_ref,  # [1, bk, kvH, hd]
    o_ref,  # [1, kvH, gp, hd]
    acc_ref, m_ref, l_ref,  # VMEM scratch (scratch_shapes)
    *,
    block_k: int,
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
        shape = (q_ref.shape[2], block_k)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        tile_update(
            q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref, kpos < length,
            sm_scale,
        )

    @pl.when(ki == nk - 1)
    def _finalize():
        finalize(o_ref, acc_ref, l_ref)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def decode_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    lengths: jax.Array,
    *,
    block_k: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """q: [B, H, hd]; k/v: [B, S_max, kvH, hd]; lengths: [B] int32 valid-KV
    counts.  Returns [B, H, hd].  Slots with ``lengths == 0`` return zeros."""
    b, h, hd = q.shape
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
    qr = q.reshape(b, kvh, group, hd)
    if gp != group:
        qr = jnp.pad(qr, ((0, 0), (0, 0), (0, gp - group), (0, 0)))
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
            pl.BlockSpec((1, kvh, gp, hd), q_map),
            pl.BlockSpec((1, block_k, kvh, hd), kv_map),
            pl.BlockSpec((1, block_k, kvh, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, kvh, gp, hd), q_map),
        scratch_shapes=scratch_shapes(kvh, gp, hd),
    )
    kernel = functools.partial(
        _decode_kernel, block_k=block_k, sm_scale=hd**-0.5
    )
    out = pl.pallas_call(
        kernel,
        name="decode_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, gp, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(lengths, qr, k, v)
    return out[:, :, :group].reshape(b, h, hd)
