"""Flash attention Pallas TPU kernel.

Layout: q [B, H, Sq, hd], k/v [B, H, Sk, hd] (heads pre-expanded for GQA).
Grid: (B*H, num_q_blocks, num_kv_blocks); the kv dimension is ``arbitrary``
(sequential) and accumulates the online softmax in VMEM scratch, writing the
output block on the final kv step — the canonical TPU flash schedule.

Block shapes default to (128, head_dim) q-tiles and (512, head_dim) kv-tiles:
q/k/v tiles plus fp32 accumulators stay well under ~2 MiB VMEM per core while
keeping the MXU matmul dims at multiples of 128 (hardware-aligned).

Forward only: the kernel has no custom VJP and ``jax.grad`` through a
``pallas_call`` fails.  ``ops.attention(impl="auto")`` selects it on TPU for
forward-only callers (monolithic prefill); ``make_train_step`` maps "auto"
to the XLA family and refuses ``impl="pallas"``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref,  # [1, bq, hd], [1, bk, hd]
    o_ref,  # [1, bq, hd]
    acc_ref, m_ref, l_ref,  # VMEM scratch: [bq, hd], [bq, 1], [bq, 1] (fp32)
    *,
    causal: bool,
    sm_scale: float,
    block_q: int,
    block_k: int,
    kv_len: int,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = qi * block_q
    k_start = ki * block_k

    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale  # [bq, bk]
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = kpos < kv_len
        if causal:
            mask = mask & (kpos <= qpos)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]  # [bq, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)  # [bq, bk]
        corr = jnp.exp(m_prev - m_new)  # [bq, 1]
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0].astype(jnp.float32)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        acc_ref[...] = acc_ref[...] * corr + pv
        m_ref[...] = m_new

    if causal:
        # kv blocks strictly above the causal diagonal contribute nothing;
        # skip their math entirely (the scheduler still visits the step).
        pl.when(k_start <= q_start + block_q - 1)(_compute)
    else:
        _compute()

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret")
)
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    block_q: int = 128,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """q: [B, H, Sq, hd]; k/v: [B, H, Sk, hd].  Returns [B, H, Sq, hd]."""
    b, h, sq, hd = q.shape
    sk = k.shape[2]
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    nq = (sq + block_q - 1) // block_q
    nk = (sk + block_k - 1) // block_k
    pad_q = nq * block_q - sq
    pad_k = nk * block_k - sk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    qf = q.reshape(b * h, nq * block_q, hd)
    kf = k.reshape(b * h, nk * block_k, hd)
    vf = v.reshape(b * h, nk * block_k, hd)

    kernel = functools.partial(
        _flash_kernel,
        causal=causal,
        sm_scale=hd**-0.5,
        block_q=block_q,
        block_k=block_k,
        kv_len=sk,
    )
    out = pl.pallas_call(
        kernel,
        name="flash_attention",
        grid=(b * h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, hd), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, hd), lambda bh, qi, ki: (bh, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, nq * block_q, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, hd), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, h, nq * block_q, hd)[:, :, :sq, :]
