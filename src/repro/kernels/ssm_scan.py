"""Mamba1 selective-scan Pallas TPU kernel (one chunk).

TPU adaptation of the CUDA selective-scan (DESIGN.md §2): the recurrent state
``h [d_inner, d_state]`` lives in VMEM scratch for the whole chunk, so HBM
traffic is only the chunk inputs/outputs — the XLA fallback materializes the
[B, Q, d_inner, d_state] state tensor in HBM, which is what makes the SSM
cells memory-bound (§Roofline).

Grid: (B, d_inner / block_d); time is a sequential ``fori_loop`` inside the
kernel (the recurrence is inherently serial in t, parallel in d_inner).
block_d defaults to 512 lanes: h scratch is 512*d_state fp32 (32 KiB at
d_state=16) and the per-step row ops are VPU-aligned (8x128 tiles).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssm_kernel(
    xi_ref, dt_ref,  # [1, Q, bd]
    b_ref, c_ref,  # [1, Q, ds]
    a_ref,  # [bd, ds]
    h0_ref,  # [1, bd, ds]
    y_ref,  # out [1, Q, bd]
    h_out_ref,  # out [1, bd, ds]
    h_scratch,  # VMEM [bd, ds] fp32
    *,
    chunk: int,
):
    h_scratch[...] = h0_ref[0].astype(jnp.float32)
    a_mat = a_ref[...].astype(jnp.float32)  # A (negative) [bd, ds]

    def step(t, _):
        dt_t = dt_ref[0, t, :].astype(jnp.float32)  # [bd]
        xi_t = xi_ref[0, t, :].astype(jnp.float32)  # [bd]
        b_t = b_ref[0, t, :].astype(jnp.float32)  # [ds]
        c_t = c_ref[0, t, :].astype(jnp.float32)  # [ds]
        decay = jnp.exp(dt_t[:, None] * a_mat)  # [bd, ds]
        h = decay * h_scratch[...] + (dt_t * xi_t)[:, None] * b_t[None, :]
        h_scratch[...] = h
        y_ref[0, t, :] = (h @ c_t).astype(y_ref.dtype)  # [bd]
        return 0

    jax.lax.fori_loop(0, chunk, step, 0)
    h_out_ref[0] = h_scratch[...].astype(h_out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def ssm_scan_chunk(
    xi: jax.Array,
    dt: jax.Array,
    B_: jax.Array,
    C_: jax.Array,
    A: jax.Array,
    h0: jax.Array,
    *,
    block_d: int = 512,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """One chunk of the selective scan.

    xi/dt: [B, Q, di]; B_/C_: [B, Q, ds]; A: [di, ds]; h0: [B, di, ds].
    Returns (y [B, Q, di], h_final [B, di, ds]); fp32 in/out.
    """
    b, q, di = xi.shape
    ds = B_.shape[-1]
    block_d = min(block_d, di)
    assert di % block_d == 0, (di, block_d)
    nd = di // block_d

    kernel = functools.partial(_ssm_kernel, chunk=q)
    y, h_fin = pl.pallas_call(
        kernel,
        name="ssm_scan_chunk",
        grid=(b, nd),
        in_specs=[
            pl.BlockSpec((1, q, block_d), lambda bi, d: (bi, 0, d)),
            pl.BlockSpec((1, q, block_d), lambda bi, d: (bi, 0, d)),
            pl.BlockSpec((1, q, ds), lambda bi, d: (bi, 0, 0)),
            pl.BlockSpec((1, q, ds), lambda bi, d: (bi, 0, 0)),
            pl.BlockSpec((block_d, ds), lambda bi, d: (d, 0)),
            pl.BlockSpec((1, block_d, ds), lambda bi, d: (bi, d, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, q, block_d), lambda bi, d: (bi, 0, d)),
            pl.BlockSpec((1, block_d, ds), lambda bi, d: (bi, d, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, q, di), jnp.float32),
            jax.ShapeDtypeStruct((b, di, ds), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_d, ds), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")
        ),
        interpret=interpret,
    )(xi, dt, B_, C_, A, h0)
    return y, h_fin
