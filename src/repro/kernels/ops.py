"""Jit'd dispatch wrappers over the Pallas kernels with XLA fallbacks.

``impl`` semantics of the serving kernels (decode / verify / tree verify /
chunked prefill, dense and paged):
  * "auto"   -- the Pallas kernel on TPU, the XLA fallback elsewhere
  * "xla"    -- the masked dense XLA fallback (the kernel's oracle)
  * "pallas" -- the Pallas kernel; compiled for the chip on TPU, run in
                the Pallas interpreter elsewhere (tests only: the
                interpreter does not apply the TPU lowering rules, which
                tests/test_tpu_compile.py checks against a described v5e)

Full-sequence ``attention`` has the same "auto", but its Pallas flash
kernel is forward only (no VJP): the train step asks for "xla_auto", the
XLA family on every backend.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_FLASH_SEQ_THRESHOLD = 8192


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    impl: str = "auto",
) -> jax.Array:
    """q: [B, Sq, H, hd]; k/v: [B, Sk, kvH, hd].  Returns [B, Sq, H, hd].

    ``impl``:
      * "auto"      -- "pallas" on TPU, "xla_auto" elsewhere
      * "xla_auto"  -- "xla", or "xla_flash" from ``_FLASH_SEQ_THRESHOLD``
                       query rows, on every backend (differentiable)
      * "xla"       -- plain einsum attention
      * "xla_flash" -- lax.scan blocked online softmax (no S^2 buffer)
      * "pallas"    -- the Pallas flash kernel, forward only: it has no
                       VJP, so a training step must not select it
    """
    from repro.models import layers as L

    if impl == "auto":
        impl = "pallas" if _on_tpu() else "xla_auto"
    if impl == "xla_auto":
        impl = "xla_flash" if q.shape[1] >= _FLASH_SEQ_THRESHOLD else "xla"

    if impl == "xla":
        return L.attention_xla(q, k, v, causal=causal)
    if impl == "xla_flash":
        return L.attention_xla_flash(q, k, v, causal=causal)
    if impl == "pallas":
        from repro.kernels.flash_attention import flash_attention

        qh = q.shape[2]
        kk = L._repeat_kv(k, qh)
        vv = L._repeat_kv(v, qh)
        out = flash_attention(
            q.transpose(0, 2, 1, 3),
            kk.transpose(0, 2, 1, 3),
            vv.transpose(0, 2, 1, 3),
            causal=causal,
            interpret=not _on_tpu(),
        )
        return out.transpose(0, 2, 1, 3)
    raise ValueError(f"unknown attention impl {impl!r}")


def decode_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    lengths: jax.Array,
    *,
    impl: str = "auto",
) -> jax.Array:
    """Single-token decode attention over a ragged KV cache.

    q: [B, H, hd]; k/v_cache: [B, S_max, kvH, hd]; lengths: [B] int32 valid-KV
    counts (0 == empty slot -> zero output).  Returns [B, H, hd].

    ``impl`` as in the module docstring; "xla" is length-masked dense
    attention over S_max.
    """
    from repro.models import layers as L

    if impl == "auto":
        impl = "pallas" if _on_tpu() else "xla"
    if impl == "xla":
        s_max = k_cache.shape[1]
        length_mask = jnp.arange(s_max)[None, :] < lengths[:, None]
        out = L.attention_xla(
            q[:, None],
            k_cache.astype(q.dtype),
            v_cache.astype(q.dtype),
            causal=False,
            length_mask=length_mask,
        )[:, 0]
        # empty slots are all-masked -> uniform softmax garbage; zero them to
        # match the kernel's defined output
        return jnp.where(lengths[:, None, None] > 0, out, 0.0)
    if impl == "pallas":
        from repro.kernels.decode_attention import decode_attention as _kernel

        return _kernel(
            q,
            k_cache.astype(q.dtype),
            v_cache.astype(q.dtype),
            lengths,
            interpret=not _on_tpu(),
        )
    raise ValueError(f"unknown decode attention impl {impl!r}")


def verify_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    lengths: jax.Array,
    *,
    impl: str = "auto",
) -> jax.Array:
    """Chunk-verify attention over a ragged KV cache (speculative decoding).

    q: [B, T, H, hd] — the T = gamma+1 chunk queries per slot; k/v_cache:
    [B, S_max, kvH, hd] with the chunk's own K/V already written at positions
    ``lengths - T .. lengths - 1``; lengths: [B] int32 valid-KV counts
    *including* the chunk (0 == empty slot -> zero output).  Chunk query t
    attends to ``kpos <= lengths - T + t`` — the prefix plus the chunk's own
    causal triangle.  Returns [B, T, H, hd].

    ``impl`` as in the module docstring; "xla" is chunk-causal
    length-masked dense attention over S_max.
    """
    from repro.models import layers as L

    if impl == "auto":
        impl = "pallas" if _on_tpu() else "xla"
    if impl == "xla":
        b, t, h, hd = q.shape
        s_max = k_cache.shape[1]
        kk = L._repeat_kv(k_cache.astype(q.dtype), h)
        vv = L._repeat_kv(v_cache.astype(q.dtype), h)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, kk).astype(jnp.float32)
        scores = scores * hd**-0.5
        kpos = jnp.arange(s_max)
        bound = (lengths - t)[:, None] + jnp.arange(t)[None, :]  # [B, T]
        mask = kpos[None, None, :] <= bound[:, :, None]  # [B, T, S_max]
        scores = jnp.where(mask[:, None], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        out = jnp.einsum("bhqk,bkhd->bqhd", probs, vv)
        # all-masked rows (empty slots, and chunk rows whose causal window
        # is empty when lengths < T) are uniform softmax garbage; zero them
        # to match the kernel's defined output
        return jnp.where(bound[:, :, None, None] >= 0, out, 0.0)
    if impl == "pallas":
        from repro.kernels.verify_attention import verify_attention as _kernel

        return _kernel(
            q,
            k_cache.astype(q.dtype),
            v_cache.astype(q.dtype),
            lengths,
            interpret=not _on_tpu(),
        )
    raise ValueError(f"unknown verify attention impl {impl!r}")


def tree_verify_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    lengths: jax.Array,
    anc: jax.Array,
    *,
    impl: str = "auto",
) -> jax.Array:
    """Tree-verify attention over a ragged KV cache (multi-candidate
    speculative decoding).

    q: [B, N, H, hd] — one query per packed-tree node, the node's K/V
    already written at position ``lengths - N + node``; k/v_cache:
    [B, S_max, kvH, hd]; lengths: [B] int32 valid-KV counts *including*
    the N tree positions; anc: [B, N] int32 ancestor bitmasks (bit i of
    anc[b, j] = node i visible from node j; self bit set).  Node j attends
    the committed prefix ``kpos < lengths - N`` plus the intra-chunk
    positions its bitmask admits.  A linear-chain anc reproduces
    ``verify_attention`` exactly.  Returns [B, N, H, hd].

    ``impl`` as in the module docstring; "xla" is ancestor-masked dense
    attention over S_max.
    """
    from repro.models import layers as L

    if impl == "auto":
        impl = "pallas" if _on_tpu() else "xla"
    if impl == "xla":
        b, t, h, hd = q.shape
        s_max = k_cache.shape[1]
        kk = L._repeat_kv(k_cache.astype(q.dtype), h)
        vv = L._repeat_kv(v_cache.astype(q.dtype), h)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, kk).astype(jnp.float32)
        scores = scores * hd**-0.5
        kpos = jnp.arange(s_max)[None, :]  # [1, S]
        base = (lengths - t)[:, None]  # [B, 1]
        prefix = kpos < base  # [B, S]
        jpos = kpos - base  # [B, S] intra-chunk node index of each key
        in_chunk = (jpos >= 0) & (jpos < t)
        bits = (anc.astype(jnp.int32)[:, :, None]
                >> jnp.clip(jpos, 0, 31)[:, None, :]) & 1  # [B, N, S]
        mask = prefix[:, None, :] | (in_chunk[:, None, :] & (bits == 1))
        scores = jnp.where(mask[:, None], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        out = jnp.einsum("bhqk,bkhd->bqhd", probs, vv)
        # rows with an empty visibility set (empty slots, lengths < N) are
        # uniform softmax garbage; zero them to match the kernel
        any_vis = mask.any(axis=-1)  # [B, N]
        return jnp.where(any_vis[:, :, None, None], out, 0.0)
    if impl == "pallas":
        from repro.kernels.tree_verify_attention import (
            tree_verify_attention as _kernel,
        )

        return _kernel(
            q,
            k_cache.astype(q.dtype),
            v_cache.astype(q.dtype),
            lengths,
            anc,
            interpret=not _on_tpu(),
        )
    raise ValueError(f"unknown tree verify attention impl {impl!r}")


def prefill_chunk_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    starts: jax.Array,
    chunk_lens: jax.Array,
    *,
    impl: str = "auto",
) -> jax.Array:
    """Ragged chunked-prefill attention over a dense KV cache.

    q: [B, C, H, hd] — one fixed-width prefill chunk per slot; k/v_cache:
    [B, S_max, kvH, hd] with the chunk's *real* K/V already written at
    positions ``starts .. starts + chunk_lens - 1``; starts: [B] int32
    per-slot prefill progress (KV entries before the chunk); chunk_lens:
    [B] int32 real tokens per chunk (ragged; 0 == frozen slot).  Chunk
    query t attends ``kpos <= starts + t`` — the previously-written prefix
    plus the chunk's own causal triangle.  Returns [B, C, H, hd]; rows
    ``t >= chunk_lens`` return zeros.

    ``impl`` as in the module docstring; "xla" is chunk-causal masked
    dense attention over S_max.
    """
    from repro.models import layers as L

    if impl == "auto":
        impl = "pallas" if _on_tpu() else "xla"
    if impl == "xla":
        b, c, h, hd = q.shape
        s_max = k_cache.shape[1]
        kk = L._repeat_kv(k_cache.astype(q.dtype), h)
        vv = L._repeat_kv(v_cache.astype(q.dtype), h)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, kk).astype(jnp.float32)
        scores = scores * hd**-0.5
        kpos = jnp.arange(s_max)
        bound = starts[:, None] + jnp.arange(c)[None, :]  # [B, C]
        valid = jnp.arange(c)[None, :] < chunk_lens[:, None]  # [B, C]
        mask = (kpos[None, None, :] <= bound[:, :, None]) & valid[:, :, None]
        scores = jnp.where(mask[:, None], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        out = jnp.einsum("bhqk,bkhd->bqhd", probs, vv)
        # pad rows (t >= chunk_lens, frozen slots included) are uniform
        # softmax garbage; zero them to match the kernel's defined output
        return jnp.where(valid[:, :, None, None], out, 0.0)
    if impl == "pallas":
        from repro.kernels.prefill_attention import (
            prefill_attention as _kernel,
        )

        return _kernel(
            q,
            k_cache.astype(q.dtype),
            v_cache.astype(q.dtype),
            starts,
            chunk_lens,
            interpret=not _on_tpu(),
        )
    raise ValueError(f"unknown prefill chunk attention impl {impl!r}")


def _gather_pages(pool: jax.Array, block_tables: jax.Array) -> jax.Array:
    """Materialize a paged pool into its per-slot dense layout.

    pool: [P, page, kvH, hd]; block_tables: [B, W] int32 whose LAST column
    is the overflow sentinel (never holds live KV; ``lengths <= (W-1) *
    page`` — see ``transformer.init_paged_cache``), so only W-1 columns are
    gathered and the fallback's attention width matches the dense layout
    exactly.  Returns [B, (W-1) * page, kvH, hd]; positions past a slot's
    length hold sentinel/stale garbage, which the caller masks by length
    exactly as in the dense path."""
    b, w = block_tables.shape
    page, kvh, hd = pool.shape[1:]
    return pool[block_tables[:, :-1]].reshape(b, (w - 1) * page, kvh, hd)


def paged_decode_attention(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    block_tables: jax.Array,
    lengths: jax.Array,
    *,
    impl: str = "auto",
) -> jax.Array:
    """Single-token decode attention over the paged KV pool.

    q: [B, H, hd]; k/v_pool: [P, page, kvH, hd] physical pages shared across
    slots; block_tables: [B, W] int32 per-slot logical->physical page map
    (unused entries hold the sentinel page 0); lengths: [B] int32 valid-KV
    counts (0 == empty slot -> zero output).  Returns [B, H, hd].

    ``impl`` as in the module docstring; "xla" gathers the pages dense,
    then runs length-masked attention.
    """
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "xla"
    if impl == "xla":
        return decode_attention(
            q,
            _gather_pages(k_pool, block_tables),
            _gather_pages(v_pool, block_tables),
            lengths,
            impl="xla",
        )
    if impl == "pallas":
        from repro.kernels.paged_decode_attention import (
            paged_decode_attention as _kernel,
        )

        return _kernel(
            q,
            k_pool.astype(q.dtype),
            v_pool.astype(q.dtype),
            block_tables,
            lengths,
            interpret=not _on_tpu(),
        )
    raise ValueError(f"unknown paged decode attention impl {impl!r}")


def paged_verify_attention(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    block_tables: jax.Array,
    lengths: jax.Array,
    *,
    impl: str = "auto",
) -> jax.Array:
    """Chunk-verify attention over the paged KV pool (speculative decoding).

    q: [B, T, H, hd] — the T = gamma+1 chunk queries per slot, whose own K/V
    has already been scattered into the slot's pages at logical positions
    ``lengths - T .. lengths - 1``; k/v_pool: [P, page, kvH, hd];
    block_tables: [B, W] int32; lengths: [B] int32 valid-KV counts
    *including* the chunk.  Returns [B, T, H, hd].

    ``impl``: as in ``paged_decode_attention``.
    """
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "xla"
    if impl == "xla":
        return verify_attention(
            q,
            _gather_pages(k_pool, block_tables),
            _gather_pages(v_pool, block_tables),
            lengths,
            impl="xla",
        )
    if impl == "pallas":
        from repro.kernels.paged_verify_attention import (
            paged_verify_attention as _kernel,
        )

        return _kernel(
            q,
            k_pool.astype(q.dtype),
            v_pool.astype(q.dtype),
            block_tables,
            lengths,
            interpret=not _on_tpu(),
        )
    raise ValueError(f"unknown paged verify attention impl {impl!r}")


def paged_tree_verify_attention(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    block_tables: jax.Array,
    lengths: jax.Array,
    anc: jax.Array,
    *,
    impl: str = "auto",
) -> jax.Array:
    """Tree-verify attention over the paged KV pool (multi-candidate
    speculative decoding).

    q: [B, N, H, hd] — one query per packed-tree node, whose K/V has
    already been scattered into the slot's pages at logical position
    ``lengths - N + node``; k/v_pool: [P, page, kvH, hd]; block_tables:
    [B, W] int32; lengths: [B] int32 *including* the N tree positions;
    anc: [B, N] int32 ancestor bitmasks.  Returns [B, N, H, hd].

    ``impl``: as in ``paged_decode_attention``.
    """
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "xla"
    if impl == "xla":
        return tree_verify_attention(
            q,
            _gather_pages(k_pool, block_tables),
            _gather_pages(v_pool, block_tables),
            lengths,
            anc,
            impl="xla",
        )
    if impl == "pallas":
        from repro.kernels.paged_tree_verify_attention import (
            paged_tree_verify_attention as _kernel,
        )

        return _kernel(
            q,
            k_pool.astype(q.dtype),
            v_pool.astype(q.dtype),
            block_tables,
            lengths,
            anc,
            interpret=not _on_tpu(),
        )
    raise ValueError(f"unknown paged tree verify attention impl {impl!r}")


def paged_prefill_chunk_attention(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    block_tables: jax.Array,
    starts: jax.Array,
    chunk_lens: jax.Array,
    *,
    impl: str = "auto",
) -> jax.Array:
    """Ragged chunked-prefill attention over the paged KV pool.

    q: [B, C, H, hd] — one fixed-width prefill chunk per slot, whose real
    K/V has already been scattered into the slot's pages at positions
    ``starts .. starts + chunk_lens - 1``; k/v_pool: [P, page, kvH, hd];
    block_tables: [B, W] int32; starts / chunk_lens: [B] int32 as in
    ``prefill_chunk_attention``.  Returns [B, C, H, hd].

    ``impl``: as in ``paged_decode_attention``.
    """
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "xla"
    if impl == "xla":
        return prefill_chunk_attention(
            q,
            _gather_pages(k_pool, block_tables),
            _gather_pages(v_pool, block_tables),
            starts,
            chunk_lens,
            impl="xla",
        )
    if impl == "pallas":
        from repro.kernels.paged_prefill_attention import (
            paged_prefill_attention as _kernel,
        )

        return _kernel(
            q,
            k_pool.astype(q.dtype),
            v_pool.astype(q.dtype),
            block_tables,
            starts,
            chunk_lens,
            interpret=not _on_tpu(),
        )
    raise ValueError(f"unknown paged prefill chunk attention impl {impl!r}")


def ssm_scan_chunk(xi, dt, B_, C_, A, h0):
    """Pallas selective-scan chunk (the interpreter off the TPU)."""
    from repro.kernels.ssm_scan import ssm_scan_chunk as _kernel

    y, h = _kernel(
        xi.astype(jnp.float32),
        dt.astype(jnp.float32),
        B_.astype(jnp.float32),
        C_.astype(jnp.float32),
        A.astype(jnp.float32),
        h0.astype(jnp.float32),
        block_d=min(512, xi.shape[-1]),
        interpret=not _on_tpu(),
    )
    return y, h
