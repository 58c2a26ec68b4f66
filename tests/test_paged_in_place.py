"""The paged cache's layers write their K/V into the one pool the layer scan
carries whole (``transformer._scan_paged_layers``): chunked prefill then
fused decode on a paged cache whose pages are scattered over the pool give
the dense cache's tokens, and every layer's pages hold the dense cache's
rows of that layer, with the Pallas kernels (interpreted) and the XLA path.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import transformer as T

CFG = configs.smoke_config("qwen3-1.7b")
PARAMS = T.init_params(CFG, jax.random.PRNGKey(0))
SLOTS, PAGE, PPS = 3, 8, 6  # 3 slots of 6 pages of 8 tokens
MAX_SEQ = PAGE * PPS
CHUNK, K = 8, 5


def _caches(seed):
    """A dense cache and a paged one whose slots own shuffled pages of a
    pool with two pages no slot owns (page 0, the sentinel that takes the
    pad rows' writes, and one spare), both empty."""
    rng = np.random.RandomState(seed)
    num_pages = SLOTS * PPS + 2
    pages = rng.permutation(np.arange(1, num_pages))[: SLOTS * PPS]
    paged = T.init_paged_cache(CFG, SLOTS, num_pages, PAGE, PPS, jnp.float32)
    tables = np.zeros((SLOTS, PPS + 1), np.int32)
    tables[:, :PPS] = pages.reshape(SLOTS, PPS)
    paged["block_tables"] = jnp.asarray(tables)
    dense = T.init_cache(CFG, SLOTS, MAX_SEQ, jnp.float32)
    dense["index"] = jnp.zeros((SLOTS,), jnp.int32)
    return dense, paged, tables


def _serve(cache, impl, prompts, lens):
    """Prefill ``prompts`` [SLOTS, 2 * CHUNK] a chunk at a time (``lens``
    real tokens a slot, ragged), then K fused decode microsteps."""
    prefill = jax.jit(lambda c, toks, n: T.prefill_chunks_into_slots(
        CFG, PARAMS, toks, n, c, compute_dtype=jnp.float32, attn_impl=impl))
    for j in range(2):
        n = np.clip(lens - j * CHUNK, 0, CHUNK).astype(np.int32)
        first, cache = prefill(cache, jnp.asarray(prompts[:, j * CHUNK:(j + 1) * CHUNK]),
                               jnp.asarray(n))
    tokens, cache, _, toks_seq, _, bad = jax.jit(lambda c, t: T.decode_loop(
        CFG, PARAMS, t, c, jnp.full((SLOTS,), K, jnp.int32), k=K,
        max_seq=MAX_SEQ, compute_dtype=jnp.float32, attn_impl=impl))(cache, first)
    assert not np.asarray(bad).any()
    return np.asarray(first), np.asarray(toks_seq), cache


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_paged_layers_write_in_place_and_match_dense(impl):
    dense, paged, tables = _caches(11)
    rng = np.random.RandomState(12)
    prompts = rng.randint(1, CFG.vocab_size, (SLOTS, 2 * CHUNK)).astype(np.int32)
    lens = np.asarray([2 * CHUNK, CHUNK + 3, 5])
    first_d, seq_d, dense = _serve(dense, impl, prompts, lens)
    first_p, seq_p, paged = _serve(paged, impl, prompts, lens)
    np.testing.assert_array_equal(first_p, first_d)
    np.testing.assert_array_equal(seq_p, seq_d)
    np.testing.assert_array_equal(np.asarray(paged["index"]), lens + K)
    for name in ("k", "v"):
        pool = np.asarray(paged["layers"][name])  # [L, P, page, kvH, hd]
        rows = np.asarray(dense["layers"][name])  # [L, B, S, kvH, hd]
        for b in range(SLOTS):
            n = lens[b] + K
            got = pool[:, tables[b, :PPS]].reshape(CFG.num_layers, MAX_SEQ,
                                                   *pool.shape[3:])
            np.testing.assert_allclose(got[:, :n], rows[:, b, :n],
                                       rtol=1e-5, atol=1e-5)
        # no write strayed onto the spare page, in any layer
        spare = np.setdiff1d(np.arange(1, pool.shape[1]), tables[:, :PPS])
        assert spare.size == 1 and not pool[:, spare].any()
