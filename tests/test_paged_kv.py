"""Paged KV pool validation: allocator/radix invariants, paged-vs-dense
kernel equality on random ragged batches, engine-level byte-identical
generation (cold, prefix-hit, and speculative), capacity-based admission,
and the Principle-I memory accounting fix.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.configs.base import draft_config
from repro.kernels import ops
from repro.models import transformer as T
from repro.serving.engine import InferenceEngine, Request
from repro.serving.kv_pool import PageAllocError, PagePool, RadixCache

CFG = configs.smoke_config("qwen3-1.7b")
PARAMS = T.init_params(CFG, jax.random.PRNGKey(0))


# ---------------------------------------------------------------------------
# PagePool / RadixCache unit behavior
# ---------------------------------------------------------------------------


def test_pool_alloc_refcount_free():
    pool = PagePool(num_pages=6, page_size=4)
    assert pool.free_pages == 5  # sentinel page 0 excluded
    a = pool.alloc(3)
    assert len(a) == 3 and 0 not in a
    pool.incref(a[:1])
    assert pool.decref(a) == a[1:]  # a[0] still tree/slot-held
    assert pool.decref(a[:1]) == a[:1]
    assert pool.free_pages == 5
    assert pool.pages_for(9) == 3


def test_pool_reservations_gate_allocation():
    pool = PagePool(num_pages=5, page_size=4)
    pool.reserve(3)
    assert pool.available == 1
    pool.alloc(2, reserved=True)  # converts promise to pages
    assert pool.reserved == 1 and pool.free_pages == 2
    # exhaustion is a recoverable runtime condition (DESIGN.md §9), not a bug
    with pytest.raises(PageAllocError):
        pool.alloc(2)  # only 1 available (1 free page is still promised)
    pool.unreserve(1)
    assert pool.available == 2


def test_radix_match_insert_evict():
    pool = PagePool(num_pages=10, page_size=2)
    tree = RadixCache(pool)
    toks = [1, 2, 3, 4, 5, 6]
    pages = pool.alloc(3)
    tree.insert(toks, pages)  # tree increfs all three
    assert tree.pages_cached == 3
    assert tree.match(toks) == pages
    assert tree.match([1, 2, 3, 9]) == pages[:1]
    assert tree.match([9, 9]) == []
    # probe mode leaves counters alone
    h, m = tree.hits, tree.misses
    tree.match(toks, record=False)
    assert (tree.hits, tree.misses) == (h, m)
    # slot releases its refs; pages survive via the tree, then evict LRU
    pool.decref(pages)
    assert pool.free_pages == 10 - 1 - 3
    assert tree.evictable_pages() == 3
    assert tree.evict(2) == 2
    assert tree.match(toks) == pages[:1]  # deepest chunks evicted first
    assert tree.evict(5) == 1
    assert pool.free_pages == 9


def test_radix_never_shares_partial_pages():
    pool = PagePool(num_pages=8, page_size=4)
    tree = RadixCache(pool)
    pages = pool.alloc(1)
    tree.insert([1, 2, 3, 4, 5, 6], pages)  # only one FULL page
    assert tree.pages_cached == 1
    assert tree.match([1, 2, 3, 4, 5, 6, 7, 8]) == pages


# ---------------------------------------------------------------------------
# Paged kernels == dense kernels on random ragged batches
# ---------------------------------------------------------------------------


def _paged_from_dense(k, v, page, rng):
    """Scatter a dense [B, S, kvH, hd] cache into a randomly-permuted page
    pool + block tables (one sentinel-padded column, as the engine lays
    them out)."""
    b, s, kvh, hd = k.shape
    npages = s // page
    pool_n = 1 + b * npages
    perm = rng.permutation(np.arange(1, pool_n))
    bt = perm.reshape(b, npages)
    k_pool = np.zeros((pool_n, page, kvh, hd), np.float32)
    v_pool = np.zeros((pool_n, page, kvh, hd), np.float32)
    for i in range(b):
        for j in range(npages):
            k_pool[bt[i, j]] = np.asarray(k[i, j * page:(j + 1) * page])
            v_pool[bt[i, j]] = np.asarray(v[i, j * page:(j + 1) * page])
    bt = np.concatenate([bt, np.zeros((b, 1), np.int64)], axis=1)
    return (jnp.asarray(k_pool), jnp.asarray(v_pool),
            jnp.asarray(bt, jnp.int32))


def _rand_case(seed, b, h, kvh, s, hd, t=None):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    qs = (b, h, hd) if t is None else (b, t, h, hd)
    q = jax.random.normal(ks[0], qs, jnp.float32)
    k = jax.random.normal(ks[1], (b, s, kvh, hd), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, kvh, hd), jnp.float32)
    return q, k, v


def _ragged_lengths(rng, b, s):
    """Random per-slot lengths biased toward the boundary cases (empty
    slot, single token, page-edge, full)."""
    picks = [0, 1, s, max(s - 1, 0)] + list(rng.randint(0, s + 1, size=b))
    return jnp.asarray([picks[rng.randint(0, len(picks))] for _ in range(b)],
                       jnp.int32)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_paged_decode_matches_dense_kernel(impl):
    """Property (seeded sweep): paged decode attention is element-wise equal
    to the dense kernel on random ragged batches with randomly-permuted
    physical page placement."""
    geoms = [(4, 2, 16), (8, 2, 32), (4, 4, 16), (2, 1, 16)]
    for seed in range(12):
        rng = np.random.RandomState(seed)
        h, kvh, hd = geoms[seed % len(geoms)]
        b = rng.randint(1, 5)
        page = int(rng.choice([8, 16]))
        s = page * rng.randint(2, 6)
        q, k, v = _rand_case(seed, b, h, kvh, s, hd)
        lengths = _ragged_lengths(rng, b, s)
        k_pool, v_pool, bt = _paged_from_dense(k, v, page, rng)
        ref = ops.decode_attention(q, k, v, lengths, impl="xla")
        out = ops.paged_decode_attention(
            q, k_pool, v_pool, bt, lengths, impl=impl
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5,
            err_msg=f"seed={seed} b={b} page={page} s={s} "
                    f"lengths={np.asarray(lengths)}",
        )


def _share_prefix(k, v, bt, pages, page):
    """Make slot 1's first ``pages`` logical pages the very physical pages
    of slot 0 (a radix-shared prefix): the dense cache copies slot 0's
    rows, the block table points at slot 0's pages."""
    n = pages * page
    k = k.at[1, :n].set(k[0, :n])
    v = v.at[1, :n].set(v[0, :n])
    bt = bt.at[1, :pages].set(bt[0, :pages])
    return k, v, bt


# Lengths around the kernel's block of 8 16-token pages, in a table of 11
# logical pages (not a multiple of 8): empty, one token, one page, one past
# it, a block edge, one past it, mid second block, the full 11 pages.
BLOCK_EDGE_LENGTHS = [0, 1, 16, 17, 128, 129, 150, 176]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("layout", ["shuffled", "prefix_shared"])
@pytest.mark.parametrize("h,kvh", [(4, 4), (4, 2)], ids=["mha", "gqa2"])
def test_paged_decode_blocks_match_dense(h, kvh, layout, dtype):
    """The paged decode kernel, several pages a block and a loop bounded by
    each slot's length, equals dense XLA attention at every block edge:
    W-1 = 11 pages is not a multiple of the 8 pages a block, physical pages
    are shuffled, and (``prefix_shared``) two tables name the same pages.
    bfloat16 pages take the kernel's two-heads-a-word loads; the reference
    then attends the same rounded values in float32, and the kernel's
    output is rounded to bfloat16 (2**-8 relative)."""
    from repro.kernels.paged_decode_attention import pages_per_block

    page, hd, nk = 16, 16, 11
    dt = jnp.dtype(dtype)
    assert pages_per_block(page, kvh, hd, dt.itemsize, nk) == 8
    b, s = len(BLOCK_EDGE_LENGTHS), nk * page
    q, k, v = (x.astype(dt).astype(jnp.float32)
               for x in _rand_case(7, b, h, kvh, s, hd))
    rng = np.random.RandomState(7)
    k_pool, v_pool, bt = _paged_from_dense(k, v, page, rng)
    lengths = BLOCK_EDGE_LENGTHS
    if layout == "prefix_shared":
        # slot 1 (one token long in the shuffled case) reads 9 shared pages
        k, v, bt = _share_prefix(k, v, bt, pages=9, page=page)
        lengths = [0, 140] + lengths[2:]
    lengths = jnp.asarray(lengths, jnp.int32)
    ref = ops.decode_attention(q, k, v, lengths, impl="xla")
    out = ops.paged_decode_attention(
        q.astype(dt), k_pool.astype(dt), v_pool.astype(dt), bt, lengths,
        impl="pallas",
    )
    tol = 2e-5 if dt == jnp.float32 else 1e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref), rtol=tol, atol=tol,
        err_msg=f"lengths={np.asarray(lengths)}",
    )
    assert not np.asarray(out[0]).any()  # the empty slot writes zeros


@pytest.mark.parametrize("shape,want", [
    ((16, 8, 128, 2, 128), 8),  # qwen3-1.7b: 0.5 MiB of K and V a buffer
    ((16, 16, 128, 2, 128), 8),  # OLMo-1B: 1 MiB a buffer
    ((16, 8, 128, 2, 5), 5),  # a table narrower than a block
    ((8, 8, 128, 2, 160), 16),  # 8-token pages: 16 of them make 128 tokens
    ((16, 64, 256, 4, 128), 2),  # wide f32 pages halve the block to fit
])
def test_pages_per_block_rule(shape, want):
    """Pages a block come from the shapes alone: at least 128 tokens, halved
    while the double-buffered K and V pass the VMEM budget, at most the
    table's logical pages."""
    from repro.kernels.paged_decode_attention import pages_per_block

    assert pages_per_block(*shape) == want


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_paged_verify_matches_dense_kernel(impl):
    """Property (seeded sweep): paged chunk-verify attention equals the
    dense verify kernel on random ragged batches, including chunks larger
    than a slot's causal window."""
    geoms = [(4, 2, 16), (4, 4, 16), (2, 1, 32)]
    for seed in range(10):
        rng = np.random.RandomState(1000 + seed)
        h, kvh, hd = geoms[seed % len(geoms)]
        b = rng.randint(1, 4)
        t = rng.randint(1, 5)
        page = int(rng.choice([8, 16]))
        s = page * rng.randint(2, 5)
        q, k, v = _rand_case(seed, b, h, kvh, s, hd, t=t)
        lengths = _ragged_lengths(rng, b, s)
        k_pool, v_pool, bt = _paged_from_dense(k, v, page, rng)
        ref = ops.verify_attention(q, k, v, lengths, impl="xla")
        out = ops.paged_verify_attention(
            q, k_pool, v_pool, bt, lengths, impl=impl
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5,
            err_msg=f"seed={seed} b={b} t={t} page={page} s={s} "
                    f"lengths={np.asarray(lengths)}",
        )


# ---------------------------------------------------------------------------
# Engine equivalence: paged layout is invisible in the token stream
# ---------------------------------------------------------------------------


def _drain(engine, k=4, guard=200):
    while engine.num_active and guard:
        engine.decode_loop(k)
        guard -= 1
    assert engine.num_active == 0


def _run_engine(paged, cases, **kw):
    eng = InferenceEngine(
        CFG, PARAMS, max_slots=3, max_seq=64,
        kv_page_size=None if paged else 0, **kw,
    )
    reqs = [Request(prompt=np.arange(1, n + 1), max_new_tokens=m)
            for n, m in cases]
    for r in reqs:
        assert eng.add_request(r)
    _drain(eng)
    return [r.generated for r in reqs], eng


def test_paged_engine_stream_equals_dense():
    cases = [(5, 12), (17, 7), (33, 40)]  # ragged; one hits the seq horizon
    gp, ep = _run_engine(True, cases)
    gd, _ = _run_engine(False, cases)
    assert gp == gd
    # full retirement releases every page except the radix-cached prefixes
    assert ep.pool.pages_in_use == ep.prefix_cache.pages_cached
    assert ep.pool.reserved == 0


def test_prefix_hit_skips_prefill_and_is_byte_identical():
    eng = InferenceEngine(CFG, PARAMS, max_slots=2, max_seq=64)
    prompt = np.arange(1, 40)  # 39 tokens -> 2 full pages (page=16) cacheable
    cold = Request(prompt=prompt, max_new_tokens=10)
    assert eng.add_request(cold)
    _drain(eng)
    assert eng.prefill_skipped_tokens == 0
    assert eng.prefix_cache.pages_cached == 2

    warm = Request(prompt=prompt, max_new_tokens=10)
    assert eng.add_request(warm)
    # the shared length ran zero prefill FLOPs (counter-verified)
    assert eng.prefill_skipped_tokens == 32
    assert eng.prefill_skip_fraction == pytest.approx(32 / 78)
    _drain(eng)
    assert warm.generated == cold.generated


def test_prefix_hit_shares_pages_physically():
    eng = InferenceEngine(CFG, PARAMS, max_slots=2, max_seq=64)
    prompt = np.arange(1, 40)
    assert eng.add_request(Request(prompt=prompt, max_new_tokens=4))
    shared_pages = eng._slot_pages[0][:2]
    assert eng.add_request(Request(prompt=prompt, max_new_tokens=4))
    # the second slot's first two logical pages ARE the first slot's
    assert eng._slot_pages[1][:2] == shared_pages
    assert all(eng.pool.refcount[p] == 3 for p in shared_pages)  # 2 slots + tree
    _drain(eng)
    assert all(eng.pool.refcount[p] == 1 for p in shared_pages)  # tree only


def test_partial_prefix_hit_prefills_only_suffix():
    eng = InferenceEngine(CFG, PARAMS, max_slots=2, max_seq=64)
    a = np.arange(1, 40)
    b = np.concatenate([a[:32], np.arange(100, 110)])  # diverges after 2 pages
    r_a = Request(prompt=a, max_new_tokens=6)
    assert eng.add_request(r_a)
    _drain(eng)
    r_b = Request(prompt=b, max_new_tokens=6)
    assert eng.add_request(r_b)
    assert eng.prefill_skipped_tokens == 32
    _drain(eng)
    # cross-check against a cold engine: the shared-prefix suffix prefill
    # must not change the stream
    cold = InferenceEngine(CFG, PARAMS, max_slots=2, max_seq=64)
    r_cold = Request(prompt=b, max_new_tokens=6)
    assert cold.add_request(r_cold)
    _drain(cold)
    assert r_b.generated == r_cold.generated


# ---------------------------------------------------------------------------
# Capacity-based admission (pool pages, not dense rows)
# ---------------------------------------------------------------------------


def test_admission_is_capacity_based_and_recovers():
    # 8 real pages of 16 tokens; each request needs ceil(24/16) = 2 pages
    eng = InferenceEngine(
        CFG, PARAMS, max_slots=8, max_seq=64, kv_pool_pages=9,
        enable_prefix_cache=False,
    )
    reqs = [Request(prompt=np.arange(1, 9), max_new_tokens=16)
            for _ in range(5)]
    admitted = [eng.add_request(r) for r in reqs]
    # 4 * 2 pages exhaust the pool although 4 more dense slots are free
    assert admitted == [True] * 4 + [False]
    assert not eng.can_admit(reqs[4])
    _drain(eng)
    assert eng.can_admit(reqs[4]) and eng.add_request(reqs[4])
    _drain(eng)


def test_admission_evicts_cached_prefixes_when_full():
    eng = InferenceEngine(
        CFG, PARAMS, max_slots=4, max_seq=64, kv_pool_pages=6,  # 5 real pages
    )
    warm = Request(prompt=np.arange(1, 33), max_new_tokens=2)  # 2 pages cached
    assert eng.add_request(warm)
    _drain(eng)
    assert eng.prefix_cache.pages_cached == 2
    assert eng.pool.available == 3
    # needs 4 pages: only admittable by evicting part of the cached prefix
    big = Request(prompt=np.arange(100, 140), max_new_tokens=24)
    assert eng.can_admit(big)
    assert eng.add_request(big)
    assert len(eng.prefix_cache.match(np.arange(1, 33), record=False)) < 2
    _drain(eng)


def test_paged_engine_fits_more_slots_at_equal_hbm():
    """The headline capacity claim: at the HBM of a 4-slot dense cache, the
    paged engine holds >= 2x the concurrent short requests."""
    max_seq = 64
    dense = InferenceEngine(CFG, PARAMS, max_slots=4, max_seq=max_seq,
                            kv_page_size=0)
    paged = InferenceEngine(
        CFG, PARAMS, max_slots=32, max_seq=max_seq,
        kv_pool_pages=4 * (max_seq // 16) + 1,  # == dense KV HBM
    )
    assert paged.kv_cache_bytes() <= dense.kv_cache_bytes() * 1.1

    def fill(eng):
        n = 0
        while True:
            r = Request(prompt=np.arange(1, 9), max_new_tokens=8)
            if not eng.add_request(r):
                return n
            n += 1

    dense_slots, paged_slots = fill(dense), fill(paged)
    assert dense_slots == 4
    assert paged_slots >= 2 * dense_slots


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_paged_verify_lengths_past_capacity_keep_causal_bound(impl):
    """Regression: suffix prefill passes lengths = shared + T_bucket, which
    can exceed the pool's logical capacity when the bucket's pad tail
    spills past max_seq.  Clamping lengths inside the kernel would shift
    the causal bound (length - chunk + t_row) and silently mask real
    prefix positions for the real rows."""
    h, kvh, hd, page, npages, t = 4, 2, 16, 16, 4, 16
    s = page * npages  # logical capacity 64
    q, k, v = _rand_case(7, 2, h, kvh, s, hd, t=t)
    # lengths exceed capacity by part of the chunk's pad tail; real rows
    # (small t) still attend only in-capacity positions
    lengths = jnp.asarray([s + 8, s + 3], jnp.int32)
    k_pool, v_pool, bt = _paged_from_dense(k, v, page, np.random.RandomState(7))
    ref = ops.verify_attention(q, k, v, lengths, impl="xla")
    out = ops.paged_verify_attention(q, k_pool, v_pool, bt, lengths, impl=impl)
    # rows whose causal window fits the capacity must match exactly
    for b in range(2):
        real_rows = s - 1 - (int(lengths[b]) - t)  # bound <= s-1 for t < this
        np.testing.assert_allclose(
            np.asarray(out[b, :real_rows]), np.asarray(ref[b, :real_rows]),
            rtol=2e-5, atol=2e-5,
        )


def test_spec_engine_admits_on_unaligned_max_seq():
    """Regression: the paged bucket cap (max_seq rounded up to a page
    multiple) must not leak into the dense draft cache's prefill, whose
    K/V pad width is exactly max_seq."""
    eng = InferenceEngine(
        CFG, PARAMS, max_slots=1, max_seq=200,
        draft_cfg=DCFG, draft_params=DPARAMS,
    )
    r = Request(prompt=np.arange(1, 151), max_new_tokens=4)
    assert eng.add_request(r)
    while eng.num_active:
        eng.spec_decode_loop(2, 2)
    assert len(r.generated) == 4


def test_unaligned_max_seq_buckets_stay_page_aligned():
    """Regression: a paged engine whose max_seq is not a page multiple must
    still admit prompts whose bucket clamps at max_seq (the clamp rounds up
    to a page multiple; positions past max_seq are pad)."""
    paged = InferenceEngine(CFG, PARAMS, max_slots=2, max_seq=200)
    dense = InferenceEngine(CFG, PARAMS, max_slots=2, max_seq=200,
                            kv_page_size=0)
    rp = Request(prompt=np.arange(1, 151), max_new_tokens=5)
    rd = Request(prompt=np.arange(1, 151), max_new_tokens=5)
    assert paged.add_request(rp) and dense.add_request(rd)
    _drain(paged)
    _drain(dense)
    assert rp.generated == rd.generated


def test_request_fits_flags_structural_impossibility():
    eng = InferenceEngine(
        CFG, PARAMS, max_slots=4, max_seq=64, kv_pool_pages=3,  # 2 real pages
    )
    assert not eng.request_fits(
        Request(prompt=np.arange(100), max_new_tokens=1)  # prompt > max_seq
    )
    assert not eng.request_fits(
        Request(prompt=np.arange(8), max_new_tokens=60)  # 4 pages > pool
    )
    ok = Request(prompt=np.arange(8), max_new_tokens=8)  # 1 page
    assert eng.request_fits(ok) and eng.can_admit(ok)


# ---------------------------------------------------------------------------
# Speculative decoding on the paged cache
# ---------------------------------------------------------------------------


DCFG = draft_config(CFG)
DPARAMS = T.init_params(DCFG, jax.random.PRNGKey(5))


def test_spec_greedy_paged_identical_with_rollback():
    plain = InferenceEngine(CFG, PARAMS, max_slots=2, max_seq=64,
                            compute_dtype=jnp.float32)
    spec = InferenceEngine(
        CFG, PARAMS, max_slots=2, max_seq=64, compute_dtype=jnp.float32,
        draft_cfg=DCFG, draft_params=DPARAMS,
    )
    assert plain.paged and spec.paged
    cases = [(5, 11), (18, 9)]
    rp = [Request(prompt=np.arange(1, n + 1), max_new_tokens=m)
          for n, m in cases]
    rs = [Request(prompt=np.arange(1, n + 1), max_new_tokens=m)
          for n, m in cases]
    for r in rp:
        assert plain.add_request(r)
    for r in rs:
        assert spec.add_request(r)
    _drain(plain)
    guard = 60
    while spec.num_active and guard:
        spec.spec_decode_loop(2, 2)
        guard -= 1
    assert [r.generated for r in rs] == [r.generated for r in rp]
    # random-init draft: ~every round rejects, so rollback page-trims ran
    assert spec.spec_drafted > 0 and spec.spec_acceptance_rate < 0.5
    assert spec.pool.reserved == 0
    assert spec.pool.pages_in_use == spec.prefix_cache.pages_cached


def test_retirement_resets_draft_index_on_all_paths():
    """Regression: plain decode_loop retirements (multi-step and one-step
    loops) left the draft cache index stale on spec-enabled engines."""
    for path in ("loop", "single"):
        eng = InferenceEngine(
            CFG, PARAMS, max_slots=1, max_seq=64,
            draft_cfg=DCFG, draft_params=DPARAMS,
        )
        assert eng.add_request(
            Request(prompt=np.arange(1, 6), max_new_tokens=3)
        )
        guard = 20
        while eng.num_active and guard:
            eng.decode_loop(2 if path == "loop" else 1)
            guard -= 1
        assert int(np.asarray(eng.draft_cache["index"])[0]) == 0, path
        # slot reuse after the reset must still be exact
        plain = InferenceEngine(CFG, PARAMS, max_slots=1, max_seq=64)
        r_ref = Request(prompt=np.arange(3, 9), max_new_tokens=4)
        assert plain.add_request(r_ref)
        _drain(plain)
        r2 = Request(prompt=np.arange(3, 9), max_new_tokens=4)
        assert eng.add_request(r2)
        while eng.num_active:
            eng.spec_decode_loop(2, 2)
        assert r2.generated == r_ref.generated, path


# ---------------------------------------------------------------------------
# Principle-I memory accounting
# ---------------------------------------------------------------------------


def test_memory_bytes_counts_draft_and_pool():
    plain = InferenceEngine(CFG, PARAMS, max_slots=2, max_seq=64)
    spec = InferenceEngine(
        CFG, PARAMS, max_slots=2, max_seq=64,
        draft_cfg=DCFG, draft_params=DPARAMS,
    )
    leaf_bytes = lambda t: sum(
        x.size * x.dtype.itemsize for x in jax.tree.leaves(t)
    )
    assert plain.memory_bytes() == (
        leaf_bytes(plain.params) + leaf_bytes(plain.cache)
    )
    # the pool (inside cache) is accounted, and the draft side no longer
    # disappears from the capacity input
    assert spec.memory_bytes() == (
        leaf_bytes(spec.params) + leaf_bytes(spec.cache)
        + leaf_bytes(spec.draft_params) + leaf_bytes(spec.draft_cache)
    )
    assert spec.memory_bytes() > plain.memory_bytes()
    # the bf16 engine holds its serving copy, not the f32 tree: matrices
    # at half their f32 bytes, vectors as they are
    matrix_bytes = lambda t: sum(
        x.size * x.dtype.itemsize for x in jax.tree.leaves(t) if x.ndim > 1
    )
    assert 2 * matrix_bytes(plain.params) == matrix_bytes(PARAMS)
