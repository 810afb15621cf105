"""Paged KV serving: kernel vs dense oracle, allocator invariants,
chunked-prefill interleaving, dense/paged engine parity, and the
prefix-sharing cache (refcounts, COW forks, LRU eviction)."""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cluster.request import Request
from repro.configs import get_config, reduced
from repro.kernels import ref
from repro.kernels.decode_attention import paged_flash_decode
from repro.models.transformer import init_params
from repro.serving.engine import ServeEngine
from repro.serving.paged_kv import (BlockTable, PagePool, PrefixCache,
                                    paged_supported)

KEY = jax.random.key(0)

# CI runs the kernel-parity tests twice: REPRO_PAGED_TEST_MODE=interpret
# exercises the Pallas kernel through its interpreter, =default goes
# through the ops wrapper's backend-default pick (the compiled kernel on
# TPU, the XLA gather oracle elsewhere) — the exact path serving uses.
_MODE = os.environ.get("REPRO_PAGED_TEST_MODE", "interpret")


def _kernel_paged_decode(q, kp, vp, tbl, lens):
    if _MODE == "default":
        from repro.kernels import ops
        return ops.paged_flash_decode(q, kp, vp, tbl, lens, interpret=None)
    return paged_flash_decode(q, kp, vp, tbl, lens, interpret=True)


# ---------------------------------------------------------------------------
# kernel: paged gather == dense attention over the same tokens
# ---------------------------------------------------------------------------


def _paged_case(B, KV, H, hd, ps, npages, lengths, seed=0):
    """Random pool + per-sequence scrambled page tables, plus the dense
    (B, KV, S, hd) cache holding the same tokens in order."""
    rng = np.random.default_rng(seed)
    pool_pages = 1 + B * npages                    # page 0 = null
    perm = 1 + rng.permutation(B * npages)         # scrambled, non-contig
    tables = perm.reshape(B, npages).astype(np.int32)
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (B, H, hd), jnp.float32)
    k_pages = jax.random.normal(ks[1], (pool_pages, KV, ps, hd), jnp.float32)
    v_pages = jax.random.normal(ks[2], (pool_pages, KV, ps, hd), jnp.float32)
    # dense view: logical position p of sequence b lives at
    # page tables[b, p // ps], slot p % ps
    kp, vp = np.asarray(k_pages), np.asarray(v_pages)
    S = npages * ps
    kd = np.stack([kp[tables[b]].transpose(1, 0, 2, 3).reshape(KV, S, hd)
                   for b in range(B)])
    vd = np.stack([vp[tables[b]].transpose(1, 0, 2, 3).reshape(KV, S, hd)
                   for b in range(B)])
    return (q, k_pages, v_pages, jnp.asarray(tables),
            jnp.asarray(lengths, jnp.int32), jnp.asarray(kd),
            jnp.asarray(vd))


def test_paged_kernel_matches_dense_ref_ragged_scrambled():
    """Kernel (mode per REPRO_PAGED_TEST_MODE) vs the dense decode
    oracle: ragged lengths (including a partial last page and a
    single-token sequence) through deliberately non-contiguous page
    tables."""
    B, KV, H, hd, ps, npages = 4, 2, 8, 64, 8, 6
    lengths = [1, 7, 23, 48]        # mid-page, full, ragged, exactly full
    q, kp, vp, tbl, lens, kd, vd = _paged_case(B, KV, H, hd, ps, npages,
                                               lengths)
    got = _kernel_paged_decode(q, kp, vp, tbl, lens)
    want = ref.decode_ref(q, kd, vd, lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-3, rtol=1e-3)
    # and the XLA serving-path oracle agrees with both
    ora = ref.paged_decode_ref(q, kp, vp, tbl, lens)
    np.testing.assert_allclose(np.asarray(ora), np.asarray(want),
                               atol=1e-3, rtol=1e-3)


def test_paged_kernel_ignores_unmapped_table_entries():
    """Entries past ceil(length/ps) may point anywhere (here: all at the
    null page) without changing the output."""
    B, KV, H, hd, ps, npages = 2, 2, 4, 32, 8, 4
    lengths = [9, 17]
    q, kp, vp, tbl, lens, kd, vd = _paged_case(B, KV, H, hd, ps, npages,
                                               lengths, seed=3)
    base = _kernel_paged_decode(q, kp, vp, tbl, lens)
    tbl2 = np.asarray(tbl).copy()
    for b, ln in enumerate(lengths):
        tbl2[b, -(-ln // ps):] = 0                 # null out unmapped tail
    got = _kernel_paged_decode(q, kp, vp, jnp.asarray(tbl2), lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(base),
                               atol=1e-6, rtol=1e-6)


# ---------------------------------------------------------------------------
# allocator invariants
# ---------------------------------------------------------------------------


def test_page_pool_alloc_free_recycle():
    pool = PagePool(num_pages=8, page_size=4)
    assert pool.num_free == 7                      # page 0 reserved
    a = pool.alloc(3)
    b = pool.alloc(2)
    assert 0 not in a + b                          # null page never leaves
    assert len(set(a + b)) == 5 and pool.num_free == 2
    pool.free(a)
    assert pool.num_free == 5
    c = pool.alloc(5)                              # recycles a's pages
    assert set(a) <= set(c) and pool.num_free == 0
    with pytest.raises(RuntimeError):
        pool.alloc(1)                              # exhausted
    pool.free(b)
    with pytest.raises(RuntimeError):
        pool.free(b)                               # double free
    pool.reset()
    assert pool.num_free == 7


def test_page_pool_pages_needed_and_block_table():
    pool = PagePool(num_pages=16, page_size=4)
    assert pool.pages_needed(0) == 0
    assert pool.pages_needed(1) == 1
    assert pool.pages_needed(4) == 1
    assert pool.pages_needed(5) == 2
    t = BlockTable(pool, tokens=9)                 # 3 pages
    assert len(t.pages) == 3
    row = t.row(6)
    assert row[:3] == t.pages and row[3:] == [0, 0, 0]
    with pytest.raises(ValueError):
        t.row(2)                                   # mapping doesn't fit
    free_before = pool.num_free
    t.release()
    assert pool.num_free == free_before + 3
    t.release()                                    # idempotent
    assert pool.num_free == free_before + 3


def test_paged_supported_gating():
    assert paged_supported(reduced(get_config("qwen2-1.5b")))
    assert not paged_supported(reduced(get_config("xlstm-350m")))


# ---------------------------------------------------------------------------
# engine: chunked prefill interleaving + capacity beyond kv_slots
# ---------------------------------------------------------------------------


def _paged_engine(**kw):
    cfg = dataclasses.replace(reduced(get_config("qwen2-1.5b")),
                              num_layers=2)
    params = init_params(KEY, cfg)
    kw.setdefault("max_len", 64)
    kw.setdefault("kv_slots", 2)
    kw.setdefault("page_size", 8)
    kw.setdefault("prefill_chunk", 8)
    return ServeEngine(cfg, params, paged=True, **kw)


def test_engine_paged_matches_dense_greedy():
    cfg = dataclasses.replace(reduced(get_config("qwen2-1.5b")),
                              num_layers=2)
    params = init_params(KEY, cfg)
    prompts = jax.random.randint(jax.random.key(1), (2, 16), 0,
                                 cfg.vocab_size)
    dense = ServeEngine(cfg, params, max_len=64, kv_slots=4, paged=False)
    paged = ServeEngine(cfg, params, max_len=64, kv_slots=4, paged=True,
                        page_size=8, prefill_chunk=8)
    r_d = dense.generate(prompts, 5)
    r_p = paged.generate(prompts, 5)
    for a, b in zip(r_d.tokens, r_p.tokens):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_paged_engine_scan_layers_bf16_matches_flat():
    """The layout published configs serve in — bf16 weights and pools,
    layers stacked under one scan — gives the same greedy tokens through
    the paged engine as the same weights applied layer by layer."""
    flat = dataclasses.replace(reduced(get_config("qwen2-1.5b")),
                               num_layers=3, dtype="bfloat16")
    scan = dataclasses.replace(flat, scan_layers=True)
    prompts = jax.random.randint(jax.random.key(1), (2, 20), 0,
                                 flat.vocab_size)
    toks = []
    for cfg in (flat, scan):
        eng = ServeEngine(cfg, init_params(KEY, cfg), max_len=64,
                          kv_slots=2, paged=True, page_size=8,
                          prefill_chunk=8)
        assert ("scan" in eng.params["layers"]) == cfg.scan_layers
        toks.append(np.stack([np.asarray(t) for t in
                              eng.generate(prompts, 6).tokens]))
        assert eng.kv_leak == 0
    np.testing.assert_array_equal(toks[0], toks[1])


def test_chunked_prefill_interleaves_with_decode():
    """A short request must make decode progress BETWEEN the prefill
    chunks of a long prompt — the whole point of chunking."""
    eng = _paged_engine(max_lanes=4)
    vocab = eng.cfg.vocab_size
    long_p = jax.random.randint(jax.random.key(2), (1, 48), 0, vocab)
    short_p = jax.random.randint(jax.random.key(3), (1, 8), 0, vocab)
    rl = Request(rid=0, prompt=long_p, max_new_tokens=4)
    rs = Request(rid=1, prompt=short_p, max_new_tokens=3)
    eng.admit(rl)
    eng.admit(rs)
    saw_interleave = False
    for _ in range(200):
        if not eng.has_work:
            break
        eng.step()
        if rl.t_prefill_end is None and len(rs.tokens) > 1:
            saw_interleave = True                  # decode mid-prefill
    assert saw_interleave
    assert rl.done and rs.done
    assert len(rl.tokens) == 4 and len(rs.tokens) == 3


def test_paged_capacity_exceeds_kv_slots_and_recycles():
    """With the same KV budget that gives the dense engine 2 slots, the
    page pool holds 6 short requests in flight; every page is recycled."""
    eng = _paged_engine(kv_slots=2, max_lanes=8)
    vocab = eng.cfg.vocab_size
    prompt = jax.random.randint(jax.random.key(4), (1, 8), 0, vocab)
    reqs = [Request(rid=i, prompt=prompt, max_new_tokens=4)
            for i in range(6)]
    for r in reqs:
        eng.admit(r)
    done = eng.run_to_completion()
    assert len(done) == 6
    assert eng.peak_inflight > eng.kv_slots
    # every lane page came back; only the prefix cache's deliberate
    # residency (the one shared prompt block) stays allocated
    cached = eng.prefix_cached_pages
    assert cached == 1
    assert eng._pool.num_free == eng.num_pages - 1 - cached
    assert eng.kv_leak == 0
    # identical prompts + greedy -> identical tokens across all lanes
    for r in reqs[1:]:
        np.testing.assert_array_equal(np.stack(r.tokens),
                                      np.stack(reqs[0].tokens))


def test_engine_reset_clears_rate_and_rid_state():
    """reset() must restart the EWMA rate and request-id counter (stale
    values leaked scheduler backlog estimates across benchmark runs)."""
    eng = _paged_engine()
    prompt = jax.random.randint(jax.random.key(5), (1, 8),
                                0, eng.cfg.vocab_size)
    eng.generate(prompt, 3)
    assert eng._ewma_tok_s > 0 and eng._next_rid == 1
    eng.reset()
    assert eng._ewma_tok_s == 0.0
    assert eng._next_rid == 0
    assert eng.pending_seconds == 0.0
    assert eng.peak_inflight == 0
    # engine still serves correctly after reset
    res = eng.generate(prompt, 2)
    assert len(res.tokens) == 2


def test_mixed_paged_dense_fleet_parity():
    """A heterogeneous cluster mixes KV backends: the attention engine
    auto-selects the page pool, the xLSTM engine keeps the dense slot
    pool, and each model's greedy tokens in the shared fleet match a
    solo run of the same engine (the backends don't interfere)."""
    from repro.cluster import EdgeCluster, make_scheduler
    from repro.serving.builders import build_fleet

    fleet = build_fleet(("qwen2-1.5b", "xlstm-350m"), max_len=48,
                        kv_slots=2, depths=[2, 2])
    assert fleet[0].paged and not fleet[1].paged
    vocab = min(e.cfg.vocab_size for e in fleet)
    prompts = jax.random.randint(jax.random.key(6), (2, 8), 0, vocab)

    # solo references, one per backend
    solo = []
    for e, p in zip(fleet, prompts):
        r = Request(rid=0, prompt=p[None], max_new_tokens=4)
        e.admit(r)
        e.run_to_completion()
        solo.append(np.stack(r.tokens))
        e.reset()

    # same prompts through the mixed fleet, pinned by a local scheduler
    cluster = EdgeCluster(fleet, make_scheduler("local", 2))
    reqs = [Request(rid=i, prompt=prompts[i][None], max_new_tokens=4,
                    origin=i, arrival_s=0.0) for i in range(2)]
    done = cluster.run(reqs)
    assert len(done) == 2
    for i, r in enumerate(sorted(done, key=lambda r: r.rid)):
        assert r.engine_id == i
        np.testing.assert_array_equal(np.stack(r.tokens), solo[i])


# ---------------------------------------------------------------------------
# refcounted pool + prefix cache
# ---------------------------------------------------------------------------


def test_page_pool_refcounts_monotone():
    """retain/release move refcounts by exactly one; a page frees only at
    zero, and the pool rejects refs on pages it never handed out."""
    pool = PagePool(num_pages=8, page_size=4)
    pages = pool.alloc(2)
    assert all(pool.refcount(p) == 1 for p in pages)
    assert pool.total_refs == 2
    pool.retain(pages)
    assert all(pool.refcount(p) == 2 for p in pages)
    assert pool.total_refs == 4
    pool.release(pages)                        # 2 -> 1: still allocated
    assert pool.num_free == 5
    assert all(pool.refcount(p) == 1 for p in pages)
    pool.release(pages)                        # 1 -> 0: freed
    assert pool.num_free == 7 and pool.total_refs == 0
    assert all(pool.refcount(p) == 0 for p in pages)
    with pytest.raises(RuntimeError):
        pool.retain([pages[0]])                # retain of a freed page
    with pytest.raises(RuntimeError):
        pool.release([pages[0]])               # double free


def test_prefix_cache_match_insert_cow_and_clamp():
    pool = PagePool(num_pages=16, page_size=4)
    cache = PrefixCache(pool)
    p1 = jnp.arange(12, dtype=jnp.int32)[None]     # 3 full blocks
    t1 = BlockTable(pool, tokens=12)
    assert cache.insert(p1, t1.pages) == 3
    assert cache.size == 3
    m = cache.match(p1)
    assert list(m.pages) == t1.pages and m.cow_page is None
    assert m.tokens == 12
    # mid-block divergence -> 2 full blocks + a COW fork of block 2
    p2 = p1.at[0, 9].set(999)
    m2 = cache.match(p2)
    assert list(m2.pages) == t1.pages[:2]
    assert m2.cow_page == t1.pages[2] and m2.cow_tokens == 1
    assert m2.tokens == 9
    # max_tokens clamps BOTH full-block and in-block matching
    m3 = cache.match(p1, max_tokens=11)
    assert len(m3.pages) == 2 and m3.cow_tokens == 3
    assert m3.tokens == 11
    # re-inserting the same chain adds nothing and leaks no refs
    refs_before = pool.total_refs
    assert cache.insert(p1, t1.pages) == 0
    assert pool.total_refs == refs_before


def test_prefix_cache_acquire_release_and_lru_leaf_eviction():
    pool = PagePool(num_pages=16, page_size=4)
    cache = PrefixCache(pool)
    p1 = jnp.arange(12, dtype=jnp.int32)[None]
    t1 = BlockTable(pool, tokens=12)
    cache.insert(p1, t1.pages)
    m = cache.match(p1)
    cache.acquire(m)                           # lane's own refs on top
    assert all(pool.refcount(p) == 3 for p in t1.pages)  # t1+cache+match
    cache.release_match(m)
    t1.release()                               # cache alone keeps them
    assert pool.total_refs == 3 == cache.size
    # leaf-first LRU: only the chain tail is evictable; parents survive
    assert cache._evict_one()
    assert cache.size == 2 and cache.evictions == 1
    assert cache.match(p1).tokens == 8         # tail gone, parents match
    cache.clear()
    assert cache.size == 0 and pool.num_free == 15 and pool.total_refs == 0


def test_prefix_eviction_never_frees_referenced_page():
    """Evicting a cache entry whose page a live lane still shares must
    drop only the cache's reference — the page stays allocated."""
    pool = PagePool(num_pages=8, page_size=4)
    cache = PrefixCache(pool)
    p1 = jnp.arange(8, dtype=jnp.int32)[None]
    t1 = BlockTable(pool, tokens=8)            # the "live lane"
    cache.insert(p1, t1.pages)
    while cache._evict_one():
        pass
    assert cache.size == 0
    assert pool.num_free == 7 - 2              # lane still holds 2 pages
    assert all(pool.refcount(p) == 1 for p in t1.pages)
    t1.release()
    assert pool.num_free == 7


def test_ensure_free_reports_exhaustion():
    pool = PagePool(num_pages=4, page_size=4)  # 3 usable
    cache = PrefixCache(pool)
    t = BlockTable(pool, tokens=12)            # all 3 pages live
    assert not cache.ensure_free(1)            # nothing evictable
    t.release()
    assert cache.ensure_free(3)


# ---------------------------------------------------------------------------
# engine: prefix hits, COW forks, reset, eviction under pressure
# ---------------------------------------------------------------------------


def _run_one(eng, prompt, rid=0, tokens=4):
    r = Request(rid=rid, prompt=prompt, max_new_tokens=tokens)
    eng.admit(r)
    eng.run_to_completion()
    return r, np.stack([np.asarray(t).ravel() for t in r.tokens])


def test_prefix_hit_skips_prefill_tokens_identically():
    """A repeated prompt reuses 2 full blocks + a COW tail (clamped at
    prompt_len - 1) and emits byte-identical tokens to a cache-off
    engine; a fresh engine peeks 0 expected tokens."""
    eng = _paged_engine(max_lanes=4)
    off = _paged_engine(max_lanes=4, prefix_cache=False)
    vocab = eng.cfg.vocab_size
    prompt = jax.random.randint(jax.random.key(7), (1, 24), 0, vocab)
    r_probe = Request(rid=99, prompt=prompt, max_new_tokens=4)
    assert eng.expected_prefix_tokens(r_probe) == 0
    _, base = _run_one(off, prompt, rid=0)
    _, first = _run_one(eng, prompt, rid=0)
    assert eng.prefill_tokens_saved == 0       # cold cache: no hit
    assert eng.prefix_cached_pages == 3        # 24 tokens / page_size 8
    assert eng.expected_prefix_tokens(r_probe) == 23   # plen - 1 clamp
    r2, second = _run_one(eng, prompt, rid=1)
    assert r2.prefix_tokens == 23              # 2 full pages + 7 COW
    assert eng.prefill_tokens_saved == 23
    assert eng.cow_forks == 1
    assert eng.prefix_hit_rate == 0.5          # 1 hit / 2 lookups
    np.testing.assert_array_equal(first, base)
    np.testing.assert_array_equal(second, base)
    assert eng.kv_leak == 0
    assert off.prefix_lookups == 0             # cache-off: no index at all


def test_cow_fork_leaves_parent_chain_byte_identical():
    """Forking a cached page for a divergent lane must not write a single
    byte into the parent's pages, and the parent chain stays matchable."""
    eng = _paged_engine(kv_slots=4, max_lanes=4)
    vocab = eng.cfg.vocab_size
    parent = jax.random.randint(jax.random.key(8), (1, 24), 0, vocab)
    _run_one(eng, parent, rid=0, tokens=2)
    m = eng._prefix.match(parent)
    pages = np.asarray(m.pages)
    snap = [np.asarray(leaf[pages])
            for leaf in jax.tree_util.tree_leaves(eng._paged_states)]
    child = parent.at[0, 20].set((int(parent[0, 20]) + 1) % vocab)
    r1, _ = _run_one(eng, child, rid=1, tokens=2)
    assert r1.prefix_tokens == 20 and eng.cow_forks == 1
    after = [np.asarray(leaf[pages])
             for leaf in jax.tree_util.tree_leaves(eng._paged_states)]
    for a, b in zip(snap, after):
        np.testing.assert_array_equal(a, b)
    assert eng._prefix.match(parent).tokens == 24      # chain intact


def test_engine_reset_releases_prefix_cache_and_pool():
    eng = _paged_engine()
    vocab = eng.cfg.vocab_size
    prompt = jax.random.randint(jax.random.key(9), (1, 24), 0, vocab)
    _run_one(eng, prompt, rid=0)
    _run_one(eng, prompt, rid=1)
    assert eng.prefix_cached_pages > 0 and eng.prefill_tokens_saved > 0
    eng.reset()                                # asserts pool all-free inside
    assert eng.prefix_cached_pages == 0
    assert eng._pool.num_free == eng.num_pages - 1
    assert eng._pool.total_refs == 0
    assert eng.prefill_tokens_saved == 0 and eng.prefix_lookups == 0
    assert eng.cow_forks == 0 and eng.kv_leak == 0
    _, toks = _run_one(eng, prompt, rid=0, tokens=2)   # still serves
    assert toks.shape[0] == 2


def test_prefix_eviction_under_pool_pressure_still_admits():
    """Distinct prompts fill the pool with cached chains; later
    admissions must evict cached leaves (never lane pages) and proceed."""
    eng = _paged_engine(max_lanes=2)
    vocab = eng.cfg.vocab_size
    for i in range(8):
        prompt = jax.random.randint(jax.random.key(20 + i), (1, 24),
                                    0, vocab)
        r, _ = _run_one(eng, prompt, rid=i)
        assert len(r.tokens) == 4
        assert eng.kv_leak == 0
    assert eng.prefix_evictions > 0            # pressure actually evicted
    assert eng._pool.total_refs == eng.prefix_cached_pages


def test_prefix_cache_off_engine_matches_pre_cache_behaviour():
    """prefix_cache=False keeps the pool free of residual pages after
    each request — the pre-PR lifecycle."""
    eng = _paged_engine(prefix_cache=False)
    vocab = eng.cfg.vocab_size
    prompt = jax.random.randint(jax.random.key(11), (1, 24), 0, vocab)
    _run_one(eng, prompt, rid=0)
    assert eng._pool.num_free == eng.num_pages - 1
    assert eng.prefix_cached_pages == 0 and eng.kv_leak == 0


# ---------------------------------------------------------------------------
# scheduler + trace + summarize integration
# ---------------------------------------------------------------------------


def test_prefix_affinity_routes_to_warm_engine():
    """The prefix-affinity scheduler reads the appended expected-hit
    observation block and routes a repeated prompt to the engine holding
    its prefix — engine 1 here, against the argmin tie-default of 0."""
    from repro.cluster import EdgeCluster, make_scheduler
    from repro.serving.builders import build_engines

    engines = build_engines("qwen2-1.5b", 2, max_len=64, kv_slots=2,
                            depths=[2, 2], page_size=8, prefill_chunk=8)
    assert all(e.paged for e in engines)
    vocab = engines[0].cfg.vocab_size
    prompt = jax.random.randint(jax.random.key(12), (1, 24), 0, vocab)
    _run_one(engines[1], prompt, rid=0)        # warm ONLY engine 1
    sched = make_scheduler("prefix-affinity", 2)
    cluster = EdgeCluster(engines, sched)
    assert cluster.prefix_obs
    assert cluster.obs_dim == 2 + 2 * 2        # base (2+E) + hit block E
    req = Request(rid=1, prompt=prompt, max_new_tokens=2, arrival_s=0.0)
    row = np.asarray(cluster.observe(req))
    assert row.shape == (cluster.obs_dim,)
    assert row[-1] > row[-2] == 0.0            # hit feature: engine 1 only
    done = cluster.run([req])
    assert done[0].engine_id == 1
    assert done[0].prefix_tokens > 0


def test_prefix_affinity_state_dim_guard():
    """Suppressing the prefix block (and the fault layout it would alias
    into: base+E == 6 too) must fail construction with a message that
    names the prefix extension."""
    from repro.cluster import EdgeCluster, make_scheduler
    from repro.serving.builders import build_engines
    engines = build_engines("qwen2-1.5b", 2, max_len=48, kv_slots=2,
                            depths=[2, 2], page_size=8, prefill_chunk=8)
    sched = make_scheduler("prefix-affinity", 2)
    with pytest.raises(ValueError, match="prefix"):
        EdgeCluster(engines, sched, prefix_obs=False, fault_obs=False)


def test_poisson_trace_shared_prefix_and_stream_identity():
    """prefix_len>0 stamps the SAME system-prompt tokens onto the chosen
    fraction; prefix_len=0 consumes a bit-identical random stream to the
    legacy trace."""
    from repro.cluster import poisson_trace
    kw = dict(rate=50.0, prompt_len=16, max_new_tokens=2,
              vocab_size=97, num_origins=2, seed=3)
    shared = poisson_trace(12, prefix_len=12, prefix_frac=1.0, **kw)
    head0 = np.asarray(shared[0].prompt[..., :12])
    for r in shared:
        np.testing.assert_array_equal(np.asarray(r.prompt[..., :12]), head0)
    legacy = poisson_trace(6, **kw)
    zeroed = poisson_trace(6, prefix_len=0, prefix_frac=0.9, **kw)
    for a, b in zip(legacy, zeroed):
        np.testing.assert_array_equal(np.asarray(a.prompt),
                                      np.asarray(b.prompt))
        assert a.arrival_s == b.arrival_s
    # frac in (0,1): some share, some don't
    mixed = poisson_trace(40, prefix_len=12, prefix_frac=0.5, **kw)
    hits = sum(bool(np.array_equal(np.asarray(r.prompt[..., :12]), head0))
               for r in mixed)
    assert 0 < hits < 40


def test_summarize_and_sim_report_prefix_savings():
    from repro.cluster import summarize
    from repro.cluster.request import Request as Rq
    reqs = []
    for i, saved in enumerate([0, 10, 14]):
        r = Rq(rid=i, prompt=None, max_new_tokens=1, arrival_s=0.0)
        r.t_arrival, r.t_finish, r.status = 0.0, 1.0, "ok"
        r.prefix_tokens = saved
        reqs.append(r)
    out = summarize(reqs)
    assert out["prefill_tokens_saved"] == 24
    assert out["prefix_hit_rate"] == pytest.approx(2 / 3)
    # sim-side schema parity (no KV model -> identically zero, keys exist)
    from repro.cluster import evaluate_scheduler, make_scheduler
    from repro.core.env import EnvParams
    p = EnvParams(num_bs=2, num_slots=3, max_tasks=2)
    res = evaluate_scheduler(make_scheduler("jsq", 2), p, 1,
                             jax.random.key(0))
    assert res["prefill_tokens_saved"] == 0
    assert res["prefix_hit_rate"] == 0.0
