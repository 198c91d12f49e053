"""Paged KV cache + radix-tree prefix reuse (serving/cache.py, ISSUE 5).

Host-side contracts (no model): page pool accounting, radix-tree
match/insert/refcounts, LRU eviction that never touches a mapped page,
deferred admission under pool exhaustion. Engine contracts (tiny gpt2):
a prefix-hit request is token-exact vs the cold path with strictly fewer
prefill chunks, copy-on-write sharing isolates concurrent sharers from
each other's cancellation/retirement, the compile count stays flat
across hit/miss/eviction mixes, and strict-mode audits pass on the
gather/scatter programs."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.models import gpt2
from accelerate_tpu.serving import (
    Engine,
    EngineConfig,
    PagedAllocator,
    PagedKVCache,
    PagePool,
    PrefixIndex,
    Request,
    RequestStatus,
)
from accelerate_tpu.serving.scheduler import Slot


@pytest.fixture(scope="module", autouse=True)
def _persistent_compile_cache(tmp_path_factory):
    """Engines here compile the same three tiny programs as
    tests/test_serving.py; the persistent cache turns repeats into
    deserializes."""
    import os

    from accelerate_tpu.utils.environment import configure_compilation_cache

    prev = os.environ.get("ACCELERATE_TPU_COMPILATION_CACHE_MIN_COMPILE_SECS")
    os.environ.setdefault(
        "ACCELERATE_TPU_COMPILATION_CACHE_MIN_COMPILE_SECS", "0")
    configure_compilation_cache(
        str(tmp_path_factory.mktemp("xla_cache")), force=True)
    yield
    # scoped: hand the process back with caching OFF — a later module that
    # re-traces an AOT-compiled train step would deserialize a threshold-0
    # entry from this dir and segfault jaxlib (ISSUE 16 hit this the moment
    # an engine module sorted before test_launched_scripts)
    if prev is None:
        os.environ.pop(
            "ACCELERATE_TPU_COMPILATION_CACHE_MIN_COMPILE_SECS", None)
    configure_compilation_cache("off", force=True)


@pytest.fixture(scope="module")
def gpt2_setup():
    cfg = gpt2.GPT2Config.tiny()
    params = gpt2.init_params(cfg, jax.random.key(0))
    return cfg, params


def _engine(cfg, params, **overrides):
    defaults = dict(num_slots=2, max_len=64, prefill_chunk=8, page_size=8,
                    cache_dtype=jnp.float32)
    defaults.update(overrides)
    return Engine(gpt2, cfg, params, EngineConfig(**defaults))


def _ref_tokens(cfg, params, prompt, n):
    out = gpt2.generate(cfg, params, jnp.asarray(prompt)[None, :],
                        max_new_tokens=n)
    return np.asarray(out)[0, len(prompt):].tolist()


def _req(tokens, mnt=4):
    return Request(prompt=np.asarray(tokens, np.int32), max_new_tokens=mnt)


def _slot(alloc, req, index=0, prompt_done=None):
    s = Slot(index)
    s.alloc, s.request = alloc, req
    # a finished slot has prefilled its whole prompt; release() caps the
    # insertable range at prompt_done (a finish_early mid-prefill must
    # not cache garbage pages — see its regression test below)
    s.prompt_done = req.prompt_len if prompt_done is None else prompt_done
    return s


# ---------------------------------------------------------------------------
# host-side accounting (no model, no jit)
# ---------------------------------------------------------------------------


def test_page_pool_alloc_release_exact():
    pool = PagePool(4)
    assert pool.free_count == 4 and pool.used_count == 0
    got = pool.alloc(3)
    assert len(got) == 3 and len(set(got)) == 3
    assert pool.alloc(2) is None          # only 1 left: no partial grants
    assert pool.free_count == 1           # failed alloc changed nothing
    pool.release(got)
    assert pool.free_count == 4


@pytest.mark.parametrize("freed_by", ["release", "release-two-lists",
                                      "evict_lru"])
def test_page_pool_alloc_is_ascending_whatever_order_pages_were_freed_in(
        freed_by):
    """A table row shows the adjacency its pages have only if they come
    ascending: after a retirement's `release` (one list, or two requests'
    lists out of order) and after an eviction's (oldest leaves first,
    which is no order of ids)."""
    if freed_by == "evict_lru":
        al = PagedAllocator(page_size=4, num_pages=12, pad_slack=0)
        for base in (300, 100, 200):          # three prompts of 4 pages
            r = _req(list(range(base, base + 16)), mnt=0)
            al.release(_slot(al.allocate(r), r), finished=True)
        assert al.pages_free == 0 and al.index.cached_pages == 12
        got = al.allocate(_req(list(range(900, 932)), mnt=0)).pages
        assert al.evictions == 8
    else:
        pool = PagePool(24)
        a, b, c = pool.alloc(8), pool.alloc(8), pool.alloc(8)
        assert (a, b, c) == (list(range(8)), list(range(8, 16)),
                             list(range(16, 24)))
        if freed_by == "release":
            pool.release(b[::-1])
        else:
            pool.release(c[4:])
            pool.release(a[:4])
        got = pool.alloc(8)
        assert sorted(got) == (b if freed_by == "release" else a[:4] + c[4:])
    assert got == sorted(got)


def test_table_run_pages_counts_aligned_sub_groups_of_consecutive_ids():
    from accelerate_tpu.ops import paged_attention
    from accelerate_tpu.serving import cache

    # the allocator's books and the kernel read a table the same way
    assert cache.TABLE_RUN_PAGES == paged_attention.PAGES_PER_RUN == 8
    count = cache.table_run_pages
    assert count(list(range(40, 64))) == 24
    assert count(list(range(40, 63))) == 16       # the tail is no sub-group
    assert count(list(range(7))) == 0
    assert count([]) == 0
    # a run that starts unaligned loses the sub-group it starts in only
    assert count([9] + list(range(40, 63))) == 16
    assert count(list(range(8)) + [8, 9, 10, 12, 13, 14, 15, 16]
                 + list(range(30, 38))) == 16     # broken inside
    assert count(list(range(15, -1, -1))) == 0    # descending
    assert count([1, 2, 3, 4, 9, 8], run=4) == 4


def test_a_recycled_table_is_runs_but_for_its_boundary_sub_groups():
    """A request admitted into the pool another just left: its pages are
    the leaver's, ascending, so every aligned sub-group of its table row
    is a run but the one that straddles the pages a third request still
    holds; the allocation says so, and the engine's counters are the sums
    of what the allocations said."""
    from accelerate_tpu.serving.metrics import ServingMetrics

    al = PagedAllocator(page_size=4, num_pages=64, prefix_cache=False)
    metrics = ServingMetrics()
    pages = run_pages = 0

    def admit(prompt_len):
        nonlocal pages, run_pages
        req = _req(list(range(prompt_len)), mnt=0)
        alloc = al.allocate(req)
        metrics.note_admission(req.prompt_len, alloc.reused_len,
                               table_pages=len(alloc.pages),
                               run_pages=alloc.run_pages)
        pages += len(alloc.pages)
        run_pages += alloc.run_pages
        return alloc, req

    first, r1 = admit(4 * 20)                  # pages 0..19
    kept, _ = admit(4 * 3)                     # 20, 21, 22
    assert (first.run_pages, kept.run_pages) == (16, 0)
    al.release(_slot(first, r1), finished=True)
    # 30 pages: the 20 the first left, then 10 from past the kept three
    again, _ = admit(4 * 30)
    assert again.pages == list(range(20)) + list(range(23, 33))
    # sub-groups [0:8] [8:16] are runs; [16:24] straddles the kept pages
    # (16..19 | 23..26); [24:30] is no whole sub-group
    assert again.run_pages == 16
    assert (metrics.kv_table_pages, metrics.kv_table_run_pages) \
        == (pages, run_pages) == (53, 32)
    summary = metrics.summary()
    assert summary["kv_table_run_pages"] == 32.0
    assert summary["kv_table_pages"] == 53.0


def test_prefix_index_match_caps_below_full_prompt():
    """Reuse never covers the whole prompt: the last token must prefill
    to produce the first output logits."""
    idx = PrefixIndex(page_size=4)
    prompt = np.arange(8, dtype=np.int32)
    idx.insert(prompt, [0, 1], 2)         # both full pages cached
    assert len(idx.match(prompt)) == 1    # (8-1)//4 = 1, not 2
    longer = np.arange(9, dtype=np.int32)
    assert [n.page for n in idx.match(longer)] == [0, 1]


def test_prefix_index_insert_dedupes_concurrent_equal_chunks():
    idx = PrefixIndex(page_size=4)
    prompt = np.arange(8, dtype=np.int32)
    assert idx.insert(prompt, [0, 1], 2) == []
    # a second request computed the same prefix into different pages:
    # the tree keeps the first copy, the duplicates come back to free
    assert idx.insert(prompt, [5, 6], 2) == [5, 6]
    assert idx.cached_pages == 2


def test_allocator_lru_eviction_never_evicts_mapped_pages():
    al = PagedAllocator(page_size=4, num_pages=8, pad_slack=0)
    A = _req(list(range(100, 112)), mnt=0)    # 3 pages
    B = _req(list(range(200, 212)), mnt=0)    # 3 pages
    for r in (A, B):
        al.release(_slot(al.allocate(r), r), finished=True)
    assert al.index.cached_pages == 6 and al.pages_free == 2
    # touch A (now most-recent AND mapped), then demand 4 cold pages:
    # only B's pages are evictable, leaf-first, oldest-first
    a2 = al.allocate(_req(list(range(100, 112)) + [7], mnt=0))
    assert a2.reused_len == 12
    c = al.allocate(_req(list(range(300, 316)), mnt=0))
    assert c is not None and c.reused_len == 0
    assert al.evictions == 3                  # exactly B's three pages
    assert all(n.parent is not None for n in a2.nodes)  # A survived


def test_allocator_defers_admission_until_pages_free():
    al = PagedAllocator(page_size=4, num_pages=4, pad_slack=0)
    D = _req(list(range(16)), mnt=0)          # takes the whole pool
    d = al.allocate(D)
    assert d is not None
    E = _req(list(range(50, 62)), mnt=0)
    assert al.allocate(E) is None             # mapped pages: unevictable
    al.release(_slot(d, D), finished=True)    # retire -> pages cached
    e = al.allocate(E)                        # now evictable
    assert e is not None and al.evictions == 3


def test_allocate_releases_refcounts_when_on_evict_raises():
    """ATP201 regression (ISSUE 13 self-lint finding): on_evict is a
    caller-supplied callback running MID-allocate; if it raises, the
    matched prefix nodes' refcounts must not leak (a leaked refcount
    pins its whole root path unevictable forever)."""
    def boom(n):
        raise RuntimeError("exporter fell over")

    al = PagedAllocator(page_size=4, num_pages=8, pad_slack=0,
                        on_evict=boom)
    A = _req(list(range(100, 108)), mnt=0)    # 2 pages
    C = _req(list(range(200, 208)), mnt=0)    # 2 pages
    for r in (A, C):
        al.release(_slot(al.allocate(r), r), finished=True)
    assert al.index.cached_pages == 4 and al.pages_free == 4
    # B reuses A's prefix (2 acquired nodes) and needs 5 private pages:
    # eviction fires, on_evict raises mid-protocol
    B = _req(list(range(100, 108)) + list(range(300, 304)), mnt=16)
    with pytest.raises(RuntimeError, match="exporter fell over"):
        al.allocate(B)
    assert al.index.mapped_pages == 0         # the refcounts came back
    # the allocator still works once the callback behaves
    al.on_evict = None
    b = al.allocate(B)
    assert b is not None and b.reused_len == 8


def test_release_after_early_finish_caches_only_prefilled_pages():
    """finish_early can retire a slot whose prefill is still mid-flight;
    release(finished=True) must cap the cached range at prompt_done —
    pages past it were never written and caching them would serve
    garbage KV to the next prefix hit (ISSUE 13 lifecycle-audit fix)."""
    al = PagedAllocator(page_size=4, num_pages=8, pad_slack=0)
    A = _req(list(range(100, 116)), mnt=0)    # 16 tokens, 4 pages
    a = al.allocate(A)
    slot = _slot(a, A, prompt_done=6)         # prefill stopped mid-page 2
    al.release(slot, finished=True)
    assert al.index.cached_pages == 1         # only the COMPLETED page
    b = al.allocate(_req(list(range(100, 116)), mnt=0))
    assert b.reused_len == 4                  # and reuse stops there
    # all other pages went back to the free list, nothing leaked:
    # 8 total - 1 cached+remapped - 3 private for b
    assert al.pages_free == 4


def test_failed_admission_evicts_nothing():
    """evict_lru is all-or-nothing: when even full eviction cannot cover
    the queue head, the cached prefixes survive untouched — a too-big
    request waiting in queue must not strip reuse from everyone else."""
    al = PagedAllocator(page_size=4, num_pages=8, pad_slack=0)
    A = _req(list(range(100, 112)), mnt=0)    # 3 pages, caches 3
    al.release(_slot(al.allocate(A), A), finished=True)
    B = _req(list(range(200, 212)), mnt=0)    # 3 more pages mapped
    b = al.allocate(B)
    assert b is not None                      # free: 8 - 3 - 3 = 2
    big = _req(list(range(300, 324)), mnt=0)  # needs 6 > 2 free + 3 cached
    assert al.index.mapped_pages == 0         # B's pages are all private
    assert al.allocate(big) is None
    assert al.evictions == 0                  # nothing was destroyed
    assert al.index.cached_pages == 3         # A's prefix still reusable
    a2 = al.allocate(_req(list(range(100, 113)), mnt=0))
    assert a2 is not None and a2.reused_len == 12
    assert al.index.mapped_pages == 3         # the evictable-count books


def test_allocator_cancel_caches_nothing():
    """A cancelled request's pages may hold garbage mid-prefill: they go
    to the free list, never into the tree."""
    al = PagedAllocator(page_size=4, num_pages=8, pad_slack=0)
    A = _req(list(range(16)), mnt=0)
    a = al.allocate(A)
    al.release(_slot(a, A), finished=False)
    assert al.index.cached_pages == 0 and al.pages_free == 8


def test_allocator_prefix_cache_off_is_always_cold():
    al = PagedAllocator(page_size=4, num_pages=8, pad_slack=0,
                        prefix_cache=False)
    A = _req(list(range(16)), mnt=0)
    al.release(_slot(al.allocate(A), A), finished=True)
    assert al.index.cached_pages == 0
    assert al.allocate(A).reused_len == 0
    assert al.hits == 0


def test_paged_cache_shapes_and_pytree():
    cache = PagedKVCache.create(num_layers=2, num_slots=3, max_len=16,
                                num_kv_heads=4, head_dim=8,
                                dtype=jnp.float32, page_size=8, pad_slack=4)
    # ceil((16+4)/8) = 3 pages/slot, default pool 9 pages + 1 trash
    assert cache.pages_per_slot == 3 and cache.num_pages == 9
    assert cache.k.shape == (2, 10, 4, 8, 8)  # [L, pages+1, H, ps, D]
    assert cache.rows == 24 and cache.trash_page == 9
    leaves, treedef = jax.tree_util.tree_flatten(cache)
    assert len(leaves) == 3
    rebuilt = jax.tree_util.tree_unflatten(treedef, leaves)
    assert rebuilt.page_size == 8 and rebuilt.pages_per_slot == 3
    with pytest.raises(ValueError):
        PagedKVCache.create(2, 3, 16, 4, 8, page_size=8, num_pages=1)


# ---------------------------------------------------------------------------
# engine contracts
# ---------------------------------------------------------------------------


def test_prefix_hit_is_token_exact_and_skips_prefill(gpt2_setup):
    """The acceptance contract: a request sharing a cached prefix decodes
    token-identically to the cold path while running strictly fewer
    prefill chunks, through the same three compiled programs."""
    cfg, params = gpt2_setup
    eng = _engine(cfg, params)
    rng = np.random.default_rng(0)
    prefix = rng.integers(0, cfg.vocab_size, (24,)).astype(np.int32)
    p1 = np.concatenate([prefix,
                         rng.integers(0, cfg.vocab_size, (6,)).astype(np.int32)])
    p2 = np.concatenate([prefix,
                         rng.integers(0, cfg.vocab_size, (6,)).astype(np.int32)])
    r1 = eng.submit(p1, max_new_tokens=6)
    eng.run_until_idle()
    cold_chunks = eng.metrics.prefill_chunks
    r2 = eng.submit(p2, max_new_tokens=6)
    eng.run_until_idle()
    warm_chunks = eng.metrics.prefill_chunks - cold_chunks
    assert r1.tokens == _ref_tokens(cfg, params, p1, 6)
    assert r2.tokens == _ref_tokens(cfg, params, p2, 6)
    assert eng.metrics.prefix_hits == 1
    assert eng.metrics.prefix_tokens_reused == 24
    assert warm_chunks < cold_chunks
    assert eng.compile_stats() == {"admit": 1, "prefill": 1, "decode": 1}


def test_cow_sharers_isolated_under_cancel_and_retire(gpt2_setup):
    """Two live requests mapping the same cached prefix pages: cancelling
    one (and letting the other retire first/later) never perturbs the
    survivor's tokens — shared pages are refcounted, never written, and a
    release only frees PRIVATE pages."""
    cfg, params = gpt2_setup
    eng = _engine(cfg, params)
    rng = np.random.default_rng(1)
    prefix = rng.integers(0, cfg.vocab_size, (16,)).astype(np.int32)

    def with_suffix(n):
        return np.concatenate(
            [prefix, rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)])

    warm = eng.submit(with_suffix(4), max_new_tokens=2)
    eng.run_until_idle()                      # prefix pages now cached
    # equal suffix lengths + budgets keep the reference `generate` to
    # ONE compiled shape across c and d (tier-1 budget)
    pb, pc = with_suffix(5), with_suffix(7)
    b = eng.submit(pb, max_new_tokens=10)
    c = eng.submit(pc, max_new_tokens=10)
    for _ in range(6):                        # both mid-flight, sharing
        eng.step()
    assert eng.metrics.prefix_hits == 2
    assert eng.cancel(b)
    eng.run_until_idle()
    assert b.status is RequestStatus.CANCELLED
    assert c.status is RequestStatus.FINISHED
    assert c.tokens == _ref_tokens(cfg, params, pc, 10)
    # and the prefix is STILL reusable after both sharers are gone
    pd = with_suffix(7)
    d = eng.submit(pd, max_new_tokens=10)
    eng.run_until_idle()
    assert eng.metrics.prefix_hits == 3
    assert d.tokens == _ref_tokens(cfg, params, pd, 10)


def test_eviction_under_pool_pressure_stays_exact(gpt2_setup):
    """A pool sized below the cached working set forces LRU evictions;
    outputs stay exact and no compiled program is added."""
    cfg, params = gpt2_setup
    # pool at the floor (pages_per_slot = ceil((64+8)/8) = 9): each
    # 40-token prompt needs ceil((40+4+8)/8) = 7 pages but a retired one
    # caches 5, so every later admission must evict. Equal lengths keep
    # the reference `generate` to ONE compiled shape (tier-1 budget).
    eng = _engine(cfg, params, num_pages=9)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg.vocab_size, (40,)).astype(np.int32)
               for _ in range(3)]
    for p in prompts:
        r = eng.submit(p, max_new_tokens=4)
        eng.run_until_idle()
        assert r.tokens == _ref_tokens(cfg, params, p, 4)
    assert eng.metrics.page_evictions > 0
    assert eng.compile_stats() == {"admit": 1, "prefill": 1, "decode": 1}
    s = eng.metrics_summary()
    assert s["page_evictions"] > 0
    assert s["pages_in_use"] + s["pages_free"] == 9


def test_compile_count_flat_across_hit_miss_eviction_mix(gpt2_setup):
    """The PR 2 guard extended per ISSUE 5: shared-prefix hits, cold
    misses, and eviction churn are all DATA — page tables and reused
    lengths are traced, so the program count never moves."""
    cfg, params = gpt2_setup
    # pool at the floor (pages_per_slot = 9): the evictor wave MUST churn
    eng = _engine(cfg, params, num_pages=9)
    rng = np.random.default_rng(3)
    prefix = rng.integers(0, cfg.vocab_size, (16,)).astype(np.int32)
    waves = [
        np.concatenate([prefix, rng.integers(0, cfg.vocab_size, (4,))
                        .astype(np.int32)]),               # cold prefix
        np.concatenate([prefix, rng.integers(0, cfg.vocab_size, (9,))
                        .astype(np.int32)]),               # hit
        rng.integers(0, cfg.vocab_size, (40,)).astype(np.int32),  # evictor
        np.concatenate([prefix, rng.integers(0, cfg.vocab_size, (2,))
                        .astype(np.int32)]),               # re-miss or hit
    ]
    for wave, (p, temp) in enumerate(zip(waves, (0.0, 1.0, 0.0, 0.7))):
        r = eng.submit(p, max_new_tokens=3, temperature=temp)
        eng.run_until_idle()
        assert r.status is RequestStatus.FINISHED
        counts = eng.compile_stats()
        assert counts == {"admit": 1, "prefill": 1, "decode": 1}, (
            f"wave {wave} recompiled: {counts}")
    assert eng.metrics.prefix_hits >= 1
    assert eng.metrics.page_evictions > 0


def test_strict_error_passes_on_paged_programs(gpt2_setup):
    """Acceptance: EngineConfig(strict="error") audits the paged
    gather/scatter programs (admit/prefill/decode) clean — page-axis
    gathers are data movement, not collectives — including on the
    prefix-hit path."""
    cfg, params = gpt2_setup
    eng = _engine(cfg, params, strict="error")
    rng = np.random.default_rng(4)
    p = rng.integers(0, cfg.vocab_size, (20,)).astype(np.int32)
    r1 = eng.submit(p, max_new_tokens=4)
    eng.run_until_idle()
    r2 = eng.submit(np.concatenate([p, [7, 8]]).astype(np.int32),
                    max_new_tokens=4)
    eng.run_until_idle()
    assert r1.status is RequestStatus.FINISHED
    assert r2.status is RequestStatus.FINISHED
    assert eng.metrics.prefix_hits == 1
    assert float(eng.registry.counter("analysis_findings_total").value) == 0


def test_prefix_reuse_vs_no_reuse_same_trace(gpt2_setup):
    """The serve_bench A/B, deterministically: the same prompt trace
    through a reuse engine and a prefix_cache=False engine yields
    token-identical outputs with strictly fewer prefill chunks (and a
    hit rate > 0) on the reuse side."""
    cfg, params = gpt2_setup
    rng = np.random.default_rng(5)
    pool = [rng.integers(0, cfg.vocab_size, (16,)).astype(np.int32)
            for _ in range(2)]
    trace = [np.concatenate(
        [pool[int(rng.integers(2))],
         rng.integers(0, cfg.vocab_size, (int(rng.integers(2, 6)),))
         .astype(np.int32)]) for _ in range(8)]

    results = {}
    for reuse in (True, False):
        eng = _engine(cfg, params, prefix_cache=reuse)
        reqs = [eng.submit(p, max_new_tokens=4) for p in trace]
        eng.run_until_idle()
        assert all(r.status is RequestStatus.FINISHED for r in reqs)
        results[reuse] = ([r.tokens for r in reqs],
                          eng.metrics.prefill_chunks,
                          eng.metrics_summary().get("prefix_hit_rate", 0.0))
    tokens_reuse, chunks_reuse, hit_rate = results[True]
    tokens_cold, chunks_cold, _ = results[False]
    assert tokens_reuse == tokens_cold
    assert hit_rate > 0
    assert chunks_reuse < chunks_cold, (chunks_reuse, chunks_cold)


def test_prometheus_exposition_carries_page_and_prefix_series(gpt2_setup):
    """The new pool gauges and prefix counters ride the same per-engine
    registry the exporter serves."""
    import urllib.request

    cfg, params = gpt2_setup
    eng = _engine(cfg, params, metrics_port=0)
    try:
        rng = np.random.default_rng(6)
        p = rng.integers(0, cfg.vocab_size, (12,)).astype(np.int32)
        eng.submit(p, max_new_tokens=3)
        eng.run_until_idle()
        eng.submit(np.concatenate([p, [1]]).astype(np.int32),
                   max_new_tokens=3)
        eng.run_until_idle()
        url = f"http://127.0.0.1:{eng.metrics_server.port}/metrics"
        body = urllib.request.urlopen(url, timeout=5).read().decode()
        for series in ("serving_pages_in_use", "serving_pages_free",
                       "serving_prefix_hits_total",
                       "serving_prefix_tokens_reused_total",
                       "serving_page_evictions_total",
                       "serving_kv_table_pages_total",
                       "serving_kv_table_run_pages_total"):
            assert series in body, f"{series} missing from exposition"
        assert "serving_prefix_hits_total 1.0" in body
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# ISSUE 10: int8 KV pool mode
# ---------------------------------------------------------------------------


def test_int8_pool_shapes_pytree_and_page_nbytes():
    """Quantized create: int8 codes + bf16 per-row-per-head scales as
    extra pytree children; page_nbytes is the HBM-math unit ((D+2)/2D of
    a bf16 page — half the code bytes plus the 2/D scale overhead)."""
    kw = dict(num_layers=2, num_slots=2, max_len=32, num_kv_heads=2,
              head_dim=16, page_size=8, pad_slack=8)
    bf = PagedKVCache.create(**kw)
    q = PagedKVCache.create(**kw, kv_dtype="int8")
    assert q.quantized and not bf.quantized
    assert q.k.dtype == jnp.int8
    assert q.k_scale.shape == q.k.shape[:-1]
    assert q.k_scale.dtype == jnp.bfloat16
    D = kw["head_dim"]
    assert q.page_nbytes / bf.page_nbytes == (D + 2) / (2 * D)
    assert q.nbytes() == q.k.nbytes * 2 + q.k_scale.nbytes * 2
    leaves, treedef = jax.tree_util.tree_flatten(q)
    assert len(leaves) == 5  # k, v, lengths, k_scale, v_scale
    rebuilt = jax.tree_util.tree_unflatten(treedef, leaves)
    assert rebuilt.quantized and rebuilt.compute_dtype == jnp.bfloat16
    with pytest.raises(ValueError, match="kv_dtype"):
        PagedKVCache.create(**kw, kv_dtype="int4")


def test_int8_write_then_view_roundtrips_and_leaves_other_rows_bitstable():
    """Row-granular quantized writes: a later chunk's write never
    re-encodes earlier rows (an int8 round-trip is not idempotent, so
    whole-page rewrites would drift shared bytes — the COW hazard the
    per-row design removes)."""
    from accelerate_tpu.serving.cache import paged_slot_view, paged_write_slot

    rng = np.random.default_rng(0)
    cache = PagedKVCache.create(num_layers=1, num_slots=1, max_len=16,
                                num_kv_heads=2, head_dim=8, page_size=8,
                                pad_slack=8, kv_dtype="int8",
                                dtype=jnp.float32)
    table_row = jnp.arange(cache.pages_per_slot, dtype=jnp.int32)
    R = cache.rows
    chunk = 8

    def payload(seed):
        return jnp.asarray(rng.normal(size=(1, 1, R, 2, 8)), jnp.float32)

    first = payload(1)
    cache = paged_write_slot(cache, table_row, jnp.int32(0), first, first,
                             jnp.int32(5), chunk)  # rows 0..7, 5 real
    codes_after_first = np.asarray(cache.k).copy()
    scales_after_first = np.asarray(cache.k_scale).copy()
    cache = paged_write_slot(cache, table_row, jnp.int32(0), payload(2),
                             payload(2), jnp.int32(8), chunk)  # rows 5..12
    # rows 0..4 (written only by the first chunk) are bit-identical
    np.testing.assert_array_equal(np.asarray(cache.k)[:, 0, :, :5],
                                  codes_after_first[:, 0, :, :5])
    np.testing.assert_array_equal(np.asarray(cache.k_scale)[:, 0, :, :5],
                                  scales_after_first[:, 0, :, :5])
    # and the dense view dequantizes to within the int8 error of the
    # payload on the real rows
    ks, _, length = paged_slot_view(cache, table_row, jnp.int32(0))
    assert int(length) == 13
    got = np.asarray(ks[0, 0, :5], np.float32)
    want = np.asarray(first[0, 0, :5], np.float32)
    absmax = np.abs(want).max(-1, keepdims=True)
    assert np.all(np.abs(got - want) <= absmax * (1 / 254 + 2 ** -8) + 1e-6)


def test_int8_append_rows_quantizes_one_row_per_live_slot():
    from accelerate_tpu.serving.cache import paged_append_rows

    cache = PagedKVCache.create(num_layers=1, num_slots=2, max_len=16,
                                num_kv_heads=2, head_dim=8, page_size=8,
                                pad_slack=0, kv_dtype="int8",
                                dtype=jnp.float32)
    import dataclasses

    cache = dataclasses.replace(cache,
                                lengths=jnp.asarray([3, 0], jnp.int32))
    table = jnp.asarray([[0, 1], [2, 3]], jnp.int32)
    rng = np.random.default_rng(1)
    row_k = jnp.asarray(rng.normal(size=(1, 2, 2, 8)), jnp.float32)
    row_v = jnp.asarray(rng.normal(size=(1, 2, 2, 8)), jnp.float32)
    out = paged_append_rows(cache, table, row_k, row_v,
                            jnp.asarray([True, False]))
    assert out.lengths.tolist() == [4, 0]  # only the live lane advances
    # slot 0 row landed at page 0 offset 3, quantized
    from accelerate_tpu.ops.quant import kv_dequantize_rows

    got = kv_dequantize_rows(out.k[0, 0, :, 3], out.k_scale[0, 0, :, 3],
                             jnp.float32)
    want = np.asarray(row_k[0, 0], np.float32)
    absmax = np.abs(want).max(-1, keepdims=True)
    assert np.all(np.abs(np.asarray(got) - want)
                  <= absmax * (1 / 254 + 2 ** -8) + 1e-6)


def test_int8_prefix_reuse_vs_no_reuse_same_trace(gpt2_setup):
    """COW sharing under quantization: the reuse-vs-cold A/B stays
    token-identical with int8 pages (shared pages' codes are never
    re-encoded — bit-stable however many sharers race) and still saves
    prefill chunks."""
    cfg, params = gpt2_setup
    rng = np.random.default_rng(6)
    shared = rng.integers(0, cfg.vocab_size, (16,)).astype(np.int32)
    trace = [np.concatenate(
        [shared, rng.integers(0, cfg.vocab_size,
                              (int(rng.integers(2, 6)),)).astype(np.int32)])
        for _ in range(6)]
    results = {}
    for reuse in (True, False):
        eng = _engine(cfg, params, prefix_cache=reuse, kv_dtype="int8")
        reqs = [eng.submit(p, max_new_tokens=4) for p in trace]
        eng.run_until_idle()
        assert all(r.status is RequestStatus.FINISHED for r in reqs)
        results[reuse] = ([r.tokens for r in reqs],
                          eng.metrics.prefill_chunks)
    assert results[True][0] == results[False][0]
    assert results[True][1] < results[False][1]


# ---------------------------------------------------------------------------
# ISSUE 25: every pool write is a whole-page read-modify-write
# ---------------------------------------------------------------------------


class _RowPool:
    """The pool as plain NumPy arrays, written ONE ROW AT A TIME at
    (page, offset): the semantics every write path had before its tail
    became page-granular, and the model the new tail must match bit for
    bit. Rows are encoded by the program's own `kv_quantize_rows` (a
    whole window at once, as the program does), then placed here."""

    def __init__(self, cache):
        self.quantized = cache.quantized
        self.dtype = cache.k.dtype
        self.ps, self.trash = cache.page_size, cache.trash_page
        self.pools = [np.array(a) for a in self._arrays(cache)]
        self.lengths = np.array(cache.lengths)

    @staticmethod
    def _arrays(cache):
        return ([cache.k, cache.v, cache.k_scale, cache.v_scale]
                if cache.quantized else [cache.k, cache.v])

    def encode(self, win_k, win_v):
        """[L, ..., H, D] payloads -> what lands in each pool array."""
        if not self.quantized:
            return [np.asarray(jnp.asarray(w).astype(self.dtype))
                    for w in (win_k, win_v)]
        from accelerate_tpu.ops.quant import kv_quantize_rows

        (ck, sk), (cv, sv) = (kv_quantize_rows(jnp.asarray(w))
                              for w in (win_k, win_v))
        return [np.asarray(a) for a in (ck, cv, sk, sv)]

    def put_row(self, page, row, encoded, at):
        """`encoded[i][(slice(None),) + at]` -> view row `row` of `page`."""
        for pool, enc in zip(self.pools, encoded):
            pool[:, page, :, row % self.ps] = enc[(slice(None),) + at]

    def assert_matches(self, cache, frozen=()):
        """Every page but the trash page is bit-identical (codes AND
        scales), and the `frozen` pages still hold their first bytes."""
        assert np.array_equal(np.asarray(cache.lengths), self.lengths)
        for pool, got in zip(self.pools, self._arrays(cache)):
            got = np.asarray(got)
            bits = np.uint16 if got.dtype.itemsize == 2 else np.int8
            np.testing.assert_array_equal(
                got.view(bits)[:, :self.trash], pool.view(bits)[:, :self.trash])
        for first, page in frozen:
            for was, got in zip(first, self._arrays(cache)):
                assert np.array_equal(
                    np.asarray(got[:, page]).view(np.uint8),
                    was[:, page].view(np.uint8))


def _random_pool(kv_dtype, num_slots, max_len, pad_slack, lengths, seed):
    """A pool whose every byte is random, so that a row the write should
    have left alone shows if it did not."""
    cache = PagedKVCache.create(num_layers=2, num_slots=num_slots,
                                max_len=max_len, num_kv_heads=2, head_dim=8,
                                page_size=4, pad_slack=pad_slack,
                                kv_dtype=kv_dtype, dtype=jnp.bfloat16)
    rng = np.random.default_rng(seed)

    def like(a):
        if a.dtype == jnp.int8:
            return jnp.asarray(rng.integers(-127, 128, a.shape), jnp.int8)
        return jnp.asarray(rng.normal(size=a.shape), a.dtype)

    fields = {"k": like(cache.k), "v": like(cache.v),
              "lengths": jnp.asarray(lengths, jnp.int32)}
    if cache.quantized:
        fields.update(k_scale=like(cache.k_scale), v_scale=like(cache.v_scale))
    return dataclasses.replace(cache, **fields), rng


def _replay_append_rows(kv_dtype):
    """Decode appends: offsets 0 and page_size - 1, lanes that cross a
    page boundary, a dead lane (all-trash table), two lanes that share a
    full first page, a lane mid-prefill (not live, real table)."""
    from accelerate_tpu.serving.cache import paged_append_rows

    cache, rng = _random_pool(kv_dtype, 5, 12, 0, [4, 7, 0, 9, 6], seed=25)
    T = cache.trash_page
    table = np.asarray([[0, 1, 2], [0, 3, 4],     # page 0 shared, full
                        [5, 6, T], [T, T, T],     # slot 3 retired
                        [7, 8, 9]], np.int32)
    live = np.asarray([True, True, True, False, False])
    model = _RowPool(cache)
    first = [a.copy() for a in model.pools]
    step = jax.jit(paged_append_rows)
    for _ in range(5):                  # slot 1: offsets 3, 0, 1, ...
        row_k, row_v = (rng.normal(size=(2, 5, 2, 8)).astype(np.float32)
                        for _ in range(2))
        enc = model.encode(row_k, row_v)
        for s in range(5):
            row = int(model.lengths[s])
            model.put_row(table[s, row // 4], row, enc, (s,))
        model.lengths += live
        cache = step(cache, jnp.asarray(table), jnp.asarray(row_k),
                     jnp.asarray(row_v), jnp.asarray(live))
        model.assert_matches(cache, frozen=[(first, 0)])


def _replay_write_slot(kv_dtype):
    """Prefill chunks of 6 rows on 4-row pages: a start inside a page
    after a shared full page, chunks that straddle three pages, one that
    runs into the table's padding, and one whose last page index lies
    past the table (view rows [12, 18) of a 5-page view)."""
    from accelerate_tpu.serving.cache import paged_write_slot

    chunk = 6
    cache, rng = _random_pool(kv_dtype, 3, 14, chunk, [0, 4, 0], seed=26)
    T = cache.trash_page
    assert cache.pages_per_slot == 5
    tables = {1: np.asarray([0, 1, 2, 3, 4], np.int32),   # page 0 shared
              2: np.asarray([0, 5, 6, T, T], np.int32),
              0: np.asarray([7, 8, 9, 10, 11], np.int32)}
    model = _RowPool(cache)
    first = [a.copy() for a in model.pools]
    write = jax.jit(paged_write_slot, static_argnames="chunk")
    # (slot, length before, real tokens): slot 2's second chunk ends in
    # table padding; slot 0's third starts at row 12 of 20
    plan = [(1, 4, 5), (1, 9, 3), (2, 4, 6), (2, 10, 1), (0, 0, 6),
            (0, 6, 6), (0, 12, 2)]
    for slot, length, advance in plan:
        cache = dataclasses.replace(
            cache, lengths=cache.lengths.at[slot].set(length))
        model.lengths[slot] = length
        new_k, new_v = (rng.normal(size=(2, 1, cache.rows, 2, 8))
                        .astype(np.float32) for _ in range(2))
        rows = np.arange(length, length + chunk)
        enc = model.encode(new_k[:, 0, rows], new_v[:, 0, rows])
        for i, row in enumerate(rows):
            model.put_row(tables[slot][row // 4], row, enc, (i,))
        model.lengths[slot] += advance
        cache = write(cache, jnp.asarray(tables[slot]), jnp.int32(slot),
                      jnp.asarray(new_k), jnp.asarray(new_v),
                      jnp.int32(advance), chunk=chunk)
        model.assert_matches(cache, frozen=[(first, 0)])


def _replay_append_window(kv_dtype):
    """Speculative commits of 3-row windows: windows inside one page and
    over two, accepted counts 0 to 3 (rows past the count keep the
    page's bytes), a dead lane with a real table and one with none."""
    from accelerate_tpu.serving.cache import paged_append_window

    cache, rng = _random_pool(kv_dtype, 5, 16, 3, [4, 7, 0, 9, 6], seed=27)
    T = cache.trash_page
    table = np.asarray([[0, 1, 2, 3, 4], [0, 5, 6, 7, T],   # page 0 shared
                        [8, 9, T, T, T], [T, T, T, T, T],
                        [10, 11, 12, T, T]], np.int32)
    live = np.asarray([True, True, True, False, False])
    model = _RowPool(cache)
    first = [a.copy() for a in model.pools]
    commit = jax.jit(paged_append_window)
    for counts in ([3, 1, 0, 2, 3], [1, 3, 3, 3, 0], [2, 2, 1, 0, 1],
                   [3, 3, 3, 3, 3]):
        counts = np.asarray(counts, np.int32)
        win_k, win_v = (rng.normal(size=(2, 5, 3, 2, 8)).astype(np.float32)
                        for _ in range(2))
        enc = model.encode(win_k, win_v)
        for s in range(5):
            for w in range(3):
                row = int(model.lengths[s]) + w
                valid = live[s] and w < counts[s]
                model.put_row(table[s, row // 4] if valid else T, row, enc,
                              (s, w))
        model.lengths += np.where(live, counts, 0)
        cache = commit(cache, jnp.asarray(table), jnp.asarray(win_k),
                       jnp.asarray(win_v), jnp.asarray(counts),
                       jnp.asarray(live))
        model.assert_matches(cache, frozen=[(first, 0)])


@pytest.mark.parametrize("kv_dtype", [None, "int8"], ids=["bf16", "int8"])
@pytest.mark.parametrize("replay", [_replay_append_rows, _replay_write_slot,
                                    _replay_append_window],
                         ids=["append_rows", "write_slot", "append_window"])
def test_page_granular_writes_match_the_row_by_row_pool(replay, kv_dtype):
    """`_scatter_rows` rewrites whole pages (gather, select, write back
    by page index) where it used to scatter single rows. The result has
    to be the same pool, bit for bit, in codes and in scales: rows of a
    touched page that are not written are put back as they were, never
    re-encoded; shared full pages are never touched; dead lanes change
    the trash page only (the one page the comparison leaves out)."""
    replay(kv_dtype)


@pytest.mark.parametrize("kv_dtype,window", [
    (None, None), ("int8", None), (None, 5)],
    ids=["bf16", "int8", "bf16-ring"])
def test_the_rows_taking_write_is_the_whole_view_write(kv_dtype, window):
    """`paged_write_chunk` takes the chunk's rows; `paged_write_slot` takes
    them out of whole updated views and calls it. Handed the same rows
    (a ring's: the view's rows modulo R) both leave the same pool, bit
    for bit, codes and scales: over a start inside a page behind a shared
    one, chunks that straddle three pages, a chunk whose padding runs
    past the slot's pages, and a ring that wraps inside the chunk."""
    from accelerate_tpu.serving.cache import (
        paged_write_chunk,
        paged_write_slot,
    )

    chunk = 6
    cache, rng = _random_pool(kv_dtype, 3, 30, chunk, [0, 4, 0], seed=39)
    if window is not None:
        ring = PagedKVCache.create(
            num_layers=2, num_slots=3, max_len=30, num_kv_heads=2,
            head_dim=8, page_size=4, pad_slack=chunk, dtype=jnp.bfloat16,
            window=window)
        assert ring.pages_per_slot == 4 and ring.rows == 16
        cache = dataclasses.replace(
            ring, k=cache.k[:, :ring.k.shape[1]],
            v=cache.v[:, :ring.v.shape[1]], lengths=cache.lengths)
    T, P, R = cache.trash_page, cache.pages_per_slot, cache.rows
    own = {1: [0, 1, 2, 3, 4, 5, 6, 7, 8], 2: [0, 9, 10], 0: [11, 12, 13, 14]}
    tables = {slot: np.asarray((pages + [T] * P)[:P], np.int32)
              for slot, pages in own.items()}
    whole = jax.jit(paged_write_slot, static_argnames="chunk")
    by_rows = jax.jit(paged_write_chunk)
    # (slot, length before, real tokens); a ring wraps at 16 rows
    plan = [(1, 4, 5), (1, 9, 6), (1, 15, 6), (1, 27, 3), (2, 4, 6),
            (2, 10, 1), (0, 0, 6), (0, 13, 2)]
    a = b = cache
    for slot, length, advance in plan:
        a, b = (dataclasses.replace(
            c, lengths=c.lengths.at[slot].set(length)) for c in (a, b))
        new_k, new_v = (jnp.asarray(rng.normal(size=(2, 1, R, 2, 8)),
                                    jnp.bfloat16) for _ in range(2))
        rows = np.arange(length, length + chunk)
        rows = rows % R if window is not None else rows
        a = whole(a, jnp.asarray(tables[slot]), jnp.int32(slot), new_k,
                  new_v, jnp.int32(advance), chunk=chunk)
        b = by_rows(b, jnp.asarray(tables[slot]), jnp.int32(slot),
                    new_k[:, :, rows], new_v[:, :, rows], jnp.int32(advance))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert not np.array_equal(np.asarray(a.k), np.asarray(cache.k))
