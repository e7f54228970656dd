"""Copy-on-write paged-prefix-cache tests (ISSUE 14): refcounted
allocator semantics, fork sharing/tail-copy, the allocator-driven eviction
of parked cache pages, trie donate/probe/evict invariants, a randomized
fork/free/donate property test, engine-level prefix hits with exact token
identity, best-of-N fork parity + page amplification, eviction under
pressure keeping live block tables intact, and the chaos-marked
crash-with-live-forks regression."""

import numpy as np
import pytest

from thunder_tpu import observe
from thunder_tpu.models import llama
from thunder_tpu.runtime import faults, quarantine
from thunder_tpu.runtime.faults import FaultPlan, FaultSpec
from thunder_tpu.serving import (
    EngineSupervisor,
    OutOfPages,
    PagedKVCache,
    PageGeometry,
    PrefixCache,
    SamplingParams,
    ServingEngine,
)


@pytest.fixture(autouse=True)
def _clean():
    quarantine.reset()
    yield
    quarantine.reset()
    faults.clear()


def _geometry(**kw):
    defaults = dict(n_layers=1, kv_heads=2, head_dim=16, page_size=8,
                    num_pages=16, pages_per_request=6)
    defaults.update(kw)
    return PageGeometry(**defaults)


def _cache(**kw):
    import jax.numpy as jnp

    return PagedKVCache(_geometry(**kw), jnp.float32)


@pytest.fixture(scope="module")
def model():
    cfg = llama.CONFIGS["tiny-gqa"]
    return cfg, llama.init_params(cfg, seed=0, scale_layers=1)


def _engine(params, cfg, **kw):
    defaults = dict(max_slots=3, page_size=16, max_context=128, n_layers=1,
                    prefill_chunk=32)
    defaults.update(kw)
    return ServingEngine(params, cfg, **defaults)


def _refs(params, cfg, prompts, max_new):
    return [np.asarray(llama.generate(params, cfg, p[None], max_new,
                                      n_layers=1))[0]
            for p in prompts]


# ---------------------------------------------------------------------------
# refcounted allocator + COW fork
# ---------------------------------------------------------------------------

class TestRefcounts:
    def test_retain_free_last_reference_wins(self):
        cache = _cache()
        a = cache.alloc(3)
        cache.retain(a)
        assert all(cache.refcount(p) == 2 for p in a)
        cache.free(a)                         # first drop: still live
        assert cache.pages_free == cache.pages_total - 3
        cache.free(a)                         # last drop: back on the list
        assert cache.pages_free == cache.pages_total
        cache.assert_quiescent()

    def test_overfree_and_free_page_ops_rejected(self):
        cache = _cache()
        a = cache.alloc(2)
        with pytest.raises(ValueError, match="double free"):
            cache.free(a + a)                 # 2 drops against 1 reference
        cache.free(a)
        with pytest.raises(ValueError, match="double free"):
            cache.free([a[0]])
        with pytest.raises(ValueError, match="retain of free"):
            cache.retain([a[0]])
        with pytest.raises(ValueError, match="invalid page"):
            cache.free([0])                   # the reserved scratch page

    def test_fork_shares_full_pages_copies_partial_tail(self):
        cache = _cache()
        pages = cache.alloc(3)                # 17 tokens: 2 full + partial
        forked = cache.fork(pages, 17)
        assert forked[:2] == pages[:2]        # full pages shared...
        assert forked[2] != pages[2]          # ...partial tail copied
        assert cache.cow_copies == 1
        assert all(cache.refcount(p) == 2 for p in pages[:2])
        assert cache.refcount(forked[2]) == 1
        cache.free(forked)
        cache.free(pages)
        cache.assert_quiescent()

    def test_fork_page_aligned_context_copies_nothing(self):
        cache = _cache()
        pages = cache.alloc(2)                # 16 tokens: exactly 2 pages
        forked = cache.fork(pages, 16)
        assert forked == pages and cache.cow_copies == 0
        cache.free(forked)
        cache.free(pages)
        cache.assert_quiescent()

    def test_fork_atomic_on_out_of_pages(self):
        cache = _cache(num_pages=5)           # 4 allocatable
        pages = cache.alloc(3)
        cache.alloc(1)                        # pool now empty
        with pytest.raises(OutOfPages):
            cache.fork(pages, 17)             # tail copy can't allocate
        # the failed fork released its shared retains (atomicity)
        assert all(cache.refcount(p) == 1 for p in pages)

    def test_assert_quiescent_reports_live_refcounts(self):
        cache = _cache()
        held = cache.alloc(2)
        cache.retain([held[0]])
        with pytest.raises(AssertionError, match="leak"):
            cache.assert_quiescent()
        cache.free(held)
        cache.free([held[0]])
        cache.assert_quiescent()


class TestParkedPages:
    def test_registered_page_parks_and_reclaims(self):
        cache = _cache()
        a = cache.alloc(2)
        cache.register_cached(a[0])
        cache.free(a)
        assert cache.pages_free == cache.pages_total - 1
        assert cache.cached_pages == 1
        cache.assert_quiescent()              # parked pages are accounted
        # allocator pressure reclaims the parked page (no evict_cb set)
        got = cache.alloc(cache.pages_total)
        assert a[0] in got and cache.cached_pages == 0
        cache.free(got)
        cache.assert_quiescent()

    def test_can_alloc_counts_parked_pages(self):
        cache = _cache()
        a = cache.alloc(cache.pages_total)
        for p in a[:4]:
            cache.register_cached(p)
        cache.free(a)
        assert cache.pages_free == cache.pages_total - 4
        assert cache.can_alloc(cache.pages_total)     # parked reclaimable
        assert not cache.can_alloc(cache.pages_total + 1)

    def test_retain_unparks_a_cached_page(self):
        cache = _cache()
        [p] = cache.alloc(1)
        cache.register_cached(p)
        cache.free([p])
        assert cache.cached_pages == 1
        cache.retain([p])                     # a prefix hit claims it
        assert cache.cached_pages == 0 and cache.refcount(p) == 1
        cache.free([p])
        assert cache.cached_pages == 1        # parks again on release
        cache.alloc(cache.pages_total)        # reclaim everything


# ---------------------------------------------------------------------------
# the trie
# ---------------------------------------------------------------------------

def _tok(*chunks):
    return np.concatenate([np.asarray(c, np.int32) for c in chunks])


class TestPrefixTrie:
    def test_donate_probe_roundtrip_capped_below_prompt_end(self):
        cache = _cache(page_size=4)
        trie = PrefixCache(cache)
        pages = cache.alloc(3)
        tokens = _tok(range(10))              # 2 full pages + partial
        assert trie.donate(tokens, pages) == 2
        cache.free(pages)                     # full pages park, tail frees
        assert cache.cached_pages == 2
        # identical prompt: hit both full pages... but never the whole
        # prompt — an exactly-8-token probe leaves its last page out so
        # the tail always re-prefills
        assert trie.lookup(tokens) == pages[:2]
        assert trie.lookup(_tok(range(8))) == pages[:1]
        # diverging second page: one-page hit
        assert trie.lookup(_tok(range(4), [9, 9, 9, 9], range(4))) == \
            pages[:1]
        assert trie.lookup(_tok([5, 5, 5, 5, 5])) == []

    def test_duplicate_donor_keeps_incumbent(self):
        cache = _cache(page_size=4)
        trie = PrefixCache(cache)
        a = cache.alloc(2)
        b = cache.alloc(2)
        tokens = _tok(range(9))
        assert trie.donate(tokens, a) == 2
        assert trie.donate(tokens, b) == 0    # same content: no-op
        cache.free(a)
        cache.free(b)                         # unregistered: straight to free
        assert cache.cached_pages == 2
        assert trie.lookup(tokens) == a

    def test_eviction_drops_subtree_oldest_first(self):
        cache = _cache(page_size=4, num_pages=8)   # 7 allocatable
        trie = PrefixCache(cache)
        chain = cache.alloc(3)
        trie.donate(_tok(range(12), [1]), chain)   # 3-node chain
        cache.free(chain)
        assert cache.cached_pages == 3
        observe.enable(clear=True)
        try:
            got = cache.alloc(6)              # forces subtree eviction
            snap = observe.snapshot()
        finally:
            observe.disable()
        assert len(got) == 6
        assert snap["counters"]["serving.cache_evictions"] == 3
        assert trie.lookup(_tok(range(12), [1])) == []
        assert trie.registered_pages == 0
        cache.free(got)
        cache.assert_quiescent()

    def test_live_hit_pins_chain_against_eviction(self):
        cache = _cache(page_size=4, num_pages=8)
        trie = PrefixCache(cache)
        chain = cache.alloc(2)
        trie.donate(_tok(range(8), [1]), chain)
        cache.free(chain)
        hit = trie.probe(_tok(range(8), [2, 3]))   # claims both pages
        assert hit == chain
        got = cache.alloc(5)                  # everything else
        with pytest.raises(OutOfPages):
            cache.alloc(1)                    # claimed pages NOT evictable
        assert trie.lookup(_tok(range(8), [9])) == chain   # trie intact
        cache.free(hit)
        cache.free(got)
        cache.assert_quiescent()


def test_allocator_property_random_fork_free_donate():
    """Randomized allocator soak: interleaved alloc/fork/free/donate under
    a model of held tables. Invariants after every op: refcounts match the
    model exactly, live+free+parked partitions the pool, and the final
    teardown is quiescent — refcounts can never go negative (over-frees
    raise) and no page is ever lost or double-owned."""
    rng = np.random.RandomState(0)
    cache = _cache(num_pages=24, page_size=4)
    trie = PrefixCache(cache)
    tables: list[tuple[list, int]] = []       # (pages, length)
    donated = 0
    for step in range(300):
        op = rng.randint(4)
        if op == 0 and cache.can_alloc(3):    # new table
            n = int(rng.randint(1, 4))
            if cache.can_alloc(n):
                length = int(rng.randint((n - 1) * 4 + 1, n * 4 + 1))
                tables.append((cache.alloc(n), length))
        elif op == 1 and tables:              # fork a table
            pages, length = tables[rng.randint(len(tables))]
            try:
                tables.append((cache.fork(pages, length), length))
            except OutOfPages:
                pass
        elif op == 2 and tables:              # free a table
            pages, _ = tables.pop(rng.randint(len(tables)))
            cache.free(pages)
        elif op == 3 and tables:              # donate a table's full pages
            pages, length = tables[rng.randint(len(tables))]
            tokens = np.arange(donated * 1000,
                               donated * 1000 + length, dtype=np.int32)
            donated += 1
            trie.donate(tokens, pages)
        # invariant: refcount model == sum of table references
        model: dict[int, int] = {}
        for pages, _ in tables:
            for p in pages:
                model[p] = model.get(p, 0) + 1
        for p in range(1, cache.geometry.num_pages):
            assert cache.refcount(p) == model.get(p, 0), (step, p)
        live = sum(1 for p in range(1, cache.geometry.num_pages)
                   if cache.refcount(p) > 0)
        assert live + cache.pages_free + cache.cached_pages == \
            cache.pages_total, step
    for pages, _ in tables:
        cache.free(pages)
    cache.assert_quiescent()


# ---------------------------------------------------------------------------
# engine-level: prefix hits, best-of-N, eviction, crash recovery
# ---------------------------------------------------------------------------

class TestEnginePrefix:
    def test_warm_hits_skip_prefill_and_stay_token_identical(self, model):
        """Shared-system-prompt workload: the cold round donates, warm
        requests probe-hit the system pages, prefill one tail chunk
        instead of the whole prompt, and still produce generate()'s exact
        greedy tokens."""
        cfg, params = model
        rng = np.random.RandomState(0)
        sysp = rng.randint(1, cfg.vocab_size, size=64).astype(np.int32)
        prompts = [np.concatenate(
            [sysp, rng.randint(1, cfg.vocab_size, size=8).astype(np.int32)])
            for _ in range(4)]
        refs = _refs(params, cfg, prompts, 6)
        observe.enable(clear=True)
        try:
            eng = _engine(params, cfg, prefix_cache=True)
            cold = eng.submit(prompts[0], 6)
            eng.drain()
            warm = [eng.submit(p, 6) for p in prompts[1:]]
            eng.drain()
            snap = observe.snapshot()
        finally:
            observe.disable()
        assert cold.prefix_hit_tokens == 0
        np.testing.assert_array_equal(cold.output(), refs[0])
        for r, ref in zip(warm, refs[1:]):
            assert r.prefix_hit_tokens == 64      # the full system prompt
            assert r.prefill_chunks == 1          # ONE tail chunk, not 3
            np.testing.assert_array_equal(r.output(), ref)
        assert cold.prefill_chunks == 3           # 32+32+8->16... the cold path
        assert snap["gauges"]["serving.prefix_hit_rate"] > 0.5
        assert snap["gauges"]["serving.cached_pages"] >= 4
        eng.assert_quiescent()                    # parked pages accounted

    def test_best_of_parity_and_page_amplification(self, model):
        """best_of=N over one prompt equals N independent requests with
        the forked seeds token-for-token, while allocating FAR fewer pages
        (full prompt pages shared; only tail copies + decode pages are
        new). The ISSUE acceptance: best-of-4 < 1.5x best-of-1 pages."""
        cfg, params = model
        rng = np.random.RandomState(1)
        p = rng.randint(1, cfg.vocab_size, size=100).astype(np.int32)
        sp = SamplingParams(temperature=0.9, top_k=40, seed=7)
        b4 = _engine(params, cfg, max_slots=4, max_context=128)
        prim = b4.submit(p, 8, sampling=sp, best_of=4)
        b4.drain()
        assert [r.done for r in prim.fork_group] == [True] * 4
        pages_b4 = b4.cache.pages_allocated
        assert b4.cache.cow_copies == 3           # 100 % 16 != 0: tail copies
        b1 = _engine(params, cfg, max_slots=4, max_context=128)
        b1.submit(p, 8, sampling=sp)
        b1.drain()
        pages_b1 = b1.cache.pages_allocated
        assert pages_b4 < 1.5 * pages_b1, (pages_b4, pages_b1)
        indep = _engine(params, cfg, max_slots=4, max_context=128)
        reqs = [indep.submit(p, 8, sampling=sp.fork(i) if i else sp)
                for i in range(4)]
        indep.drain()
        for fork_r, ind_r in zip(prim.fork_group, reqs):
            np.testing.assert_array_equal(fork_r.output(), ind_r.output())
        # N independent requests allocate ~N full prompts
        assert indep.cache.pages_allocated > 2 * pages_b4
        b4.assert_quiescent()

    def test_eviction_under_pressure_keeps_live_tables_intact(self, model):
        """Allocator pressure evicts parked cache pages — never a live
        request's: a resident decoding request keeps exact tokens while a
        page-hungry newcomer forces the parked prefix out."""
        cfg, params = model
        rng = np.random.RandomState(2)
        donor_p = rng.randint(1, cfg.vocab_size, size=48).astype(np.int32)
        live_p = rng.randint(1, cfg.vocab_size, size=20).astype(np.int32)
        big_p = rng.randint(1, cfg.vocab_size, size=64).astype(np.int32)
        refs = _refs(params, cfg, [donor_p, live_p, big_p], 8)
        observe.enable(clear=True)
        try:
            # pool: 9 pages. donor parks 3; live holds ~2; big grows to 5
            # — the free list runs dry and parked pages must evict
            eng = _engine(params, cfg, max_slots=2, num_pages=10,
                          prefix_cache=True)
            donor = eng.submit(donor_p, 8)
            eng.drain()
            assert eng.cache.cached_pages == 3
            live = eng.submit(live_p, 8)
            big = eng.submit(big_p, 8)
            eng.drain()
            snap = observe.snapshot()
        finally:
            observe.disable()
        assert snap["counters"].get("serving.cache_evictions", 0) >= 1
        for r, ref in zip((donor, live, big), refs):
            np.testing.assert_array_equal(r.output(), ref)
        eng.assert_quiescent()

    def test_page_aligned_donation_never_caches_the_unwritten_final_row(
            self, model):
        """Regression: a completed request's FINAL token has no K/V row
        (it was sampled, never fed back), so a page-aligned work_prompt
        must donate one page fewer — caching that page would hand a
        garbage row to any longer prompt extending the donor's tokens."""
        cfg, params = model
        rng = np.random.RandomState(6)
        eng = _engine(params, cfg, prefix_cache=True)
        p = rng.randint(1, cfg.vocab_size, size=24).astype(np.int32)
        donor = eng.submit(p, 8)                 # work_prompt = 32: aligned
        eng.drain()
        assert len(donor.work_prompt) % eng.geom.page_size == 0
        # only the page whose rows are ALL written may be cached
        assert eng.cache.cached_pages == 1
        ext = np.concatenate(
            [p, np.asarray(donor.output(), np.int32),
             rng.randint(1, cfg.vocab_size, size=8).astype(np.int32)])
        ref = _refs(params, cfg, [ext], 6)[0]
        r = eng.submit(ext, 6)                   # extends the donor's tokens
        eng.drain()
        assert r.prefix_hit_tokens == eng.geom.page_size
        np.testing.assert_array_equal(r.output(), ref)
        eng.assert_quiescent()

    def test_spilled_clones_respect_the_queue_bound(self, model):
        """Regression: never-forked best-of clones spilling to the queue at
        the primary's completion must respect ``max_queue`` — overflow
        sheds typed instead of silently growing the queue past the
        overload bound ``submit()`` enforces for everyone else."""
        from thunder_tpu.serving import AdmissionRejected

        cfg, params = model
        rng = np.random.RandomState(5)
        p = rng.randint(1, cfg.vocab_size, size=20).astype(np.int32)
        # ONE slot: clones can never fork (the primary occupies it), so at
        # the primary's completion both spill — but the queue holds 1
        eng = _engine(params, cfg, max_slots=1, max_queue=1)
        prim = eng.submit(p, 4, best_of=3,
                          sampling=SamplingParams(temperature=0.7, seed=3))
        eng.drain()
        states = sorted(("done" if r.done else "shed")
                        for r in prim.fork_group)
        assert states == ["done", "done", "shed"]
        shed = [r for r in prim.fork_group if r.failed]
        assert isinstance(shed[0].error, AdmissionRejected)
        assert "queue is full" in str(shed[0].error)
        eng.assert_quiescent()

    def test_fork_respects_priority_ordered_slots(self, model):
        """Regression: a pending best-of clone must not grab a freed slot
        ahead of a strictly higher-priority queued request — clones count
        as ordinary requests for slot acquisition too (equal priority
        still favors the clone: it is older traffic)."""
        cfg, params = model
        rng = np.random.RandomState(7)
        p = rng.randint(1, cfg.vocab_size, size=20).astype(np.int32)
        hp = rng.randint(1, cfg.vocab_size, size=8).astype(np.int32)
        eng = _engine(params, cfg, max_slots=2)
        prim = eng.submit(p, 10, best_of=3,
                          sampling=SamplingParams(temperature=0.8, seed=5))
        # the first step prefills, forks the first clone into the free slot
        # and decodes both replay rows; two more decode steps follow
        for _ in range(3):
            eng.step()
        assert sum(r.state == "decode" for r in prim.fork_group) == 2
        assert len(prim.fork_pending) == 1
        high = eng.submit(hp, 4, priority=5)
        eng.drain()
        clone2 = prim.fork_group[2]
        assert high.done and all(r.done for r in prim.fork_group)
        # the next freed slot went to the higher-priority request
        assert high.admit_seq < clone2.admit_seq
        eng.assert_quiescent()

    @pytest.mark.chaos
    def test_crash_with_live_forks_recovers_and_quiesces(self, model):
        """ISSUE 14 satellite: an engine crash (``serving:engine`` domain —
        donated pools consumed) while best-of forks are LIVE releases every
        forked page through the refcount path, the supervisor restart
        re-prefills the branches, outputs stay identical to a fault-free
        run, and the rebuilt pool is quiescent."""
        cfg, params = model
        rng = np.random.RandomState(3)
        p = rng.randint(1, cfg.vocab_size, size=40).astype(np.int32)
        sp = SamplingParams(temperature=0.8, top_k=25, seed=11)
        clean = _engine(params, cfg, max_slots=4, prefix_cache=True)
        ref_prim = clean.submit(p, 8, sampling=sp, best_of=3)
        clean.drain()
        refs = [r.output() for r in ref_prim.fork_group]
        eng = _engine(params, cfg, max_slots=4, prefix_cache=True)
        sup = EngineSupervisor(eng)
        prim = eng.submit(p, 8, sampling=sp, best_of=3)
        # let the forks materialize (they do in the first step, behind the
        # prefill) and decode a little, THEN crash
        for _ in range(4):
            sup.step()
        assert sum(r.state == "decode" for r in prim.fork_group) >= 2
        with faults.active(FaultPlan(
                [FaultSpec("serving:engine", max_fires=1)])):
            sup.drain()
        assert eng.runner is not None
        for r, ref in zip(prim.fork_group, refs):
            assert r.done
            np.testing.assert_array_equal(r.output(), ref)
        assert any(r.restarts for r in prim.fork_group)
        eng.assert_quiescent()


# ---------------------------------------------------------------------------
# marker audit: keep these tests inside the tier-1 budget
# ---------------------------------------------------------------------------

def test_no_slow_marker_here():
    import os

    with open(os.path.abspath(__file__)) as f:
        src = f.read()
    marker = "mark." + "slow"   # split so this line doesn't trip the scan
    assert marker not in src, "prefix-cache tests must stay in tier-1"
