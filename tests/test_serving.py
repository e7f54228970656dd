"""Serving engine tests: paged KV allocator, ragged paged decode attention
parity (kernel + decomposition vs the dense full-cache path), continuous
batching correctness vs ``generate()``, preemption, and chaos (step-domain
fault injection, quarantine fallback)."""

import math

import numpy as np
import pytest

import thunder_tpu as tt
from thunder_tpu import observe
from thunder_tpu.models import llama
from thunder_tpu.ops import nn as tnn
from thunder_tpu.runtime import faults, quarantine
from thunder_tpu.runtime.faults import FaultPlan, FaultSpec
from thunder_tpu.serving import (
    AdmissionRejected,
    DeadlineExceeded,
    EngineStallError,
    InfeasibleRequest,
    OutOfPages,
    PagedKVCache,
    PageGeometry,
    ServingEngine,
)


@pytest.fixture(autouse=True)
def _clean_quarantine():
    quarantine.reset()
    yield
    quarantine.reset()
    faults.clear()


def _geometry(**kw):
    defaults = dict(n_layers=1, kv_heads=2, head_dim=16, page_size=8,
                    num_pages=12, pages_per_request=4)
    defaults.update(kw)
    return PageGeometry(**defaults)


# ---------------------------------------------------------------------------
# allocator
# ---------------------------------------------------------------------------

class TestPagedKVCache:
    def test_alloc_free_reuse(self):
        import jax.numpy as jnp

        cache = PagedKVCache(_geometry(), jnp.float32)
        assert cache.pages_total == 11          # page 0 reserved
        a = cache.alloc(3)
        assert len(a) == 3 and 0 not in a
        assert cache.pages_free == 8
        cache.free(a)
        assert cache.pages_free == 11
        b = cache.alloc(11)                     # whole pool allocatable
        assert sorted(b) == list(range(1, 12))
        cache.free(b)

    def test_out_of_pages_and_peak(self):
        import jax.numpy as jnp

        cache = PagedKVCache(_geometry(), jnp.float32)
        a = cache.alloc(10)
        with pytest.raises(OutOfPages):
            cache.alloc(2)
        assert cache.peak_pages_used == 10
        cache.free(a)
        assert cache.peak_pages_used == 10      # high-water sticks
        cache.reset_peak()
        assert cache.peak_pages_used == 0

    def test_double_free_and_bad_page_rejected(self):
        import jax.numpy as jnp

        cache = PagedKVCache(_geometry(), jnp.float32)
        a = cache.alloc(2)
        cache.free(a)
        with pytest.raises(ValueError, match="double free"):
            cache.free([a[0]])
        with pytest.raises(ValueError, match="invalid page"):
            cache.free([0])                     # the reserved scratch page

    def test_assert_quiescent_leak_audit(self):
        import jax.numpy as jnp

        cache = PagedKVCache(_geometry(), jnp.float32)
        cache.assert_quiescent()                       # fresh pool is clean
        held = cache.alloc(2)
        with pytest.raises(AssertionError, match="leak"):
            cache.assert_quiescent()
        cache.free(held)
        cache.assert_quiescent(np.zeros((3, 4), np.int32))
        with pytest.raises(AssertionError, match="block-table"):
            cache.assert_quiescent(np.asarray([[0, 3, 0, 0]], np.int32))
        cache._free_set.discard(cache._free[0])        # corrupt the mirror
        with pytest.raises(AssertionError, match="mirror"):
            cache.assert_quiescent()

    def test_pools_alive_detects_consumed_buffers(self):
        import jax.numpy as jnp

        cache = PagedKVCache(_geometry(), jnp.float32)
        assert cache.pools_alive()
        cache.pools[0]["k"].delete()                   # donated-and-consumed
        assert not cache.pools_alive()

    def test_pool_shapes(self):
        import jax.numpy as jnp

        g = _geometry(n_layers=3)
        cache = PagedKVCache(g, jnp.bfloat16)
        assert len(cache.pools) == 3
        assert cache.pools[0]["k"].shape == (2, 12, 8, 16)
        assert cache.pools[0]["v"].dtype == jnp.bfloat16
        assert g.pages_for(1) == 1 and g.pages_for(8) == 1
        assert g.pages_for(9) == 2 and g.max_context == 32


# ---------------------------------------------------------------------------
# paged decode attention parity vs the dense full-cache path
# ---------------------------------------------------------------------------

def _dense_reference(q, k_pages, v_pages, bt, lengths):
    """Dense full-cache masked attention over the gathered context —
    numerically the ``forward_step`` attention path the engine replaces."""
    B, H, T, hd = q.shape
    KV, P, ps, _ = k_pages.shape
    n_rep = H // KV
    L = bt.shape[1] * ps
    out = np.zeros((B, H, T, hd), np.float32)
    for b in range(B):
        kctx = k_pages[:, bt[b]].reshape(KV, L, hd).astype(np.float32)
        vctx = v_pages[:, bt[b]].reshape(KV, L, hd).astype(np.float32)
        for h in range(H):
            s = (q[b, h].astype(np.float32) @ kctx[h // n_rep].T
                 / math.sqrt(hd))
            for r in range(T):
                s[r, int(lengths[b]) - T + r + 1:] = -np.inf
            p = np.exp(s - s.max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            out[b, h] = p @ vctx[h // n_rep]
    return out


def _paged_inputs(dtype, seed=0, B=3, H=4, KV=2, hd=16, ps=8, P=12, npg=4):
    rng = np.random.RandomState(seed)
    q = (rng.rand(B, H, 1, hd) - 0.5).astype(dtype)
    kp = (rng.rand(KV, P, ps, hd) - 0.5).astype(dtype)
    vp = (rng.rand(KV, P, ps, hd) - 0.5).astype(dtype)
    bt = np.stack([rng.permutation(np.arange(1, P))[:npg]
                   for _ in range(B)]).astype(np.int32)
    lengths = np.asarray([1, 13, npg * ps], np.int32)  # ragged incl. len-1
    return q, kp, vp, bt, lengths


def _paged_fn(q, k, v, bt, ln):
    return tnn.paged_decode_attention(q, k, v, bt, ln)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_paged_decode_decomposition_matches_dense(dtype):
    import jax.numpy as jnp

    np_dtype = np.float32 if dtype == "float32" else np.dtype(jnp.bfloat16)
    q, kp, vp, bt, ln = _paged_inputs(np_dtype)
    out = np.asarray(tt.jit(_paged_fn)(q, kp, vp, bt, ln))
    ref = _dense_reference(np.asarray(q, np.float32),
                           np.asarray(kp, np.float32),
                           np.asarray(vp, np.float32), bt, ln)
    tol = 2e-2 if dtype == "bfloat16" else 1e-5
    np.testing.assert_allclose(out.astype(np.float32), ref, atol=tol, rtol=tol)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_paged_decode_kernel_matches_dense(dtype, monkeypatch):
    import jax.numpy as jnp

    monkeypatch.setenv("THUNDER_TPU_PALLAS_INTERPRET", "1")
    np_dtype = np.float32 if dtype == "float32" else np.dtype(jnp.bfloat16)
    q, kp, vp, bt, ln = _paged_inputs(np_dtype, seed=1)
    jf = tt.jit(_paged_fn)
    out = np.asarray(jf(q, kp, vp, bt, ln))
    # the Pallas scalar-prefetch kernel claimed the composite
    names = _symbol_names(tt.last_execution_trace(jf))
    assert "pallas_paged_decode_attention" in names
    ref = _dense_reference(np.asarray(q, np.float32),
                           np.asarray(kp, np.float32),
                           np.asarray(vp, np.float32), bt, ln)
    tol = 2e-2 if dtype == "bfloat16" else 1e-5
    np.testing.assert_allclose(out.astype(np.float32), ref, atol=tol, rtol=tol)


def test_paged_prefill_rows_masked_per_row(monkeypatch):
    """T > 1 (chunked prefill): per-row ragged causal masking, and the
    kernel checker must NOT claim (decode-only kernel)."""
    monkeypatch.setenv("THUNDER_TPU_PALLAS_INTERPRET", "1")
    rng = np.random.RandomState(2)
    B, H, KV, hd, ps, P, npg, T = 1, 4, 2, 16, 8, 12, 4, 8
    q = (rng.rand(B, H, T, hd) - 0.5).astype(np.float32)
    kp = (rng.rand(KV, P, ps, hd) - 0.5).astype(np.float32)
    vp = (rng.rand(KV, P, ps, hd) - 0.5).astype(np.float32)
    bt = np.asarray([[1, 2, 3, 4]], np.int32)
    ln = np.asarray([19], np.int32)             # rows at positions 11..18
    jf = tt.jit(_paged_fn)
    out = np.asarray(jf(q, kp, vp, bt, ln))
    assert "pallas_paged_decode_attention" not in \
        _symbol_names(tt.last_execution_trace(jf))
    ref = _dense_reference(q, kp, vp, bt, ln)
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


def _symbol_names(trc):
    names = set()

    def walk(bsyms):
        for b in bsyms:
            names.add(b.sym.codegen_name())
            walk(b.subsymbols)

    walk(trc.bound_symbols)
    return names


@pytest.mark.chaos
def test_paged_decode_quarantine_falls_back_per_op(monkeypatch):
    """A dying paged-decode kernel quarantines and recompiles to the XLA
    decomposition with equal numerics (the PR7 containment contract)."""
    monkeypatch.setenv("THUNDER_TPU_PALLAS_INTERPRET", "1")
    q, kp, vp, bt, ln = _paged_inputs(np.float32, seed=3)
    ref = np.asarray(tt.jit(_paged_fn, executors=["xla"])(q, kp, vp, bt, ln))
    jf = tt.jit(_paged_fn)
    with quarantine.containment(), \
            faults.active(FaultPlan(
            [FaultSpec("kernel:pallas.paged_decode_attention")])):
        out = jf(q, kp, vp, bt, ln)             # dies -> quarantine -> XLA
    np.testing.assert_allclose(np.asarray(out), ref, atol=1e-6, rtol=1e-6)
    assert quarantine.is_quarantined("pallas.paged_decode_attention")
    assert "pallas_paged_decode_attention" not in \
        _symbol_names(tt.last_execution_trace(jf))
    np.testing.assert_allclose(np.asarray(jf(q, kp, vp, bt, ln)), ref,
                               atol=1e-6, rtol=1e-6)


# ---------------------------------------------------------------------------
# engine: continuous batching correctness
# ---------------------------------------------------------------------------

def _tiny_engine(params, cfg, **kw):
    defaults = dict(max_slots=3, page_size=16, max_context=64, n_layers=1,
                    prefill_chunk=32)
    defaults.update(kw)
    return ServingEngine(params, cfg, **defaults)


class TestServingEngine:
    @pytest.fixture(scope="class")
    def model(self):
        cfg = llama.CONFIGS["tiny-gqa"]
        return cfg, llama.init_params(cfg, seed=0, scale_layers=1)

    def _references(self, params, cfg, prompts, max_new):
        return [np.asarray(llama.generate(params, cfg, p[None], max_new,
                                          n_layers=1))[0]
                for p in prompts]

    def test_engine_matches_generate_mixed_lengths(self, model):
        """5 mixed-length requests (incl. a 1-token prompt and a chunked
        33-token prompt) through 3 slots: continuous batching, chunked
        prefill, and page growth produce generate()'s exact greedy tokens."""
        cfg, params = model
        rng = np.random.RandomState(0)
        prompts = [rng.randint(1, cfg.vocab_size, size=L).astype(np.int32)
                   for L in (1, 7, 16, 33, 24)]
        refs = self._references(params, cfg, prompts, 6)
        eng = _tiny_engine(params, cfg)
        reqs = [eng.submit(p, 6) for p in prompts]
        eng.drain()
        for r, ref in zip(reqs, refs):
            assert r.done
            np.testing.assert_array_equal(r.output(), ref)
        # completion returned every page to the free list
        assert eng.cache.pages_free == eng.cache.pages_total
        assert eng.cache.peak_pages_used > 0

    def test_preemption_recomputes_and_frees_pages(self, model):
        """With a pool too small for full residency, requests get preempted
        (pages freed immediately) and still finish with exact outputs."""
        cfg, params = model
        rng = np.random.RandomState(1)
        prompts = [rng.randint(1, cfg.vocab_size, size=L).astype(np.int32)
                   for L in (30, 28, 20)]
        refs = self._references(params, cfg, prompts, 8)
        observe.enable(clear=True)
        try:
            eng = _tiny_engine(params, cfg, max_slots=3, page_size=8,
                               num_pages=10, prefill_chunk=16)
            reqs = [eng.submit(p, 8) for p in prompts]
            eng.drain()
            snap = observe.snapshot()
        finally:
            observe.disable()
        assert snap["counters"].get("serving.preempted_requests", 0) >= 1
        assert any(r.preemptions for r in reqs)
        for r, ref in zip(reqs, refs):
            np.testing.assert_array_equal(r.output(), ref)
        assert eng.cache.pages_free == eng.cache.pages_total

    def test_eos_stops_early_and_frees_slot(self, model):
        cfg, params = model
        rng = np.random.RandomState(2)
        p = rng.randint(1, cfg.vocab_size, size=5).astype(np.int32)
        ref = self._references(params, cfg, [p], 8)[0]
        # "eos" = the first token value whose FIRST occurrence is not at
        # position 0 (so the request must decode past the first step)
        j = next(i for i in range(1, len(ref))
                 if int(ref[i]) not in [int(t) for t in ref[:i]])
        eng = _tiny_engine(params, cfg)
        req = eng.submit(p, 8, eos_id=int(ref[j]))
        eng.drain()
        assert req.done and len(req.generated) == j + 1
        np.testing.assert_array_equal(req.output(), ref[:j + 1])
        assert eng.cache.pages_free == eng.cache.pages_total

    def test_submit_capacity_contract(self, model):
        """Infeasible requests fail at submit() with the TYPED error (which
        still subclasses ValueError for pre-SLO callers) — queueing one
        forever is the classic drain() wedge."""
        cfg, params = model
        eng = _tiny_engine(params, cfg)
        with pytest.raises(InfeasibleRequest, match="context window"):
            eng.submit(np.ones(60, np.int32), 10)
        with pytest.raises(ValueError, match="empty prompt"):
            eng.submit(np.ones(0, np.int32), 1)
        small = _tiny_engine(params, cfg, num_pages=3)
        with pytest.raises(InfeasibleRequest, match="KV pages"):
            small.submit(np.ones(40, np.int32), 20)
        assert issubclass(InfeasibleRequest, ValueError)
        assert issubclass(InfeasibleRequest, AdmissionRejected)
        # nothing queued: an infeasible submit must leave no residue that
        # could wedge drain()
        assert not eng.queue and not small.queue
        assert small.drain(max_steps=10) == []

    def test_drain_stall_raises_naming_stuck_requests(self, model):
        """Regression for the drain() wedge: a queued request that can
        never admit (every page externally held — the shape of a leak) must
        raise EngineStallError naming the stuck request, not burn
        max_steps or return silently with work outstanding."""
        cfg, params = model
        eng = _tiny_engine(params, cfg, max_slots=1)
        eng.cache.alloc(eng.cache.pages_free)        # simulate a full hold
        req = eng.submit(np.ones(4, np.int32), 2)
        with pytest.raises(EngineStallError) as ei:
            eng.drain(max_steps=50)
        assert (req.request_id, "queued") in ei.value.stuck
        assert "stalled" in str(ei.value)

    def test_deadline_sheds_queued_and_evicts_resident(self, model):
        """Deadline-aware scheduling: an expired queued request sheds with
        DeadlineExceeded before ever admitting; an expired RESIDENT is
        evicted mid-flight (pages freed). Both count deadline_misses, and
        unaffected requests still produce exact tokens."""
        cfg, params = model
        rng = np.random.RandomState(7)
        p1 = rng.randint(1, cfg.vocab_size, size=5).astype(np.int32)
        p2 = rng.randint(1, cfg.vocab_size, size=7).astype(np.int32)
        ref = self._references(params, cfg, [p1], 6)[0]
        observe.enable(clear=True)
        try:
            eng = _tiny_engine(params, cfg, max_slots=1)
            r1 = eng.submit(p1, 6)
            r2 = eng.submit(p2, 4, deadline_s=0.0)   # expired on arrival
            eng.drain()
            # resident eviction, deterministically: admit r3, then move its
            # deadline into the past mid-decode
            r3 = eng.submit(p2, 8, deadline_s=60.0)
            eng.step()
            assert r3.state in ("prefill", "decode")
            r3.deadline_at = r3.submitted_s          # now in the past
            eng.drain()
            snap = observe.snapshot()
        finally:
            observe.disable()
        assert r1.done
        np.testing.assert_array_equal(r1.output(), ref)
        assert r2.failed and isinstance(r2.error, DeadlineExceeded)
        assert r2.error.request_id == r2.request_id
        assert r3.failed and isinstance(r3.error, DeadlineExceeded)
        assert snap["counters"]["serving.deadline_misses"] == 2
        assert snap["counters"]["serving.shed_requests"] == 2
        assert 0.0 < snap["gauges"]["serving.slo_attainment"] < 1.0
        eng.assert_quiescent()                       # eviction leaked nothing

    def test_bounded_queue_sheds_by_priority(self, model):
        """Priority-ordered load shedding under queue pressure: a full
        bounded queue sheds its lowest-priority request for a higher-
        priority newcomer, and rejects a newcomer that outranks nobody."""
        cfg, params = model
        observe.enable(clear=True)
        try:
            eng = _tiny_engine(params, cfg, max_slots=1, max_queue=2)
            resident = eng.submit(np.ones(4, np.int32), 6)
            eng.step()                               # resident takes the slot
            low = eng.submit(np.ones(4, np.int32), 2, priority=0)
            mid = eng.submit(np.ones(4, np.int32), 2, priority=1)
            high = eng.submit(np.ones(4, np.int32), 2, priority=2)  # sheds low
            assert low.failed and isinstance(low.error, AdmissionRejected)
            with pytest.raises(AdmissionRejected, match="queue full"):
                eng.submit(np.ones(4, np.int32), 2, priority=1)
            done = eng.drain()
            snap = observe.snapshot()
        finally:
            observe.disable()
        assert snap["counters"]["serving.shed_requests"] == 2
        assert mid.done and high.done and resident.done
        # priority-ordered admission: high joined the batch before mid
        assert done.index(high) < done.index(mid) or \
            high.admit_seq < mid.admit_seq
        eng.assert_quiescent()

    def test_zero_queue_bound_rejects_typed(self, model):
        """max_queue=0 closes the queue entirely (admission happens inside
        step(), so every request must pass through it): each submit gets
        the TYPED rejection and is recorded as shed (regression: this used
        to crash with min() on an empty deque)."""
        cfg, params = model
        observe.enable(clear=True)
        try:
            eng = _tiny_engine(params, cfg, max_slots=1, max_queue=0)
            with pytest.raises(AdmissionRejected, match="queue full"):
                eng.submit(np.ones(4, np.int32), 2)
            snap = observe.snapshot()
        finally:
            observe.disable()
        assert len(eng.shed) == 1 and eng.shed[0].failed
        assert snap["counters"]["serving.shed_requests"] == 1
        assert eng.drain(max_steps=5) == []            # nothing wedged
        eng.assert_quiescent()

    def test_page_pressure_never_preempts_higher_priority(self, model):
        """Priority-inversion regression: when the pool runs dry, a
        low-priority request growing its pages must never evict a
        higher-priority resident — it self-preempts instead. Both still
        finish with exact tokens."""
        cfg, params = model
        rng = np.random.RandomState(9)
        p_hi = rng.randint(1, cfg.vocab_size, size=30).astype(np.int32)
        p_lo = rng.randint(1, cfg.vocab_size, size=20).astype(np.int32)
        refs = self._references(params, cfg, [p_hi, p_lo], 8)
        eng = _tiny_engine(params, cfg, max_slots=2, page_size=8,
                           num_pages=7, prefill_chunk=16)
        hi = eng.submit(p_hi, 8, priority=5)
        lo = eng.submit(p_lo, 8, priority=0)
        eng.drain()
        assert hi.preemptions == 0                     # never the victim
        assert lo.preemptions >= 1                     # the pool WAS dry
        np.testing.assert_array_equal(hi.output(), refs[0])
        np.testing.assert_array_equal(lo.output(), refs[1])
        eng.assert_quiescent()

    def test_draining_engine_rejects_admissions(self, model):
        cfg, params = model
        eng = _tiny_engine(params, cfg)
        r = eng.submit(np.ones(3, np.int32), 2)
        eng.stop_admissions()
        with pytest.raises(AdmissionRejected, match="draining"):
            eng.submit(np.ones(3, np.int32), 2)
        eng.drain()
        assert r.done
        eng.assert_quiescent()

    def test_serving_metrics_emitted(self, model):
        cfg, params = model
        rng = np.random.RandomState(3)
        observe.enable(clear=True)
        try:
            eng = _tiny_engine(params, cfg)
            eng.submit(rng.randint(1, cfg.vocab_size, size=9).astype(np.int32), 3)
            eng.drain()
            snap = observe.snapshot()
            rep = observe.explain(eng.runner.decode_jit)
        finally:
            observe.disable()
        for g in ("serving.queue_depth", "serving.active_requests",
                  "serving.kv_pages_free"):
            assert g in snap["gauges"], g
        for h in ("serving.ttft_ms", "serving.decode_ms", "serving.prefill_ms"):
            assert snap["histograms"][h]["count"] >= 1, h
        assert "== serving ==" in rep and "serving.kv_pages_free" in rep

    @pytest.mark.chaos
    def test_request_survives_retried_step(self, model):
        """`step`-domain fault injection: the decode dispatch retries and
        the request completes with the SAME tokens as a fault-free run."""
        cfg, params = model
        rng = np.random.RandomState(4)
        p = rng.randint(1, cfg.vocab_size, size=9).astype(np.int32)
        ref = self._references(params, cfg, [p], 5)[0]
        observe.enable(clear=True)
        try:
            eng = _tiny_engine(params, cfg)
            req = eng.submit(p, 5)
            with faults.active(FaultPlan(
                    [FaultSpec("step", every_n=2, max_fires=2)])):
                eng.drain()
            snap = observe.snapshot()
        finally:
            observe.disable()
        assert snap["counters"].get("runtime.retries", 0) >= 2
        assert req.done
        np.testing.assert_array_equal(req.output(), ref)

    @pytest.mark.chaos
    def test_kernel_quarantine_rebinds_once(self, model, monkeypatch):
        """A dying kernel inside the BOUND decode step quarantines, and the
        scheduler re-binds on the epoch bump — the engine falls back ONCE
        instead of re-entering containment (cache clear + recompile) every
        step. With the block planner on, the decode hot path's claim is the
        whole-decode-layer megakernel, so that is what dies here; the
        paged-attention kernel then serves inside the fallback (its own
        quarantine path is covered per-op above and in
        tests/test_decode_layer.py)."""
        monkeypatch.setenv("THUNDER_TPU_PALLAS_INTERPRET", "1")
        cfg, params = model
        rng = np.random.RandomState(6)
        p = rng.randint(1, cfg.vocab_size, size=9).astype(np.int32)
        ref = self._references(params, cfg, [p], 6)[0]
        eng = _tiny_engine(params, cfg)
        req = eng.submit(p, 6)
        with quarantine.containment(), \
                faults.active(FaultPlan(
                [FaultSpec("kernel:pallas.decode_layer")])):
            eng.drain()
        assert req.done
        np.testing.assert_array_equal(req.output(), ref)
        assert quarantine.is_quarantined("pallas.decode_layer")
        # bounded compiles: claimed entry + containment recompile + one
        # re-bind of the fallback — NOT one recompile per decoded token
        assert tt.compile_stats(eng.runner.decode_jit).cache_misses <= 3

    @pytest.mark.chaos
    def test_eviction_returns_pages_under_faults(self, model):
        """Preemption (eviction) under an active step-fault plan still
        returns every page to the free list (the chaos-marked half of the
        scheduler fault contract)."""
        cfg, params = model
        rng = np.random.RandomState(5)
        prompts = [rng.randint(1, cfg.vocab_size, size=L).astype(np.int32)
                   for L in (30, 28, 20)]
        observe.enable(clear=True)
        try:
            eng = _tiny_engine(params, cfg, max_slots=3, page_size=8,
                               num_pages=10, prefill_chunk=16)
            reqs = [eng.submit(p, 8) for p in prompts]
            with faults.active(FaultPlan(
                    [FaultSpec("step", every_n=5, max_fires=2)])):
                eng.drain()
            snap = observe.snapshot()
        finally:
            observe.disable()
        assert all(r.done for r in reqs)
        assert eng.cache.pages_free == eng.cache.pages_total
        assert snap["counters"].get("serving.preempted_requests", 0) >= 1


# ---------------------------------------------------------------------------
# an iteration is schedule -> prefill -> decode: a prompt that turns resident
# rides the same iteration's decode step as its replay row
# ---------------------------------------------------------------------------

def _first_token_steps():
    return {e["request"]: e["steps"]
            for e in observe.get_registry().events
            if e["kind"] == "serving_first_token"}


def _generate(params, cfg, req):
    """``generate()``'s greedy tokens for a request's prompt and budget."""
    return np.asarray(llama.generate(params, cfg, req.prompt[None],
                                     req.max_new_tokens, n_layers=1))[0]


class TestPrefillBeforeDecode:
    @pytest.fixture(scope="class")
    def model(self):
        cfg = llama.CONFIGS["tiny-gqa"]
        return cfg, llama.init_params(cfg, seed=0, scale_layers=1)

    def _prompt(self, cfg, n, seed):
        return np.random.RandomState(seed).randint(
            1, cfg.vocab_size, size=n).astype(np.int32)

    def test_first_token_when_the_admitting_step_returns(self, model):
        """An empty engine and one with a resident decoding request alike:
        a request submitted before iteration k holds its first token when
        that ``step()`` returns, the resident got its own token from the
        same decode step, and the event says ``steps == 1``."""
        cfg, params = model
        observe.enable(clear=True)
        try:
            eng = _tiny_engine(params, cfg)
            resident = eng.submit(self._prompt(cfg, 5, 0), 10)
            eng.step()
            assert len(resident.generated) == 1     # the first step's own
            eng.step()
            newcomer = eng.submit(self._prompt(cfg, 7, 1), 4)
            two = eng.submit(self._prompt(cfg, 40, 2), 4)  # 32 + 16: a burst
            eng.step()
            assert len(newcomer.generated) == 1 and newcomer.state == "decode"
            assert len(two.generated) == 1
            assert len(resident.generated) == 3
            eng.drain()
            steps = _first_token_steps()
        finally:
            observe.disable()
        assert steps == {resident.request_id: 1, newcomer.request_id: 1,
                         two.request_id: 1}
        for r in (resident, newcomer, two):
            np.testing.assert_array_equal(r.output(),
                                          _generate(params, cfg, r))
        eng.assert_quiescent()

    def test_a_chunk_an_iteration_under_a_well_filled_batch(self, model):
        """More than half the slots decode: prefill advances one chunk an
        iteration, read from the batch BEFORE the iteration's prefill, so a
        3-chunk prompt has its first token after three steps — from the
        step that ran its last chunk — and the event says ``steps == 3``."""
        cfg, params = model
        observe.enable(clear=True)
        try:
            eng = _tiny_engine(params, cfg, prefill_chunk=16)
            residents = [eng.submit(self._prompt(cfg, 5, i), 20)
                         for i in range(2)]
            eng.step()
            assert all(len(r.generated) == 1 for r in residents)
            long = eng.submit(self._prompt(cfg, 40, 9), 3)  # 16 + 16 + 16
            for chunks in (1, 2):
                eng.step()
                assert long.prefill_chunks == chunks
                assert long.state == "prefill" and not long.generated
            eng.step()
            assert long.prefill_chunks == 3 and len(long.generated) == 1
            assert all(len(r.generated) == 4 for r in residents)
            eng.drain()
            steps = _first_token_steps()
        finally:
            observe.disable()
        assert steps[long.request_id] == 3
        assert [steps[r.request_id] for r in residents] == [1, 1]
        np.testing.assert_array_equal(long.output(),
                                      _generate(params, cfg, long))

    def test_scripted_run_keeps_every_requests_tokens(self, model):
        """Fixed arrivals over a pool too small for full residency — greedy
        and sampled requests, a best-of-3 fork, at least one preemption:
        greedy tokens are ``generate()``'s, and a sampled stream is what
        the same request draws alone on a fresh engine (a token depends on
        its seed, its count and its logits, never on which iteration or
        which batch sampled it)."""
        from thunder_tpu.serving import SamplingParams

        cfg, params = model
        sp = SamplingParams(temperature=0.8, top_k=20, top_p=0.9, seed=42)
        fork_sp = SamplingParams(temperature=0.7, top_k=30, seed=7)
        script = {      # iteration -> [(prompt length, new tokens, kwargs)]
            0: [(30, 8, {}), (28, 8, {"sampling": sp})],
            1: [(20, 8, {})],
            3: [(12, 6, {"sampling": fork_sp, "best_of": 3})],
            6: [(9, 5, {}), (17, 5, {"sampling": sp})],
        }
        eng = _tiny_engine(params, cfg, max_slots=3, page_size=8,
                           num_pages=12, prefill_chunk=16)
        reqs, it = [], 0
        while it <= max(script) or not eng.idle:
            for n, new, kw in script.get(it, ()):
                r = eng.submit(self._prompt(cfg, n, seed=100 + len(reqs)),
                               new, **kw)
                reqs.extend(r.fork_group or [r])
            eng.step()
            it += 1
            assert it < 500
        assert all(r.done for r in reqs)
        assert any(r.preemptions for r in reqs)
        assert sum(r.fork_parent is not None for r in reqs) == 2
        for r in reqs:
            if r.sampling.greedy:
                want = _generate(params, cfg, r)
            else:
                alone = _tiny_engine(params, cfg, page_size=8,
                                     prefill_chunk=16)
                solo = alone.submit(r.prompt, r.max_new_tokens,
                                    sampling=r.sampling)
                alone.drain()
                want = solo.output()
            np.testing.assert_array_equal(r.output(), want)
        eng.assert_quiescent()

    def test_a_residents_next_page_outranks_a_newcomers_chunk(self, model):
        """Four pages: the resident holds two and is about to need a third,
        the newcomer's first chunk takes the other two at admission. The
        resident's page is taken BEFORE any chunk runs: the newcomer is
        requeued without a chunk computed for it in that iteration, the
        resident keeps decoding, and both end with exact tokens."""
        cfg, params = model
        observe.enable(clear=True)
        try:
            eng = _tiny_engine(params, cfg, max_slots=2, page_size=8,
                               num_pages=5, prefill_chunk=16)
            resident = eng.submit(self._prompt(cfg, 14, 3), 8)
            for _ in range(3):
                eng.step()
            # context 16 of 2 x 8: the next token needs a third page
            assert resident.length == 16 and len(resident.pages) == 2
            newcomer = eng.submit(self._prompt(cfg, 10, 4), 4)
            eng.step()
            crowded = eng._step_count
            assert len(resident.generated) == 4 and len(resident.pages) == 3
            assert newcomer.preemptions == 1 and newcomer.state == "queued"
            assert not newcomer.prefill_chunks
            eng.step()                  # one page free of the two it needs
            assert newcomer.state == "queued"
            assert len(resident.generated) == 5
            eng.drain()
            chunks = [(s["args"]["request"], s["args"]["step"])
                      for s in observe.get_registry().spans
                      if s["name"] == "prefill_chunk"]
            steps = _first_token_steps()
        finally:
            observe.disable()
        assert resident.preemptions == 0
        mine = [step for rid, step in chunks if rid == newcomer.request_id]
        assert len(mine) == 1 and mine[0] > crowded
        # admitted again once the resident was done: that admission's own
        # iteration gave the token
        assert steps[newcomer.request_id] == 1
        for r in (resident, newcomer):
            np.testing.assert_array_equal(r.output(),
                                          _generate(params, cfg, r))
        eng.assert_quiescent()


# ---------------------------------------------------------------------------
# bind() + seq_buckets error names the serving path
# ---------------------------------------------------------------------------

def test_bind_seq_buckets_error_names_serving_engine():
    from thunder_tpu import ops

    jfn = tt.jit(lambda a: ops.sum(a, None), seq_buckets=(8, 16))
    with pytest.raises(RuntimeError,
                       match=r"serving\.ServingEngine"):
        jfn.bind(np.ones((2, 5), np.float32))
