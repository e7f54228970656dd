"""Whole-decode-layer megakernel tests (ISSUE 11): the attention sub-block
planner walk, the attn+mlp -> nn.decode_layer chaining stage, megakernel
parity vs the per-op decomposition (GQA + MHA, ragged lengths), the
fusion-shape acceptance gate (<= 2 Pallas launches per layer per decoded
token, counted via the observe registry), engine token-identity with the
megakernel claimed, and the layered quarantine fallback (decode_layer ->
two sub-block kernels -> fully per-op XLA), all CPU-only via Pallas
interpret mode."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import thunder_tpu as tt
from thunder_tpu import observe, ops
from thunder_tpu.core import cost_model, dtypes
from thunder_tpu.models import llama
from thunder_tpu.ops import nn as tnn
from thunder_tpu.runtime import faults, quarantine
from thunder_tpu.runtime.faults import FaultPlan, FaultSpec
from thunder_tpu.serving import ServingEngine


@pytest.fixture(autouse=True)
def pallas_interpret(monkeypatch):
    monkeypatch.setenv("THUNDER_TPU_PALLAS_INTERPRET", "1")


@pytest.fixture(autouse=True)
def _clean_runtime():
    faults.clear()
    quarantine.reset()
    observe.disable()
    observe.reset()
    yield
    faults.clear()
    quarantine.reset()
    observe.disable()
    observe.reset()


def _symbol_names(trc):
    names = set()

    def walk(bsyms):
        for b in bsyms:
            names.add(b.sym.codegen_name())
            walk(b.subsymbols)

    walk(trc.bound_symbols)
    return names


def _pallas_launches(trc):
    """(total claimed Pallas launches, decode_layer launches) of an
    execution trace — counting into XLA regions that absorbed claims, and
    NOT into a claimed kernel's own (never-dispatched) decomposition."""
    launches, layers = 0, 0

    def walk(bsyms):
        nonlocal launches, layers
        for b in bsyms:
            ex = b.sym.executor
            if ex is not None and ex.name == "pallas":
                launches += 1
                layers += b.sym.name == "decode_layer"
                continue
            walk(b.subsymbols)

    walk(trc.bound_symbols)
    return launches, layers


def _block_decisions(jfn, op=None):
    dec = [d for d in tt.compile_stats(jfn).last_decisions
           if d["kind"] == "block"]
    return [d for d in dec if op is None or d["op"] == op]


def _refs(params, cfg, prompts, max_new, n_layers):
    return [np.asarray(llama.generate(params, cfg, p[None], max_new,
                                      n_layers=n_layers))[0]
            for p in prompts]


def _engine(params, cfg, n_layers=2, **kw):
    defaults = dict(max_slots=3, page_size=8, max_context=64,
                    n_layers=n_layers, prefill_chunk=32)
    defaults.update(kw)
    return ServingEngine(params, cfg, **defaults)


@pytest.fixture(scope="module")
def gqa_model():
    cfg = llama.CONFIGS["tiny-gqa"]
    return cfg, jax.device_put(llama.init_params(cfg, seed=0, scale_layers=2))


# ---------------------------------------------------------------------------
# fusion shape: the acceptance gate
# ---------------------------------------------------------------------------

def test_decode_trace_plans_and_chains_by_default(gqa_model):
    """At a tiny serving geometry the T==1 decode trace plans the
    attention sub-block, chains it with the MLP megakernel into
    nn.decode_layer under the DEFAULT cost model (no block_fusion forcing),
    and the compiled decode step dispatches <= 2 Pallas launches per layer
    per decoded token — counted via the observe registry gauges the runner
    publishes at bind time, not trace grepping."""
    cfg, params = gqa_model
    n_layers = 2
    observe.enable(clear=True)
    try:
        eng = _engine(params, cfg, n_layers=n_layers)
        r = eng.submit(np.arange(1, 6, dtype=np.int32), 4)
        eng.drain()
        snap = observe.snapshot()
    finally:
        observe.disable()
    assert r.done
    dec = _block_decisions(eng.runner.decode_jit)
    by = lambda op, kind: sum(1 for d in dec
                              if d["op"] == op and d["decision"] == kind)
    assert by("nn.attn_subblock", "planned") == n_layers, dec
    assert by("nn.mlp_subblock", "planned") == n_layers, dec
    assert by("nn.decode_layer", "chained") == n_layers, dec
    # the mlp verdicts carry the decode-aware costing flag
    mlp = [d for d in dec if d["op"] == "nn.mlp_subblock"][0]
    assert mlp["cost"]["decode"] is True
    # registry gauges: one decode_layer megakernel per layer; the only
    # other Pallas launch in the program is the final pre-lm_head rms_norm
    g = snap["gauges"]
    assert g["serving.decode_layer_fusions"] == n_layers
    assert g["serving.decode_pallas_launches"] / n_layers <= 2.0
    # and the execution trace agrees with the gauges
    launches, layers = _pallas_launches(
        tt.last_execution_trace(eng.runner.decode_jit))
    assert layers == n_layers
    assert launches == g["serving.decode_pallas_launches"]
    report = observe.explain(eng.runner.decode_jit)
    assert "chained" in report and "block planner" in report


def test_decode_layer_cost_model_plans_7b_geometry():
    """The decode cost model accepts at the llama2-7b serving geometry
    (launch amortization + the decomposition's gathered-cache bytes) and
    the combined decode-layer staging stays inside the VMEM budget."""
    acost = cost_model.attn_subblock_cost(8, 4096, 32, 32, 128, 16, 32, 2)
    assert acost["vmem_feasible"] and acost["est_saved_us"] > 0
    mcost = cost_model.subblock_cost(8, 4096, 11008, 2, decode=True)
    assert mcost["est_saved_us"] > 0
    # the same MLP shape WITHOUT the decode launch term is cost-rejected —
    # the decode-aware scoring is what makes serving-width chains plan
    assert cost_model.subblock_cost(8, 4096, 11008, 2)["est_saved_us"] <= 0
    chain = cost_model.decode_layer_cost(acost, mcost, 8, 4096, 16, 2)
    assert chain["vmem_feasible"] and chain["est_saved_us"] > 0


# ---------------------------------------------------------------------------
# parity: megakernel vs per-op decomposition (direct runner programs)
# ---------------------------------------------------------------------------

def _decode_inputs(cfg, n_layers, S, npg, seed=0):
    """Consistent paged decode-step inputs: per-slot block tables over
    distinct pages, ragged lengths (incl. one crossing a page boundary and
    one idle-like length-1 slot), write_pos derived from the tables."""
    from thunder_tpu.serving.kv_cache import PagedKVCache, PageGeometry

    rng = np.random.RandomState(seed)
    ps = 8
    geom = PageGeometry(n_layers=n_layers, kv_heads=cfg.kv_heads,
                        head_dim=cfg.head_dim, page_size=ps,
                        num_pages=S * npg + 1, pages_per_request=npg)
    cache = PagedKVCache(geom, cfg.dtype.jax)
    pools = [{k: jnp.asarray(rng.randn(*v.shape).astype(np.float32) * 0.3,
                             v.dtype)
              for k, v in layer.items()} for layer in cache.pools]
    bt = np.zeros((S, npg), np.int32)
    page = 1
    for b in range(S):
        for p in range(npg):
            bt[b, p] = page
            page += 1
    lengths = np.asarray(
        [1 + (i * 5) % (npg * ps) for i in range(S)], np.int32)
    lengths[-1] = 1                       # the idle-slot degenerate
    if S > 1:
        lengths[0] = ps + 1               # fresh row just past a boundary
    write_pos = np.asarray(
        [bt[b, (lengths[b] - 1) // ps] * ps + (lengths[b] - 1) % ps
         for b in range(S)], np.int32)
    tokens = rng.randint(1, cfg.vocab_size, size=(S, 1)).astype(np.int32)
    return geom, tokens, bt, lengths, write_pos, pools


@pytest.mark.parametrize("model", ["tiny-gqa", "tiny"], ids=["gqa", "mha"])
def test_megakernel_parity_vs_decomposition(model):
    """The claimed decode-layer megakernel matches the per-op decomposition
    at T==1 — GQA (grouped q rows) and MHA head layouts, ragged lengths
    incl. a page-boundary crossing and a length-1 slot, 2 layers."""
    from thunder_tpu.serving.runner import PagedLlamaRunner

    cfg = llama.CONFIGS[model]
    params = jax.device_put(llama.init_params(cfg, seed=3, scale_layers=2))
    geom, tokens, bt, lengths, write_pos, pools = _decode_inputs(
        cfg, 2, S=4, npg=3, seed=4)
    fused = PagedLlamaRunner(cfg, geom, n_layers=2, block_fusion=True)
    plain = PagedLlamaRunner(cfg, geom, n_layers=2, block_fusion=False)
    # the decode step donates the pools: give each run its own copies
    copies = lambda: [{k: jnp.array(v) for k, v in kv.items()}
                      for kv in pools]
    S = tokens.shape[0]
    sampling = (np.zeros(S, np.float32), np.zeros(S, np.int32),
                np.ones(S, np.float32), np.zeros((S, 2), np.uint32))
    tf, lf, pf = fused.decode_jit(params, tokens, bt, lengths, write_pos,
                                  copies(), *sampling)
    tp, lp, pp = plain.decode_jit(params, tokens, bt, lengths, write_pos,
                                  copies(), *sampling)
    names = _symbol_names(tt.last_execution_trace(fused.decode_jit))
    assert "pallas_decode_layer" in names
    assert "pallas_decode_layer" not in _symbol_names(
        tt.last_execution_trace(plain.decode_jit))
    np.testing.assert_allclose(np.asarray(lf), np.asarray(lp),
                               atol=2e-5, rtol=2e-5)
    # greedy sampling rows: the in-graph token ids are the logits argmax
    np.testing.assert_array_equal(np.asarray(tf),
                                  np.asarray(lf).argmax(-1))
    np.testing.assert_array_equal(np.asarray(tp),
                                  np.asarray(lp).argmax(-1))
    for f_kv, p_kv in zip(pf, pp):
        for key in ("k", "v"):
            np.testing.assert_allclose(np.asarray(f_kv[key]),
                                       np.asarray(p_kv[key]),
                                       atol=2e-5, rtol=2e-5)


def test_block_planner_is_entered_once_a_decode_compile():
    """One planner entry (``executors/passes.py``): a compile of a decode
    step opens exactly one ``block_fusion*`` span (the train step's case is
    in ``test_block_planner.py``)."""
    from thunder_tpu.serving.runner import PagedLlamaRunner

    cfg = llama.CONFIGS["tiny"]
    params = jax.device_put(llama.init_params(cfg, seed=12, scale_layers=1))
    geom, tokens, bt, lengths, write_pos, pools = _decode_inputs(
        cfg, 1, S=2, npg=2, seed=12)
    runner = PagedLlamaRunner(cfg, geom, n_layers=1, block_fusion=True)
    observe.enable(clear=True)
    runner.decode_jit(params, tokens, bt, lengths, write_pos, pools,
                      np.zeros(2, np.float32), np.zeros(2, np.int32),
                      np.ones(2, np.float32), np.zeros((2, 2), np.uint32))
    spans = [sp["name"] for sp in observe.get_registry().spans
             if sp["name"].startswith("block_fusion")]
    observe.disable()
    assert "pallas_decode_layer" in _symbol_names(
        tt.last_execution_trace(runner.decode_jit))
    assert spans == ["block_fusion"], spans


def test_engine_tokens_identical_to_generate(gqa_model):
    """Engine outputs with the decode-layer megakernel claimed stay
    token-identical to llama.generate across mixed prompt lengths (incl. a
    1-token prompt and a chunk-spanning prompt)."""
    cfg, params = gqa_model
    rng = np.random.RandomState(7)
    prompts = [np.asarray([3], np.int32),
               rng.randint(1, cfg.vocab_size, size=9).astype(np.int32),
               rng.randint(1, cfg.vocab_size, size=33).astype(np.int32)]
    refs = _refs(params, cfg, prompts, 6, 2)
    eng = _engine(params, cfg, n_layers=2)
    reqs = [eng.submit(p, 6) for p in prompts]
    eng.drain()
    assert "pallas_decode_layer" in _symbol_names(
        tt.last_execution_trace(eng.runner.decode_jit))
    for r, ref in zip(reqs, refs):
        np.testing.assert_array_equal(r.output(), ref)


# ---------------------------------------------------------------------------
# the page walk: a request's live pages only, many to a block (ISSUE 26)
# ---------------------------------------------------------------------------

_WALK = dict(D=32, hd=8, ps=4, npg=8, ppb=2)     # window 32 tokens, block 8


def _walk_lengths():
    ps, ppb, npg = _WALK["ps"], _WALK["ppb"], _WALK["npg"]
    return [0, 1, ps - 1, ps, ps + 1, ppb * ps - 1, ppb * ps, ppb * ps + 1,
            npg * ps]


def _walk_case(H, KV, lengths, npg=_WALK["npg"], seed=0, dtype=np.float32):
    """attn_subblock operands for ``lengths``: block tables whose page ids
    are not in order, and TWO pools — ``clean`` (dead pages zero) for the
    decomposition, ``poisoned`` (every page past a request's length NaN) for
    the kernel, which must never read one into its result."""
    D, hd, ps = _WALK["D"], _WALK["hd"], _WALK["ps"]
    S = len(lengths)
    P = S * npg + 1
    rng = np.random.RandomState(seed)
    r = lambda *s: (rng.randn(*s) * 0.3).astype(np.float32)
    bt = rng.permutation(np.arange(1, P)).astype(np.int32).reshape(S, npg)
    ln = np.asarray(lengths, np.int32)
    wp = np.asarray([bt[b, (n - 1) // ps] * ps + (n - 1) % ps if n else 0
                     for b, n in enumerate(ln)], np.int32)
    kp, vp = r(KV, P, ps, hd), r(KV, P, ps, hd)
    dead = np.zeros(P, bool)
    for b, n in enumerate(ln):
        dead[bt[b, -(-int(n) // ps):]] = True
    clean = [np.where(dead[None, :, None, None], 0.0, x).astype(dtype)
             for x in (kp, vp)]
    poisoned = [np.where(dead[None, :, None, None], np.nan, x).astype(dtype)
                for x in (kp, vp)]
    head = tuple(a.astype(dtype) for a in (
        r(S, 1, D), 1 + 0.1 * rng.randn(D), r(H * hd, D), r(KV * hd, D),
        r(KV * hd, D), r(D, H * hd), r(S, 1, 1, hd // 2),
        r(S, 1, 1, hd // 2)))
    return head, clean, poisoned, (bt, ln, wp), dead


@pytest.fixture
def small_blocks(monkeypatch):
    """A walk block of ``_WALK['ppb']`` pages at the test's tiny page (the
    block is sized in bytes: shrink the bytes, not an option)."""
    def at(dtype):
        monkeypatch.setattr(
            cost_model, "DECODE_KV_BLOCK_BYTES",
            _WALK["ppb"] * _WALK["ps"] * _WALK["hd"] * np.dtype(dtype).itemsize)
    return at


# the megakernel's walk is one KV head a grid step; the per-op kernel's a
# group of them: 1, 2 and every head
@pytest.mark.parametrize("length", _walk_lengths())
@pytest.mark.parametrize("nh", [None, 1, 2, 4],
                         ids=["megakernel", "per_op_1h", "per_op_2h",
                              "per_op_4h"])
@pytest.mark.parametrize("H,KV", [(8, 4), (4, 4)], ids=["gqa", "mha"])
def test_page_walk_parity_vs_decomposition(H, KV, nh, length, small_blocks,
                                           heads_a_copy):
    """The walk against the XLA decomposition: the case length first and
    last of four slots (an idle slot between live ones, before one and
    after one, a first block started by the slot itself and by the slot
    before), the fresh row on the first and on the last row of a page, dead
    pages NaN, page ids out of order."""
    from thunder_tpu.executors import pallasex as px

    small_blocks(np.float32)
    head, clean, poisoned, tail, dead = _walk_case(
        H, KV, [length, 0, 2 * _WALK["ps"] + 3, length], seed=length)
    assert cost_model.decode_pages_per_block(
        _WALK["ps"], _WALK["hd"], 4, _WALK["npg"]) == (_WALK["ppb"], 1)
    live = np.asarray(tail[1]) > 0
    xla = lambda f: tt.jit(f, executors=["xla"])
    dev = lambda xs: (jnp.asarray(a) for a in xs)
    if nh is None:
        ref = xla(lambda *a: tnn.attn_subblock(*a))(*head, *clean, *tail)
        out = px.pallas_attn_subblock(*dev((*head, *poisoned, *tail)))
        for got, want in zip(out[1:], ref[1:]):     # the appended rows
            np.testing.assert_allclose(np.asarray(got)[:, ~dead],
                                       np.asarray(want)[:, ~dead],
                                       atol=2e-5, rtol=2e-5)
        o, o_ref = np.asarray(out[0]), np.asarray(ref[0])
    else:                   # the pools already hold this token's row
        heads_a_copy(nh)
        q = np.random.RandomState(length).randn(
            len(live), H, 1, _WALK["hd"]).astype(np.float32) * 0.3
        grid, = _pallas_grids(px.pallas_paged_decode_attention,
                              *dev((q, *poisoned, *tail[:2])))
        assert grid == (KV // nh,)
        o_ref = np.asarray(xla(lambda *a: tnn.paged_decode_attention(*a))(
            q, *clean, *tail[:2]))
        o = np.asarray(px.pallas_paged_decode_attention(
            *dev((q, *poisoned, *tail[:2]))))
    assert np.isfinite(o).all()
    np.testing.assert_allclose(o[live], o_ref[live], atol=2e-5, rtol=2e-5)
    np.testing.assert_array_equal(o[~live], 0.0)    # an idle slot attends nothing


_CELL_PAGE = dict(page_size=16, head_dim=128, dtype_bytes=2)


@pytest.mark.parametrize("head_bytes", [0, 1 << 20])
@pytest.mark.parametrize("vmem_left", [1 << 16, 1 << 20, 6 << 20, 16 << 20])
@pytest.mark.parametrize("KV", [1, 2, 8, 32])
def test_walk_block_is_a_divisor_of_the_heads_within_the_budget(KV, vmem_left,
                                                                head_bytes):
    """``decode_pages_per_block`` groups the largest divisor of the KV heads
    whose staging (K and V, two buffers, ``heads`` x ``pages`` pages) and
    per-head blocks fit the VMEM left; one head and one page at the least."""
    page = 16 * 128 * 2
    ppb, nh = cost_model.decode_pages_per_block(
        **_CELL_PAGE, pages_per_request=1024, vmem_left=vmem_left,
        kv_heads=KV, head_bytes=head_bytes)
    need = lambda heads, pages: heads * (4 * pages * page + head_bytes)
    assert KV % nh == 0 and 1 <= ppb <= 32
    assert need(nh, ppb) <= vmem_left or (nh, ppb) == (1, 1)
    assert all(need(d, ppb) > vmem_left
               for d in range(nh + 1, KV + 1) if KV % d == 0)
    # the pages of ONE head are what a caller without heads gets
    assert ppb == cost_model.decode_pages_per_block(
        **_CELL_PAGE, pages_per_request=1024, vmem_left=vmem_left,
        head_bytes=head_bytes)[0]


def test_walk_block_at_the_agent_cell_and_the_megakernel():
    """``commandaplus_serve_agent_sat``'s per-op kernel stages 4 MB: every
    one of the 8 KV heads, 32 pages, K and V, two buffers — a copy moves 32
    KB; the megakernel's walk keeps one head a grid step."""
    B, G, hd = 32, 16, 128
    head_bytes = 4 * B * G * hd * 2 + G * (hd + 256) * 4
    assert cost_model.decode_pages_per_block(
        **_CELL_PAGE, pages_per_request=257, kv_heads=8,
        head_bytes=head_bytes) == (32, 8)
    assert 8 * (4 * 32 * 4096 + head_bytes) <= cost_model.VMEM_BUDGET_BYTES
    # mistral7b_serve_decode_sat's attention sub-block: pages alone, as before
    assert cost_model.decode_subblock_pages_per_block(
        32, 4096, 32, 8, 128, 16, 0, 2, 128) == 32


@pytest.mark.parametrize("kernel", ["attn_subblock", "decode_layer",
                                    "paged_decode_attention"])
def test_page_walk_every_rung_bf16(kernel, small_blocks):
    """The three kernels that share the walk, bf16 pools, several blocks a
    request: each against its own decomposition."""
    from thunder_tpu.executors import pallasex as px

    bf16 = np.dtype(jnp.bfloat16)
    small_blocks(bf16)
    ps, npg = _WALK["ps"], _WALK["npg"]
    head, clean, poisoned, tail, _ = _walk_case(
        4, 2, [npg * ps, 1, 2 * ps + 1, 5 * ps], seed=7, dtype=bf16)
    D, F = _WALK["D"], 48
    rng = np.random.RandomState(8)
    mlp = tuple((rng.randn(*s) * 0.2).astype(bf16)
                for s in ((D,), (F, D), (F, D), (D, F)))
    xla = lambda f: tt.jit(f, executors=["xla"])
    dev = lambda xs: tuple(jnp.asarray(a) for a in xs)
    if kernel == "attn_subblock":
        ref = xla(lambda *a: tnn.attn_subblock(*a))(*head, *clean, *tail)[0]
        out = px.pallas_attn_subblock(*dev((*head, *poisoned, *tail)))[0]
    elif kernel == "decode_layer":
        ref = xla(lambda *a: tnn.decode_layer(*a))(
            *head, *clean, *tail, *mlp)[0]
        out = px.pallas_decode_layer(*dev((*head, *poisoned, *tail, *mlp)))[0]
    else:       # the per-op rung: the pools already hold this token's row
        S, H, hd = len(tail[1]), 4, _WALK["hd"]
        q = (rng.randn(S, H, 1, hd) * 0.3).astype(bf16)
        ref = xla(lambda *a: tnn.paged_decode_attention(*a))(
            q, *clean, *tail[:2])
        out = px.pallas_paged_decode_attention(*dev((q, *poisoned, *tail[:2])))
    o = np.asarray(out, np.float32)
    assert np.isfinite(o).all()
    np.testing.assert_allclose(o, np.asarray(ref, np.float32),
                               atol=3e-2, rtol=3e-2)


def _pallas_grids(fn, *args):
    """The grid of every pallas_call in ``fn``'s jaxpr."""
    grids = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                grids.append(tuple(eqn.params["grid_mapping"].grid))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    # a fresh callable: JAX caches a function's trace by its identity
    walk(jax.make_jaxpr(lambda *a: fn(*a))(*args).jaxpr)
    return grids


@pytest.mark.parametrize("kernel", ["attn_subblock", "paged_decode_attention"])
def test_attention_grid_does_not_grow_with_the_window(kernel):
    """A grid step a KV head (the megakernel) or a group of them (the per-op
    kernel: both heads here), whatever ``npg``: the pages are walked by a
    loop whose trip count is the request's own."""
    from thunder_tpu.executors import pallasex as px

    H, KV, hd = 4, 2, _WALK["hd"]
    grids = {}
    for npg in (8, 128):
        head, clean, _, tail, _ = _walk_case(H, KV, [3, 9], npg=npg)
        if kernel == "attn_subblock":
            fn, args = px.pallas_attn_subblock, (*head, *clean, *tail)
        else:
            q = np.zeros((2, H, 1, hd), np.float32)
            fn, args = px.pallas_paged_decode_attention, \
                (q, *clean, *tail[:2])
        grids[npg] = _pallas_grids(fn, *(jnp.asarray(a) for a in args))
    assert grids[8] == grids[128]
    steps = {"attn_subblock": H + 2 * KV + KV,      # qkv heads + a walk a head
             "paged_decode_attention": 1}[kernel]
    assert grids[8] == [(steps,)]


# ---------------------------------------------------------------------------
# planner verdicts (hand-built traces)
# ---------------------------------------------------------------------------

def _chain_shapes(S=3, D=16, H=4, KV=2, hd=4, P=9, ps=4, npg=2, F=24):
    return dict(S=S, D=D, H=H, KV=KV, hd=hd, P=P, ps=ps, npg=npg, F=F)


def _emit_decode_chain(sh, proxies, escape_q=False):
    """Emit the runner-shaped per-layer op chain on proxies/arrays."""
    from thunder_tpu.models.llama import _apply_rope
    from thunder_tpu.core import prims

    (h, wn1, wq, wk, wv, wo, cos, sin, kpp, vpp, bt, ln, wp,
     wn2, wg, wu, wd) = proxies
    S, D, H, KV, hd, P, ps = (sh[k] for k in
                              ("S", "D", "H", "KV", "hd", "P", "ps"))
    x = ops.rms_norm(h, wn1, eps=1e-5)
    q = ops.transpose(ops.reshape(ops.linear(x, wq), (S, 1, H, hd)),
                      (0, 2, 1, 3))
    k = ops.transpose(ops.reshape(ops.linear(x, wk), (S, 1, KV, hd)),
                      (0, 2, 1, 3))
    v = ops.transpose(ops.reshape(ops.linear(x, wv), (S, 1, KV, hd)),
                      (0, 2, 1, 3))
    q = _apply_rope(q, cos, sin)
    k = _apply_rope(k, cos, sin)
    flat = (KV, P * ps, hd)
    kp = ops.reshape(tnn.decode_row_write(ops.reshape(kpp, flat), k, wp),
                     (KV, P, ps, hd))
    vp = ops.reshape(tnn.decode_row_write(ops.reshape(vpp, flat), v, wp),
                     (KV, P, ps, hd))
    attn = tnn.paged_decode_attention(q, kp, vp, bt, ln)
    attn = ops.reshape(ops.transpose(attn, (0, 2, 1, 3)), (S, 1, H * hd))
    h2 = ops.add(h, ops.linear(attn, wo))
    x2 = ops.rms_norm(h2, wn2, eps=1e-5)
    y = ops.mul(ops.silu(ops.linear(x2, wg)), ops.linear(x2, wu))
    out = ops.add(h2, ops.linear(y, wd))
    if escape_q:
        return out, kp, vp, q
    return out, kp, vp


def _chain_arrays(sh, seed=0):
    rng = np.random.RandomState(seed)
    S, D, H, KV, hd, P, ps, npg, F = (sh[k] for k in
                                      ("S", "D", "H", "KV", "hd", "P",
                                       "ps", "npg", "F"))
    r = lambda *s: (rng.randn(*s) * 0.2).astype(np.float32)
    bt = np.arange(1, 1 + S * npg, dtype=np.int32).reshape(S, npg)
    ln = np.asarray([1 + i % (npg * ps) for i in range(S)], np.int32)
    wp = np.asarray([bt[b, (ln[b] - 1) // ps] * ps + (ln[b] - 1) % ps
                     for b in range(S)], np.int32)
    return (r(S, 1, D), (1 + 0.1 * rng.randn(D)).astype(np.float32),
            r(H * hd, D), r(KV * hd, D), r(KV * hd, D), r(D, H * hd),
            r(S, 1, 1, hd // 2), r(S, 1, 1, hd // 2),
            r(KV, P, ps, hd), r(KV, P, ps, hd), bt, ln, wp,
            (1 + 0.1 * rng.randn(D)).astype(np.float32),
            r(F, D), r(F, D), r(D, F))


def test_planner_plans_and_chains_hand_built_trace():
    sh = _chain_shapes()
    args = _chain_arrays(sh)
    jf = tt.jit(lambda *a: _emit_decode_chain(sh, a),
                executors=["pallas", "xla"], block_fusion=True)
    out = jf(*args)
    names = _symbol_names(tt.last_execution_trace(jf))
    assert "pallas_decode_layer" in names
    dec = _block_decisions(jf)
    assert any(d["op"] == "nn.attn_subblock" and d["decision"] == "planned"
               for d in dec), dec
    assert any(d["op"] == "nn.decode_layer" and d["decision"] == "chained"
               for d in dec), dec
    # numerics match the unfused pipeline
    ref = tt.jit(lambda *a: _emit_decode_chain(sh, a), block_fusion=False)(*args)
    for a, b in zip(out, ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5, rtol=2e-5)


def test_planner_rejects_escaping_attn_interior():
    """A chain interior (the roped q) that is also a trace output blocks
    the attention sub-block with the interior-escapes verdict; the trace
    stays per-op and the MLP half still plans on its own."""
    sh = _chain_shapes()
    args = _chain_arrays(sh, seed=1)
    jf = tt.jit(lambda *a: _emit_decode_chain(sh, a, escape_q=True),
                executors=["pallas", "xla"], block_fusion=True)
    jf(*args)
    names = _symbol_names(tt.last_execution_trace(jf))
    assert "pallas_attn_subblock" not in names
    assert "pallas_decode_layer" not in names
    dec = _block_decisions(jf, op="nn.attn_subblock")
    assert any(d["decision"] == "interior-escapes" for d in dec), dec


def test_planner_chain_blocked_without_mlp_partner():
    """An attention sub-block whose residual add feeds something other
    than the layer's MLP sub-block records chain-blocked and keeps the
    standalone attn_subblock claim (two-launch form)."""
    sh = _chain_shapes()
    args = _chain_arrays(sh, seed=2)[:13]

    def attn_only(*a):
        (h, wn1, wq, wk, wv, wo, cos, sin, kpp, vpp, bt, ln, wp) = a
        from thunder_tpu.models.llama import _apply_rope
        S, D, H, KV, hd, P, ps = (sh[k] for k in
                                  ("S", "D", "H", "KV", "hd", "P", "ps"))
        x = ops.rms_norm(h, wn1, eps=1e-5)
        q = ops.transpose(ops.reshape(ops.linear(x, wq), (S, 1, H, hd)),
                          (0, 2, 1, 3))
        k = ops.transpose(ops.reshape(ops.linear(x, wk), (S, 1, KV, hd)),
                          (0, 2, 1, 3))
        v = ops.transpose(ops.reshape(ops.linear(x, wv), (S, 1, KV, hd)),
                          (0, 2, 1, 3))
        q = _apply_rope(q, cos, sin)
        k = _apply_rope(k, cos, sin)
        flat = (KV, P * ps, hd)
        kp = ops.reshape(tnn.decode_row_write(ops.reshape(kpp, flat), k, wp),
                         (KV, P, ps, hd))
        vp = ops.reshape(tnn.decode_row_write(ops.reshape(vpp, flat), v, wp),
                         (KV, P, ps, hd))
        attn = tnn.paged_decode_attention(q, kp, vp, bt, ln)
        attn = ops.reshape(ops.transpose(attn, (0, 2, 1, 3)),
                           (S, 1, H * hd))
        return ops.add(h, ops.linear(attn, wo)), kp, vp

    jf = tt.jit(attn_only, executors=["pallas", "xla"], block_fusion=True)
    out = jf(*args)
    names = _symbol_names(tt.last_execution_trace(jf))
    assert "pallas_attn_subblock" in names
    assert "pallas_decode_layer" not in names
    dec = _block_decisions(jf, op="nn.decode_layer")
    assert any(d["decision"] == "chain-blocked" for d in dec), dec
    ref = tt.jit(attn_only, block_fusion=False)(*args)
    for a, b in zip(out, ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5, rtol=2e-5)


def _proxy_chain_trace(sh, dist_wq=False):
    """Hand-built proxy trace of the decode chain (no arrays)."""
    from thunder_tpu.core.proxies import DistParallelType, TensorProxy
    from thunder_tpu.core.trace import TraceCtx, tracectx

    S, D, H, KV, hd, P, ps, npg, F = (sh[k] for k in
                                      ("S", "D", "H", "KV", "hd", "P",
                                       "ps", "npg", "F"))
    trc = TraceCtx("decode_chain")
    with tracectx(trc):
        f32, i32 = dtypes.float32, dtypes.int32
        h = TensorProxy("h", shape=(S, 1, D), dtype=f32)
        wn1 = TensorProxy("wn1", shape=(D,), dtype=f32)
        wq = TensorProxy("wq", shape=(H * hd, D), dtype=f32)
        if dist_wq:
            wq.distparallel_type = DistParallelType.FULLY_SHARDED
        wk = TensorProxy("wk", shape=(KV * hd, D), dtype=f32)
        wv = TensorProxy("wv", shape=(KV * hd, D), dtype=f32)
        wo = TensorProxy("wo", shape=(D, H * hd), dtype=f32)
        cos = TensorProxy("cos", shape=(S, 1, 1, hd // 2), dtype=f32)
        sin = TensorProxy("sin", shape=(S, 1, 1, hd // 2), dtype=f32)
        kpp = TensorProxy("kpp", shape=(KV, P, ps, hd), dtype=f32)
        vpp = TensorProxy("vpp", shape=(KV, P, ps, hd), dtype=f32)
        bt = TensorProxy("bt", shape=(S, npg), dtype=i32)
        ln = TensorProxy("ln", shape=(S,), dtype=i32)
        wp = TensorProxy("wp", shape=(S,), dtype=i32)
        wn2 = TensorProxy("wn2", shape=(D,), dtype=f32)
        wg = TensorProxy("wg", shape=(F, D), dtype=f32)
        wu = TensorProxy("wu", shape=(F, D), dtype=f32)
        wd = TensorProxy("wd", shape=(D, F), dtype=f32)
        out = _emit_decode_chain(sh, (h, wn1, wq, wk, wv, wo, cos, sin,
                                      kpp, vpp, bt, ln, wp, wn2, wg, wu, wd))
    trc.output = out
    return trc


def _run_planner(trc, options=None):
    from thunder_tpu.core.compile_data import CompileContext, compile_context
    from thunder_tpu.core.fusion_passes import block_fusion_pass
    from thunder_tpu.executors import pallasex
    from thunder_tpu.observe import decisions as obs_decisions

    with obs_decisions.collect() as log:
        with compile_context(CompileContext(options or {})):
            new = block_fusion_pass(trc, [pallasex.ex])
    return new, list(log)


def test_planner_never_plans_dist_annotated_attn():
    sh = _chain_shapes()
    trc = _proxy_chain_trace(sh, dist_wq=True)
    new, log = _run_planner(trc, {"block_fusion": True})
    assert all(b.sym.id != "nn.attn_subblock" for b in new.bound_symbols)
    assert any(d["kind"] == "block" and d["op"] == "nn.attn_subblock"
               and d["decision"] == "dist-annotated" for d in log), log


def test_planner_vmem_infeasible_attn():
    """Per-grid-step staging beyond the scoped-VMEM budget records the
    vmem-infeasible verdict and never plans (hand proxy trace at a shape
    whose resident rows alone exceed 16 MiB)."""
    sh = _chain_shapes(S=8, D=1 << 20, H=2, KV=2, hd=128, P=17, ps=8,
                       npg=2, F=128)
    assert not cost_model.attn_subblock_cost(
        8, 1 << 20, 2, 2, 128, 8, 2, 4)["vmem_feasible"]
    trc = _proxy_chain_trace(sh)
    new, log = _run_planner(trc)
    assert all(b.sym.id != "nn.attn_subblock" for b in new.bound_symbols)
    assert any(d["kind"] == "block" and d["op"] == "nn.attn_subblock"
               and d["decision"] == "vmem-infeasible" for d in log), log


def test_planner_cost_rejected_attn(monkeypatch):
    """When the decode cost model says the fused path loses, the planner
    records cost-rejected and keeps the chain per-op. The model itself
    essentially always accepts a VMEM-feasible T==1 decode chain (that is
    the launch-bound physics), so the losing verdict is injected."""
    sh = _chain_shapes()
    _orig = cost_model.attn_subblock_cost
    from thunder_tpu.core import fusion_passes
    monkeypatch.setattr(fusion_passes.cost_model, "attn_subblock_cost",
                        lambda *a, **kw: dict(_orig(*a, **kw),
                                              est_saved_us=-1.0))
    trc = _proxy_chain_trace(sh)
    new, log = _run_planner(trc)
    assert all(b.sym.id != "nn.attn_subblock" for b in new.bound_symbols)
    assert any(d["kind"] == "block" and d["op"] == "nn.attn_subblock"
               and d["decision"] == "cost-rejected" for d in log), log


def test_prefill_chunks_never_plan_attn(gqa_model):
    """The attention walk is T==1-anchored: the prefill-chunk program's
    paged attention (T == chunk) records no attn sub-block verdicts and
    keeps its decomposition."""
    cfg, params = gqa_model
    eng = _engine(params, cfg, n_layers=1)
    r = eng.submit(np.arange(1, 20, dtype=np.int32), 2)
    eng.drain()
    assert r.done
    dec = _block_decisions(eng.runner.prefill_jit, op="nn.attn_subblock")
    assert dec == [], dec
    assert "pallas_decode_layer" not in _symbol_names(
        tt.last_execution_trace(eng.runner.prefill_jit))


# ---------------------------------------------------------------------------
# chaos: layered quarantine fallback
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_quarantined_decode_layer_falls_back_to_subblocks(gqa_model):
    """Quarantining pallas.decode_layer mid-generation degrades to the TWO
    sub-block kernels with token-identical engine output, logs the rebind
    through observe (counter + gauges move), and the decision log shows the
    quarantine rejection."""
    cfg, params = gqa_model
    rng = np.random.RandomState(11)
    p = rng.randint(1, cfg.vocab_size, size=7).astype(np.int32)
    ref = _refs(params, cfg, [p], 6, 2)[0]
    observe.enable(clear=True)
    try:
        eng = _engine(params, cfg, n_layers=2)
        req = eng.submit(p, 6)
        with quarantine.containment(), \
                faults.active(FaultPlan([FaultSpec("kernel:pallas.decode_layer")])):
            eng.drain()
        snap = observe.snapshot()
    finally:
        observe.disable()
    assert req.done
    np.testing.assert_array_equal(req.output(), ref)
    assert quarantine.is_quarantined("pallas.decode_layer")
    names = _symbol_names(tt.last_execution_trace(eng.runner.decode_jit))
    assert "pallas_decode_layer" not in names
    assert "pallas_attn_subblock" in names       # the middle fallback rung
    assert "pallas_mlp_subblock" in names
    assert snap["counters"].get("serving.decode_rebinds", 0) >= 1
    assert snap["gauges"]["serving.decode_layer_fusions"] == 0
    # bounded compiles: claimed entry + containment recompile + one re-bind
    assert tt.compile_stats(eng.runner.decode_jit).cache_misses <= 3


@pytest.mark.chaos
def test_quarantining_every_megakernel_reaches_per_op(gqa_model):
    """Quarantining the whole megakernel family recompiles to the fully
    per-op XLA decomposition with token-identical output — the bottom of
    the layered fallback."""
    cfg, params = gqa_model
    rng = np.random.RandomState(12)
    p = rng.randint(1, cfg.vocab_size, size=5).astype(np.int32)
    ref = _refs(params, cfg, [p], 5, 2)[0]
    eng = _engine(params, cfg, n_layers=2)
    req = eng.submit(p, 5)
    with quarantine.containment(), \
            faults.active(FaultPlan([FaultSpec("kernel:pallas.decode_layer"),
                                  FaultSpec("kernel:pallas.attn_subblock"),
                                  FaultSpec("kernel:pallas.mlp_subblock")])):
        eng.drain()
    assert req.done
    np.testing.assert_array_equal(req.output(), ref)
    names = _symbol_names(tt.last_execution_trace(eng.runner.decode_jit))
    for kern in ("pallas_decode_layer", "pallas_attn_subblock",
                 "pallas_mlp_subblock"):
        assert kern not in names
    # per-op means the sub-block composites are gone and the decomposition
    # ops are back — the standalone PR 10 paged-attention kernel (not part
    # of the quarantined family) may still claim its own op
    assert ("pallas_paged_decode_attention" in names
            or "paged_decode_attention" in names)
