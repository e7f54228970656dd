"""The ``cohere2_moe`` family on the serving path, at the rehearsal size, on
seeded weights against the plain reference (``benchmark/families/
cohere2_moe.py`` — one owner; the benchmark's comparison uses the same file).

Tolerances. Everything here is float32 under "highest" matmul precision, so
program and reference differ by summation order only: logits of order 1 over
a 64-wide model agree to ~1e-5; ``2e-4`` leaves a decade of room and is four
decades under what one rounding to bfloat16 (2**-8 relative) would move. The
kernels against their decompositions run on identical inputs: ``2e-5``.
"""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import thunder_tpu as tt
from thunder_tpu import observe
from thunder_tpu.executors import pallasex as px
from thunder_tpu.models import cohere2_moe as cm
from thunder_tpu.ops import nn as tnn
from thunder_tpu.serving import InfeasibleRequest, ServingEngine

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir)
LOGIT_TOL = 2e-4
KERNEL_TOL = 2e-5


def _family():
    path = os.path.join(ROOT, "benchmark", "families", "cohere2_moe.py")
    spec = importlib.util.spec_from_file_location("bench_families_cohere2_moe",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


fam = _family()


def _spec(**over):
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "command-a-plus-05-2026-l4e16.json")) as f:
        conf = json.load(f)
    conf["rehearse"].update(over)
    return fam.spec_from_config(conf, rehearse=True)


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setenv("THUNDER_TPU_PALLAS_INTERPRET", "1")


def _engine(spec, params, **kw):
    kw.setdefault("max_slots", 3)
    kw.setdefault("page_size", 4)
    kw.setdefault("max_context", 64)
    kw.setdefault("prefill_chunk", 16)
    return ServingEngine(params, fam.program_config(spec, max_seq_len=64), **kw)


def _serve_with_logits(eng, prompts, new):
    """Drive ``eng`` to the end; every request's logits row a token."""
    reqs = [eng.submit(p, n) for p, n in zip(prompts, new)]
    rows = {r.request_id: [] for r in reqs}
    while not eng.idle:
        before = {r.request_id: len(r.generated) for r in reqs}
        resident = {i: r for i, r in enumerate(eng.slots) if r is not None}
        assert eng.step()
        # the slots before the step (a request that finished in it has left
        # its slot) and after it (one admitted in it got its first token)
        resident.update({i: r for i, r in enumerate(eng.slots)
                         if r is not None})
        logits = np.asarray(eng.last_decode_logits)
        for i, r in resident.items():
            if len(r.generated) > before[r.request_id]:
                rows[r.request_id].append(logits[i])
    return reqs, rows


# ---------------------------------------------------------------------------
# prefill, then decode, through both cache kinds, against ref_logits
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kernels", ["pallas-interpret", "xla-decomposition"])
def test_served_logits_match_reference_past_the_ring_wrap(kernels, monkeypatch):
    """Window 8 over pages of 4: the ring holds 3 pages, so it wraps, and
    pages are recycled, within the first dozen tokens. Prompts below, at and
    above the window; one spans two prefill chunks with a padded tail; the
    decode runs to 3-4 windows. Logits, not tokens."""
    if kernels == "pallas-interpret":
        monkeypatch.setenv("THUNDER_TPU_PALLAS_INTERPRET", "1")
    else:
        monkeypatch.delenv("THUNDER_TPU_PALLAS_INTERPRET", raising=False)
    spec = _spec()
    params = fam.init_params(spec, 3)
    eng = _engine(spec, params)
    rng = np.random.RandomState(0)
    lens, new = (5, 8, 21), (28, 6, 30)
    prompts = [rng.randint(1, spec.V, size=n).astype(np.int32) for n in lens]
    observe.enable(clear=True)
    try:
        reqs, rows = _serve_with_logits(eng, prompts, new)
        recycled = observe.snapshot()["counters"]["kv.window_pages_recycled"]
    finally:
        observe.disable()
        observe.reset()
    assert recycled > 0
    for r, p, n in zip(reqs, prompts, new):
        seq = jnp.asarray(np.concatenate([p, r.output()]))
        ref = np.asarray(fam.ref_logits(params, seq, spec))
        got = np.stack(rows[r.request_id])
        assert got.shape[0] == n
        np.testing.assert_allclose(got, ref[len(p) - 1: len(p) - 1 + n],
                                   atol=LOGIT_TOL, rtol=LOGIT_TOL)
    eng.assert_quiescent()


def test_window_pools_hold_the_ring_not_the_context(interpret):
    """ceil(W / page) + 1 pages a slot for a window layer, the context's for
    a global one; three window layers' pools to one global layer's."""
    spec = _spec()
    eng = _engine(spec, fam.init_params(spec, 1), max_slots=4)
    full, window = eng.geoms
    assert (full.window, full.pages_per_request, full.n_layers) == (None, 16, 1)
    assert (window.window, window.pages_per_request, window.n_layers) \
        == (8, 3, 3)
    assert full.num_pages == 4 * 16 + 1 and window.num_pages == 4 * 3 + 1
    shapes = [tuple(kv["k"].shape) for kv in eng._pools()]
    assert shapes == [(2, 13, 4, 16)] * 3 + [(2, 65, 4, 16)]


def test_the_engine_holds_the_published_weights(interpret):
    """The decode step splits the rotary pairs on its projections, so no
    weight is re-laid out at load: the engine's leaves are the arrays it was
    given, and it holds no second copy of any (a re-laid ``wq`` of the agent
    cell is 134 MB a window layer beside the caller's, which stays alive
    until the constructor returns)."""
    spec = _spec()
    params = fam.init_params(spec, 1)
    eng = _engine(spec, params)
    held, given = (jax.tree_util.tree_leaves(t) for t in (eng.params, params))
    assert len(held) == len(given)
    assert all(a is b for a, b in zip(held, given))


# ---------------------------------------------------------------------------
# the chip's share
# ---------------------------------------------------------------------------

def test_the_shares_add_up_to_the_uncut_layer(interpret):
    """16 experts over 4 chips of 4: the routed parts of the four shares,
    with what every chip computes alike (the shared experts; the attention)
    counted once, give the uncut layer. The program's expert layer for the
    shares, the reference for the whole."""
    uncut = _spec(num_experts=16)
    key = fam.key_from_seed(5)
    whole = fam._init(uncut, key)["layers"][0]
    x = jax.random.normal(jax.random.fold_in(key, 1), (6, uncut.D), jnp.float32)
    mm = fam.matmul("float32")
    want = np.asarray(fam.ref_experts(x, whole, uncut, mm))

    def program_share(start, n_held):
        cfg = fam.program_config(_spec(num_experts=n_held,
                                       held_experts_start=start), 64)
        keep = list(range(start, start + n_held)) if start < 16 else []
        pick = np.asarray(keep + [16, 17], np.int32)    # the shared ones last
        layer = dict(whole, **{k: whole[k][pick]
                               for k in ("w_gate", "w_up", "w_down")})
        run = tt.jit(lambda x3, lay: cm.experts(x3, lay, cfg)[0])
        return np.asarray(run(x[None], layer))[0]

    shared_only = program_share(10**6, 0)       # holds no routed expert
    shares = [program_share(4 * s, 4) for s in range(4)]
    total = sum(s - shared_only for s in shares) + shared_only
    np.testing.assert_allclose(total, want, atol=LOGIT_TOL, rtol=LOGIT_TOL)
    # and the whole layer: x + attention + that sum, attention counted once
    h = fam._norm(x, whole["norm"], uncut.eps)
    layer_out = np.asarray(fam.ref_layer(x, whole, uncut, mm,
                                         "sliding_attention"))
    attn = np.asarray(fam.ref_attention(h, whole, uncut, mm, uncut.W))
    share_spec = [_spec(num_experts=4, held_experts_start=4 * s)
                  for s in range(4)]
    parts = []
    for sp in share_spec:
        pick = np.asarray(list(range(sp.E0, sp.E0 + 4)) + [16, 17], np.int32)
        lay = dict(whole, **{k: whole[k][pick]
                             for k in ("w_gate", "w_up", "w_down")})
        parts.append(np.asarray(fam.ref_experts(h, lay, sp, mm)))
    none = _spec(num_experts=0, held_experts_start=10**6)
    lay0 = dict(whole, **{k: whole[k][16:] for k in ("w_gate", "w_up", "w_down")})
    shared = np.asarray(fam.ref_experts(h, lay0, none, mm))
    np.testing.assert_allclose(
        np.asarray(x) + attn + sum(p - shared for p in parts) + shared,
        layer_out, atol=LOGIT_TOL, rtol=LOGIT_TOL)


def test_every_held_expert_has_a_row_whatever_the_routing(interpret):
    """``route``'s last column: row r is assigned held expert r at weight
    0, so a step streams all the experts the chip holds and its bytes do not
    follow the routing (which follows the seed); the sum is untouched."""
    spec = _spec()
    cfg = fam.program_config(spec, 64)
    layer = fam._init(spec, fam.key_from_seed(9))["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(0), (6, spec.D), jnp.float32)
    ids, weights, counts = (np.asarray(a) for a in
                            tt.jit(lambda x2, lay: cm.route(x2, lay, cfg))(x, layer))
    assert ids.shape == (6, spec.k + spec.Sh + 1)
    assert ids[:, -1].tolist() == [0, 1, 2, 3, -1, -1]
    assert (weights[:, -1] == 0).all()
    # the picks' weights sum to 1 over all top_k, the shared ones to 1
    np.testing.assert_allclose(weights[:, :spec.k].sum(1), 1.0, rtol=1e-6)
    np.testing.assert_allclose(weights[:, spec.k:-1].sum(1), 1.0, rtol=1e-6)
    _, tile_expert, n_live, _ = px.moe_group_layout(jnp.asarray(ids),
                                                    spec.E + spec.Sh, 6)
    live = set(np.asarray(tile_expert)[: int(n_live[0])].tolist())
    assert live == set(range(spec.E + spec.Sh))
    assert counts[0] <= spec.E and counts[1] <= 6 * spec.k
    # the fourth count is what streams: every held expert here (6 rows >= 4
    # held), whatever the routing hit
    assert counts.shape == (4,) and counts[3] == spec.E >= counts[0]


# ---------------------------------------------------------------------------
# the kernels against their XLA decompositions (interpret mode)
# ---------------------------------------------------------------------------

def _decomposed(op, *args, **kw):
    """``op``'s prim decomposition, compiled with no Pallas claim."""
    old = os.environ.pop("THUNDER_TPU_PALLAS_INTERPRET", None)
    try:
        return tt.jit(lambda *a: op(*a, **kw))(*args)
    finally:
        if old is not None:
            os.environ["THUNDER_TPU_PALLAS_INTERPRET"] = old


@pytest.mark.parametrize("nh", [1, 2, 4], ids=["1h", "2h", "4h"])
@pytest.mark.parametrize("H,KV", [(8, 4), (4, 4)], ids=["gqa", "mha"])
def test_window_page_walk_matches_decomposition(interpret, heads_a_copy, H,
                                                KV, nh):
    """Ring tables with the walk starting mid-ring, ``nh`` KV heads a copy:
    lengths below, at and far above the window (so the ring has wrapped
    several times), a page and a block less and more one, a length-1 slot,
    an idle slot between live ones, pages in scrambled pool order, every
    page no window reaches NaN."""
    heads_a_copy(nh)
    rng = np.random.RandomState(2)
    hd, ps, W = 16, 4, 8
    R, P = 3, 60
    lengths = np.asarray([1, 3, 5, 0, 8, 9, 11, 13, 23, 40], np.int32)
    B = len(lengths)
    bt = np.zeros((B, R), np.int32)
    free = list(rng.permutation(np.arange(1, P)))
    for b, ln in enumerate(lengths):
        for page in range(max(ln - W, 0) // ps, -(-ln // ps)):
            bt[b, page % R] = free.pop()
    dead = np.ones(P, bool)
    dead[bt[bt > 0]] = False
    q = jnp.asarray(rng.randn(B, H, 1, hd), jnp.float32)
    kp, vp = rng.randn(2, KV, P, ps, hd).astype(np.float32)
    poisoned = [jnp.asarray(np.where(dead[None, :, None, None], np.nan, x))
                for x in (kp, vp)]
    kp, vp = (np.where(dead[None, :, None, None], 0.0, x) for x in (kp, vp))
    observe.enable(clear=True)
    try:
        got = px.pallas_paged_decode_attention(
            q, *poisoned, jnp.asarray(bt), jnp.asarray(lengths), window=W)
        path, = [e for e in observe.get_registry().events
                 if e["kind"] == "kernel_path"]
    finally:
        observe.disable()
        observe.reset()
    assert (path["rung"], path["heads_per_copy"]) == (f"ring_walk_3p_{nh}h", nh)
    want = _decomposed(tnn.paged_decode_attention, q, jnp.asarray(kp),
                       jnp.asarray(vp), bt, lengths, window=W)
    live = lengths > 0
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_array_equal(np.asarray(got)[~live], 0.0)
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               atol=KERNEL_TOL, rtol=KERNEL_TOL)
    # and against the definition: the last W keys, by position
    for b, ln in enumerate(lengths):
        if not ln:
            continue
        pos = np.arange(max(ln - W, 0), ln)
        pages = bt[b, (pos // ps) % R]
        k = kp[:, pages, pos % ps]                        # (KV, n, hd)
        v = vp[:, pages, pos % ps]
        qg = np.asarray(q)[b, :, 0].reshape(KV, H // KV, hd)
        s = np.einsum("kgd,knd->kgn", qg, k) / np.sqrt(hd)
        p = np.exp(s - s.max(-1, keepdims=True))
        out = np.einsum("kgn,knd->kgd", p / p.sum(-1, keepdims=True), v)
        np.testing.assert_allclose(np.asarray(got)[b, :, 0],
                                   out.reshape(H, hd), atol=KERNEL_TOL,
                                   rtol=KERNEL_TOL)


@pytest.mark.parametrize("window,q_pos0,k_pos0", [
    (None, 32, 0),          # a global layer's chunk over its whole table
    (None, 0, 0),           # the first chunk: keys past the chunk masked
    (24, 40, 16),           # a window layer: ring below, chunk appended
    (24, 8, -16),           # the window reaches below position 0
    (8, 16, 8),             # a window narrower than the chunk
])
def test_banded_flash_forward_matches_decomposition(interpret, monkeypatch,
                                                    window, q_pos0, k_pos0):
    monkeypatch.setattr(px, "_banded_key_block", lambda Lk: 16)  # 4 blocks
    rng = np.random.RandomState(3)
    H, KV, Tq, hd, Lk = 8, 2, 16, 16, 64
    q = jnp.asarray(rng.randn(H, Tq, hd), jnp.float32)
    k = jnp.asarray(rng.randn(KV, Lk, hd), jnp.float32)
    v = jnp.asarray(rng.randn(KV, Lk, hd), jnp.float32)
    got = px.pallas_banded_attention(q, k, v, jnp.int32(q_pos0),
                                     jnp.int32(k_pos0), window=window)
    want = _decomposed(tnn.banded_attention, q, k, v, np.int32(q_pos0),
                       np.int32(k_pos0), window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=KERNEL_TOL, rtol=KERNEL_TOL)


@pytest.mark.parametrize("row_tile", [4, 12])
def test_grouped_expert_matmul_is_dropless_under_skew(interpret, monkeypatch,
                                                      row_tile):
    """One expert takes half the rows (more than a row tile: its group
    spans tiles), one takes none, some picks fall on experts held elsewhere;
    every held assignment is computed, whatever the skew."""
    monkeypatch.setattr(px, "_moe_tiles", lambda N, D, F, i: (row_tile, 16))
    rng = np.random.RandomState(4)
    N, D, F, E, K = 12, 32, 48, 5, 4
    x = jnp.asarray(rng.randn(N, D), jnp.float32)
    wg, wu = (jnp.asarray(rng.randn(E, F, D) / 6, jnp.float32) for _ in "gu")
    wd = jnp.asarray(rng.randn(E, D, F) / 6, jnp.float32)
    ids = rng.randint(-1, E + 3, size=(N, K)).astype(np.int32)
    ids[ids == 3] = E + 1               # expert 3: no row
    ids[: N // 2, 0] = 2                # expert 2: half the rows at least
    w = rng.rand(N, K).astype(np.float32)
    got = px.pallas_moe_experts(x, wg, wu, wd, jnp.asarray(ids), jnp.asarray(w))
    want = _decomposed(tnn.moe_experts, x, wg, wu, wd, ids, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=KERNEL_TOL, rtol=KERNEL_TOL)
    # the layout: every held assignment owns one buffer row of a tile of
    # its own expert; the live tiles are the groups' ceilings and no more
    dest, tile_expert, n_live, n_tiles = (np.asarray(a) for a in
                                          px.moe_group_layout(jnp.asarray(ids),
                                                              E, row_tile))
    held = (ids.reshape(-1) >= 0) & (ids.reshape(-1) < E)
    assert len(set(dest[held])) == held.sum() and (dest[~held] ==
                                                   n_tiles * row_tile).all()
    assert (tile_expert[dest[held] // row_tile] == ids.reshape(-1)[held]).all()
    counts = np.bincount(ids.reshape(-1)[held], minlength=E)
    assert counts[3] == 0 and counts[2] >= N // 2
    assert n_live[0] == sum(-(-c // row_tile) for c in counts)


# ---------------------------------------------------------------------------
# the allocator, both kinds
# ---------------------------------------------------------------------------

def _held_pages(eng):
    return [sum(len(r.kind_pages[k]) for r in eng.slots if r is not None)
            for k in range(len(eng.kinds))]


def test_allocator_accounts_for_both_kinds(interpret):
    """Admission, sliding, preemption and completion: at every step each
    kind's free list and the residents' pages add up to its pool, a window
    kind never holds more than its ring a request, and an idle engine is
    quiescent in both. The pools are short (2 full contexts' pages for 3
    slots), so residents are preempted and resume by recomputation with
    the tokens an unpressed engine gives."""
    spec = _spec()
    params = fam.init_params(spec, 7)
    rng = np.random.RandomState(1)
    prompts = [rng.randint(1, spec.V, size=n).astype(np.int32)
               for n in (13, 9, 21, 6)]
    new = (30, 34, 20, 25)
    easy = _engine(spec, params)
    want = [r.output().tolist() for r in
            _serve_with_logits(easy, prompts, new)[0]]
    easy.assert_quiescent()

    eng = _engine(spec, params, num_pages={"full": 23, "window": 8})
    reqs = [eng.submit(p, n) for p, n in zip(prompts, new)]
    observe.enable(clear=True)
    try:
        while not eng.idle:
            assert eng.step()
            for k, (cache, held) in enumerate(zip(eng.caches,
                                                  _held_pages(eng))):
                assert cache.pages_free + held == cache.pages_total, k
            for r in eng.slots:
                if r is not None:
                    assert len(r.kind_pages[1]) <= eng.geoms[1].pages_per_request
                    assert r.page_base[0] == 0
        snap = observe.snapshot()
    finally:
        observe.disable()
        observe.reset()
    assert snap["counters"]["serving.preempted_requests"] >= 1
    assert snap["counters"]["kv.window_pages_recycled"] > 0
    eng.assert_quiescent()
    assert [r.output().tolist() for r in reqs] == want
    state = eng.describe_state()
    assert [k["kind"] for k in state["cache_kinds"]] == ["full", "window"]
    assert state["quiescence"] == "quiescent"


def test_forks_and_prefix_reuse_of_a_ring_are_refused_typed(interpret):
    spec = _spec()
    params = fam.init_params(spec, 1)
    with pytest.raises(InfeasibleRequest, match="window ring"):
        _engine(spec, params, prefix_cache=True)
    eng = _engine(spec, params)
    with pytest.raises(InfeasibleRequest, match="window ring"):
        eng.submit(np.arange(1, 6, dtype=np.int32), 4, best_of=2)
    assert eng.idle


# ---------------------------------------------------------------------------
# spans, counters, events, the planner's record
# ---------------------------------------------------------------------------

def test_records_of_the_two_kinds_and_the_routing(interpret):
    spec = _spec()
    eng = _engine(spec, fam.init_params(spec, 2))
    observe.enable(clear=True)
    try:
        eng.submit(np.arange(1, 12, dtype=np.int32), 20)
        eng.drain()
        reg = observe.get_registry()
        walks = [s["args"] for s in reg.spans if s["name"] == "decode_dispatch"]
        sched = [s["args"] for s in reg.spans if s["name"] == "schedule"]
        routes = [e for e in reg.events if e["kind"] == "moe_route"]
        paths = [e for e in reg.events if e["kind"] == "kernel_path"]
        snap = observe.snapshot()
        text = observe.explain(eng.runner.decode_jit)
        decisions = tt.compile_stats(eng.runner.decode_jit).last_decisions
    finally:
        observe.disable()
        observe.reset()
    # the window layers walk at most the ring whatever the context; the
    # global layer the whole context
    assert walks and all(a["live_pages_window"] <= 3 * 3 for a in walks)
    assert max(a["live_pages_full"] for a in walks) == -(-31 // 4) + 2
    assert sum(a["window_pages_recycled"] for a in sched) > 0
    # one event a layer a step: held experts hit, local picks, largest load
    assert len(routes) == 4 * len(walks)
    for e in routes:
        assert 0 <= e["hit"] <= spec.E and e["max_load"] <= e["local_picks"]
        # what streams: the hit experts and those a row's zero-weight
        # column names (one a slot)
        assert max(e["hit"], min(eng.max_slots, spec.E)) \
            <= e["streamed"] <= spec.E
        # over every slot's row, the idle slots' included: the program
        # routes the batch it is given
        assert e["local_picks"] <= eng.max_slots * spec.k
    assert snap["counters"]["moe.local_picks"] == sum(e["local_picks"]
                                                      for e in routes)
    assert snap["counters"]["moe.experts_hit"] == sum(e["hit"] for e in routes)
    assert {e["op"] for e in paths} == {"nn.moe_experts", "nn.banded_attention",
                                        "nn.paged_decode_attention"}
    # the walk of BOTH kinds says what a copy moves, once a call site (a
    # layer of the one decode program): three rings to one whole table
    walk_paths = [e for e in paths if e["op"] == "nn.paged_decode_attention"]
    ring_pages, = [g.pages_per_request for g in eng.geoms if g.window]
    ring = f"ring_walk_{ring_pages}p_{spec.KV}h"
    assert [e["rung"] for e in walk_paths] == \
        [ring, ring, ring, f"walk_{spec.KV}h"]
    for e in walk_paths:
        assert e["heads_per_copy"] == spec.KV and e["pages_per_block"] >= 1
        assert e["staged_bytes"] == 4 * spec.KV * e["pages_per_block"] \
            * eng.geoms[0].page_size * spec.hd * 4
    assert f"kernel path: nn.paged_decode_attention -> walk_{spec.KV}h (" in text
    assert f"-> {ring} (" in text and f"heads_per_copy={spec.KV}" in text
    assert "cache kinds:" in text and "expert routing:" in text
    blocks = [d for d in decisions if d["kind"] == "block"]
    assert [d["decision"] for d in blocks] == ["parallel-block"] * 4
    assert all(d["cost"]["weight_bytes"] > d["cost"]["shared_row_bytes"]
               for d in blocks)
