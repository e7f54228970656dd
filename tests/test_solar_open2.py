"""The ``solar_open2`` family on the serving path, at the rehearsal size, on
seeded weights against the plain reference (``benchmark/families/
solar_open2.py`` — one owner; the benchmark's comparison uses the same
file), and the two delta-rule ops against the recurrence token by token.

Tolerances. Everything here is float32 under "highest" matmul precision, so
program and reference differ by summation order only: logits of order 1 over
a 64-wide model agree to ~1e-5; ``2e-4`` leaves a decade of room and is four
decades under what one rounding to bfloat16 (2**-8 relative) would move.
The chunked delta rule against the token recurrence (float64 here):
``2e-5``, a state of order 1 summed over 64 tokens a chunk in float32 (a
relative 2**-24 a step) reads ~1e-6; the decay-near-0 case underflows to
exact zeros on both sides. A kernel against its decomposition on identical
inputs: ``2e-5``.
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
from thunder_tpu.models import solar_open2 as so
from thunder_tpu.ops import nn as tnn
from thunder_tpu.serving import InfeasibleRequest, ServingEngine
from thunder_tpu.serving.kv_cache import SlotStateCache

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir)
LOGIT_TOL = 2e-4
SCAN_TOL = 2e-5
KERNEL_TOL = 2e-5


def _family():
    path = os.path.join(ROOT, "benchmark", "families", "solar_open2.py")
    spec = importlib.util.spec_from_file_location("bench_families_solar_open2",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


fam = _family()


def _spec(**over):
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "solar-open2-250b-l4e40.json")) as f:
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
        resident.update({i: r for i, r in enumerate(eng.slots)
                         if r is not None})
        logits = np.asarray(eng.last_decode_logits)
        for i, r in resident.items():
            if len(r.generated) > before[r.request_id]:
                rows[r.request_id].append(logits[i])
    return reqs, rows


def _check_rows(params, spec, reqs, prompts, new, rows):
    for r, p, n in zip(reqs, prompts, new):
        seq = jnp.asarray(np.concatenate([p, r.output()]))
        ref = np.asarray(fam.ref_logits(params, seq, spec))
        got = np.stack(rows[r.request_id])
        assert got.shape[0] == n
        np.testing.assert_allclose(got, ref[len(p) - 1: len(p) - 1 + n],
                                   atol=LOGIT_TOL, rtol=LOGIT_TOL)


# ---------------------------------------------------------------------------
# the two ops against the recurrence, token by token
# ---------------------------------------------------------------------------

def _recurrence(q, k, v, g, beta, S, n):
    """float64, a token at a time; positions from ``n`` on leave S."""
    H, T, _ = q.shape
    S = S.astype(np.float64).copy()
    o = np.zeros((H, T, v.shape[2]))
    for t in range(T):
        if t < n:
            S = S * np.exp(g[:, t])[:, :, None]
            kv = np.einsum("hkd,hk->hd", S, k[:, t])
            S = S + k[:, t, :, None] * (beta[:, t, None]
                                        * (v[:, t] - kv))[:, None, :]
        o[:, t] = np.einsum("hkd,hk->hd", S, q[:, t])
    return o, S


def _inputs(rng, H, T, dk, glo, ghi, blo=0.01, bhi=1.99):
    q = rng.normal(size=(H, T, dk))
    k = rng.normal(size=(H, T, dk))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.normal(size=(H, T, dk))
    g = rng.uniform(glo, ghi, size=(H, T, dk))
    beta = rng.uniform(blo, bhi, size=(H, T))
    return [a.astype(np.float32) for a in (q, k, v, g, beta)]


@pytest.mark.parametrize("kernels", ["pallas-interpret", "xla-decomposition"])
@pytest.mark.parametrize("T,n,decay,beta", [
    (37, 30, (-0.02, 0.0), (0.01, 1.99)),     # decay near 1, ragged end
    (37, 37, (-40.0, -8.0), (0.01, 1.99)),    # decay near 0 (underflows)
    (64, 50, (-3.0, 0.0), (1.5, 1.99)),       # beta near 2: the eigenvalue
    (20, 7, (-1.0, 0.0), (0.01, 0.5)),        # one short inner chunk
])
def test_chunked_delta_rule_matches_the_recurrence(kernels, monkeypatch, T, n,
                                                   decay, beta):
    """``nn.kda_chunk`` with inner chunks of 16 against the token
    recurrence: lengths that are not multiples of the inner chunk, padding
    past ``n`` that leaves the state exactly as the last prompt token did."""
    if kernels == "pallas-interpret":
        monkeypatch.setenv("THUNDER_TPU_PALLAS_INTERPRET", "1")
    else:
        monkeypatch.delenv("THUNDER_TPU_PALLAS_INTERPRET", raising=False)
    rng = np.random.default_rng(T + n)
    H, dk = 2, 8
    q, k, v, g, b = _inputs(rng, H, T, dk, *decay, *beta)
    S = rng.normal(size=(H, dk, dk)).astype(np.float32)
    run = tt.jit(lambda *a: tnn.kda_chunk(*a, chunk=16))
    o, S1 = run(q, k, v, g, b, S, np.int32(n))
    ro, rS = _recurrence(q, k, v, g, b, S, n)
    np.testing.assert_allclose(np.asarray(o)[:, :n], ro[:, :n],
                               atol=SCAN_TOL, rtol=SCAN_TOL)
    np.testing.assert_allclose(np.asarray(S1), rS, atol=SCAN_TOL,
                               rtol=SCAN_TOL)


def test_a_state_carried_over_two_calls_equals_one_call(interpret):
    rng = np.random.default_rng(1)
    H, T, dk = 2, 48, 8
    q, k, v, g, b = _inputs(rng, H, T, dk, -1.0, 0.0)
    S = np.zeros((H, dk, dk), np.float32)
    run = tt.jit(lambda *a: tnn.kda_chunk(*a, chunk=16))
    o, S1 = run(q, k, v, g, b, S, np.int32(T))
    cut = 32
    part = lambda a, s: a[:, s]
    oa, Sa = run(*(part(a, slice(0, cut)) for a in (q, k, v, g, b)), S,
                 np.int32(cut))
    ob, Sb = run(*(part(a, slice(cut, T)) for a in (q, k, v, g, b)),
                 np.asarray(Sa), np.int32(T - cut))
    np.testing.assert_allclose(np.concatenate([oa, ob], 1), np.asarray(o),
                               atol=SCAN_TOL, rtol=SCAN_TOL)
    np.testing.assert_allclose(np.asarray(Sb), np.asarray(S1), atol=SCAN_TOL,
                               rtol=SCAN_TOL)


@pytest.mark.parametrize("kernels", ["pallas-interpret", "xla-decomposition"])
def test_decode_step_after_a_prefill_is_the_recurrence_next_step(kernels,
                                                                 monkeypatch):
    """A chunk, then ``nn.kda_decode`` with rows that take their token (1)
    and rows that only read (0: a replay row, an idle row)."""
    if kernels == "pallas-interpret":
        monkeypatch.setenv("THUNDER_TPU_PALLAS_INTERPRET", "1")
    else:
        monkeypatch.delenv("THUNDER_TPU_PALLAS_INTERPRET", raising=False)
    rng = np.random.default_rng(2)
    Sl, H, T, dk = 3, 2, 21, 8
    seqs = [_inputs(rng, H, T + 1, dk, -2.0, 0.0) for _ in range(Sl)]
    chunk = tt.jit(lambda *a: tnn.kda_chunk(*a, chunk=16))
    zero = np.zeros((H, dk, dk), np.float32)
    states = [np.asarray(chunk(*(a[:, :T] for a in s), zero, np.int32(T))[1])
              for s in seqs]
    nxt = lambda i: np.stack([s[i][:, T] for s in seqs])     # (Sl, H, ...)
    update = np.asarray([1, 0, 1], np.int32)
    o, S1 = tt.jit(tnn.kda_decode)(nxt(0), nxt(1), nxt(2), nxt(3), nxt(4),
                                   np.stack(states), update)
    for i, s in enumerate(seqs):
        ro, rS = _recurrence(*s, zero, T + update[i])
        np.testing.assert_allclose(np.asarray(o)[i], ro[:, T], atol=SCAN_TOL,
                                   rtol=SCAN_TOL)
        np.testing.assert_allclose(np.asarray(S1)[i], rS, atol=SCAN_TOL,
                                   rtol=SCAN_TOL)


@pytest.mark.parametrize("H,hg", [(64, 8), (64, 16), (64, 32), (64, 64),
                                  (24, None)])
def test_decode_head_groups_are_bit_for_bit_the_groups_of_8(interpret,
                                                            monkeypatch, H,
                                                            hg):
    """``pallas_kda_decode`` with ``hg`` heads a grid step (None: the
    rule's own, 24 for 24 heads) gives the outputs and state of 8 heads a
    step, bit for bit: each head's operations and their order do not
    depend on the grouping. 4 slots of the cell's 128-wide heads, one row
    that only reads."""
    rng = np.random.default_rng(H)
    Sl, dk = 4, 128
    q, k, v, g = (rng.normal(size=(Sl, H, dk)).astype(np.float32)
                  for _ in range(4))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    g = -np.exp(g)
    beta = rng.uniform(0.01, 1.99, size=(Sl, H)).astype(np.float32)
    S = rng.normal(size=(Sl, H, dk, dk)).astype(np.float32)
    update = np.asarray([1, 1, 0, 1], np.int32)
    rule = px._kda_heads_per_step

    def run(n):
        monkeypatch.setattr(px, "_kda_heads_per_step",
                            lambda H, dk, dv: n or rule(H, dk, dv))
        o, S1 = px.pallas_kda_decode(q, k, v, g, beta, S.copy(), update)
        return np.asarray(o), np.asarray(S1)

    if hg is None:
        assert rule(H, dk, dk) == H
    (o, S1), (o8, S8) = run(hg), run(8)
    assert np.array_equal(o, o8) and np.array_equal(S1, S8)
    assert np.array_equal(S1[2], S[2])


# ---------------------------------------------------------------------------
# prefill, then decode, through both cache kinds, against ref_logits
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kernels", ["pallas-interpret", "xla-decomposition"])
def test_served_logits_match_reference(kernels, monkeypatch):
    """Prompts of one token, of a ragged single chunk, and of two chunks
    with a padded tail; decode to several times the prompt. Logits."""
    if kernels == "pallas-interpret":
        monkeypatch.setenv("THUNDER_TPU_PALLAS_INTERPRET", "1")
    else:
        monkeypatch.delenv("THUNDER_TPU_PALLAS_INTERPRET", raising=False)
    spec = _spec()
    params = fam.init_params(spec, 3)
    eng = _engine(spec, params)
    rng = np.random.RandomState(0)
    lens, new = (1, 7, 21), (24, 9, 30)
    prompts = [rng.randint(1, spec.V, size=n).astype(np.int32) for n in lens]
    reqs, rows = _serve_with_logits(eng, prompts, new)
    _check_rows(params, spec, reqs, prompts, new, rows)
    eng.assert_quiescent()


def test_a_reused_slot_starts_from_zero(interpret):
    """One slot, three requests in turn: each starts from a zero state, so
    each is served as if alone (its logits against the reference's)."""
    spec = _spec()
    params = fam.init_params(spec, 4)
    eng = _engine(spec, params, max_slots=1)
    rng = np.random.RandomState(3)
    prompts = [rng.randint(1, spec.V, size=n).astype(np.int32)
               for n in (9, 18, 5)]
    new = (6, 5, 8)
    observe.enable(clear=True)
    try:
        reqs, rows = _serve_with_logits(eng, prompts, new)
        resets = observe.snapshot()["counters"]["kv.state_resets"]
    finally:
        observe.disable()
        observe.reset()
    _check_rows(params, spec, reqs, prompts, new, rows)
    assert resets == 3
    eng.assert_quiescent()


def test_request_state_is_what_the_slot_took_in(interpret):
    """Between steps a decoding slot's state has taken in the prompt and
    every generated token but the last: the same tokens prefilled as a
    prompt in another engine leave the same state (decode's path against
    prefill's, both float32)."""
    spec = _spec()
    params = fam.init_params(spec, 9)
    prompt = np.random.RandomState(4).randint(1, spec.V, size=11) \
        .astype(np.int32)
    eng = _engine(spec, params)
    req = eng.submit(prompt, 8)
    while len(req.generated) < 5:
        assert eng.step()
    took = np.concatenate([prompt, req.generated[:-1]]).astype(np.int32)
    again = _engine(spec, params)
    other = again.submit(took, 2)
    while not other.generated:
        assert again.step()
    got, want = eng.request_state(req), again.request_state(other)
    assert len(got) == 3 and [sorted(a) for a in got] == [["conv", "s"]] * 3
    for a, b in zip(got, want):
        np.testing.assert_allclose(a["s"], b["s"], atol=SCAN_TOL,
                                   rtol=SCAN_TOL)
        np.testing.assert_allclose(a["conv"], b["conv"], atol=SCAN_TOL,
                                   rtol=SCAN_TOL)


def test_a_preempted_request_resumes_with_its_logits(interpret):
    """A full pool too short for every context: residents are preempted,
    re-prefill from a zero state with their generated tokens, and every
    token's logits are still the reference's."""
    spec = _spec()
    params = fam.init_params(spec, 7)
    rng = np.random.RandomState(1)
    prompts = [rng.randint(1, spec.V, size=n).astype(np.int32)
               for n in (13, 9, 21)]
    new = (30, 34, 20)
    eng = _engine(spec, params, num_pages={"full": 20})
    observe.enable(clear=True)
    try:
        reqs, rows = _serve_with_logits(eng, prompts, new)
        snap = observe.snapshot()
    finally:
        observe.disable()
        observe.reset()
    assert snap["counters"]["serving.preempted_requests"] >= 1
    _check_rows(params, spec, reqs, prompts, new, rows)
    eng.assert_quiescent()
    state = eng.describe_state()
    assert [k["kind"] for k in state["cache_kinds"]] == ["full", "state"]
    assert state["cache_kinds"][1]["state_bytes"] == eng.caches[1].nbytes


def test_state_rows_are_a_slot_each_and_hold_no_pages(interpret):
    spec = _spec()
    eng = _engine(spec, fam.init_params(spec, 1), max_slots=4)
    full, state = eng.geoms
    assert full.n_layers == 1 and state.n_layers == 3 and state.slots == 4
    cache = eng.caches[1]
    assert isinstance(cache, SlotStateCache)
    shapes = [{k: tuple(a.shape) for k, a in kv.items()}
              for kv in eng._pools()]
    assert shapes[1:] == [{"s": (4, 4, 16, 16), "conv": (4, 4, 192)}] * 3
    assert cache.nbytes == 3 * 4 * (4 * 16 * 16 * 4 + 4 * 192 * 4)


def test_forks_and_prefix_reuse_of_a_state_are_refused_typed(interpret):
    spec = _spec()
    params = fam.init_params(spec, 1)
    with pytest.raises(InfeasibleRequest, match="state kind"):
        _engine(spec, params, prefix_cache=True)
    eng = _engine(spec, params)
    with pytest.raises(InfeasibleRequest, match="state kind"):
        eng.submit(np.arange(1, 6, dtype=np.int32), 4, best_of=2)
    assert eng.idle


# ---------------------------------------------------------------------------
# the chip's share and the router's correction bias
# ---------------------------------------------------------------------------

def test_the_shares_add_up_to_the_uncut_layer(interpret):
    """16 experts over 8 chips of 2: the routed parts of the eight shares,
    with what every chip computes alike (the shared expert; the attention)
    counted once, give the uncut reference layer. The program's expert
    layer for the shares, the reference for the whole."""
    uncut = _spec(n_routed_experts=16)
    key = fam.key_from_seed(5)
    whole = fam._init(uncut, key)["layers"][1]              # a KDA layer
    x = jax.random.normal(jax.random.fold_in(key, 1), (6, uncut.D),
                          jnp.float32)
    mm = fam.matmul("float32")
    layer_out = np.asarray(fam.ref_layer(x, whole, uncut, mm, "kda"))
    mixed = np.asarray(x) + np.asarray(fam.ref_kda(
        fam._rms(x, whole["attn_norm"], uncut.eps), whole, uncut, mm))
    u = fam._rms(jnp.asarray(mixed), whole["ffn_norm"], uncut.eps)

    def program_share(start, n_held):
        cfg = fam.program_config(_spec(n_routed_experts=n_held,
                                       held_experts_start=start), 64)
        keep = list(range(start, start + n_held)) if start < 16 else []
        pick = np.asarray(keep + [16], np.int32)        # the shared one last
        layer = dict(whole, **{k: whole[k][pick]
                               for k in ("w_gate", "w_up", "w_down")})
        run = tt.jit(lambda u3, lay: so._moe(u3, lay, cfg)[0])
        return np.asarray(run(u[None], layer))[0]

    shared_only = program_share(10**6, 0)
    shares = [program_share(2 * s, 2) for s in range(8)]
    total = mixed + sum(s - shared_only for s in shares) + shared_only
    np.testing.assert_allclose(total, layer_out, atol=LOGIT_TOL,
                               rtol=LOGIT_TOL)


def test_the_correction_bias_picks_and_does_not_weigh(interpret):
    """With a bias that outweighs the scores, the picks are the bias's
    top_k, and their weights are their SCORES over the picks' sum."""
    from thunder_tpu.models import cohere2_moe as cm

    spec = _spec()
    cfg = fam.program_config(spec, 64)
    layer = dict(fam._init(spec, fam.key_from_seed(9))["layers"][1])
    layer["router_bias"] = jnp.zeros(16).at[jnp.asarray([3, 7, 11, 15])].set(
        10.0)
    x = jax.random.normal(jax.random.PRNGKey(0), (5, spec.D), jnp.float32)
    ids, weights, _ = (np.asarray(a) for a in tt.jit(
        lambda x2, lay: cm.route(x2, lay, cfg))(x, layer))
    # held experts 0-3: pick 3 is local, 7 / 11 / 15 are held elsewhere
    assert sorted(ids[0, :spec.k].tolist()) == [-1, -1, -1, 3]
    s = 1 / (1 + np.exp(-np.asarray(x) @ np.asarray(layer["router"]).T))
    w = s[:, [3, 7, 11, 15]] / s[:, [3, 7, 11, 15]].sum(1, keepdims=True)
    np.testing.assert_allclose(np.sort(weights[:, :spec.k], 1),
                               np.sort(w, 1), rtol=1e-5)


# ---------------------------------------------------------------------------
# records: kernel paths, state args, counters, gauges, explain
# ---------------------------------------------------------------------------

def test_records_of_the_state_kind(interpret):
    spec = _spec()
    eng = _engine(spec, fam.init_params(spec, 2))
    observe.enable(clear=True)
    try:
        eng.submit(np.arange(1, 22, dtype=np.int32), 6)
        eng.submit(np.arange(1, 4, dtype=np.int32), 3)
        eng.drain()
        reg = observe.get_registry()
        walks = [s["args"] for s in reg.spans if s["name"] == "decode_dispatch"]
        chunks = [s["args"] for s in reg.spans if s["name"] == "prefill_chunk"]
        paths = [e for e in reg.events if e["kind"] == "kernel_path"]
        snap = observe.snapshot()
        labeled = dict(reg.labeled_gauges)
        text = observe.explain(eng.runner.decode_jit)
    finally:
        observe.disable()
        observe.reset()
    assert walks and all("state_rows" in a for a in walks)
    # every slot's row, idle ones too: the kernel walks the whole pool
    assert all(a["state_rows"] == eng.max_slots for a in walks)
    assert min(a["batch"] for a in walks) < eng.max_slots
    # the 21-token prompt is two chunks: the first from zero, the second
    # carried; the 3-token prompt one chunk from zero
    assert sorted(a["state_in"] for a in chunks) == [0, 0, 1]
    assert snap["counters"]["kv.state_resets"] == 2
    state_gauges = [v for (name, key), v in labeled.items()
                    if name == "serving.state_bytes" and "state" in str(key)]
    assert state_gauges and all(v == eng.caches[1].nbytes
                                for v in state_gauges)
    kda = {e["op"]: e for e in paths if e["op"].startswith("nn.kda")}
    assert set(kda) == {"nn.kda_chunk", "nn.kda_decode"}
    for e in kda.values():
        assert {"rung", "heads_per_step", "chunk", "state_dtype"} <= set(e)
        assert e["state_dtype"] == "float32"
    # once a call site: three KDA layers in the decode program
    assert sum(1 for e in paths if e["op"] == "nn.kda_decode") == 3
    assert "cache kinds: state read for" in text


def test_route_without_a_bias_traces_the_routing_it_had(interpret):
    """A layer with no ``router_bias`` (every Command A+ layer) is routed as
    before: one top_k of the scores and no gather of them; the picks'
    weights are their scores over their sum. (Traces of the Llama and
    Command A+ serving programs were compared with the previous revision's,
    text for text; this pins the one branch they share with this model.)"""
    from thunder_tpu.models import cohere2_moe as cm

    spec = _spec()
    cfg = fam.program_config(spec, 64)
    layer = dict(fam._init(spec, fam.key_from_seed(9))["layers"][1])
    del layer["router_bias"]
    x = jax.random.normal(jax.random.PRNGKey(1), (5, spec.D), jnp.float32)
    run = tt.jit(lambda x2, lay: cm.route(x2, lay, cfg))
    ids, weights, _ = (np.asarray(a) for a in run(x, layer))
    text = tt.last_traces(run)[0].python()
    assert "topk" in text and "take_along_axis" not in text
    s = 1 / (1 + np.exp(-np.asarray(x) @ np.asarray(layer["router"]).T))
    top = np.sort(s, 1)[:, ::-1][:, :spec.k]
    np.testing.assert_allclose(np.sort(weights[:, :spec.k], 1)[:, ::-1],
                               top / top.sum(1, keepdims=True), rtol=1e-5)
