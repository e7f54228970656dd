"""One span tree (ISSUE 25): every span record has an identity and a cause;
the serving iteration is a tree of leaves that cover it; a request's road to
its first token adds up to its TTFT; a ``tt.jit`` call shows its guard and
its dispatch, and a miss says why. All of it costs the flight ring nothing
it did not cost before."""

import importlib.util
import os
from collections import Counter

import numpy as np
import pytest

import thunder_tpu as tt
from thunder_tpu import observe, ops
from thunder_tpu.models import llama
from thunder_tpu.observe import flight
from thunder_tpu.observe import registry as reg
from thunder_tpu.serving import ServingEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

LEAVES = ("schedule", "decode_build", "decode_enqueue", "decode_wait",
          "decode_deliver", "prefill_build", "prefill_chunk",
          "prefill_deliver")


@pytest.fixture(autouse=True)
def _clean_registry():
    observe.disable()
    observe.reset()
    yield
    observe.disable()
    observe.reset()


def _spans(name=None):
    return [s for s in observe.get_registry().spans
            if name is None or s["name"] == name]


def _one(name):
    (s,) = _spans(name)
    return s


# ---------------------------------------------------------------------------
# identity and cause
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("path", ["module", "labeled"])
def test_ids_unique_and_parent_follows_nesting(path):
    """Both span forms, module path and ``Labeled`` path alike: the
    context-manager form nests by the block; a span handed over with its
    timestamps hangs under the span that was open when it BEGAN."""
    h = reg if path == "module" else observe.labeled(engine="e-test")
    observe.enable(clear=True)
    before = reg._now_us()
    with h.span("outer", "test"):
        with h.span("inner", "test"):
            t0 = reg._now_us()
            h.record_span("handed_inner", "test", t0, 1.0)
        # began before "outer" opened: no span of this thread was open then
        h.record_span("handed_early", "test", before, 1.0)
        t1 = reg._now_us()
    h.record_span("handed_late", "test", t1, 1.0)    # its parent has closed
    spans = _spans()
    ids = [s["id"] for s in spans]
    assert len(set(ids)) == len(ids) == 5 and all(isinstance(i, int) for i in ids)
    by = {s["name"]: s for s in spans}
    assert by["outer"]["parent"] is None
    assert by["inner"]["parent"] == by["outer"]["id"]
    assert by["handed_inner"]["parent"] == by["inner"]["id"]
    assert by["handed_early"]["parent"] is None
    assert by["handed_late"]["parent"] is None
    if path == "labeled":
        assert all(s["labels"] == {"engine": "e-test"} for s in spans)
    # the ring's copy of each record carries the same identity
    ring = {r["name"]: r for r in flight.snapshot()
            if r["type"] == "span" and r["id"] in ids}
    assert {n: (r["id"], r["parent"]) for n, r in ring.items()} == \
        {n: (s["id"], s["parent"]) for n, s in by.items()}


def test_parent_is_per_thread():
    """A span opened on another thread is not this thread's parent."""
    import threading

    observe.enable(clear=True)
    with observe.span("main_outer", "test"):
        t = threading.Thread(
            target=lambda: observe.span("other", "test").__enter__()
            .__exit__(None, None, None))
        t.start()
        t.join(timeout=10)
        assert not t.is_alive()
    assert _one("other")["parent"] is None


@pytest.mark.parametrize("path", ["module", "labeled"])
def test_registry_only_span_costs_nothing_when_off(path):
    """``ring=False``: with the registry off the span is THE shared no-op —
    no record anywhere; with it on, a registry record and still no ring
    record."""
    h = reg if path == "module" else observe.labeled(engine="e-test")
    flight.clear()
    a, b = h.span("sub", "test", ring=False), h.span("sub", "test", ring=False)
    assert a is b is reg._NO_SPAN and not a.live
    with a as sp:
        sp.cancel()
    assert flight.snapshot() == [] and not _spans()
    observe.enable(clear=True)
    with h.span("sub", "test", {"step": 1}, ring=False) as sp:
        assert sp.live
    assert _one("sub")["args"] == {"step": 1}
    assert not [r for r in flight.snapshot() if r.get("name") == "sub"]


def test_cancelled_span_leaves_no_record():
    flight.clear()
    observe.enable(clear=True)
    with observe.span("kept", "test"):
        with observe.span("dropped", "test") as sp:
            sp.cancel()
            with observe.span("child", "test"):
                pass
    assert {s["name"] for s in _spans()} == {"kept", "child"}
    assert "dropped" not in {r.get("name") for r in flight.snapshot()}
    assert sp.dur_us >= 0


@pytest.fixture
def annotations(monkeypatch):
    """The ``TraceAnnotation``s entered and left, in order."""
    seen = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            seen.append(("enter", self.name))

        def __exit__(self, *exc):
            seen.append(("exit", self.name))

    monkeypatch.setattr(reg, "_TraceAnnotation", Annotation)
    return seen


def test_span_enters_a_trace_annotation_while_enabled(annotations):
    """An operator's profiler trace shows the program's spans: the
    context-manager form enters a ``TraceAnnotation`` of the same name while
    the registry is on, and none while it is off."""
    seen = annotations
    with observe.span("off", "test"):
        pass
    assert seen == []
    observe.enable(clear=True)
    with observe.span("a", "test"):
        with observe.labeled(engine="e").span("b", "test", ring=False):
            pass
    assert seen == [("enter", "a"), ("enter", "b"), ("exit", "b"), ("exit", "a")]


def test_step_span_enters_a_trace_annotation(annotations):
    """``step:<fn>`` is a live span: the profiler's trace holds the
    dispatch of a compiled entry on its own clock, inside ``jit_call``'s
    annotation, while the registry is on."""
    seen = annotations
    jf = tt.jit(lambda a: ops.mul(a, 2.0).sum())
    x = np.ones((8, 8), np.float32)
    jf(x)
    assert not [n for _, n in seen if n.startswith("step:")]
    observe.enable(clear=True)
    jf(x)
    steps = [(e, n) for e, n in seen if n.startswith("step:")]
    assert [e for e, _ in steps] == ["enter", "exit"]
    names = [n for _, n in seen]
    assert names.index("jit_call") < names.index(steps[0][1]) \
        < len(names) - 1 - names[::-1].index("jit_call")
    (span,) = [s for s in _spans() if s["name"].startswith("step:")]
    assert span["name"] == steps[0][1] and span["args"] == {"first_call": False}


def test_real_trace_annotation_is_entered():
    import jax.profiler

    observe.enable(clear=True)
    with observe.span("annotated", "test"):
        pass
    assert reg._TraceAnnotation is jax.profiler.TraceAnnotation


# ---------------------------------------------------------------------------
# the serving iteration
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def model():
    cfg = llama.CONFIGS["tiny-gqa"]
    return cfg, llama.init_params(cfg, seed=0)


def _engine(model, **kw):
    cfg, params = model
    opts = dict(max_slots=3, page_size=16, max_context=64, n_layers=1,
                prefill_chunk=32)
    opts.update(kw)
    return ServingEngine(params, cfg, **opts)


def _prompt(n, seed=0):
    return np.random.RandomState(seed).randint(1, 500, size=n).astype(np.int32)


def _warm(eng):
    """Every prefill rung and the decode program, compiled and bound."""
    for n in (5, 20, 40):
        eng.submit(_prompt(n), 2)
    eng.drain()
    eng.completed.clear()


def _tiling_leaves(root):
    """The leaves of one ``engine_step`` in time order, checked to lie inside
    it without overlap."""
    by_id = {s["id"]: s for s in _spans()}
    mine = sorted((s for s in _spans() if s["name"] in LEAVES
                   and (s["parent"] == root["id"]
                        or by_id.get(s["parent"], {}).get("parent") == root["id"])),
                  key=lambda s: s["ts_us"])
    assert mine, root
    end = root["ts_us"]
    for s in mine:
        assert s["ts_us"] >= end - 0.5, (s["name"], "overlaps")
        end = s["ts_us"] + s["dur_us"]
    assert end <= root["ts_us"] + root["dur_us"] + 0.5
    return mine


def test_engine_step_leaves_cover_it(model):
    """Over N iterations: one ``engine_step`` root each; its leaves lie
    inside it and do not overlap; together they cover at least 95% of the
    iterations' time. (All four layers and a wide batch, so that a step is
    long against the microseconds each record costs.)"""
    eng = _engine(model, max_slots=16, max_context=512, n_layers=None)
    _warm(eng)
    observe.enable(clear=True)
    for i, n in enumerate((9, 20, 40, 5, 33, 17, 60, 12)):
        eng.submit(_prompt(n, seed=i), 12)
    steps = 0
    while not eng.idle:
        eng.step()
        steps += 1
    eng.step()                      # an idle poll leaves no root
    observe.disable()
    roots = _spans("engine_step")
    assert len(roots) == steps >= 12
    by_id = {s["id"]: s for s in _spans()}
    covered = total = 0.0
    for root in roots:
        assert set(root["args"]) == {"step", "decoding", "queued"}
        mine = _tiling_leaves(root)
        covered += sum(s["dur_us"] for s in mine)
        total += root["dur_us"]
        names = Counter(s["name"] for s in mine)
        if root["args"]["decoding"]:
            assert names["decode_enqueue"] == names["decode_wait"] == 1
    assert covered / total >= 0.95, covered / total
    # decode_dispatch stays, as the parent of its two parts
    for d in _spans("decode_dispatch"):
        kids = [s["name"] for s in _spans() if s["parent"] == d["id"]]
        assert kids == ["decode_enqueue", "decode_wait"]
    # the bound decode program has no guard: its dispatch hangs under
    # decode_enqueue directly
    for s in _spans("step:serving_decode"):
        assert by_id[s["parent"]]["name"] == "decode_enqueue"
    # every span of an iteration carries the step; every request's, its id
    for s in _spans():
        if s["name"] in LEAVES or s["name"] == "decode_dispatch":
            assert "step" in s["args"], s["name"]
        if s["cat"] == "serving:request" or s["name"].startswith("prefill_"):
            assert "request" in s["args"], s["name"]


def _kids(span):
    return [s for s in _spans() if s["parent"] == span["id"]]


def _serve_decode_heavy(eng):
    _warm(eng)
    observe.enable(clear=True)
    for i, n in enumerate((9, 20, 40, 5, 33, 17, 60, 12)):
        eng.submit(_prompt(n, seed=i), 12)
    eng.drain()
    observe.disable()


def test_decode_enqueue_and_wait_split_into_their_causes(model):
    """The call into the bound program is the launch; the wait is the
    device's run and the fetch. The children lie inside their parent in that
    order, and the parents keep their extents: the children cover the wait,
    and all but the bound call's own flatten of the enqueue (a step's share;
    the median, so a step the OS preempted between two spans does not decide
    it)."""
    eng = _engine(model, max_slots=16, max_context=512, n_layers=None)
    _serve_decode_heavy(eng)
    enqueues, waits = _spans("decode_enqueue"), _spans("decode_wait")
    assert len(enqueues) == len(waits) >= 12
    for parent, names in ((enqueues, ["step:serving_decode"]),
                          (waits, ["decode_ready", "decode_fetch"])):
        shares = []
        for p in parent:
            kids = sorted(_kids(p), key=lambda s: s["ts_us"])
            assert [k["name"] for k in kids] == names
            end = p["ts_us"]
            for k in kids:
                assert k["ts_us"] >= end - 0.5
                end = k["ts_us"] + k["dur_us"]
                assert k["args"].get("step", p["args"].get("step")) \
                    == p["args"]["step"]
            assert end <= p["ts_us"] + p["dur_us"] + 0.5
            shares.append(sum(k["dur_us"] for k in kids) / p["dur_us"])
        # the enqueue's rest (the bound call's flatten, the fault and epoch
        # checks) is a quarter of a tiny CPU launch
        least = 0.5 if names[0] == "step:serving_decode" else 0.95
        assert np.median(shares) >= least, (names, sorted(shares))
    # the launch keeps its name and first_call, under decode_enqueue
    launches = _spans("step:serving_decode")
    assert len(launches) == len(enqueues)
    assert not any(s["args"]["first_call"] for s in launches)


@pytest.fixture(scope="module")
def routed():
    """The routed family at its rehearsal size: its decode program returns
    the ``moe_route`` aux beside the tokens."""
    import json

    path = os.path.join(ROOT, "benchmark", "families", "cohere2_moe.py")
    spec_ = importlib.util.spec_from_file_location("bench_cohere2_span", path)
    fam = importlib.util.module_from_spec(spec_)
    spec_.loader.exec_module(fam)
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "command-a-plus-05-2026-l4e16.json")) as f:
        spec = fam.spec_from_config(json.load(f), rehearse=True)
    return fam.program_config(spec, max_seq_len=64), fam.init_params(spec, 2)


@pytest.mark.parametrize("registry", ["on", "off"])
def test_route_record_rides_the_token_fetch(routed, monkeypatch, registry):
    """While the registry is on, the step's aux comes over with the token ids
    in ONE fetch inside ``decode_fetch``, and ``decode_deliver`` hands the
    record host arrays; while it is off, the fetch carries the ids alone and
    nothing is recorded."""
    import jax

    monkeypatch.setenv("THUNDER_TPU_PALLAS_INTERPRET", "1")
    cfg, params = routed
    eng = ServingEngine(params, cfg, max_slots=3, page_size=4, max_context=64,
                        prefill_chunk=16)
    fetches, given = [], []
    real_get = jax.device_get

    def device_get(x):
        fetches.append((reg._open_stack()[-1][0] if reg._open_stack()
                        else None, x))
        return real_get(x)

    record = eng.desc.on_decode_aux
    monkeypatch.setattr(jax, "device_get", device_get)
    monkeypatch.setattr(eng.desc, "on_decode_aux",
                        lambda obs, aux, step: (given.append(aux),
                                                record(obs, aux, step)))
    if registry == "on":
        observe.enable(clear=True)
    eng.submit(np.arange(1, 12, dtype=np.int32), 6)
    eng.drain()
    observe.disable()
    steps = len(eng.completed[0].generated)
    assert len(fetches) == steps
    routes = [e for e in observe.get_registry().events
              if e["kind"] == "moe_route"]
    if registry == "off":
        assert all(aux is None for _, (_, aux) in fetches)
        assert not given and not routes and not _spans()
        return
    by_id = {s["id"]: s["name"] for s in _spans()}
    assert {by_id[sid] for sid, _ in fetches} == {"decode_fetch"}
    assert all(set(aux) == {"moe_route"} for _, (_, aux) in fetches)
    assert len(given) == steps and len(routes) == 4 * steps
    assert all(isinstance(v, np.ndarray) for aux in given for v in aux.values())


def test_busy_iteration_runs_schedule_prefill_decode(model):
    """One iteration with a decoding resident and two arrivals (one of two
    chunks, so the thin batch bursts three chunks): the leaves lie inside
    ``engine_step`` without overlap and cover it, in the order schedule,
    the prefill leaves a chunk at a time, the decode leaves; the root says
    what the decode step ran; and the benchmark's reader finds each
    arrival's ``prefill_wait`` inside ``schedule`` + ``prefill_build`` —
    no decode step stands between an admission and its first chunk."""
    eng = _engine(model, max_slots=16, max_context=512, n_layers=None)
    _warm(eng)
    resident = eng.submit(_prompt(9), 12)
    eng.step()
    observe.enable(clear=True)
    arrivals = [eng.submit(_prompt(20, seed=1), 4),
                eng.submit(_prompt(40, seed=2), 4)]
    eng.step()
    observe.disable()
    assert [len(q.generated) for q in (resident, *arrivals)] == [2, 1, 1]
    root = _one("engine_step")
    assert root["args"]["decoding"] == 3 and root["args"]["queued"] == 0
    mine = _tiling_leaves(root)
    chunk = ["prefill_build", "prefill_chunk", "prefill_deliver"]
    assert [s["name"] for s in mine] == ["schedule"] + 3 * chunk + [
        "decode_build", "decode_enqueue", "decode_wait", "decode_deliver"]
    assert sum(s["dur_us"] for s in mine) / root["dur_us"] >= 0.95
    reg_ = observe.get_registry()
    got = _phases_reader().phases(list(reg_.spans), list(reg_.events))
    sched, decode_build = mine[0], mine[-4]
    for q in arrivals:
        first_chunk = min(s["ts_us"] for s in mine if s["name"] == "prefill_chunk"
                          and s["args"]["request"] == q.request_id)
        wait_us = got[q.request_id]["prefill_wait"] * 1e3
        # admitted inside ``schedule``; its chunk starts as its build ends
        assert 0 <= wait_us <= first_chunk - sched["ts_us"]
        assert first_chunk < decode_build["ts_us"]
    steps = {e["request"]: e["steps"] for e in reg_.events
             if e["kind"] == "serving_first_token"}
    assert steps == {q.request_id: 1 for q in arrivals}


def test_registry_off_ring_gets_what_it_got_before(model):
    """The sub-phase spans are registry-only: with the registry off, a fixed
    scenario leaves in the flight ring exactly the records it left before
    this tree existed, less the one iteration the requests no longer wait
    (both prompts prefill, and their replay rows decode, in the iteration
    that admits them), and the registry nothing."""
    eng = _engine(model)
    _warm(eng)
    flight.clear()
    eng.submit(_prompt(9), 4)
    eng.submit(_prompt(40), 3)      # two chunks: 32 + 16
    steps = 0
    while not eng.idle:
        eng.step()
        steps += 1
    eng.step()
    eng.step()                      # idle polls add nothing
    assert steps == 4
    recs = flight.snapshot()
    got = Counter((r["type"], r.get("kind") or r["name"].split(" ")[0])
                  for r in recs)
    assert got == Counter({
        ("event", "serving_submitted"): 2, ("event", "serving_admitted"): 2,
        ("event", "serving_prefill_chunk"): 3,
        ("event", "serving_first_token"): 2, ("event", "serving_complete"): 2,
        ("gauge", "serving.queue_depth"): 6,
        ("gauge", "serving.active_requests"): 6,
        ("gauge", "serving.kv_pages_free"): 6,
        ("gauge", "serving.slo_attainment"): 6,
        ("span", "schedule"): 4, ("span", "decode_dispatch"): 4,
        ("span", "prefill_chunk"): 3, ("span", "queued"): 2,
        ("span", "prefill"): 2, ("span", "decode"): 2, ("span", "request"): 2,
    }), got
    assert len(recs) == 54
    assert not observe.get_registry().spans
    assert not observe.get_registry().events


def test_decode_dispatch_counts_the_pages_the_walk_touches(model):
    """``decode_dispatch`` says how much of the block-table window the
    decode attention walks: every slot's live pages (an idle slot's one
    scratch page too) of slots x pages a request. In the ring with the
    registry off, and from there in ``explain()``'s request timeline."""
    eng = _engine(model)            # 3 slots, 16-token pages, 4 a request
    _warm(eng)
    flight.clear()
    eng.submit(_prompt(20), 3)      # contexts 20..22: two pages
    eng.submit(_prompt(9), 3)       # contexts 9..11: one page
    eng.drain()
    walks = [r["args"] for r in flight.snapshot()
             if r["type"] == "span" and r["name"] == "decode_dispatch"]
    assert walks and not observe.get_registry().spans
    for a in walks:
        assert set(a) == {"step", "batch", "live_pages", "window_pages"}
        assert a["window_pages"] == 3 * 4
        # each decoding request's pages, and one for every other slot
        assert a["live_pages"] in (3, 4)
    assert max(a["live_pages"] for a in walks) == 2 + 1 + 1
    from thunder_tpu.observe.explain import _request_timeline_lines

    line = [ln for ln in _request_timeline_lines() if "decode walk" in ln]
    live = sum(a["live_pages"] for a in walks)
    assert line == [f"  decode walk: {live} live of {12 * len(walks)} window "
                    f"pages ({100.0 * live / (12 * len(walks)):.1f}%) over "
                    f"{len(walks)} steps"]


# ---------------------------------------------------------------------------
# a request's road to its first token
# ---------------------------------------------------------------------------

def _phases_reader():
    """The benchmark's own reader of the phases: the test holds the program
    and the reader to each other."""
    path = os.path.join(ROOT, "benchmark", "readers", "request_phases.py")
    spec = importlib.util.spec_from_file_location("bench_request_phases", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _check_phases_add_up(reqs):
    rp = _phases_reader()
    r = observe.get_registry()
    got = rp.phases(list(r.spans), list(r.events))
    ttft = {e["request"]: e["ttft_ms"] for e in r.events
            if e["kind"] == "serving_first_token"}
    assert set(got) == {q.request_id for q in reqs} == set(ttft)
    for q in reqs:
        p = got[q.request_id]
        assert set(p) == set(rp.PHASES)
        assert all(v >= 0 for v in p.values()), p
        assert abs(sum(p.values()) - ttft[q.request_id]) < 1.0, (p, ttft)
        assert abs(ttft[q.request_id] - q.ttft_s * 1e3) < 1e-2
    return got


def test_ttft_phases_add_up_for_every_request(model):
    """More requests than slots, one of them chunked: for every completed
    request the four phases are non-negative and add up to its
    ``serving.ttft_ms`` sample within 1 ms."""
    eng = _engine(model)
    _warm(eng)
    observe.enable(clear=True)
    reqs = [eng.submit(_prompt(n, seed=n), 5) for n in (9, 40, 20, 5, 33)]
    eng.drain()
    observe.disable()
    assert all(q.done for q in reqs)
    got = _check_phases_add_up(reqs)
    # the two that found no free slot waited in the queue for one
    queue = [got[q.request_id]["queue"] for q in reqs]
    assert min(queue[3:]) > max(queue[:3])


def test_ttft_phases_of_a_preempted_request(model):
    """A pool too small for full residency preempts: a preempted request
    keeps one ``request`` id, and its phases still add up — time before its
    last admission counts as ``queue``, the rest runs from that admission."""
    eng = _engine(model, num_pages=6)
    observe.enable(clear=True)
    reqs = [eng.submit(_prompt(n, seed=n), 30) for n in (15, 14, 13)]
    eng.drain()
    observe.disable()
    assert all(q.done for q in reqs) and any(q.preemptions for q in reqs)
    _check_phases_add_up(reqs)
    r = observe.get_registry()
    firsts = Counter(e["request"] for e in r.events
                     if e["kind"] == "serving_first_token")
    assert set(firsts.values()) == {1}
    victim = next(q for q in reqs if q.preemptions)
    queued = [s for s in r.spans if s["name"] == "queued"
              and s["args"]["request"] == victim.request_id]
    assert len(queued) == 1 + victim.preemptions


def test_first_token_event_carries_the_resident_instant(model):
    eng = _engine(model)
    _warm(eng)
    observe.enable(clear=True)
    q = eng.submit(_prompt(9), 2)
    eng.drain()
    (e,) = [e for e in observe.get_registry().events
            if e["kind"] == "serving_first_token"]
    prefill = next(s for s in _spans("prefill")
                   if s["args"]["request"] == q.request_id)
    assert e["request"] == q.request_id
    assert e["resident_us"] == pytest.approx(
        prefill["ts_us"] + prefill["dur_us"], abs=50)
    assert e["resident_us"] < e["ts_us"]
    assert e["steps"] == 1          # admitted, resident and decoded in one


def test_explain_prints_the_share_of_first_tokens_without_a_wait(model):
    """``serving_first_token`` carries ``steps``; the request timeline of
    ``explain()`` prints the share with ``steps == 1`` from the always-on
    ring: two residents fill the batch past half, so a 3-chunk prompt needs
    three iterations and the three others one each."""
    from thunder_tpu.observe.explain import _request_timeline_lines

    eng = _engine(model, prefill_chunk=16)
    _warm(eng)
    flight.clear()
    for i in range(2):
        eng.submit(_prompt(5, seed=i), 20)
    eng.step()
    eng.submit(_prompt(40, seed=3), 2)      # 16 + 16 + 16 under a full batch
    eng.drain()
    eng.submit(_prompt(9, seed=4), 2)
    eng.drain()
    steps = sorted(r["steps"] for r in flight.snapshot()
                   if r["type"] == "event"
                   and r.get("kind") == "serving_first_token")
    assert steps == [1, 1, 1, 3]
    line = [ln for ln in _request_timeline_lines() if "own iteration" in ln]
    assert line == ["  first token in its admission's own iteration: "
                    "3 of 4 (75.0%)"]


def test_prefill_ms_is_the_chunk_spans_length(model):
    eng = _engine(model)
    _warm(eng)
    observe.enable(clear=True)
    eng.submit(_prompt(40), 2)
    eng.drain()
    h = observe.snapshot()["histograms"]["serving.prefill_ms"]
    chunks = _spans("prefill_chunk")
    assert h["count"] == len(chunks) == 2
    assert h["sum"] == pytest.approx(sum(s["dur_us"] for s in chunks) / 1e3)


# ---------------------------------------------------------------------------
# the tt.jit call
# ---------------------------------------------------------------------------

def _misses():
    return [e for e in observe.get_registry().events if e["kind"] == "cache_miss"]


def test_jit_call_holds_guard_and_dispatch_on_a_hit_only():
    def scale(a):
        return ops.mul(a, 3.0).sum()

    jf = tt.jit(scale)
    x = np.ones((8, 8), np.float32)
    observe.enable(clear=True)
    with observe.span("caller", "test"):
        jf(x)                                   # a miss: compile spans
    kids = lambda s: [k["name"] for k in _spans() if k["parent"] == s["id"]]
    call = _one("jit_call")
    assert call["parent"] == _one("caller")["id"]
    assert call["args"] == {"fn": "scale"}
    assert "jit_guard" not in kids(call)
    assert {"compile", "step:scale"} <= set(kids(call))
    observe.reset()
    jf(x)                                       # a hit: guard, dispatch
    call = _one("jit_call")
    assert kids(call) == ["jit_guard", "step:scale"]
    guard, step = _one("jit_guard"), _one("step:scale")
    assert guard["ts_us"] + guard["dur_us"] <= step["ts_us"] + 0.5
    assert call["dur_us"] >= guard["dur_us"] + step["dur_us"] - 0.5


def test_jit_call_spans_are_registry_only():
    jf = tt.jit(lambda a: ops.mul(a, 2.0))
    x = np.ones((4,), np.float32)
    jf(x)
    flight.clear()
    jf(x)                                       # registry off: nothing at all
    assert flight.snapshot() == [] and not _spans()
    observe.enable(clear=True)
    jf(x)
    names = {r.get("name") for r in flight.snapshot()}
    assert "jit_call" not in names and "jit_guard" not in names
    assert _spans("jit_call") and _spans("jit_guard")


@pytest.mark.parametrize("change, reason", [
    (lambda: (np.ones((2, 8), np.float32), 1.0), "leaf 0 shape (2, 4) -> (2, 8)"),
    (lambda: (np.ones((2, 4), np.int32), 1.0), "leaf 0 dtype float32 -> int32"),
    (lambda: (np.ones((2, 4), np.float32), 2.0), "leaf 1 value"),
    (lambda: (np.ones((2, 4), np.float32), True), "leaf 1 kind N -> B"),
    (lambda: (np.ones((2, 8), np.int32), 1.0), "leaf 0 shape (2, 4) -> (2, 8)"),
    (lambda: ([np.ones((2, 4), np.float32)], 1.0), "treedef"),
])
def test_cache_miss_names_what_moved(change, reason):
    jf = tt.jit(lambda a, k: ops.mul(a[0] if isinstance(a, list) else a, k))
    observe.enable(clear=True)
    jf(np.ones((2, 4), np.float32), 1.0)
    assert [e["reason"] for e in _misses()] == ["first"]
    jf(np.ones((2, 4), np.float32), 1.0)        # a hit: no event
    assert len(_misses()) == 1
    jf(*change())
    assert _misses()[-1]["reason"] == reason and _misses()[-1]["fn"] == "<lambda>"


def test_cache_miss_reason_is_against_the_nearest_entry():
    """Two entries held; the call differs from one of them in one leaf and
    from the other in two: the reason names the single difference."""
    jf = tt.jit(lambda a, b: ops.add(a, b))
    f32 = lambda *shape: np.ones(shape, np.float32)
    observe.enable(clear=True)
    jf(f32(2, 4), f32(2, 4))
    jf(f32(8, 4), f32(8, 4))
    jf(f32(8, 4), f32(1, 4))
    assert [e["reason"] for e in _misses()] == [
        "first", "leaf 0 shape (2, 4) -> (8, 4) (+1 more)",
        "leaf 1 shape (8, 4) -> (1, 4)"]


def test_cache_miss_names_the_quarantine_epoch():
    from thunder_tpu.runtime import quarantine

    jf = tt.jit(lambda a: ops.mul(a, 2.0))
    x = np.ones((4,), np.float32)
    observe.enable(clear=True)
    jf(x)
    q = quarantine.get_quarantine()
    q.add("test.span_tree_claim", reason="test", phase="compile")
    try:
        jf(x)
    finally:
        q.remove("test.span_tree_claim")
    assert [e["reason"] for e in _misses()] == ["first", "quarantine epoch"]
