"""thunder_tpu.observe: registry semantics, compile spans + decision log,
runtime step metrics, exporters (JSONL / Chrome trace / Prometheus), and the
explain report. All CPU-only and inside the tier-1 budget."""

import json
import os

import numpy as np
import pytest

import thunder_tpu as tt
from thunder_tpu import observe, ops
from thunder_tpu.observe import registry as obs_registry


@pytest.fixture(autouse=True)
def _clean_registry():
    """Each test starts disabled with an empty registry and leaves it so."""
    observe.disable()
    observe.reset()
    yield
    observe.disable()
    observe.reset()


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_disabled_recording_is_a_noop():
    observe.inc("x")
    observe.set_gauge("g", 5.0)
    observe.observe_value("h", 1.0)
    observe.event("e", detail=1)
    snap = observe.snapshot()
    assert snap["counters"] == {} and snap["gauges"] == {}
    assert snap["histograms"] == {} and snap["events"] == []
    # spans are NOT a registry no-op when disabled: the edge must reach the
    # always-on flight ring (black-box contract), registry stays empty
    with observe.span("disabled_span", cat="test"):
        pass
    assert observe.snapshot()["spans"] == []
    from thunder_tpu.observe import flight
    assert any(r["type"] == "span" and r["name"] == "disabled_span"
               for r in flight.snapshot())


def test_enabled_counters_gauges_histograms_events():
    observe.enable(clear=True)
    observe.inc("c")
    observe.inc("c", 2.0)
    observe.set_gauge("g", 7.5)
    for v in (0.2, 3.0, 40.0):
        observe.observe_value("h", v)
    observe.event("e", detail="d")
    with observe.span("work", cat="test"):
        pass
    snap = observe.snapshot()
    assert snap["counters"]["c"] == 3.0
    assert snap["gauges"]["g"] == 7.5
    h = snap["histograms"]["h"]
    assert h["count"] == 3 and abs(h["sum"] - 43.2) < 1e-9
    assert h["min"] == 0.2 and h["max"] == 40.0
    assert snap["events"][0]["kind"] == "e" and snap["events"][0]["detail"] == "d"
    spans = [s for s in snap["spans"] if s["name"] == "work"]
    assert spans and spans[0]["dur_us"] >= 0 and spans[0]["cat"] == "test"


def test_enabled_span_is_one_ring_record_plus_registry_histogram():
    """An enabled span must not double into the flight ring: the span edge
    IS the black-box record; the histogram a span names goes to the
    registry only (doubling would halve the ring's usable history). A span
    that names none feeds none: the span record holds its duration."""
    from thunder_tpu.observe import flight

    observe.enable(clear=True)
    try:
        with observe.span("solo", cat="test", histogram="test.solo.ms"):
            pass
        with observe.span("plain", cat="test"):
            pass
        recs = [r for r in flight.snapshot()
                if r.get("name") in ("solo", "test.solo.ms")]
        assert [r["type"] for r in recs] == ["span"]
        hists = observe.snapshot()["histograms"]
        assert hists["test.solo.ms"]["count"] == 1
        assert not [h for h in hists if "plain" in h]
    finally:
        observe.disable()


def test_enable_clear_resets():
    observe.enable(clear=True)
    observe.inc("c")
    observe.enable(clear=True)
    assert observe.snapshot()["counters"] == {}


def test_record_span_gates_on_enabled():
    """Regression: ``record_span`` wrote to the registry unconditionally
    while every other write path gated on the enabled flag — a disabled
    process accumulated spans (bounded, but nonzero memory and a lock per
    span). It must gate like ``inc``/``set_gauge``/``observe_value``/
    ``event``; the flight ring still gets the edge (that is the always-on
    black box, not a leak)."""
    obs_registry.record_span("direct", "test", 1.0, 2.0, {"k": 1})
    assert observe.snapshot()["spans"] == []
    observe.enable()
    obs_registry.record_span("direct", "test", 1.0, 2.0, {"k": 1})
    spans = observe.snapshot()["spans"]
    assert [s["name"] for s in spans] == ["direct"]


def test_pass_sink_collects_with_registry_off_and_span_gated():
    """The per-compile ``_pass_sink`` path (CompileStats.last_pass_times)
    keeps working with the registry off AND leaks nothing into the
    registry now that record_span gates."""
    sink: dict = {}
    with obs_registry.collect_pass_times(sink):
        with observe.span("outer"):
            with observe.span("inner"):
                pass
    assert sink.get("outer", 0) > 0 and sink.get("outer/inner", 0) > 0
    assert observe.snapshot()["spans"] == []


# ---------------------------------------------------------------------------
# compile pipeline instrumentation
# ---------------------------------------------------------------------------

def test_compile_spans_and_cache_events():
    observe.enable(clear=True)
    jf = tt.jit(lambda a, b: ops.tanh(a @ b).sum())
    x = np.ones((4, 5), np.float32)
    w = np.ones((5, 3), np.float32)
    jf(x, w)
    jf(x, w)
    snap = observe.snapshot()
    assert snap["counters"]["cache.misses"] == 1
    assert snap["counters"]["cache.hits"] == 1
    assert snap["counters"]["compile.count"] == 1
    assert snap["gauges"]["compile.transform_ms"] > 0
    names = {s["name"] for s in snap["spans"]}
    for expected in ("compile", "trace", "transform_for_execution", "claim",
                     "codegen", "fusion_pass:xla"):
        assert expected in names, (expected, names)


def test_pass_times_collected_without_enable():
    """Per-pass walltimes and the decision log land in CompileStats even when
    the process-wide registry is off (explain works cold)."""
    jf = tt.jit(lambda a: ops.mul(ops.sin(a), 2.0))
    jf(np.ones((8,), np.float32))
    stats = tt.compile_stats(jf)
    assert stats.last_pass_times.get("trace", 0) > 0
    assert stats.last_pass_times.get("transform_for_execution", 0) > 0
    assert any(d["kind"] == "claim" for d in stats.last_decisions)
    assert observe.snapshot()["spans"] == []  # nothing leaked into the registry


def test_compile_stats_surfaces_interpret_and_transform_times():
    jf = tt.jit(lambda a: ops.add(a, 1.0))
    jf(np.zeros((4,), np.float32))
    stats = tt.compile_stats(jf)
    assert stats.last_interpreted_ns > 0 and stats.last_transform_ns > 0
    assert stats.last_interpreted_ms == stats.last_interpreted_ns / 1e6
    text = stats.summary()
    assert "tracing (interpretation)" in text and "transforms + dispatch" in text
    assert repr(stats).startswith("<CompileStats")


# ---------------------------------------------------------------------------
# runtime step metrics
# ---------------------------------------------------------------------------

def test_step_metrics_recorded_per_call():
    from thunder_tpu.observe import flight

    flight.clear()
    observe.enable(clear=True)
    jf = tt.jit(lambda a: ops.mul(a, 3.0).sum())
    x = np.ones((64, 64), np.float32)
    for _ in range(3):
        jf(x)
    snap = observe.snapshot()
    # one span a call; the first pays lazy XLA compile and says so
    step_spans = [s for s in snap["spans"] if s["name"].startswith("step:")]
    assert [s["args"] for s in step_spans] == \
        [{"first_call": True}, {"first_call": False}, {"first_call": False}]
    assert all(s["cat"] == "step" and s["dur_us"] >= 0 for s in step_spans)
    # registry-only, like the other hot-loop spans: the ring keeps none
    assert not [r for r in flight.snapshot()
                if str(r.get("name", "")).startswith("step:")]
    # nothing else is recorded a call
    assert not [k for k in snap["counters"] if k.startswith("step.")]
    assert not [k for k in snap["histograms"] if k.startswith("step.")]
    # static per entry: published once, at compile, not on every step
    assert snap["gauges"]["step.est_live_bytes"] > 0
    assert len([r for r in flight.snapshot()
                if r.get("name") == "step.est_live_bytes"]) == 1


def test_step_metrics_off_when_disabled():
    jf = tt.jit(lambda a: ops.mul(a, 3.0).sum())
    x = np.ones((8,), np.float32)
    jf(x)
    observe.enable()  # enable AFTER compile: the wrapper reads the live flag
    jf(x)
    snap = observe.snapshot()
    assert [s["args"] for s in snap["spans"] if s["name"].startswith("step:")] \
        == [{"first_call": False}]


# ---------------------------------------------------------------------------
# decision log + explain (acceptance: tiny-llama train step)
# ---------------------------------------------------------------------------

def _tiny_llama_step():
    from thunder_tpu.models import llama
    from thunder_tpu.optim import SGD

    cfg = llama.CONFIGS["tiny"]
    params = llama.init_params(cfg, seed=7, scale_layers=2)
    opt = SGD(lr=1e-2)

    def train_step(params, opt_state, tokens, targets):
        loss, grads = tt.value_and_grad(
            lambda p: llama.loss_fn(p, tokens, targets, cfg))(params)
        return loss, *opt.update(params, grads, opt_state)

    rng = np.random.RandomState(7)
    tokens = rng.randint(0, cfg.vocab_size, size=(2, 16)).astype(np.int32)
    targets = np.roll(tokens, -1, 1).astype(np.int32)
    return train_step, params, opt.init(params), tokens, targets


_compiled_step_cache: list = []


def _compiled_tiny_llama_step():
    """One shared pallas+xla tiny-llama compile for the explain/decision
    tests (compiling it is the expensive part of this module — tier-1 budget)."""
    if not _compiled_step_cache:
        train_step, params, opt_state, tokens, targets = _tiny_llama_step()
        jstep = tt.jit(train_step, executors=["pallas", "xla"])
        jstep(params, opt_state, tokens, targets)
        _compiled_step_cache.append(jstep)
    return _compiled_step_cache[0]


def test_explain_tiny_llama_train_step():
    """Acceptance: explain() names the executor for every bound symbol of the
    execution trace and lists >= 1 fusion decision with cost-model inputs."""
    from thunder_tpu.core.prims import PrimIDs

    jstep = _compiled_tiny_llama_step()
    report = observe.explain(jstep)
    exec_trc = tt.last_execution_trace(jstep)
    skip = (PrimIDs.PYTHON_RETURN, PrimIDs.COMMENT, PrimIDs.PYTHON_DEL)
    named = 0
    for bsym in exec_trc.bound_symbols:
        if bsym.sym.id in skip:
            continue
        ex = bsym.sym.executor.name if bsym.sym.executor is not None else "eagerjax"
        assert f"{bsym.sym.name} [{ex}]" in report, bsym.sym.name
        named += 1
    assert named >= 1

    decisions = tt.compile_stats(jstep).last_decisions
    fusion = [d for d in decisions if d["kind"] == "fusion"]
    assert len(fusion) >= 1
    with_cost = [d for d in fusion if d.get("cost")]
    assert with_cost, fusion
    # the horizontal-merge verdicts carry the actual byte-model inputs
    hm = [d for d in fusion if d["op"] == "horizontal_merge"]
    assert hm and {"m_tokens", "widths", "siblings"} <= set(hm[0]["cost"])
    # ... and the textual report shows them
    assert "horizontal_merge" in report and "m_tokens" in report
    assert "== claim decisions" in report and "eagerjax" in report


def test_explain_before_compile_is_graceful():
    jf = tt.jit(lambda a: ops.add(a, 1.0))
    assert "no compilation has run yet" in observe.explain(jf)


def test_claim_rejection_reasons_logged():
    """A pallas-claimable op that the cost model keeps inside XLA regions
    shows up as a rejected claim with the cost numbers."""
    jstep = _compiled_tiny_llama_step()
    decisions = tt.compile_stats(jstep).last_decisions
    rejected = [d for d in decisions
                if d["kind"] == "claim" and d["decision"] == "rejected"]
    assert rejected
    assert any(d.get("cost") or "checker" in d.get("reason", "")
               for d in rejected)


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------

def _compile_and_step_3x():
    jf = tt.jit(lambda a, b: ops.tanh(a @ b).sum())
    x = np.ones((16, 8), np.float32)
    w = np.ones((8, 4), np.float32)
    for _ in range(3):
        jf(x, w)
    return jf


def test_chrome_trace_export_loads_structurally(tmp_path):
    """Acceptance: the Perfetto export of a compile+3-step run is a valid
    Chrome Trace Event Format object (what chrome://tracing loads)."""
    observe.enable(clear=True)
    _compile_and_step_3x()
    path = str(tmp_path / "trace.json")
    n = observe.export_chrome_trace(path)
    assert n > 0
    with open(path) as f:
        trace = json.load(f)
    assert isinstance(trace["traceEvents"], list) and trace["traceEvents"]
    assert trace["displayTimeUnit"] == "ms"
    complete = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
    for e in complete:
        assert {"name", "cat", "ts", "dur", "pid", "tid"} <= set(e)
        assert e["dur"] >= 0
    names = {e["name"] for e in complete}
    assert "compile" in names                      # compile span present
    assert sum(1 for e in complete
               if e["name"].startswith("step:")) >= 3  # the 3 steps
    # metadata rows give the timeline its labels
    assert any(e.get("ph") == "M" for e in trace["traceEvents"])


def test_jsonl_export_roundtrips(tmp_path):
    observe.enable(clear=True)
    _compile_and_step_3x()
    observe.observe_value("test.call_ms", 1.5)
    path = str(tmp_path / "events.jsonl")
    n = observe.export_jsonl(path)
    with open(path) as f:
        recs = [json.loads(line) for line in f]
    assert len(recs) == n
    types = {r["type"] for r in recs}
    assert {"counter", "gauge", "histogram", "span"} <= types
    counters = {r["name"]: r["value"] for r in recs if r["type"] == "counter"}
    assert counters["cache.misses"] == 1 and counters["cache.hits"] == 2
    assert sum(1 for r in recs if r["type"] == "span"
               and r["name"].startswith("step:")) == 3


def test_prometheus_export_format(tmp_path):
    observe.enable(clear=True)
    _compile_and_step_3x()
    for ms in (0.4, 2.0):
        observe.observe_value("test.call_ms", ms)
    path = str(tmp_path / "metrics.prom")
    text = observe.export_prometheus(path)
    assert os.path.exists(path)
    assert "# TYPE thunder_tpu_cache_misses counter" in text
    assert "thunder_tpu_cache_misses 1" in text
    assert "# TYPE thunder_tpu_test_call_ms histogram" in text
    assert 'thunder_tpu_test_call_ms_bucket{le="+Inf"} 2' in text
    assert "thunder_tpu_test_call_ms_count 2" in text
    # every non-comment line is "<metric possibly with labels> <value>"
    for line in text.strip().splitlines():
        if line.startswith("#"):
            continue
        metric, value = line.rsplit(" ", 1)
        assert metric.startswith("thunder_tpu_")
        float(value)


def test_exports_roundtrip_non_jsonable_field_values(tmp_path):
    """Events and spans carry arbitrary user values — exceptions, numpy
    scalars/arrays, whole request objects. EVERY export path must coerce
    them (``_jsonable``) rather than raise: one exotic value must not lose
    a trace, a JSONL archive, or a postmortem."""
    class Opaque:
        def __repr__(self):
            return "<Opaque>"

    observe.enable(clear=True)
    cyclic = {"x": 1}
    cyclic["self"] = cyclic             # must not recurse forever
    observe.event("incident", error=ValueError("boom"),
                  scalar=np.float32(1.5), arr=np.arange(4),
                  obj=Opaque(), nested={"deep": Opaque(), "n": np.int64(7)},
                  seq=[np.float64(0.25), Opaque()], loop=cyclic)
    with observe.span("weird", cat="test",
                      args={"exc": RuntimeError("x"), "v": np.int32(3)}):
        pass

    jl = str(tmp_path / "weird.jsonl")
    assert observe.export_jsonl(jl) > 0
    recs = [json.loads(line) for line in open(jl)]
    ev = next(r for r in recs if r["type"] == "event")
    assert "boom" in ev["error"] and ev["scalar"] == 1.5
    assert ev["nested"]["n"] == 7 and ev["nested"]["deep"] == "<Opaque>"
    assert ev["seq"][0] == 0.25
    # the cyclic container serialized finitely (json.loads above already
    # proves no RecursionError and valid JSON)
    assert ev["loop"]["x"] == 1
    sp = next(r for r in recs if r["type"] == "span" and r["name"] == "weird")
    assert sp["args"]["v"] == 3 and "x" in sp["args"]["exc"]

    trace = observe.chrome_trace_dict()
    json.dumps(trace)                   # fully serializable
    inst = next(e for e in trace["traceEvents"]
                if e.get("ph") == "i" and e["name"] == "incident")
    assert inst["args"]["scalar"] == 1.5

    from thunder_tpu.observe import flight

    fl = str(tmp_path / "flight.jsonl")
    assert flight.dump_jsonl(fl) > 0
    for line in open(fl):
        json.loads(line)


# ---------------------------------------------------------------------------
# labeled series (engine-scoped telemetry)
# ---------------------------------------------------------------------------

def test_labeled_dual_writes_and_keeps_series_disjoint():
    """A labeled write updates BOTH stores: the unlabeled rollup (counters
    summed, gauges last-writer-wins) and the per-label-set series — and
    two label sets never collide."""
    observe.enable(clear=True)
    a = observe.labeled(engine="e0")
    b = observe.labeled(engine="e1")
    a.inc("serving.shed_requests", 2)
    b.inc("serving.shed_requests", 3)
    a.set_gauge("serving.queue_depth", 5)
    b.set_gauge("serving.queue_depth", 1)
    a.observe_value("serving.ttft_ms", 4.0)
    sa, sb = a.snapshot(), b.snapshot()
    assert sa["counters"]["serving.shed_requests"] == 2
    assert sb["counters"]["serving.shed_requests"] == 3
    assert sa["gauges"]["serving.queue_depth"] == 5
    assert sb["gauges"]["serving.queue_depth"] == 1
    assert sa["histograms"]["serving.ttft_ms"]["count"] == 1
    assert "serving.ttft_ms" not in sb["histograms"]
    snap = observe.snapshot()
    assert snap["counters"]["serving.shed_requests"] == 5   # summed
    assert snap["gauges"]["serving.queue_depth"] == 1       # last writer
    assert observe.engines_seen() == ["e0", "e1"]
    # label order never forks a series: kwargs freeze to one sorted key
    observe.labeled(b="2", a="1").inc("x")
    observe.labeled(a="1", b="2").inc("x")
    labeled_x = [r for r in observe.snapshot()["labeled"]["counters"]
                 if r["name"] == "x"]
    assert len(labeled_x) == 1 and labeled_x[0]["value"] == 2.0


def test_labeled_requires_at_least_one_label():
    with pytest.raises(ValueError):
        observe.labeled()


def test_labeled_disabled_noop_registry_but_ring_records_labels():
    """Disabled gating matches the module entry points exactly — labeled
    counters/histograms are dropped, while labeled gauge moves, events,
    and span edges still reach the always-on ring WITH their label dict."""
    from thunder_tpu.observe import flight

    flight.clear()
    try:
        rec = observe.labeled(engine="e7")
        rec.inc("c")
        rec.observe_value("h", 1.0)
        rec.set_gauge("serving.queue_depth", 2)
        rec.event("serving_shed", request=1, reason="x")
        with rec.span("work", cat="serving:sched"):
            pass
        snap = observe.snapshot()
        assert snap["counters"] == {} and snap["gauges"] == {}
        assert snap["labeled"] == {"counters": [], "gauges": [],
                                   "histograms": []}
        ring = flight.snapshot()
        assert {r["type"] for r in ring} == {"gauge", "event", "span"}
        assert all(r["labels"] == {"engine": "e7"} for r in ring)
    finally:
        flight.clear()


def test_reset_and_enable_clear_drop_labeled_series_ring_survives():
    """Multi-engine reset semantics, both directions: ``reset()`` and
    ``enable(clear=True)`` clear the labeled series for ALL engines (a
    per-round bench reset must not leak engine A's series into engine B's
    round), while the flight ring keeps its labeled records."""
    from thunder_tpu.observe import flight

    flight.clear()
    try:
        observe.enable(clear=True)
        for eid in ("e0", "e1"):
            h = observe.labeled(engine=eid)
            h.inc("c")
            h.set_gauge("g", 1.0)
            h.observe_value("h", 1.0)
        assert observe.engines_seen() == ["e0", "e1"]
        observe.reset()
        assert observe.engines_seen() == []
        snap = observe.snapshot()
        assert snap["labeled"] == {"counters": [], "gauges": [],
                                   "histograms": []}
        ring = [r for r in flight.snapshot() if r["type"] == "gauge"]
        assert {r["labels"]["engine"] for r in ring} == {"e0", "e1"}

        observe.labeled(engine="e2").inc("c")
        observe.enable(clear=True)              # the other direction
        assert observe.engines_seen() == []
        assert flight.snapshot()                # ring still survives
    finally:
        flight.clear()


def test_labeled_span_records_histogram_and_ring_edge():
    from thunder_tpu.observe import flight

    flight.clear()
    try:
        observe.enable(clear=True)
        rec = observe.labeled(engine="e0")
        with rec.span("schedule", cat="serving:sched", args={"n": 2},
                      histogram="serving.schedule_ms"):
            pass
        s = rec.snapshot()
        assert s["histograms"]["serving.schedule_ms"]["count"] == 1
        spans = observe.snapshot()["spans"]
        assert spans[0]["name"] == "schedule"
        assert spans[0]["labels"] == {"engine": "e0"}
        edge = next(r for r in flight.snapshot() if r["type"] == "span")
        assert edge["labels"] == {"engine": "e0"} and edge["args"] == {"n": 2}
    finally:
        flight.clear()


def test_prometheus_renders_labeled_next_to_rollup_with_escaping(tmp_path):
    """Exposition-format round-trip: labeled series render under ONE
    ``# TYPE`` per metric next to the unlabeled rollup, label values
    escape backslash/quote/newline, histogram buckets merge the ``le``
    label into the series labels."""
    observe.enable(clear=True)
    h = observe.labeled(engine="e0")
    h.inc("serving.shed_requests", 2)
    h.set_gauge("serving.queue_depth", 3)
    h.observe_value("serving.ttft_ms", 0.2)
    nasty = observe.labeled(engine='w\\x"y\nz')
    nasty.set_gauge("serving.queue_depth", 9)
    text = observe.export_prometheus(str(tmp_path / "m.prom"))
    assert text.count("# TYPE thunder_tpu_serving_queue_depth gauge") == 1
    assert "\nthunder_tpu_serving_queue_depth 9" in "\n" + text  # rollup
    assert 'thunder_tpu_serving_queue_depth{engine="e0"} 3' in text
    assert ('thunder_tpu_serving_queue_depth{engine="w\\\\x\\"y\\nz"} 9'
            in text)
    assert 'thunder_tpu_serving_shed_requests{engine="e0"} 2' in text
    assert ('thunder_tpu_serving_ttft_ms_bucket{engine="e0",le="+Inf"} 1'
            in text)
    assert 'thunder_tpu_serving_ttft_ms_count{engine="e0"} 1' in text
    # still line-structured: "<metric possibly with labels> <value>" — use
    # the file side of the round-trip for the parse audit
    for line in (tmp_path / "m.prom").read_text().splitlines():
        if line.startswith("#") or '"y' in line:   # the newline-bearing label
            continue
        metric, value = line.rsplit(" ", 1)
        assert metric.startswith("thunder_tpu_")
        float(value)


def test_jsonl_export_emits_labeled_records(tmp_path):
    observe.enable(clear=True)
    h = observe.labeled(engine="e0")
    h.inc("serving.shed_requests", 2)
    h.set_gauge("serving.queue_depth", 3)
    h.observe_value("serving.ttft_ms", 1.5)
    path = str(tmp_path / "labeled.jsonl")
    observe.export_jsonl(path)
    recs = [json.loads(line) for line in open(path)]
    by_type = {}
    for r in recs:
        by_type.setdefault(r["type"], []).append(r)
    for fam in ("labeled_counter", "labeled_gauge", "labeled_histogram"):
        rs = [r for r in by_type.get(fam, ())]
        assert len(rs) == 1
        assert rs[0]["labels"] == {"engine": "e0"}
    assert by_type["labeled_gauge"][0]["value"] == 3.0
    assert by_type["labeled_histogram"][0]["count"] == 1


# ---------------------------------------------------------------------------
# bench integration + tier-1 hygiene
# ---------------------------------------------------------------------------

def test_bench_metric_names_exist_after_compile():
    """Dashboards and the docs name these registry entries; renaming them
    must fail a test, not silently zero a reading."""
    observe.enable(clear=True)
    train_step, params, opt_state, tokens, targets = _tiny_llama_step()
    jstep = tt.jit(train_step, horizontal_fusion=True)
    jstep(params, opt_state, tokens, targets)
    snap = observe.snapshot()
    assert snap["counters"].get("fusion.xla_regions", 0) >= 1
    assert snap["counters"].get("fusion.horizontal_merges", 0) >= 1
    assert snap["gauges"]["compile.transform_ms"] > 0


def test_sdpa_bwd_rung_is_counted_once_a_call_site(monkeypatch):
    """Which flash-backward rung engaged is a program record: compiling a
    small causal train step bumps exactly one ``pallas.sdpa_bwd.*`` counter,
    once a call site (dispatch is trace time; a second step adds nothing),
    the ``kernel_path`` event says why, and ``explain()`` prints it."""
    monkeypatch.setenv("THUNDER_TPU_PALLAS_INTERPRET", "1")
    import thunder_tpu.ops as ops
    from thunder_tpu.executors import pallasex as px
    from thunder_tpu.observe import flight

    T, hd = 256, 16
    rng = np.random.RandomState(34)
    q, k, v = ((rng.randn(1, 2, T, hd) * 0.3).astype(np.float32) for _ in range(3))

    def step(q, k, v):
        def loss(q, k, v):
            a = ops.scaled_dot_product_attention(q, k, v, is_causal=True)
            b = ops.scaled_dot_product_attention(a, k, v, is_causal=True)
            return ops.sum(ops.mul(b, b))
        return tt.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)

    flight.clear()
    observe.enable(clear=True)
    jstep = tt.jit(step, executors=["pallas", "xla"])
    jstep(q, k, v)
    jstep(q, k, v)
    snap = observe.snapshot()
    rungs = {n: c for n, c in snap["counters"].items()
             if n.startswith("pallas.sdpa_bwd.")}
    assert rungs == {"pallas.sdpa_bwd.one_pass": 2.0}, rungs
    paths = [e for e in snap["events"] if e["kind"] == "kernel_path"]
    assert len(paths) == 2
    for e in paths:
        assert (e["op"], e["rung"], e["T"], e["hd"]) == ("nn.sdpa_bwd", "one_pass", T, hd)
        assert e["staged_bytes"] == px._one_pass_staged_bytes(T, hd, 4) > 0
    report = observe.explain(jstep)
    compile_section = report.split("== compile ==")[1].split("\n== ")[0]
    assert "kernel path: nn.sdpa_bwd -> one_pass (T=256, hd=16" in compile_section
    assert "x2" in compile_section


def test_fused_optimizer_decisions_logged(monkeypatch):
    """Satellite of the r6 fused multi-tensor AdamW: every bucket verdict —
    accept with the byte-model numbers, or reject with the gate that refused
    — lands in CompileStats.last_decisions, and the accepted buckets bump
    the fusion.optimizer_buckets counter."""
    monkeypatch.setenv("THUNDER_TPU_PALLAS_INTERPRET", "1")
    from thunder_tpu.optim import AdamW
    from thunder_tpu.models import llama

    cfg = llama.CONFIGS["tiny"]
    params = llama.init_params(cfg, seed=11, scale_layers=1)
    opt = AdamW(lr=1e-3)

    observe.enable(clear=True)
    try:
        jstep = tt.jit(lambda p, g, s: opt.update(p, g, s),
                       executors=["pallas", "xla"])
        grads = params
        jstep(params, grads, opt.init(params))
        snap = observe.snapshot()
    finally:
        observe.disable()
    assert snap["counters"].get("fusion.optimizer_buckets", 0) >= 1

    decisions = tt.compile_stats(jstep).last_decisions
    fused = [d for d in decisions if d["op"] == "optim.fused_adamw"]
    bucketed = [d for d in fused if d["decision"] == "bucketed"]
    assert bucketed, fused
    cost = bucketed[0]["cost"]
    assert {"tensors", "total_bytes", "saved_launches",
            "est_unfused_us", "est_fused_us"} <= set(cost)
    assert cost["tensors"] >= 2 and cost["total_bytes"] > 0
    # ... and the human report surfaces the verdict
    report = observe.explain(jstep)
    assert "optim.fused_adamw" in report and "bucketed" in report

    # the OFF switch compiles with no bucket decisions and no fused calls
    joff = tt.jit(lambda p, g, s: opt.update(p, g, s),
                  executors=["pallas", "xla"], fused_optimizer=False)
    joff(params, grads, opt.init(params))
    off = [d for d in tt.compile_stats(joff).last_decisions
           if d["op"] == "optim.fused_adamw"]
    assert not off


def test_observe_tests_stay_in_tier1():
    """Marker audit: this module must run under ``-m 'not slow'`` in full —
    no test here may carry the slow marker (tier-1 is the only gate that
    runs on every PR, and observability regressions must fail it)."""
    with open(__file__) as f:
        src = f.read()
    marker = "mark." + "slow"  # split so this line doesn't trip the scan
    assert marker not in src, "observe tests must stay in the tier-1 budget"
