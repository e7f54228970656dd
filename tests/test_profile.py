"""Measured-time observatory tests: region naming, the profiled window, the
model-vs-measured residual ledger, persistent calibration, the budget gate.

The acceptance criteria of the observatory live here:

- a CPU profiled window joins EVERY est-carrying decision into the ledger
  (measured or explicitly unattributed — no silent drops);
- fit → persist → reset (fresh-process simulation) → reload flips a
  previously cost-rejected fusion to planned as a typed ``calibrated[...]``
  decision;
- fitted constants must land inside the committed CALIBRATION_BUDGETS.json
  bands (an out-of-band fit is a loud tier-1 failure, not a silent
  recalibration);
- ``observe.explain()`` renders the "model vs measured" section from the
  always-on flight ring with the registry disabled.
"""

import gzip
import json
import os
import re

import numpy as np
import pytest

import thunder_tpu as tt
from thunder_tpu import observe
from thunder_tpu.core import cost_model
from thunder_tpu.models import llama
from thunder_tpu.observe import calibrate, profile

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BUDGETS_PATH = os.path.join(REPO_ROOT, "CALIBRATION_BUDGETS.json")


@pytest.fixture(autouse=True)
def pallas_interpret(monkeypatch):
    monkeypatch.setenv("THUNDER_TPU_PALLAS_INTERPRET", "1")


@pytest.fixture(autouse=True)
def clean_calibration():
    """Every test starts and ends with no calibration overlay and a fresh,
    unattached store — calibration state must never leak across tests."""
    calibrate.reset()
    yield
    calibrate.reset()


def _adamw_train_step(cfg_name="tiny"):
    from thunder_tpu.optim import AdamW

    cfg = llama.CONFIGS[cfg_name]
    params = llama.init_params(cfg, seed=9, scale_layers=2)
    opt = AdamW(lr=1e-3)

    def train_step(params, opt_state, tokens, targets):
        loss, grads = tt.value_and_grad(
            lambda p: llama.loss_fn(p, tokens, targets, cfg))(params)
        new_params, new_state = opt.update(params, grads, opt_state)
        return loss, new_params, new_state

    rng = np.random.RandomState(9)
    tokens = rng.randint(0, cfg.vocab_size, size=(2, 16)).astype(np.int32)
    targets = np.roll(tokens, -1, 1).astype(np.int32)
    return train_step, params, opt.init(params), tokens, targets


@pytest.fixture(scope="module")
def profiled_window():
    """One compiled tiny train step + its profiled window, shared by the
    read-only assertions below (the window re-executes the trace region by
    region — a few hundred ms — and the compile itself is the slow part)."""
    old = os.environ.get("THUNDER_TPU_PALLAS_INTERPRET")
    os.environ["THUNDER_TPU_PALLAS_INTERPRET"] = "1"
    try:
        train_step, params, opt_state, tokens, targets = _adamw_train_step()
        jstep = tt.jit(train_step, executors=["pallas", "xla"])
        out = observe.profile_window(jstep, (params, opt_state, tokens, targets),
                                     steps=2, warmup=1)
        yield jstep, out
    finally:
        if old is None:
            os.environ.pop("THUNDER_TPU_PALLAS_INTERPRET", None)
        else:
            os.environ["THUNDER_TPU_PALLAS_INTERPRET"] = old


# ---------------------------------------------------------------------------
# region naming — the one owner of the scheme
# ---------------------------------------------------------------------------

def test_region_names_scheme(profiled_window):
    """Names align 1:1 with the region trace's bound symbols, follow
    executor:symbol#occurrence, are unique, and skip codegen artifacts."""
    jstep, _ = profiled_window
    entry = tt.compile_stats(jstep).last_entry
    trc = profile.region_trace_for(entry)
    assert "Region annotations" in str(trc.provenance)
    names = profile.region_names_for(trc)
    assert len(names) == len(trc.bound_symbols)
    non_null = [n for n in names if n is not None]
    assert len(set(non_null)) == len(non_null), "region names must be unique"
    for b, n in zip(trc.bound_symbols, names):
        if b.sym.name in profile._SKIP_SYM_NAMES:
            assert n is None
        else:
            assert n == f"{profile.executor_name(b)}:{b.sym.name}#{n.rsplit('#')[-1]}"
    # the bucketed optimizer chain materializes as a claimed pallas region
    assert any(n.startswith("pallas:fused_adamw#") for n in non_null)


def test_region_names_occurrences_sequential(profiled_window):
    """The k-th region of a given executor:symbol base is named #k — the
    occurrence counter is dense and ordered, which is what makes the
    decision-log join by occurrence order well defined."""
    jstep, _ = profiled_window
    trc = profile.region_trace_for(tt.compile_stats(jstep).last_entry)
    by_base = {}
    for n in profile.region_names_for(trc):
        if n is None:
            continue
        base, k = n.rsplit("#", 1)
        by_base.setdefault(base, []).append(int(k))
    for base, ks in by_base.items():
        assert ks == list(range(len(ks))), base


def test_region_trace_precedes_fusion_absorption(profiled_window):
    """The region trace speaks at claim granularity: the claimed pallas
    kernels the XLA fusion pass later absorbs into its jax.jit regions are
    still individual bound symbols there (the final execution trace may be
    a single fused region — useless for attribution)."""
    jstep, _ = profiled_window
    entry = tt.compile_stats(jstep).last_entry
    region_names = [n for n in
                    profile.region_names_for(profile.region_trace_for(entry))
                    if n is not None]
    pallas = [n for n in region_names if n.startswith("pallas:")]
    assert pallas, "claimed kernels must be visible as regions"


# ---------------------------------------------------------------------------
# residual ledger — no silent drops
# ---------------------------------------------------------------------------

def test_residual_ledger_no_silent_drops(profiled_window):
    """Every decision carrying est_*_us gets exactly one ledger record:
    measured (joined to a region with a real clock) or explicitly
    unattributed. The CPU smoke criterion: ledger coverage >= 90%."""
    jstep, out = profiled_window
    decisions = tt.compile_stats(jstep).last_decisions
    est = [d for d in decisions if profile._has_estimates(d)]
    assert est, "the tiny train step must produce est-carrying decisions"
    assert len(out["ledger"]) == len(est)
    assert out["summary"]["ledger_coverage"] >= 0.9
    for rec in out["ledger"]:
        assert rec["status"] in ("measured", "unattributed")
        assert rec["predicted_us"] is not None
        if rec["status"] == "measured":
            assert rec["region"] and rec["measured_us"] > 0
            assert rec["residual_us"] == pytest.approx(
                rec["measured_us"] - rec["predicted_us"], rel=1e-6)


def test_profiled_window_measures_accepted_fusion(profiled_window):
    """The bucketed fused_adamw verdict (ACCEPTED — its region exists) is
    measured, and its profile region carries per-step mean and call count."""
    jstep, out = profiled_window
    measured = [r for r in out["ledger"] if r["status"] == "measured"]
    adamw = [r for r in measured if r["op"] == "optim.fused_adamw"]
    assert len(adamw) == 1
    region = adamw[0]["region"]
    prof = out["profile"]
    assert prof.regions[region]["calls"] == prof.steps
    assert prof.mean_us(region) > 0
    # rejected verdicts kept the unfused form: nothing to measure, but the
    # ledger says so explicitly instead of dropping them
    rejected = [r for r in out["ledger"] if r["decision"] == "cost-rejected"]
    for r in rejected:
        assert r["status"] == "unattributed"


def test_profile_stashed_on_compile_stats(profiled_window):
    jstep, out = profiled_window
    assert tt.compile_stats(jstep).last_profile is out


# ---------------------------------------------------------------------------
# profiler-trace ingestion (the TPU path, unit-tested from a hand-built dump)
# ---------------------------------------------------------------------------

def test_ingest_profiler_trace(tmp_path):
    events = [
        {"ph": "X", "name": "pallas:fused_adamw#0", "dur": 10.0},
        {"ph": "X", "name": "jit_step/pallas:fused_adamw#0/fusion", "dur": 5.0},
        {"ph": "X", "name": "jit_step/something_else/fusion", "dur": 99.0},
        {"ph": "M", "name": "pallas:fused_adamw#0"},  # not a complete event
    ]
    (tmp_path / "a.trace.json").write_text(json.dumps({"traceEvents": events}))
    with gzip.open(tmp_path / "b.trace.json.gz", "wt") as f:
        json.dump({"traceEvents": [
            {"ph": "X", "name": "xla:fusion0#0/convert", "dur": 7.0}]}, f)
    (tmp_path / "ignored.txt").write_text("not a trace")

    got = profile.ingest_profiler_trace(
        str(tmp_path), ["pallas:fused_adamw#0", "xla:fusion0#0"])
    assert got["pallas:fused_adamw#0"] == {"total_us": 15.0, "calls": 2}
    assert got["xla:fusion0#0"] == {"total_us": 7.0, "calls": 1}


def test_ingest_profiler_trace_torn_file(tmp_path):
    (tmp_path / "torn.trace.json").write_text("{not json")
    assert profile.ingest_profiler_trace(str(tmp_path), ["r#0"]) == {}


# ---------------------------------------------------------------------------
# calibration fits
# ---------------------------------------------------------------------------

def test_fit_recovers_slope_and_intercept():
    """measured = stream_us/eff + launch: three exact points recover both
    constants (eff = 1/slope) through the normal equations."""
    records = [{"status": "measured", "kind": "fusion",
                "stream_us": x, "measured_us": 4.0 * x + 12.0}
               for x in (5.0, 10.0, 20.0)]
    fit = calibrate.fit(records, platform_key="testplat")
    assert fit["platform"] == "testplat"
    assert fit["fitted_from"] == 3
    assert fit["constants"]["ADAMW_FUSED_EFFICIENCY"] == pytest.approx(0.25)
    assert fit["constants"]["ADAMW_LAUNCH_OVERHEAD_US"] == pytest.approx(12.0)


def test_fit_comm_family_bandwidth():
    """measured = launch + recv_bytes/bw * 1e6: bandwidth comes back as
    1e6/slope (bytes/s)."""
    bw = 1e9
    records = [{"status": "measured", "kind": "comm",
                "recv_bytes": b, "measured_us": 2.0 + b / bw * 1e6}
               for b in (1e6, 2e6, 8e6)]
    fit = calibrate.fit(records, platform_key="testplat")
    assert fit["constants"]["ICI_BW_BYTES_PER_S"] == pytest.approx(bw, rel=1e-6)
    assert fit["constants"]["COLLECTIVE_LAUNCH_US"] == pytest.approx(2.0)


def test_fit_single_record_pins_intercept():
    """A single record cannot separate slope from intercept: the fallback
    pins the intercept at the current modeled constant and solves the
    slope from the one point."""
    launch = cost_model.constant("ADAMW_LAUNCH_OVERHEAD_US")
    records = [{"status": "measured", "kind": "fusion",
                "stream_us": 10.0, "measured_us": 10.0 * 10.0 + launch}]
    fit = calibrate.fit(records, platform_key="testplat")
    assert fit["constants"]["ADAMW_LAUNCH_OVERHEAD_US"] == pytest.approx(launch)
    assert fit["constants"]["ADAMW_FUSED_EFFICIENCY"] == pytest.approx(0.1)


def test_fit_ignores_unattributed_records():
    records = [{"status": "unattributed", "kind": "fusion",
                "stream_us": 10.0, "measured_us": None}]
    fit = calibrate.fit(records, platform_key="testplat")
    assert fit["fitted_from"] == 0
    assert fit["constants"] == {}


def test_apply_calibration_rejects_unknown_constant():
    with pytest.raises(ValueError):
        cost_model.apply_calibration("testplat", {"NOT_A_CONSTANT": 1.0})


# ---------------------------------------------------------------------------
# persistence + the round-trip flip (the loop-closing acceptance test)
# ---------------------------------------------------------------------------

def test_store_schema_version_drift(tmp_path):
    path = tmp_path / "cost_calibration.json"
    path.write_text(json.dumps({"version": 999, "platforms": {
        "x": {"constants": {"ADAMW_FUSED_EFFICIENCY": 0.5}}}}))
    store = calibrate.CalibrationStore(str(path))
    assert store.platforms() == ()  # schema drift: refit rather than misread


def test_calibration_round_trip_flips_verdict(tmp_path):
    """The whole loop: a compile cost-rejects the tiny MLP sub-block chains
    of an inference program (the planner plans nothing under autodiff) → a
    fit (from block-family ledger records) is persisted → the process
    'restarts' (reset + configure from the same directory) → recompiling
    flips the verdict to planned, and the decision is TYPED
    ``calibrated[<platform>]`` — never a silent change."""
    _, params, _, tokens, _ = _adamw_train_step()
    cfg = llama.CONFIGS["tiny"]

    def forward(p, t):
        return llama.forward(p, t, cfg)

    base = tt.jit(forward, executors=["pallas", "xla"])
    base.compile(params, tokens)
    before = [d for d in tt.compile_stats(base).last_decisions
              if d["op"] == "nn.mlp_subblock"]
    assert before and all(d["decision"] == "cost-rejected" for d in before)
    assert not any(d["reason"].startswith("calibrated[") for d in before)

    # fit from synthetic block-family records: measured - boundary_us =
    # flop_us/eff + launch with eff=2.0, launch=0 — a fused efficiency
    # ABOVE the XLA baseline plus zero launch makes the byte saving win
    plat = calibrate.platform()
    records = [
        {"status": "measured", "kind": "block",
         "flop_us": 10.0, "boundary_us": 1.0, "measured_us": 6.0},
        {"status": "measured", "kind": "block",
         "flop_us": 20.0, "boundary_us": 1.0, "measured_us": 11.0},
    ]
    fit = calibrate.fit(records, platform_key=plat)
    assert fit["constants"]["SUBBLOCK_FUSED_EFFICIENCY"] == pytest.approx(2.0)
    assert fit["constants"]["SUBBLOCK_LAUNCH_OVERHEAD_US"] == pytest.approx(
        0.0, abs=1e-9)
    calibrate.configure(str(tmp_path))
    calibrate.save(fit, apply=False)
    assert os.path.exists(tmp_path / "cost_calibration.json")

    # fresh-process simulation: drop overlay + store, reload from disk
    calibrate.reset()
    assert cost_model.calibration_platform() is None
    assert calibrate.configure(str(tmp_path)) is True
    assert cost_model.calibration_platform() == plat

    recal = tt.jit(forward, executors=["pallas", "xla"])
    recal.compile(params, tokens)
    after = [d for d in tt.compile_stats(recal).last_decisions
             if d["op"] == "nn.mlp_subblock"]
    assert after and all(d["decision"] == "planned" for d in after), after
    for d in after:
        assert d["reason"].startswith(f"calibrated[{plat}]"), d["reason"]
    trc = tt.last_execution_trace(recal)
    assert "mlp_subblock" in trc.python()

    # the planned program still computes the same logits
    np.testing.assert_allclose(np.asarray(recal(params, tokens)),
                               np.asarray(base(params, tokens)),
                               rtol=2e-5, atol=2e-5)


def test_calibration_changes_are_scoped_per_platform(tmp_path):
    """A fit persisted for ANOTHER platform never activates here."""
    fit = {"platform": "tpu-v5p", "fitted_from": 2,
           "constants": {"SUBBLOCK_FUSED_EFFICIENCY": 2.0}, "families": {}}
    calibrate.configure(str(tmp_path))
    calibrate.save(fit, apply=True)
    assert cost_model.calibration_platform() is None  # we are not on v5p


# ---------------------------------------------------------------------------
# the committed budget gate
# ---------------------------------------------------------------------------

def _budgets():
    with open(BUDGETS_PATH) as f:
        return json.load(f)


def test_budget_bands_cover_every_calibratable_constant():
    budgets = _budgets()
    plats = [k for k in budgets if not k.startswith("_")]
    assert "cpu-interpret" in plats
    for plat in plats:
        assert set(budgets[plat]) == set(cost_model.CALIBRATABLE), plat
        for name, (lo, hi) in budgets[plat].items():
            assert lo < hi, f"{plat}:{name}"


def test_check_budget_flags_out_of_band_and_unbudgeted():
    band = {"ADAMW_FUSED_EFFICIENCY": [0.05, 1.0]}
    ok = {"platform": "p", "constants": {"ADAMW_FUSED_EFFICIENCY": 0.5}}
    assert calibrate.check_budget(ok, band) == []
    bad = {"platform": "p", "constants": {"ADAMW_FUSED_EFFICIENCY": 3.0}}
    (violation,) = calibrate.check_budget(bad, band)
    assert "outside budget" in violation
    unbudgeted = {"platform": "p", "constants": {"COLLECTIVE_LAUNCH_US": 5.0}}
    (violation,) = calibrate.check_budget(unbudgeted, band)
    assert "no budget band" in violation


def test_real_cpu_fit_lands_in_committed_bands(profiled_window):
    """The tier-1 gate itself: fitting the REAL profiled window of this
    session must land inside CALIBRATION_BUDGETS.json's cpu-interpret
    bands. If this fails, measured reality shifted (or the fit broke) —
    re-band deliberately, never widen blindly."""
    _, out = profiled_window
    fit = calibrate.fit(out["ledger"])
    assert fit["platform"] == "cpu-interpret"
    assert fit["fitted_from"] >= 1
    violations = calibrate.check_budget(fit, _budgets()[fit["platform"]])
    assert violations == [], violations


# ---------------------------------------------------------------------------
# explain(): the model-vs-measured section renders registry-off
# ---------------------------------------------------------------------------

def test_explain_renders_model_vs_measured_registry_off(profiled_window):
    from thunder_tpu.observe import registry

    jstep, out = profiled_window
    was = registry.is_enabled()
    registry.disable()
    try:
        report = observe.explain(jstep)
    finally:
        if was:
            registry.enable()
    assert "== model vs measured (residual ledger) ==" in report
    assert "coverage:" in report
    assert "unattributed" in report
    # the measured fused_adamw record is rendered with its region name
    adamw = [r for r in out["ledger"]
             if r["status"] == "measured" and r["op"] == "optim.fused_adamw"]
    if adamw:
        assert adamw[0]["region"] in report


def test_explain_section_coverage_audit():
    """Every ``== section ==`` header explain() can render is in the
    committed expected set (and vice versa): adding a section without
    updating this audit — or silently losing one — fails loudly."""
    import inspect

    from thunder_tpu.observe import explain as explain_mod

    src = inspect.getsource(explain_mod)
    found = {m.split(" (")[0].strip()
             for m in re.findall(r"== (.*?) ==", src)}
    expected = {
        "compile",
        "executors",
        "block planner",
        "fusion decisions",
        "claim decisions",
        "compiled program",
        "comm reorder",
        "model vs measured",
        "numerics sentinel",
        "serving",
        "serving fleet",
        "fleet router",
        "serving prefix cache",
        "serving slo/supervision",
        "request timeline",
        "step estimates",
    }
    assert found == expected, (
        f"explain() sections drifted from the audit set: "
        f"missing={expected - found}, unaudited={found - expected}")
