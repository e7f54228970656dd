"""Supervised serving-engine lifecycle tests: crash recovery with
token-identical re-admission, restart-budget escalation, graceful
drain/shutdown, the stall watchdog, and the seeded chaos soak spanning all
four serving fault domains (``serving:prefill`` / ``serving:decode`` /
``serving:admission`` / ``serving:engine``)."""

import os
import re
import time

import numpy as np
import pytest

from thunder_tpu import observe
from thunder_tpu.models import llama
from thunder_tpu.runtime import faults, quarantine
from thunder_tpu.runtime.faults import FaultPlan, FaultSpec
from thunder_tpu.runtime.retry import RestartBudget
from thunder_tpu.serving import (
    AdmissionRejected,
    DeadlineExceeded,
    EngineFault,
    EngineSupervisor,
    RestartBudgetExceeded,
    ServingEngine,
)


@pytest.fixture(autouse=True)
def _clean():
    quarantine.reset()
    yield
    quarantine.reset()
    faults.clear()


@pytest.fixture(scope="module")
def model():
    cfg = llama.CONFIGS["tiny-gqa"]
    return cfg, llama.init_params(cfg, seed=0, scale_layers=1)


def _engine(params, cfg, **kw):
    defaults = dict(max_slots=3, page_size=16, max_context=64, n_layers=1,
                    prefill_chunk=32)
    defaults.update(kw)
    return ServingEngine(params, cfg, **defaults)


def _references(params, cfg, prompts, max_new):
    return [np.asarray(llama.generate(params, cfg, p[None], max_new,
                                      n_layers=1))[0]
            for p in prompts]


# fast supervised retries: chaos runs shouldn't sleep through real backoff
def _fast_retry():
    from thunder_tpu.runtime.retry import RetryPolicy

    return RetryPolicy(max_attempts=3, base_delay_s=0.001, max_delay_s=0.01)


# ---------------------------------------------------------------------------
# crash recovery
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_supervisor_restart_recovers_in_flight_token_identical(model):
    """The engine-level fallback rung: a ``serving:engine`` fault consumes
    the donated page pools mid-decode (FATAL to in-place retry); the
    supervisor rebuilds pools + binding and re-admits every in-flight
    request by re-prefilling prompt+generated — outputs stay
    token-identical to a fault-free run (the ``_preempt`` discipline,
    generalized to crash recovery)."""
    cfg, params = model
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, cfg.vocab_size, size=L).astype(np.int32)
               for L in (5, 9, 17)]
    refs = _references(params, cfg, prompts, 6)
    observe.enable(clear=True)
    try:
        eng = _engine(params, cfg, retry_policy=_fast_retry())
        sup = EngineSupervisor(eng, max_restarts=2, restart_window_s=600.0)
        reqs = [sup.submit(p, 6) for p in prompts]
        with faults.active(FaultPlan([FaultSpec("serving:engine",
                                                at_steps={4})])):
            done = sup.drain()
        snap = observe.snapshot()
    finally:
        observe.disable()
    assert len(done) == 3 and sup.restarts == 1
    for r, ref in zip(reqs, refs):
        assert r.done and r.restarts == 1
        np.testing.assert_array_equal(r.output(), ref)
    assert snap["counters"]["serving.engine_restarts"] == 1
    assert snap["histograms"]["serving.drain_ms"]["count"] == 1
    kinds = {e["kind"] for e in snap["events"]}
    assert "serving_engine_restart" in kinds
    eng.assert_quiescent()


@pytest.mark.chaos
def test_restart_budget_exhaustion_escalates(model):
    """An engine failing faster than the sliding-window budget allows must
    escalate RestartBudgetExceeded to the caller, not flap forever."""
    cfg, params = model
    eng = _engine(params, cfg, retry_policy=_fast_retry())
    sup = EngineSupervisor(eng, restart_budget=RestartBudget(
        max_restarts=1, window_s=3600.0))
    sup.submit(np.ones(5, np.int32), 8)
    plan = FaultPlan([FaultSpec("serving:engine", every_n=3,
                                transient=False)])
    with faults.active(plan):
        with pytest.raises(RestartBudgetExceeded) as ei:
            sup.drain()
    assert sup.restarts == 1            # one restart granted, second refused
    assert ei.value.max_restarts == 1 and ei.value.in_window == 2
    # the causal chain stays readable: budget <- engine fault <- injection
    assert isinstance(ei.value.__cause__, EngineFault)
    assert isinstance(ei.value.__cause__.__cause__, faults.InjectedFault)


@pytest.mark.chaos
def test_chaos_soak_all_serving_domains(model):
    """The acceptance soak: a seeded fault plan spanning all FOUR serving
    domains over a mixed-length workload on a tight page pool (so
    preemption fires too). Every surviving request must be token-identical
    to the fault-free run, zero KV pages may leak
    (``assert_quiescent``), and restarts stay within the budget."""
    cfg, params = model
    rng = np.random.RandomState(42)
    lengths = (30, 5, 17, 9, 28, 12)
    prompts = [rng.randint(1, cfg.vocab_size, size=L).astype(np.int32)
               for L in lengths]
    refs = _references(params, cfg, prompts, 8)
    plan = FaultPlan([
        # randomized-but-seeded: the same draws (and therefore the same
        # injection points) every run
        FaultSpec("serving:prefill", every_n=6, max_fires=3),
        FaultSpec("serving:decode", probability=0.06, seed=7, max_fires=3),
        FaultSpec("serving:admission", probability=0.2, seed=5, max_fires=2),
        # every_n counts decode-dispatch attempts, so both engine crashes
        # are guaranteed to land while decodes are actually in flight
        FaultSpec("serving:engine", every_n=8, max_fires=2),
    ])
    observe.enable(clear=True)
    try:
        eng = _engine(params, cfg, page_size=8, num_pages=10,
                      prefill_chunk=16, retry_policy=_fast_retry())
        budget = RestartBudget(max_restarts=3, window_s=3600.0)
        sup = EngineSupervisor(eng, restart_budget=budget)
        reqs = [sup.submit(p, 8) for p in prompts]
        with faults.active(plan):
            done = sup.drain()
        snap = observe.snapshot()
    finally:
        observe.disable()
    # no deadlines, so nothing may shed: every request survives the chaos
    assert len(done) == len(prompts)
    for r, ref in zip(reqs, refs):
        assert r.done, (r.request_id, r.state)
        np.testing.assert_array_equal(r.output(), ref)
    assert sup.restarts == 2            # both scheduled engine faults fired
    assert sup.restarts <= budget.max_restarts
    assert snap["counters"]["serving.engine_restarts"] == 2
    assert snap["counters"]["runtime.faults_injected"] >= 5
    assert snap["counters"].get("serving.shed_requests", 0) == 0
    # the soak exercised the tight pool too
    assert snap["counters"].get("serving.preempted_requests", 0) >= 1
    eng.assert_quiescent()


# ---------------------------------------------------------------------------
# graceful drain / shutdown / watchdog
# ---------------------------------------------------------------------------

def test_drain_bounds_wall_clock_and_stops_admissions(model):
    """Graceful drain: admissions stop (typed rejection), residents run
    under the wall-clock bound, the remainder sheds with DeadlineExceeded,
    and the episode lands in the serving.drain_ms histogram."""
    cfg, params = model
    observe.enable(clear=True)
    try:
        eng = _engine(params, cfg)
        sup = EngineSupervisor(eng)
        r1 = sup.submit(np.ones(5, np.int32), 30)
        sup.step()              # admit, prefill, first-token replay decode
        assert len(r1.generated) == 1
        sup.step()              # one more decode step
        done = sup.drain(deadline_s=0.0)         # bound expires immediately
        snap = observe.snapshot()
    finally:
        observe.disable()
    assert done == [] and r1.failed
    assert isinstance(r1.error, DeadlineExceeded)
    assert len(r1.generated) >= 1                # partial output stays readable
    with pytest.raises(AdmissionRejected, match="draining"):
        sup.submit(np.ones(3, np.int32), 2)
    assert snap["histograms"]["serving.drain_ms"]["count"] == 1
    assert snap["counters"]["serving.shed_requests"] == 1
    eng.assert_quiescent()


def test_shutdown_drains_to_completion(model):
    cfg, params = model
    eng = _engine(params, cfg)
    sup = EngineSupervisor(eng)
    r = sup.submit(np.ones(4, np.int32), 3)
    done = sup.shutdown()
    assert r.done and done == [r]
    eng.assert_quiescent()


def test_watchdog_escalates_stalled_engine(model, tmp_path):
    """The heartbeat published from step() goes stale when the engine
    hangs; the watchdog escalates (once per episode) instead of the stall
    passing unobserved."""
    cfg, params = model
    stalls = []
    observe.enable(clear=True)
    try:
        eng = _engine(params, cfg)
        sup = EngineSupervisor(eng, heartbeat_path=str(tmp_path / "hb.json"),
                               stall_timeout_s=0.05, on_stall=stalls.append,
                               postmortem_dir=str(tmp_path / "pm"))
        try:
            r = sup.submit(np.ones(4, np.int32), 4)
            sup.step()                          # publishes one heartbeat
            deadline = time.monotonic() + 5.0
            while not stalls and time.monotonic() < deadline:
                time.sleep(0.01)                # engine "hangs": no beats
            assert stalls and stalls[0] > 0.05
            assert sup.watchdog.escalations >= 1
            done = sup.shutdown()               # recovers and finishes
        finally:
            sup.close()
        snap = observe.snapshot()
    finally:
        observe.disable()
    assert r.done and done == [r]
    assert snap["counters"]["runtime.watchdog_escalations"] >= 1
    assert any(e["kind"] == "serving_engine_stalled" for e in snap["events"])
    # the stall dumped a black-box bundle before anyone killed the process
    stall_bundles = [d for d in os.listdir(tmp_path / "pm") if "stall" in d]
    assert len(stall_bundles) >= 1


# ---------------------------------------------------------------------------
# marker audits (same contract as test_runtime / test_elastic)
# ---------------------------------------------------------------------------

def test_supervisor_tests_stay_in_tier1():
    """Marker audit: serving-lifecycle regressions must fail the gate that
    runs on every PR, so nothing here may carry the slow marker."""
    with open(__file__) as f:
        src = f.read()
    marker = "mark." + "slow"  # split so this line doesn't trip the scan
    assert marker not in src, "supervisor tests must stay in the tier-1 budget"


def test_serving_fault_injection_tests_carry_chaos_marker():
    """Chaos-marker audit: every serving test that installs a FaultPlan
    (``faults.active``) must be ``@pytest.mark.chaos``-marked, here AND in
    tests/test_serving.py — the chaos selection (``-m chaos``) is how the
    recovery suite is run in isolation, and an unmarked fault-injection
    test silently drops out of it."""
    import os

    here = os.path.dirname(os.path.abspath(__file__))
    needle = "faults." + "active("  # split so this audit doesn't flag itself
    for fname in ("test_serving.py", "test_serving_supervisor.py",
                  "test_flight.py", "test_prefix_cache.py",
                  "test_serving_sampling.py", "test_fleet.py",
                  "test_router.py"):
        with open(os.path.join(here, fname)) as f:
            src = f.read()
        tests = list(re.finditer(r"^\s*def (test_\w+)", src, re.M))
        for m, nxt in zip(tests, tests[1:] + [None]):
            body = src[m.end():nxt.start() if nxt is not None else len(src)]
            if needle not in body:
                continue
            decorators = []
            for line in reversed(src[:m.start()].splitlines()):
                line = line.strip()
                if not line.startswith("@"):
                    break
                decorators.append(line)
            assert any("chaos" in d for d in decorators), (
                f"{fname}::{m.group(1)} injects faults but is not "
                f"@pytest.mark.chaos-marked")
