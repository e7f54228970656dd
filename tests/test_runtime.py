"""thunder_tpu.runtime: layered fault injection, retry/backoff policies,
kernel quarantine + graceful degradation. All deterministic (seeded
schedules, injected clocks/sleeps), all CPU, all inside tier-1."""

import json
import os

import numpy as np
import pytest

import thunder_tpu as tt
from thunder_tpu import observe, ops
from thunder_tpu.runtime import faults, quarantine, retry
from thunder_tpu.runtime.faults import (
    FaultPlan,
    FaultSpec,
    InjectedFault,
    KernelExecutionError,
)
from thunder_tpu.runtime.retry import RestartBudget, RetryPolicy


@pytest.fixture(autouse=True)
def _clean_runtime():
    """Every test starts with no fault plan, an empty in-memory quarantine,
    and a clean observe registry — and leaves the process that way."""
    faults.clear()
    quarantine.reset()
    observe.disable()
    observe.reset()
    yield
    faults.clear()
    quarantine.reset()
    observe.disable()
    observe.reset()


@pytest.fixture()
def interpret(monkeypatch):
    monkeypatch.setenv("THUNDER_TPU_PALLAS_INTERPRET", "1")


# ---------------------------------------------------------------------------
# fault plans: deterministic schedules
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_fault_spec_at_steps_transient_vs_permanent():
    transient = FaultSpec("step", at_steps={3})
    plan = FaultPlan([transient])
    plan.maybe_fail("step", step=2)  # no fire
    with pytest.raises(InjectedFault) as ei:
        plan.maybe_fail("step", step=3)
    assert ei.value.domain == "step" and ei.value.step == 3 and ei.value.transient
    plan.maybe_fail("step", step=3)  # transient: the replay sees healthy

    permanent = FaultPlan([FaultSpec("step", at_steps={3}, transient=False)])
    for _ in range(3):
        with pytest.raises(InjectedFault):
            permanent.maybe_fail("step", step=3)


@pytest.mark.chaos
def test_fault_spec_every_n_and_probability_are_deterministic():
    plan = FaultPlan([FaultSpec("dispatch", every_n=3, transient=False)])

    def fires(p, n, **kw):
        out = []
        for _ in range(n):
            try:
                p.maybe_fail("dispatch", **kw)
                out.append(False)
            except InjectedFault:
                out.append(True)
        return out

    assert fires(plan, 6) == [False, False, True, False, False, True]

    a = FaultPlan([FaultSpec("dispatch", probability=0.5, seed=7, transient=False)])
    b = FaultPlan([FaultSpec("dispatch", probability=0.5, seed=7, transient=False)])
    seq_a, seq_b = fires(a, 20), fires(b, 20)
    assert seq_a == seq_b and any(seq_a) and not all(seq_a)


@pytest.mark.chaos
def test_fault_spec_max_fires_and_wildcard_domains():
    plan = FaultPlan([FaultSpec("kernel:*", transient=False, max_fires=2)])
    for _ in range(2):
        with pytest.raises(InjectedFault):
            plan.maybe_fail("kernel:pallas.sdpa_fwd")
    plan.maybe_fail("kernel:pallas.sdpa_fwd")  # exhausted
    plan.maybe_fail("collective")              # different domain: never matched


def test_unscheduled_transient_fires_exactly_once():
    plan = FaultPlan([FaultSpec("compile")])
    with pytest.raises(InjectedFault):
        plan.maybe_fail("compile")
    plan.maybe_fail("compile")  # cleared


def test_no_plan_is_a_noop_and_context_manager_restores():
    faults.maybe_fail("dispatch")  # no plan installed: free
    plan = FaultPlan([FaultSpec("dispatch")])
    with faults.active(plan):
        assert faults.active_plan() is plan
        with pytest.raises(InjectedFault):
            faults.maybe_fail("dispatch")
    assert faults.active_plan() is None


# ---------------------------------------------------------------------------
# hook points: every layer raises where its domain says
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_compile_and_dispatch_domains_hooked():
    jf = tt.jit(lambda a: ops.mul(a, 2.0))
    x = np.ones((4,), np.float32)
    with faults.active(FaultPlan([FaultSpec("compile")])):
        with pytest.raises(InjectedFault, match="domain 'compile'"):
            jf(x)
    np.testing.assert_allclose(np.asarray(jf(x)), 2 * x)  # healthy after

    with faults.active(FaultPlan([FaultSpec("dispatch")])):
        with pytest.raises(InjectedFault, match="domain 'dispatch'"):
            jf(x)
    np.testing.assert_allclose(np.asarray(jf(x)), 2 * x)


@pytest.mark.chaos
def test_checkpoint_io_domain_hooked(tmp_path):
    from thunder_tpu.checkpoint import save_checkpoint

    with faults.active(FaultPlan([FaultSpec("checkpoint_io")])):
        with pytest.raises(InjectedFault, match="checkpoint_io"):
            save_checkpoint(str(tmp_path / "ck"), {"w": np.ones((4,))})
    save_checkpoint(str(tmp_path / "ck"), {"w": np.ones((4,))})  # healthy after


@pytest.mark.chaos
def test_collective_domain_hooked(eight_devices):
    from thunder_tpu.core.devices import MeshSpec
    from thunder_tpu.distributed import ddp

    def step(p, x):
        loss, g = tt.value_and_grad(lambda q: ops.sum(ops.mul(q, x)))(p)
        return loss, g

    N = len(eight_devices)
    p = np.ones((4,), np.float32)
    x = np.ones((N, 4), np.float32)
    ddp(step, MeshSpec.make(dp=N))(p, x)  # healthy: lowerings run clean
    with faults.active(FaultPlan([FaultSpec("collective", transient=False)])):
        js = ddp(step, MeshSpec.make(dp=N))
        with pytest.raises(Exception, match="collective"):
            js(p, x)  # the grad all_reduce lowering hosts the fault


# ---------------------------------------------------------------------------
# retry / backoff / budget
# ---------------------------------------------------------------------------

def test_retry_policy_backoff_is_exponential_and_deterministic():
    p = RetryPolicy(base_delay_s=0.1, multiplier=2.0, jitter=0.0)
    assert [p.delay_s(i) for i in (1, 2, 3)] == [0.1, 0.2, 0.4]
    assert RetryPolicy(base_delay_s=1.0, max_delay_s=2.0, jitter=0.0).delay_s(10) == 2.0
    j1 = RetryPolicy(jitter=0.5, seed=3)
    j2 = RetryPolicy(jitter=0.5, seed=3)
    assert [j1.delay_s(i) for i in range(1, 5)] == [j2.delay_s(i) for i in range(1, 5)]


def test_call_with_retry_recovers_transient_and_respects_fatal():
    calls = {"n": 0}
    slept = []

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise RuntimeError("transient")
        return "ok"

    out = retry.call_with_retry(
        flaky, policy=RetryPolicy(max_attempts=5, base_delay_s=0.01, jitter=0.0),
        sleep=slept.append)
    assert out == "ok" and calls["n"] == 3
    assert slept == [0.01, 0.02]  # measurable, increasing backoff

    with pytest.raises(KeyboardInterrupt):
        retry.call_with_retry(lambda: (_ for _ in ()).throw(KeyboardInterrupt()),
                              sleep=slept.append)


def test_call_with_retry_exhausts_attempts_and_deadline():
    def always():
        raise OSError("down")

    with pytest.raises(OSError):
        retry.call_with_retry(always, policy=RetryPolicy(max_attempts=3, jitter=0.0),
                              sleep=lambda d: None)

    t = {"now": 0.0}

    def clock():
        return t["now"]

    def sleep(d):
        t["now"] += d

    with pytest.raises(OSError):
        retry.call_with_retry(
            always, policy=RetryPolicy(max_attempts=100, base_delay_s=1.0,
                                       jitter=0.0, deadline_s=2.5),
            sleep=sleep, clock=clock)
    assert t["now"] <= 2.5  # stopped by the deadline budget, not attempts


def test_classifier_verdicts():
    assert retry.classify(KeyboardInterrupt()) == retry.FATAL
    assert retry.classify(ValueError("bug")) == retry.FATAL
    assert retry.classify(RuntimeError("device")) == retry.RETRYABLE
    assert retry.classify(OSError("io")) == retry.RETRYABLE
    assert retry.classify(InjectedFault("x")) == retry.RETRYABLE
    assert retry.classify(KernelExecutionError("pallas.x")) == retry.DEGRADABLE


def test_restart_budget_sliding_window():
    t = {"now": 0.0}
    b = RestartBudget(max_restarts=2, window_s=10.0, clock=lambda: t["now"])
    assert b.record()          # 1 in window
    t["now"] = 1.0
    assert b.record()          # 2 in window
    t["now"] = 2.0
    assert not b.record()      # 3 in 10s: exhausted
    t["now"] = 50.0            # everything ages out
    assert b.record() and b.in_window == 1

    lifetime = RestartBudget(max_restarts=1, window_s=None, clock=lambda: t["now"])
    assert lifetime.record()
    t["now"] = 1e9
    assert not lifetime.record()  # legacy: no window, restarts never age out


# ---------------------------------------------------------------------------
# kernel quarantine + graceful degradation (the acceptance path)
# ---------------------------------------------------------------------------

def _rms_jit(**opts):
    return tt.jit(lambda a, w: ops.rms_norm(a, w), **opts)


def _rms_inputs():
    x = np.random.RandomState(0).randn(8, 128).astype(np.float32)
    w = np.linspace(0.5, 1.5, 128).astype(np.float32)
    return x, w


@pytest.mark.chaos
def test_kernel_fault_is_loud_unless_containment_was_asked_for(interpret):
    """Strict by default: a claimed kernel that dies while being traced
    raises KernelExecutionError (with the cause chained) and quarantines
    NOTHING — only ``quarantine.containment()`` (the supervisors' opt-in)
    turns it into the XLA fallback the tests below exercise."""
    x, w = _rms_inputs()
    jf = _rms_jit()
    with faults.active(FaultPlan([FaultSpec("kernel:pallas.rms_norm")])):
        with pytest.raises(KernelExecutionError) as exc:
            jf(x, w)
    assert exc.value.claim_id == "pallas.rms_norm"
    assert isinstance(exc.value.__cause__, faults.InjectedFault)
    assert not quarantine.is_quarantined("pallas.rms_norm")
    assert len(quarantine.get_quarantine()) == 0
    quarantine.assert_clean()
    # and the same call inside containment() degrades instead
    with quarantine.containment(), \
            faults.active(FaultPlan([FaultSpec("kernel:pallas.rms_norm")])):
        jf(x, w)
    assert quarantine.is_quarantined("pallas.rms_norm")
    with pytest.raises(RuntimeError, match="kernel fallback"):
        quarantine.assert_clean()


@pytest.mark.chaos
def test_compile_time_kernel_fault_degrades_to_xla(interpret):
    x, w = _rms_inputs()
    observe.enable(clear=True)
    ref = np.asarray(_rms_jit()(x, w))

    jclean = _rms_jit()
    jclean(x, w)
    assert "pallas_rms_norm" in str(tt.last_execution_trace(jclean))

    jf = _rms_jit()
    with quarantine.containment(), \
            faults.active(FaultPlan([FaultSpec("kernel:pallas.rms_norm")])):
        out = jf(x, w)  # kernel dies while traced -> quarantine -> recompile
    np.testing.assert_allclose(np.asarray(out), ref, atol=1e-6)
    # the claim is quarantined and the recompiled trace has no pallas kernel
    assert quarantine.is_quarantined("pallas.rms_norm")
    assert "pallas_rms_norm" not in str(tt.last_execution_trace(jf))
    # visible in the decision log / explain and the runtime.fallbacks counter
    report = observe.explain(jf)
    assert "quarantined" in report
    assert observe.snapshot()["counters"]["runtime.fallbacks"] >= 1
    # subsequent calls stay on the fallback without re-failing
    np.testing.assert_allclose(np.asarray(jf(x, w)), ref, atol=1e-6)


@pytest.mark.chaos
def test_runtime_kernel_fault_degrades_mid_serving(interpret):
    """whole_program_jit=False keeps the per-region path: the claimed impl
    runs on every call, so a fault on the Nth call is a *runtime* kernel
    failure — the entry already served traffic, then the kernel died."""
    x, w = _rms_inputs()
    ref = np.asarray(_rms_jit()(x, w))
    jf = _rms_jit(whole_program_jit=False)
    plan = FaultPlan([FaultSpec("kernel:pallas.rms_norm", every_n=2)])
    with quarantine.containment(), faults.active(plan):
        out1 = jf(x, w)  # healthy call through the pallas claim
        np.testing.assert_allclose(np.asarray(out1), ref, atol=1e-6)
        out2 = jf(x, w)  # the kernel dies at runtime -> degrade in-place
    np.testing.assert_allclose(np.asarray(out2), ref, atol=1e-6)
    assert quarantine.is_quarantined("pallas.rms_norm")
    assert quarantine.get_quarantine()._kernels["pallas.rms_norm"]["phase"] == "runtime"


@pytest.mark.chaos
def test_quarantine_persists_across_process_restart(interpret, tmp_path):
    x, w = _rms_inputs()
    ref = np.asarray(_rms_jit()(x, w))
    quarantine.configure(str(tmp_path))
    jf = _rms_jit()
    with quarantine.containment(), \
            faults.active(FaultPlan([FaultSpec("kernel:pallas.rms_norm")])):
        jf(x, w)
    qfile = quarantine.get_quarantine().path
    assert qfile and os.path.exists(qfile)
    on_disk = json.load(open(qfile))["kernels"]
    assert "pallas.rms_norm" in on_disk

    # "restart": fresh in-memory state, same cache dir -> the known-bad
    # kernel is skipped at the first compile, no failure needed
    quarantine.reset()
    assert not quarantine.is_quarantined("pallas.rms_norm")
    quarantine.configure(str(tmp_path))
    assert quarantine.is_quarantined("pallas.rms_norm")
    jf2 = _rms_jit()
    out = jf2(x, w)
    np.testing.assert_allclose(np.asarray(out), ref, atol=1e-6)
    assert "pallas_rms_norm" not in str(tt.last_execution_trace(jf2))
    decisions = [d for d in tt.compile_stats(jf2).last_decisions
                 if d["decision"] == "rejected" and "quarantined" in d["reason"]]
    assert decisions and decisions[0]["executor"] == "pallas"


def test_quarantine_epoch_invalidates_cached_entries(interpret):
    x, w = _rms_inputs()
    jf = _rms_jit()
    jf(x, w)
    assert jf.cache_misses == 1
    jf(x, w)
    assert jf.cache_hits == 1
    quarantine.get_quarantine().add("pallas.rms_norm", reason="manual")
    jf(x, w)  # epoch bumped: the pre-quarantine entry must not serve
    assert jf.cache_misses == 2
    assert "pallas_rms_norm" not in str(tt.last_execution_trace(jf))


def test_quarantine_file_is_atomic_and_merge_loads(tmp_path):
    q = quarantine.configure(str(tmp_path))
    q.add("pallas.a", reason="r1")
    # a second process wrote its own entry meanwhile
    data = json.load(open(q.path))
    data["kernels"]["pallas.b"] = {"reason": "r2", "phase": "compile",
                                   "time": 0.0, "count": 1}
    json.dump(data, open(q.path, "w"))
    quarantine.reset()
    q2 = quarantine.configure(str(tmp_path))
    assert set(q2.ids()) >= {"pallas.a", "pallas.b"}
    # torn file: starts empty instead of crashing
    with open(q2.path, "w") as f:
        f.write('{"version": 1, "kern')
    quarantine.reset()
    q3 = quarantine.configure(str(tmp_path))
    assert len(q3) == 0


# ---------------------------------------------------------------------------
# observe wiring
# ---------------------------------------------------------------------------

def test_runtime_metrics_reach_the_exporters(interpret):
    from thunder_tpu.observe.exporters import export_prometheus

    observe.enable(clear=True)
    x, w = _rms_inputs()
    jf = _rms_jit()
    with quarantine.containment(), \
            faults.active(FaultPlan([FaultSpec("kernel:pallas.rms_norm")])):
        jf(x, w)
    snap = observe.snapshot()
    assert snap["counters"]["runtime.faults_injected"] >= 1
    assert snap["counters"]["runtime.fallbacks"] >= 1
    assert snap["gauges"]["runtime.quarantined_kernels"] == 1
    kinds = {e["kind"] for e in snap["events"]}
    assert {"fault_injected", "kernel_quarantined", "kernel_fallback"} <= kinds
    text = export_prometheus()
    assert "thunder_tpu_runtime_fallbacks" in text
    assert "thunder_tpu_runtime_quarantined_kernels" in text


def test_runtime_tests_stay_in_tier1():
    """Marker audit (same contract as test_observe.py): fault-injection
    schedules are seeded and clocks are injected, so every test in this
    module is deterministic and must run under ``-m 'not slow'``."""
    with open(__file__) as f:
        src = f.read()
    marker = "mark." + "slow"  # split so this line doesn't trip the scan
    assert marker not in src, "runtime tests must stay in the tier-1 budget"
