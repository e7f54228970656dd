"""Comm/compute overlap evidence for distributed entries (VERDICT r2 item 5).

The reference schedules overlap explicitly and asserts on it
(``thunder/distributed/utils.py:60-196``; trace asserts in
``thunder/tests/distributed/test_fsdp.py``). Here overlap is delegated to
XLA's latency-hiding scheduler — the right TPU call — and these tests verify
XLA actually DOES it: the FSDP / fsdp×tp train steps are AOT-compiled for an
8-device v5e topology (``jax.experimental.topologies`` — the compiler runs
without the chips) and the optimized HLO must mark collectives async
(``async_collective_name="all-gather-start.N"`` — the scheduler's
certification that the op was split into start/done with compute between).
Negative control: recompiling with ``xla_enable_async_all_gather=false``
removes every marker while keeping the collectives.

The comm_report tests run everywhere (trace-level, CPU mesh).
"""

import numpy as np
import pytest

import thunder_tpu as tt
from thunder_tpu.core.devices import MeshSpec
from thunder_tpu.distributed.transforms import fsdp, fsdp_tp
from thunder_tpu.examine import comm_report
from thunder_tpu.models import llama
from thunder_tpu.optim import SGD


def _tpu_topology():
    # get_topology guards against hosts that ship a libtpu with no chips
    # attached (PJRT topology init BLOCKS instead of raising there); this
    # helper runs at collection time (skipif below), so that hang would
    # stall the whole suite, not just skip these tests
    from thunder_tpu.benchmarks.northstar import get_topology

    return get_topology("v5e:2x4")


def _step_fn(cfg, opt):
    def step(params, opt_state, tokens, targets):
        loss, grads = tt.value_and_grad(
            lambda p: llama.loss_fn(p, tokens, targets, cfg))(params)
        newp, news = opt.update(params, grads, opt_state)
        return loss, newp, news

    return step


def _args(cfg, n_layers=2, batch=8, seq=8):
    params = llama.init_params(cfg, seed=2, scale_layers=n_layers)
    opt = SGD(lr=1e-2)
    rng = np.random.RandomState(0)
    tokens = rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32)
    targets = np.roll(tokens, -1, 1).astype(np.int32)
    return opt, (params, opt.init(params), tokens, targets)


def _aot_entry(jstep, topo, args):
    """Compile a DistributedFunction entry against TOPOLOGY devices (no
    execution — the chips aren't attached) and return its lowered jit."""
    jstep._mesh = jstep.mesh_spec.build(list(topo.devices))
    entry = jstep.compile(*args)
    assert entry.jit_obj is not None
    return entry.jit_obj.lower(*entry.input_avals)


@pytest.mark.skipif(_tpu_topology() is None,
                    reason="TPU compiler unavailable — "
                           "topology AOT compile impossible")
class TestAsyncCollectivesOnTPU:
    def test_fsdp_entry_schedules_async_all_gather(self):
        topo = _tpu_topology()
        cfg = llama.CONFIGS["tiny"]
        opt, args = _args(cfg)
        jstep = fsdp(_step_fn(cfg, opt), MeshSpec.make(fsdp=8))
        lowered = _aot_entry(jstep, topo, args)

        hlo = lowered.compile().as_text()
        n_async = hlo.count('async_collective_name="all-gather-start')
        assert n_async > 0, "no async all-gather in the FSDP step's TPU HLO"
        assert hlo.count("all-gather(") >= n_async

        # negative control: async disabled -> markers vanish, collectives stay
        hlo_sync = lowered.compile(
            compiler_options={"xla_enable_async_all_gather": "false"}).as_text()
        assert hlo_sync.count("async_collective_name") == 0
        assert hlo_sync.count("all-gather(") > 0

    def test_fsdp_tp_entry_schedules_async_all_gather(self):
        topo = _tpu_topology()
        cfg = llama.CONFIGS["tiny"]
        opt, args = _args(cfg)
        jstep = fsdp_tp(_step_fn(llama.tp_config(cfg, 2), opt),
                        MeshSpec.make(fsdp=4, tp=2),
                        column_patterns=llama.TP_COLUMN_PATTERNS,
                        row_patterns=llama.TP_ROW_PATTERNS)
        lowered = _aot_entry(jstep, topo, args)
        hlo = lowered.compile().as_text()
        assert hlo.count('async_collective_name="all-gather-start') > 0, \
            "no async all-gather in the fsdp×tp step's TPU HLO"


class TestCommReport:
    def test_fsdp_comm_report(self, eight_devices):
        cfg = llama.CONFIGS["tiny"]
        opt, args = _args(cfg)
        jstep = fsdp(_step_fn(cfg, opt), MeshSpec.make(fsdp=8))
        jstep(*args)
        rep = comm_report(jstep)
        names = set(rep["collectives"])
        # forward param gathers (synchronize lowers to all-gather at runtime)
        # + grad reduce-scatters must both appear
        assert "synchronize" in names
        assert "reduce_scatter" in names
        sync = rep["collectives"]["synchronize"]
        assert sync["count"] > 0
        # gathering dim-0 shards grows bytes toward mesh_size x the input
        assert sync["out_bytes"] > sync["in_bytes"]
        rs = rep["collectives"]["reduce_scatter"]
        assert rs["in_bytes"] == 8 * rs["out_bytes"]  # scatter shrinks by N
        assert rep["total_in_bytes"] > 0

    def test_examine_includes_comm(self):
        from thunder_tpu import ops
        from thunder_tpu.examine import examine

        rep = examine(lambda a, b: ops.matmul(a, b),
                      np.ones((4, 5), np.float32), np.ones((5, 3), np.float32))
        assert rep["comm"]["collectives"] == {}  # single-device: no comm


@pytest.fixture(scope="module")
def reorder_tiny_step():
    """ONE 1-layer comm_reorder=True compile shared by every test in this
    module that only reads its traces/decisions (compiles dominate suite
    wall-time; don't repeat them per test)."""
    import jax

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 (virtual) devices")
    cfg = llama.CONFIGS["tiny"]
    opt, args = _args(cfg, n_layers=1)
    jstep = fsdp(_step_fn(cfg, opt), MeshSpec.make(fsdp=8),
                 comm_reorder=True)
    jstep.compile(*args)
    return jstep


class TestCommReorderReport:
    def test_sort_waits_reports_what_it_did(self, reorder_tiny_step):
        """The comm_reorder pass records its schedule as decisions: a
        summary (hoisted-issue / sunk-wait counts) plus one
        ``overlap_window`` record per collective with the issue→wait
        distance before vs after — the baseline the ROADMAP-3 overlap pass
        is judged against — and explain() renders the section."""
        from thunder_tpu import observe

        jstep = reorder_tiny_step
        decs = [d for d in tt.compile_stats(jstep).last_decisions
                if d["kind"] == "comm"]
        assert decs, "comm_reorder recorded no decisions"
        summary = [d for d in decs if d["op"] == "comm_reorder"]
        assert len(summary) == 1
        cost = summary[0]["cost"]
        assert cost["issues"] > 0 and cost["waits"] > 0
        assert 0 <= cost["hoisted_issues"] <= cost["issues"]
        assert 0 <= cost["sunk_waits"] <= cost["waits"]
        windows = [d for d in decs if d["decision"] == "overlap_window"]
        assert windows, "no per-collective issue->wait distances recorded"
        for d in windows:
            c = d["cost"]
            assert c["issue_at"] < c["wait_at"]
            assert c["distance"] == c["wait_at"] - c["issue_at"]
            assert c["distance"] >= 1 and c["distance_before"] >= 1
        # the reschedule actually widened at least one window
        assert any(d["cost"]["distance"] > d["cost"]["distance_before"]
                   for d in windows)
        rep = observe.explain(jstep)
        assert "== comm reorder ==" in rep
        assert "issue@" in rep and "wait@" in rep

    def test_plain_compile_has_no_comm_section(self):
        from thunder_tpu import observe
        from thunder_tpu.ops import matmul

        jfn = tt.jit(lambda a, b: matmul(a, b))
        jfn(np.ones((4, 5), np.float32), np.ones((5, 3), np.float32))
        assert "== comm reorder ==" not in observe.explain(jfn)


def _collective_issue_order(trc) -> list[str]:
    """Collective issue sequence of a trace (recursing into fusions): the
    thing every SPMD rank must agree on."""
    from thunder_tpu.distributed.comm_reorder import _is_issue

    names: list[str] = []

    def walk(bsyms):
        for b in bsyms:
            if _is_issue(b):
                names.append(b.sym.name)
                continue
            walk(b.subsymbols)

    walk(trc.bound_symbols)
    return names


class TestOverlapScheduling:
    def test_issue_order_is_rank_deterministic(self, reorder_tiny_step,
                                               eight_devices):
        """The no-deadlock property: two independent compiles of the same
        program (what every rank of an SPMD job does) schedule the SAME
        collective issue order under hoisting + bucketing — the scheduler
        takes no clock, hash-order, or id() input. Rank 0 is the shared
        module compile; rank 1 is a fresh wrapper over fresh proxies."""
        cfg = llama.CONFIGS["tiny"]
        orders = [_collective_issue_order(
            tt.last_execution_trace(reorder_tiny_step))]
        opt, args = _args(cfg, n_layers=1)
        jstep = fsdp(_step_fn(cfg, opt), MeshSpec.make(fsdp=8),
                     comm_reorder=True)
        jstep.compile(*args)
        orders.append(_collective_issue_order(
            tt.last_execution_trace(jstep)))
        assert orders[0], "no collective issues in the scheduled trace"
        assert orders[0] == orders[1]

    def test_sort_waits_is_deterministic_and_order_preserving(
            self, reorder_tiny_step):
        """Property test on the pass itself: scheduling the same trace twice
        yields the identical bsym sequence; every collective issue survives
        the reschedule; and SAME-KIND issues never pass each other (they
        contend on one channel — cross-kind hoisting past each other is the
        pass doing its job). The input is the shared compile's PRE-pass
        trace (the stage comm_reorder actually runs at — it still carries
        the fused ``synchronize`` ops)."""
        from thunder_tpu.distributed.comm_reorder import (
            _is_issue, bucket_collectives, decompose_collectives, sort_waits)

        trc = next(t for t in tt.last_traces(reorder_tiny_step)
                   if any(b.sym.name == "synchronize" for b in t.bound_symbols))
        pre = bucket_collectives(decompose_collectives(trc), n_dev=8)
        s1 = sort_waits(pre, n_dev=8)
        s2 = sort_waits(pre, n_dev=8)
        assert [b.sym.name for b in s1.bound_symbols] \
            == [b.sym.name for b in s2.bound_symbols]

        def issue_ids(t):
            ids = []

            def walk(bs):
                for b in bs:
                    if _is_issue(b):
                        ids.append((b.sym.name, str(b.output)))
                        continue
                    walk(b.subsymbols)

            walk(t.bound_symbols)
            return ids

        pi, si = issue_ids(pre), issue_ids(s1)
        assert pi, "no collective issues in the pre-pass trace"
        assert sorted(pi) == sorted(si)  # nothing dropped or duplicated
        for kind in {k for k, _ in pi}:
            assert [o for k, o in pi if k == kind] \
                == [o for k, o in si if k == kind], kind

    def test_no_use_after_del_in_scheduled_trace(self, fsdp_overlap_step):
        """Del/comment pinning regression: after the reschedule, no variable
        is consumed by a real op at a position later than its `del` —
        the del-after-consumer edges must survive hoisting and sinking."""
        from thunder_tpu.core.prims import PrimIDs
        from thunder_tpu.core.utils import consumed_vars

        jstep, _ = fsdp_overlap_step
        trc = tt.last_execution_trace(jstep)
        del_at: dict = {}
        for i, b in enumerate(trc.bound_symbols):
            if b.sym.id is PrimIDs.PYTHON_DEL:
                for v in consumed_vars(b):
                    del_at[v] = i
        for i, b in enumerate(trc.bound_symbols):
            if b.sym.id is PrimIDs.PYTHON_DEL:
                continue
            for v in consumed_vars(b):
                assert del_at.get(v, len(trc.bound_symbols)) >= i, \
                    f"{b.sym.name}@{i} consumes a var deleted at {del_at[v]}"

    def test_cycle_bails_out_with_typed_decision(self):
        """A malformed (cyclic) trace must not hang or half-schedule: the
        pass returns the input trace unchanged and records a typed `comm`
        bailout decision, which explain() renders as a BAILOUT line."""
        from thunder_tpu import observe, ops
        from thunder_tpu.core.proxies import Variable
        from thunder_tpu.core.trace import from_trace
        from thunder_tpu.distributed.comm_reorder import sort_waits
        from thunder_tpu.observe import decisions as _decisions

        jfn = tt.jit(lambda a, b: ops.add(ops.add(a, b), b))
        jfn(np.ones((3,), np.float32), np.ones((3,), np.float32))
        trc = tt.last_traces(jfn)[0]  # pre-fusion: the adds are visible
        adds = [b for b in trc.bound_symbols if b.sym.name == "add"]
        assert len(adds) == 2
        b1, b2 = adds  # b2 consumes b1's output
        ret = [b for b in trc.bound_symbols
               if b.sym.name not in ("add",)][-1:]
        # rewire b1 to consume b2's output: a dependency cycle
        b1c = b1.from_bsym_swap_proxies(
            {Variable(b1.args[1]): b2.output}, skip_output=True)
        cyc = from_trace(trc)
        cyc.bound_symbols = [b1c, b2] + ret
        with _decisions.collect() as decs:
            out = sort_waits(cyc)
        assert out is cyc, "cyclic trace must be returned unscheduled"
        bail = [d for d in decs
                if d["kind"] == "comm" and d["decision"] == "bailout"]
        assert len(bail) == 1
        assert "cycle" in bail[0]["reason"]
        assert bail[0]["cost"]["scheduled"] < bail[0]["cost"]["groups"]
        # the renderer surfaces it (inject into a real compile's log)
        tt.compile_stats(jfn).last_decisions.append(bail[0])
        assert "BAILOUT: " in observe.explain(jfn)

    def test_bucketing_reduces_collective_count(self, fsdp_overlap_step,
                                                eight_devices):
        """Acceptance: on the small-param smoke config the fused buckets
        replace the per-param collectives — strictly fewer collective
        issues than the unbucketed zero-2 trace (21 gathers + 21 scatters
        + 2 all-reduces), with the bucketed pair present."""
        from thunder_tpu.examine import comm_report

        jstep, _ = fsdp_overlap_step
        rep = comm_report(jstep)
        names = set(rep["collectives"])
        assert "bucketed_all_gather" in names
        assert "bucketed_reduce_scatter" in names
        n_issues = sum(e["count"] for e in rep["collectives"].values())
        assert n_issues < 44, rep["collectives"]
        # bucket verdicts are on the decision log
        decs = [d for d in tt.compile_stats(jstep).last_decisions
                if d["kind"] == "comm" and d["decision"] == "bucketed"]
        assert len(decs) >= 2
        for d in decs:
            assert d["cost"]["members"] >= 2
            assert d["cost"]["saved_issues"] == d["cost"]["members"] - 1
            assert "dtype" in d["cost"] and "mesh_axis" in d["cost"]


@pytest.fixture
def eight_devices():
    import jax

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 (virtual) devices")
    yield
