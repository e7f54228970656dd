"""Fusion 3.0 tests: the block-level megakernel planner and slab-persistent
optimizer state.

CPU-only (Pallas interpret mode), tier-1. Covers: sub-block megakernel
parity vs the unfused decomposition (forward, ragged shapes; gradients go
through the decomposition), planner verdicts in the decision log /
explain(), dist-annotated operands never planned across shards, the
planner's one entry (a train step plans nothing under any ``block_fusion``
value), quarantine fallback to the per-op XLA decomposition (chaos),
and the slab-persistent AdamW contracts (kernel-level bit-identity,
layout-version checkpoint round-trips).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import thunder_tpu as tt
from thunder_tpu import observe, ops
from thunder_tpu.core import cost_model, dtypes
from thunder_tpu.models import llama
from thunder_tpu.runtime import faults, quarantine
from thunder_tpu.runtime.faults import FaultPlan, FaultSpec


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


def _count_symbols(trc, name):
    n = 0

    def walk(bsyms):
        nonlocal n
        for b in bsyms:
            if b.sym.name == name:
                n += 1
            walk(b.subsymbols)

    walk(trc.bound_symbols)
    return n


def _block_decisions(jfn):
    return [d for d in tt.compile_stats(jfn).last_decisions if d["kind"] == "block"]


def _subblock_ref(r, x, wn, wg, wu, wd, act=jax.nn.silu, eps=1e-5):
    """Hand-written jax reference of the sub-block chain (f32 norm stats,
    model-dtype matmuls — same recipe as the unfused composite)."""
    h = r + x
    h32 = h.astype(jnp.float32)
    msq = jnp.mean(h32 * h32, -1, keepdims=True)
    n = (h32 * jax.lax.rsqrt(msq + eps)).astype(h.dtype) * wn
    y = act(n @ wg.T) * (n @ wu.T)
    return h + y @ wd.T


def _chain(r, x, wn, wg, wu, wd):
    h = ops.add(r, x)
    n = ops.rms_norm(h, wn, eps=1e-5)
    gate = ops.silu(ops.linear(n, wg))
    up = ops.linear(n, wu)
    return ops.add(h, ops.linear(ops.mul(gate, up), wd))


def _chain_inputs(np_dtype=np.float32, N=16, D=32, F=48, seed=0):
    rng = np.random.RandomState(seed)
    cast = (lambda a: jnp.asarray(a, jnp.bfloat16)) if np_dtype is not np.float32 \
        else (lambda a: a)
    return (cast(rng.randn(N, D).astype(np.float32) * 0.5),
            cast(rng.randn(N, D).astype(np.float32) * 0.5),
            cast((1.0 + 0.1 * rng.randn(D)).astype(np.float32)),
            cast(rng.randn(F, D).astype(np.float32) * 0.2),
            cast(rng.randn(F, D).astype(np.float32) * 0.2),
            cast(rng.randn(D, F).astype(np.float32) * 0.2))


# ---------------------------------------------------------------------------
# megakernel parity vs the unfused decomposition
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("np_dtype", [np.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_subblock_megakernel_forward_parity(np_dtype):
    args = _chain_inputs(np_dtype)
    jf = tt.jit(_chain, executors=["pallas", "xla"], block_fusion=True)
    got = jf(*args)
    assert "pallas_mlp_subblock" in _symbol_names(tt.last_execution_trace(jf))
    want = _subblock_ref(*args)
    tol = dict(atol=1e-5, rtol=1e-5) if np_dtype == np.float32 \
        else dict(atol=8e-2, rtol=8e-2)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol)


@pytest.mark.parametrize("np_dtype", [np.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_subblock_megakernel_backward_parity(np_dtype):
    """``nn.mlp_subblock`` is a serving kernel with no VJP rule. Called
    directly, its forward is claimed by Pallas; under ``tt.value_and_grad``
    it differentiates through its decomposition (no ``pallas_mlp_subblock*``
    op in the step) and the gradients match jax autodiff of the unfused
    reference, for every operand."""
    from thunder_tpu.ops import nn as tnn

    args = _chain_inputs(np_dtype)
    jfwd = tt.jit(lambda *a: tnn.mlp_subblock(*a, act="silu", eps=1e-5),
                  executors=["pallas", "xla"])
    out = jfwd(*args)
    assert "pallas_mlp_subblock" in _symbol_names(tt.last_execution_trace(jfwd))

    def loss(*a):
        return ops.sum(ops.mul(tnn.mlp_subblock(*a, act="silu", eps=1e-5), 0.1))

    jf = tt.jit(lambda *a: tt.value_and_grad(loss, argnums=tuple(range(6)))(*a),
                executors=["pallas", "xla"], block_fusion=True)
    lval, grads = jf(*args)
    names = _symbol_names(tt.last_execution_trace(jf))
    assert not [n for n in names if "mlp_subblock" in n], names
    assert not _block_decisions(jf)

    def jref_loss(*a):
        return (_subblock_ref(*a).astype(jnp.float32) * 0.1).sum()

    jl, jg = jax.value_and_grad(jref_loss, argnums=tuple(range(6)))(*args)
    fwd_tol, loss_rtol, grad_tol = (1e-5, 2e-3, 2e-4) \
        if np_dtype == np.float32 else (8e-2, 2e-2, 0.12)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(_subblock_ref(*args), np.float32),
                               atol=fwd_tol, rtol=fwd_tol)
    np.testing.assert_allclose(np.asarray(lval, np.float32),
                               np.asarray(jl, np.float32), rtol=loss_rtol)
    for g, jg_i in zip(grads, jg):
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(jg_i, np.float32),
                                   atol=grad_tol, rtol=grad_tol)


def test_subblock_megakernel_ragged_rows():
    """Row counts that don't tile to the 128-row budget (ragged T) still run
    under interpret mode and match the reference — the kernel falls back to
    whole-dimension blocks when no divisor fits."""
    args = _chain_inputs(np.float32, N=13, D=24, F=56, seed=3)
    jf = tt.jit(_chain, executors=["pallas", "xla"], block_fusion=True)
    got = jf(*args)
    assert "pallas_mlp_subblock" in _symbol_names(tt.last_execution_trace(jf))
    np.testing.assert_allclose(np.asarray(got), np.asarray(_subblock_ref(*args)),
                               atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# planner verdicts
# ---------------------------------------------------------------------------

def test_planner_rejects_escaping_interior():
    """If a chain interior (here the normed value) is also returned, the
    megakernel would hide it — the planner must reject with the
    interior-escapes verdict and the trace stays unfused."""
    args = _chain_inputs(np.float32, seed=4)

    def f(r, x, wn, wg, wu, wd):
        h = ops.add(r, x)
        n = ops.rms_norm(h, wn, eps=1e-5)
        gate = ops.silu(ops.linear(n, wg))
        up = ops.linear(n, wu)
        return ops.add(h, ops.linear(ops.mul(gate, up), wd)), n  # n escapes

    jf = tt.jit(f, executors=["pallas", "xla"], block_fusion=True)
    out, n_out = jf(*args)
    assert "pallas_mlp_subblock" not in _symbol_names(tt.last_execution_trace(jf))
    dec = _block_decisions(jf)
    assert any(d["decision"] == "interior-escapes" for d in dec), dec
    np.testing.assert_allclose(np.asarray(out), np.asarray(_subblock_ref(*args)),
                               atol=1e-5, rtol=1e-5)


def test_planner_cost_rejects_tiny_shapes_by_default():
    """At tiny-llama shapes with DEFAULT options the cost model must reject
    (the 8 µs launch term dwarfs the interior-byte saving) — and say so in
    the decision log with the saved-bytes objective attached."""
    args = _chain_inputs(np.float32, seed=5)
    jf = tt.jit(_chain, executors=["pallas", "xla"])
    jf(*args)
    assert "pallas_mlp_subblock" not in _symbol_names(tt.last_execution_trace(jf))
    dec = _block_decisions(jf)
    rejected = [d for d in dec if d["decision"] == "cost-rejected"]
    assert rejected, dec
    assert "saved_boundary_bytes" in rejected[0]["cost"]
    assert "est_saved_us" in rejected[0]["cost"]


def test_planner_never_plans_dist_annotated():
    """Dist-annotated operands are never planned across shards, even when
    block_fusion=True forces past the cost gates."""
    from thunder_tpu.core.compile_data import CompileContext, compile_context
    from thunder_tpu.core.fusion_passes import block_fusion_pass
    from thunder_tpu.core.proxies import DistParallelType, TensorProxy
    from thunder_tpu.core.trace import TraceCtx, tracectx
    from thunder_tpu.executors import pallasex
    from thunder_tpu.observe import decisions as obs_decisions

    trc = TraceCtx("blk")
    with tracectx(trc):
        kw = dict(shape=(16, 32), dtype=dtypes.float32)
        r = TensorProxy("r", **kw)
        x = TensorProxy("x", **kw)
        wn = TensorProxy("wn", shape=(32,), dtype=dtypes.float32)
        wg = TensorProxy("wg", shape=(48, 32), dtype=dtypes.float32)
        wg.distparallel_type = DistParallelType.FULLY_SHARDED
        wu = TensorProxy("wu", shape=(48, 32), dtype=dtypes.float32)
        wd = TensorProxy("wd", shape=(32, 48), dtype=dtypes.float32)
        out = _chain(r, x, wn, wg, wu, wd)
    trc.output = out

    with obs_decisions.collect() as log:
        with compile_context(CompileContext({"block_fusion": True})):
            new = block_fusion_pass(trc, [pallasex.ex])
    assert all(b.sym.id != "nn.mlp_subblock" for b in new.bound_symbols)
    assert any(d["kind"] == "block" and d["decision"] == "dist-annotated"
               for d in log), log


def test_planner_vmem_infeasibility():
    """The VMEM-residency feasibility check: shapes whose per-grid-step
    staging exceeds the scoped-VMEM budget are never planned (and the
    planner records the verdict); bench-geometry shapes are feasible AND
    profitable under the cost model."""
    huge = cost_model.subblock_cost(16384, 8192, 32768, 2)
    assert not huge["vmem_feasible"]
    assert not cost_model.subblock_profitable(huge)
    bench = cost_model.subblock_cost(16384, 4096, 11008, 2)
    assert bench["vmem_feasible"]
    assert cost_model.subblock_profitable(bench)
    assert bench["est_saved_us"] > 0
    tiny = cost_model.subblock_cost(32, 64, 176, 4)
    assert not cost_model.subblock_profitable(tiny)

    # planner-level: a hand trace at the infeasible shape records the verdict
    from thunder_tpu.core.compile_data import CompileContext, compile_context
    from thunder_tpu.core.fusion_passes import block_fusion_pass
    from thunder_tpu.core.proxies import TensorProxy
    from thunder_tpu.core.trace import TraceCtx, tracectx
    from thunder_tpu.executors import pallasex
    from thunder_tpu.observe import decisions as obs_decisions

    trc = TraceCtx("blk")
    with tracectx(trc):
        kw = dict(shape=(16384, 8192), dtype=dtypes.bfloat16)
        r = TensorProxy("r", **kw)
        x = TensorProxy("x", **kw)
        wn = TensorProxy("wn", shape=(8192,), dtype=dtypes.bfloat16)
        wg = TensorProxy("wg", shape=(32768, 8192), dtype=dtypes.bfloat16)
        wu = TensorProxy("wu", shape=(32768, 8192), dtype=dtypes.bfloat16)
        wd = TensorProxy("wd", shape=(8192, 32768), dtype=dtypes.bfloat16)
        out = _chain(r, x, wn, wg, wu, wd)
    trc.output = out
    with obs_decisions.collect() as log:
        with compile_context(CompileContext({})):
            new = block_fusion_pass(trc, [pallasex.ex])
    assert all(b.sym.id != "nn.mlp_subblock" for b in new.bound_symbols)
    assert any(d["kind"] == "block" and d["decision"] == "vmem-infeasible"
               for d in log), log


# the verdicts as the cost model gave them before the training pair came (PR
# 29) and after it went (PR 30): these dicts must not move by a digit (decode,
# the prefill chunk, the training shape, and the shapes the two older tests use)
_FORWARD_ONLY_GOLDEN = {
    "decode32": ((32, 4096, 14336, 2), True, {
        "n_tokens": 32, "d_model": 4096, "d_ff": 14336, "flops": 11274289152,
        "decode": True, "saved_boundary_bytes": 7077888, "flop_us": 57.23,
        "boundary_us": 431.145, "vmem_bytes_per_step": 8650752,
        "vmem_feasible": True, "est_unfused_us": 539.918,
        "est_fused_us": 510.683, "est_saved_us": 29.236}),
    "prefill512": ((512, 4096, 14336, 2), False, {
        "n_tokens": 512, "d_model": 4096, "d_ff": 14336,
        "flops": 180388626432, "decode": False,
        "saved_boundary_bytes": 113246208, "flop_us": 915.678,
        "boundary_us": 445.549, "vmem_bytes_per_step": 15728640,
        "vmem_feasible": True, "est_unfused_us": 1673.916,
        "est_fused_us": 1598.147, "est_saved_us": 75.769}),
    "train16384": ((16384, 4096, 14336, 2), False, {
        "n_tokens": 16384, "d_model": 4096, "d_ff": 14336,
        "flops": 5772436045824, "decode": False,
        "saved_boundary_bytes": 3623878656, "flop_us": 29301.706,
        "boundary_us": 921.825, "vmem_bytes_per_step": 15728640,
        "vmem_feasible": True, "est_unfused_us": 40229.568,
        "est_fused_us": 37556.957, "est_saved_us": 2672.611}),
    "bench11008": ((16384, 4096, 11008, 2), False, {
        "n_tokens": 16384, "d_model": 4096, "d_ff": 11008,
        "flops": 4432406249472, "decode": False,
        "saved_boundary_bytes": 2969567232, "flop_us": 22499.524,
        "boundary_us": 821.961, "vmem_bytes_per_step": 15728640,
        "vmem_feasible": True, "est_unfused_us": 31232.954,
        "est_fused_us": 28954.366, "est_saved_us": 2278.588}),
    "huge": ((16384, 8192, 32768, 2), False, {
        "n_tokens": 16384, "d_model": 8192, "d_ff": 32768,
        "flops": 26388279066624, "decode": False,
        "saved_boundary_bytes": 8053063680, "flop_us": 133950.655,
        "boundary_us": 2949.84, "vmem_bytes_per_step": 31457280,
        "vmem_feasible": False, "est_unfused_us": 172247.706,
        "est_fused_us": 170396.159, "est_saved_us": 1851.547}),
    "tiny": ((32, 64, 176, 4), False, {
        "n_tokens": 32, "d_model": 64, "d_ff": 176, "flops": 2162688,
        "decode": False, "saved_boundary_bytes": 184320, "flop_us": 0.011,
        "boundary_us": 0.195, "vmem_bytes_per_step": 245760,
        "vmem_feasible": True, "est_unfused_us": 0.433,
        "est_fused_us": 8.209, "est_saved_us": -7.776}),
    "decode8_11008": ((8, 4096, 11008, 2), True, {
        "n_tokens": 8, "d_model": 4096, "d_ff": 11008, "flops": 2164260864,
        "decode": True, "saved_boundary_bytes": 1449984, "flop_us": 10.986,
        "boundary_us": 330.561, "vmem_bytes_per_step": 6881280,
        "vmem_feasible": True, "est_unfused_us": 377.41,
        "est_fused_us": 352.293, "est_saved_us": 25.116}),
    "fwd8_11008": ((8, 4096, 11008, 2), False, {
        "n_tokens": 8, "d_model": 4096, "d_ff": 11008, "flops": 2164260864,
        "decode": False, "saved_boundary_bytes": 1449984, "flop_us": 10.986,
        "boundary_us": 330.561, "vmem_bytes_per_step": 6881280,
        "vmem_feasible": True, "est_unfused_us": 345.41,
        "est_fused_us": 352.293, "est_saved_us": -6.884}),
}

@pytest.mark.parametrize("case", list(_FORWARD_ONLY_GOLDEN))
def test_subblock_cost_forward_only_unchanged(case):
    """The one scoring of an MLP chain, forward-only (decode, prefill): the
    numbers of the commit before PR 29, to the last digit."""
    shape, decode, golden = _FORWARD_ONLY_GOLDEN[case]
    assert cost_model.subblock_cost(*shape, decode=decode) == golden


@pytest.mark.parametrize("block_fusion", [None, True, False],
                         ids=["default", "forced", "off"])
def test_train_step_plans_no_mlp_chain(block_fusion):
    """A ``tt.jit`` train step under each of ``block_fusion``'s three values:
    the planner has nothing to select under autodiff — no chain planned, no
    ``block`` decision, no sub-block kernel — and all three match."""
    step, params, tokens, targets = _tiny_train_inputs(11)
    kw = {} if block_fusion is None else {"block_fusion": block_fusion}
    observe.enable(clear=True)
    jf = tt.jit(step, executors=["pallas", "xla"], **kw)
    loss, grads = jf(params, tokens, targets)
    n_fusions = observe.snapshot()["counters"].get("fusion.block_fusions", 0)
    observe.disable()
    plain = tt.jit(step, executors=["pallas", "xla"], block_fusion=False)
    l_u, g_u = plain(params, tokens, targets)
    np.testing.assert_allclose(np.asarray(loss), np.asarray(l_u), atol=2e-5)
    for a, b in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(g_u)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=5e-4, rtol=5e-4)
    names = _symbol_names(tt.last_execution_trace(jf))
    assert not [n for n in names if "mlp_subblock" in n], names
    assert not _block_decisions(jf) and n_fusions == 0
    assert "(none — no sub-block chains found" in observe.explain(jf)
    assert (tt.last_execution_trace(jf).python()
            == tt.last_execution_trace(plain).python())


def test_inference_chain_is_scored_forward_only():
    """The planner's entry (``transform_for_execution``) scores a chain by
    the forward-only byte objective, and the decision carries its terms."""
    args = _chain_inputs(np.float32, seed=5)
    jf = tt.jit(_chain, executors=["pallas", "xla"])
    jf(*args)
    (d,) = _block_decisions(jf)
    assert d["decision"] == "cost-rejected"
    assert "saved boundary bytes lose" in d["reason"]
    assert d["cost"] == dict(cost_model.subblock_cost(16, 32, 48, 4),
                             chain=d["cost"]["chain"], act="silu", ops=8)


def test_block_planner_is_entered_once_a_train_compile():
    """One planner entry (``executors/passes.py``): a compile of a train
    step opens exactly one ``block_fusion*`` span (the decode step's case is
    in ``test_decode_layer.py``)."""
    observe.enable(clear=True)
    step, params, tokens, targets = _tiny_train_inputs(12)
    tt.jit(step, executors=["pallas", "xla"])(params, tokens, targets)
    spans = [sp["name"] for sp in observe.get_registry().spans
             if sp["name"].startswith("block_fusion")]
    observe.disable()
    assert spans == ["block_fusion"], spans


def test_training_pair_is_gone():
    """No ``nn.mlp_subblock_bwd`` op is registered, ``pallasex`` exports no
    backward for the sub-block, and ``nn.mlp_subblock`` has no VJP rule."""
    from thunder_tpu.core import transforms
    from thunder_tpu.executors import pallasex
    from thunder_tpu.ops import _opsym_registry, get_op, nn as tnn

    assert get_op("nn.mlp_subblock") is not None
    assert not [k for k in _opsym_registry if "mlp_subblock_bwd" in str(k)]
    assert not hasattr(tnn, "mlp_subblock_bwd")
    assert not [n for n in dir(pallasex) if "mlp_subblock_bwd" in n]
    assert "nn.mlp_subblock" not in transforms._vjp_rules


def test_autodiff_imports_nothing_from_the_planner():
    """``core/transforms.py`` (autodiff) imports nothing from
    ``core/fusion_passes``: the pipeline is a straight line."""
    import ast
    import inspect

    from thunder_tpu.core import transforms

    for node in ast.walk(ast.parse(inspect.getsource(transforms))):
        if isinstance(node, ast.ImportFrom):
            assert "fusion_passes" not in (node.module or ""), ast.dump(node)
            assert not [a for a in node.names if a.name == "fusion_passes"]
        elif isinstance(node, ast.Import):
            assert not [a for a in node.names if "fusion_passes" in a.name]


def test_planner_decisions_use_registered_kinds_only():
    from thunder_tpu.core import fusion_passes

    src_kinds = set(fusion_passes.BLOCK_DECISION_KINDS)
    import inspect
    import re

    src = inspect.getsource(fusion_passes)
    recorded = set(re.findall(r"_record_block\(\s*[\"']([a-z-]+)[\"']", src))
    assert recorded, "planner records no block decisions?"
    assert recorded <= src_kinds, recorded - src_kinds


# ---------------------------------------------------------------------------
# tiny-llama train trace: fusion shape + parity (the acceptance path)
# ---------------------------------------------------------------------------

def _tiny_train_inputs(seed):
    """The two-layer tiny-Llama train step and one batch for it."""
    cfg = llama.CONFIGS["tiny"]
    params = llama.init_params(cfg, seed=seed, scale_layers=2)
    rng = np.random.RandomState(seed)
    tokens = rng.randint(0, cfg.vocab_size, size=(2, 16)).astype(np.int32)
    targets = np.roll(tokens, -1, 1).astype(np.int32)

    def train_step(params, tokens, targets):
        return tt.value_and_grad(
            lambda p: llama.loss_fn(p, tokens, targets, cfg))(params)

    return train_step, params, tokens, targets


def test_llama_train_step_block_planner_shape_and_parity():
    """The two-layer tiny-Llama train step under ``block_fusion=True``: no
    MLP chain is planned (the planner's entry comes after autodiff and the
    chain is prim-level there), numerics match ``block_fusion=False``, the
    report says so, and the region count is no higher."""
    step, params, tokens, targets = _tiny_train_inputs(7)

    planned = tt.jit(step, executors=["pallas", "xla"], block_fusion=True)
    plain = tt.jit(step, executors=["pallas", "xla"], block_fusion=False)
    l_p, g_p = planned(params, tokens, targets)
    l_u, g_u = plain(params, tokens, targets)
    np.testing.assert_allclose(np.asarray(l_p), np.asarray(l_u), atol=2e-5)
    for a, b in zip(jax.tree_util.tree_leaves(g_p), jax.tree_util.tree_leaves(g_u)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=5e-4, rtol=5e-4)

    trc = tt.last_execution_trace(planned)
    assert _count_symbols(trc, "mlp_subblock") == 0
    assert not [n for n in _symbol_names(trc) if "mlp_subblock" in n]
    n_planned = sum(1 for b in trc.bound_symbols
                    if str(b.sym.id).startswith("xla.fusion"))
    n_plain = sum(1 for b in tt.last_execution_trace(plain).bound_symbols
                  if str(b.sym.id).startswith("xla.fusion"))
    assert n_planned <= n_plain, (n_planned, n_plain)

    assert not _block_decisions(planned)
    report = observe.explain(planned)
    assert "block planner (0 candidate chains)" in report


def test_planner_counter_and_marker_inference():
    """Inference traces plan in transform_for_execution: the trace carries
    the block-fusion marker, and the fusion.block_fusions counter ticks."""
    cfg = llama.CONFIGS["tiny"]
    params = llama.init_params(cfg, seed=8, scale_layers=2)
    rng = np.random.RandomState(8)
    tokens = rng.randint(0, cfg.vocab_size, size=(2, 16)).astype(np.int32)
    observe.enable(clear=True)
    jf = tt.jit(lambda p, t: llama.forward(p, t, cfg),
                executors=["pallas", "xla"], block_fusion=True)
    out = jf(params, tokens)
    snap = observe.snapshot()
    observe.disable()
    assert snap["counters"].get("fusion.block_fusions", 0) >= 2
    src = tt.last_execution_trace(jf).python()
    assert "block-fusion" in src
    jref = tt.jit(lambda p, t: llama.forward(p, t, cfg), block_fusion=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(jref(params, tokens)),
                               atol=2e-5)


@pytest.mark.chaos
def test_quarantined_megakernel_recompiles_to_per_op_fallback():
    """A quarantined megakernel claim recompiles to the per-op XLA
    decomposition with equal numerics — the claim id dies, the chain
    survives."""
    args = _chain_inputs(np.float32, seed=9)
    ref = np.asarray(tt.jit(_chain, block_fusion=False)(*args))

    jf = tt.jit(_chain, executors=["pallas", "xla"], block_fusion=True)
    with quarantine.containment(), \
            faults.active(FaultPlan([FaultSpec("kernel:pallas.mlp_subblock")])):
        out = jf(*args)  # kernel dies at trace -> quarantine -> recompile
    np.testing.assert_allclose(np.asarray(out), ref, atol=1e-5, rtol=1e-5)
    assert quarantine.is_quarantined("pallas.mlp_subblock")
    trc = tt.last_execution_trace(jf)
    assert "pallas_mlp_subblock" not in _symbol_names(trc)
    # the decomposition's ops are back (per-op fallback), and stay healthy
    np.testing.assert_allclose(np.asarray(jf(*args)), ref, atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# slab-persistent optimizer state
# ---------------------------------------------------------------------------

def _slab_fixture(seed=0):
    from thunder_tpu.optim import AdamW

    rng = np.random.RandomState(seed)
    params = {"a": rng.randn(17, 9).astype(np.float32),
              "b": rng.randn(5,).astype(np.float32)}
    grads = {"a": (rng.randn(17, 9) * 0.1).astype(np.float32),
             "b": (rng.randn(5,) * 0.1).astype(np.float32)}
    return AdamW, params, grads


def test_slab_kernel_bit_identical_to_packed_kernel():
    """The acceptance contract at the kernel level: the slab-persistent
    claim and the pack-per-step claim run the SAME kernel on the SAME slab
    geometry, so given identical inputs their parameter updates are
    BIT-identical (np.array_equal, not allclose)."""
    from thunder_tpu.executors.pallasex import (
        pallas_fused_adamw,
        pallas_fused_adamw_slab,
        _slab_pack,
    )
    from thunder_tpu.ops.optim import slab_geometry

    rng = np.random.RandomState(1)
    ps = [jnp.asarray(rng.randn(17, 9).astype(np.float32)),
          jnp.asarray(rng.randn(5,).astype(np.float32))]
    gs = [jnp.asarray((rng.randn(17, 9) * 0.1).astype(np.float32)),
          jnp.asarray((rng.randn(5,) * 0.1).astype(np.float32))]
    ms = [jnp.zeros_like(p) for p in ps]
    vs = [jnp.zeros_like(p) for p in ps]
    sizes = [int(np.prod(p.shape)) for p in ps]
    rows_pad, _ = slab_geometry(sum(sizes))
    m_slab = _slab_pack(ms, sizes, rows_pad)
    v_slab = _slab_pack(vs, sizes, rows_pad)
    bc1, bc2 = jnp.float32(1 - 0.9), jnp.float32(1 - 0.999)

    hyper = dict(lr=1e-2, beta1=0.9, beta2=0.999, eps=1e-8, weight_decay=0.01)
    pn_ref, mn_ref, vn_ref = pallas_fused_adamw(ps, gs, ms, vs, bc1, bc2, **hyper)
    pn, mn, vn = pallas_fused_adamw_slab(ps, gs, m_slab, v_slab, bc1, bc2,
                                         sizes=sizes, **hyper)
    for a, b in zip(pn, pn_ref):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    # the new state matches too (slab holds exactly the packed new moments)
    assert np.array_equal(np.asarray(mn), np.asarray(_slab_pack(mn_ref, sizes, rows_pad)))
    assert np.array_equal(np.asarray(vn), np.asarray(_slab_pack(vn_ref, sizes, rows_pad)))


def test_slab_persistent_update_matches_fused_path():
    """End-to-end traced updates: slab-persistent vs pack-per-step fused
    AdamW track each other at final-bit ULPs over multiple steps (strict
    bit-identity across two different XLA programs is ill-defined — FMA
    contraction differs per program; see KERNELS.md — the kernel-level test
    above pins the bit-exact contract), the composite is claimed, and the
    bucket verdict carries the zeroed pack-bytes term."""
    AdamW, params, grads = _slab_fixture()
    opt_n = AdamW(lr=1e-2)
    opt_s = AdamW(lr=1e-2, slab_persistent=True)
    jn = tt.jit(lambda p, g, s: opt_n.update(p, g, s),
                executors=["pallas", "xla"], fused_optimizer=True)
    js = tt.jit(lambda p, g, s: opt_s.update(p, g, s),
                executors=["pallas", "xla"])
    pn, sn = params, opt_n.init(params)
    ps, ss = params, opt_s.init(params)
    for _ in range(3):
        pn, sn = jn(pn, grads, sn)
        ps, ss = js(ps, grads, ss)
        for k in ("a", "b"):
            np.testing.assert_allclose(np.asarray(pn[k]), np.asarray(ps[k]),
                                       rtol=0, atol=1e-7)
    assert "pallas_fused_adamw_slab" in _symbol_names(tt.last_execution_trace(js))
    dec = [d for d in tt.compile_stats(js).last_decisions
           if d["op"] == "optim.fused_adamw_slab"]
    assert len(dec) == 1 and dec[0]["decision"] == "bucketed"
    assert dec[0]["cost"]["pack_bytes_if_unabsorbed"] == 0
    assert dec[0]["cost"]["slab_persistent"] is True


def test_slab_state_dtype_buckets_and_moment_dtypes():
    """A mixed f32/bf16 tree gets one slab pair per parameter dtype, with
    m in state_dtype and v in v_dtype."""
    from thunder_tpu.optim import AdamW

    rng = np.random.RandomState(2)
    params = {"f": rng.randn(9, 3).astype(np.float32),
              "h": jnp.asarray(rng.randn(4, 4).astype(np.float32), jnp.bfloat16)}
    grads = jax.tree_util.tree_map(lambda p: (p * 0.1).astype(p.dtype), params)
    opt = AdamW(lr=1e-2, state_dtype=dtypes.bfloat16, slab_persistent=True)
    state = opt.init(params)
    assert set(state["m"]) == {"float32", "bfloat16"}
    jf = tt.jit(lambda p, g, s: opt.update(p, g, s), executors=["pallas", "xla"])
    new_p, new_s = jf(params, grads, state)
    for key in ("float32", "bfloat16"):
        assert jnp.asarray(new_s["m"][key]).dtype == jnp.bfloat16
        assert jnp.asarray(new_s["v"][key]).dtype == jnp.float32
    # numerics: matches the non-persistent path at ULP tolerance
    ref_p, _ = tt.jit(lambda p, g, s: AdamW(lr=1e-2, state_dtype=dtypes.bfloat16)
                      .update(p, g, s), fused_optimizer=False)(
        params, grads, AdamW(lr=1e-2, state_dtype=dtypes.bfloat16).init(params))
    for a, b in zip(jax.tree_util.tree_leaves(new_p), jax.tree_util.tree_leaves(ref_p)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-5)


def test_slab_persistent_rejects_dist_annotated_params():
    from thunder_tpu.core.proxies import DistParallelType, TensorProxy
    from thunder_tpu.core.trace import TraceCtx, tracectx
    from thunder_tpu.optim import AdamW

    opt = AdamW(lr=1e-2, slab_persistent=True)
    host = {"w": np.zeros((8, 8), np.float32)}
    state = opt.init(host)
    trc = TraceCtx("t")
    with pytest.raises(Exception, match="dist-annotated"):
        with tracectx(trc):
            p = TensorProxy("p_w", shape=(8, 8), dtype=dtypes.float32)
            p.distparallel_type = DistParallelType.FULLY_SHARDED
            g = TensorProxy("g_w", shape=(8, 8), dtype=dtypes.float32)
            from thunder_tpu.core.proxies import TensorProxy as TP

            st = {"m": {"float32": TP("m_s", shape=state["m"]["float32"].shape,
                                      dtype=dtypes.float32)},
                  "v": {"float32": TP("v_s", shape=state["v"]["float32"].shape,
                                      dtype=dtypes.float32)},
                  "step": TP("st", shape=(), dtype=dtypes.float32),
                  "layout_version": TP("lv", shape=(), dtype=dtypes.int32)}
            opt.update({"w": p}, {"w": g}, st)


def test_slab_checkpoint_roundtrip_both_directions(tmp_path):
    """The layout-version contract: a pre-slab checkpoint restores into a
    slab-persistent run (and vice versa) through CheckpointManager without
    shape errors, and training continues with matching numerics."""
    from thunder_tpu.elastic import CheckpointManager
    from thunder_tpu.optim import (
        AdamW,
        adapt_opt_state,
        opt_state_layout_version,
    )

    AdamW_, params, grads = (lambda A, p, g: (A, p, g))(*_slab_fixture(3))
    opt_tree = AdamW_(lr=1e-2)
    opt_slab = AdamW_(lr=1e-2, slab_persistent=True)
    jtree = tt.jit(lambda p, g, s: opt_tree.update(p, g, s),
                   executors=["pallas", "xla"], fused_optimizer=True)
    jslab = tt.jit(lambda p, g, s: opt_slab.update(p, g, s),
                   executors=["pallas", "xla"])

    # direction 1: tree-layout checkpoint -> slab-persistent run
    p1, s1 = jtree(params, grads, opt_tree.init(params))
    mgr = CheckpointManager(str(tmp_path / "ck1"), keep=2)
    mgr.save(1, {"params": p1, "opt": s1})
    step, loaded = mgr.restore_latest()
    assert opt_state_layout_version(loaded["opt"]) == 0
    s1_slab = adapt_opt_state(loaded["opt"], params=loaded["params"], opt=opt_slab)
    assert opt_state_layout_version(s1_slab) == 1
    p2s, s2s = jslab(loaded["params"], grads, s1_slab)       # no shape errors
    p2t, s2t = jtree(p1, grads, s1)
    for k in ("a", "b"):
        np.testing.assert_allclose(np.asarray(p2s[k]), np.asarray(p2t[k]),
                                   rtol=0, atol=1e-7)

    # direction 2: slab checkpoint -> tree-layout run
    mgr2 = CheckpointManager(str(tmp_path / "ck2"), keep=2)
    mgr2.save(2, {"params": p2s, "opt": s2s})
    _, loaded2 = mgr2.restore_latest()
    assert opt_state_layout_version(loaded2["opt"]) == 1
    s_back = adapt_opt_state(loaded2["opt"], params=loaded2["params"], opt=opt_tree)
    assert opt_state_layout_version(s_back) == 0
    p3t, _ = jtree(loaded2["params"], grads, s_back)          # no shape errors
    p3s, _ = jslab(p2s, grads, s2s)
    for k in ("a", "b"):
        np.testing.assert_allclose(np.asarray(p3t[k]), np.asarray(p3s[k]),
                                   rtol=0, atol=1e-7)


def test_fused_adamw_cost_slab_flag():
    c0 = cost_model.fused_adamw_cost(100, 1 << 30)
    assert c0["pack_bytes_if_unabsorbed"] == 2 << 30
    assert c0["slab_persistent"] is False
    c1 = cost_model.fused_adamw_cost(100, 1 << 30, slab_persistent=True)
    assert c1["pack_bytes_if_unabsorbed"] == 0
    assert c1["slab_persistent"] is True
    assert 0 < c1["pg_pack_bytes_if_unabsorbed"] < c0["pack_bytes_if_unabsorbed"]
    # time estimate is layout-independent (same kernel, same bytes)
    assert c1["est_fused_us"] == c0["est_fused_us"]
