"""Tensor-parallel serving tests (pjit/GSPMD over a 1-D mesh): the
Megatron column/row weight plan + kv-head-sharded paged pool, 8-device
decode token-identity vs single-device (greedy, best-of-N COW fork, and
prefix-cache warm hits), the committed CENSUS_BUDGETS.json collective
budget for the meshed decode program (≤2 all-reduces per layer, zero
gathers), typed sharding-geometry rejection, crash recovery restoring the
exact shardings from the fault's ``RestartState``, and the megakernel
planner's one-rung mesh cap. All CPU: the 8 host devices come from
``tests/conftest.py``'s ``--xla_force_host_platform_device_count=8``."""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import thunder_tpu as tt
from thunder_tpu import observe
from thunder_tpu.distributed import TensorParallelMesh, shard_params
from thunder_tpu.distributed.gspmd import mesh_descriptor
from thunder_tpu.models import llama
from thunder_tpu.observe import census
from thunder_tpu.runtime import faults, quarantine
from thunder_tpu.runtime.faults import FaultPlan, FaultSpec
from thunder_tpu.serving import (
    EngineSupervisor,
    PagedKVCache,
    PageGeometry,
    RestartState,
    SamplingParams,
    ServingEngine,
    ShardingGeometryError,
)
from thunder_tpu.serving.errors import ServingError

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_runtime():
    faults.clear()
    quarantine.reset()
    yield
    faults.clear()
    quarantine.reset()


def _engine(params, cfg, n_layers, **kw):
    defaults = dict(max_slots=4, page_size=8, max_context=64,
                    n_layers=n_layers, prefill_chunk=32)
    defaults.update(kw)
    return ServingEngine(params, cfg, **defaults)


def _refs(params, cfg, prompts, max_new, n_layers):
    return [np.asarray(llama.generate(params, cfg, p[None], max_new,
                                      n_layers=n_layers))[0]
            for p in prompts]


def _pool_sharding(eng):
    sh = eng.cache.pools[0]["k"].sharding
    return sh


def _spec_axes(sh):
    """The partitioned axes of a NamedSharding spec, trailing-None
    normalized (a compiled step's output spec drops trailing Nones; a
    fresh ``device_put`` keeps them — same sharding either way)."""
    axes = tuple(sh.spec)
    while axes and axes[-1] is None:
        axes = axes[:-1]
    return axes


@pytest.fixture(scope="module")
def gqa_model():
    cfg = llama.CONFIGS["tiny-gqa"]
    return cfg, llama.init_params(cfg, seed=0, scale_layers=1)


@pytest.fixture(scope="module")
def tp_model():
    cfg = llama.CONFIGS["tiny-tp"]
    return cfg, llama.init_params(cfg, seed=0, scale_layers=2)


@pytest.fixture(scope="module")
def tp8_engine(tp_model):
    """One shared tp=8 engine (the compile is the expensive part): the
    token-identity and census-budget tests both read it."""
    cfg, params = tp_model
    return _engine(params, cfg, n_layers=2, mesh=8)


# ---------------------------------------------------------------------------
# the fast 2-device smoke (the tier-1 front line)
# ---------------------------------------------------------------------------

def test_tp2_engine_smoke_token_identical(gqa_model):
    """tiny-gqa over a 2-way mesh (kv_heads=2 divides): weights land
    column/row-sharded, the pool lands kv-head-sharded, greedy outputs are
    token-identical to the dense single-device ``generate()``, and the
    mesh is announced on the registry + flight ring."""
    cfg, params = gqa_model
    rng = np.random.RandomState(0)
    prompts = [np.asarray([3], np.int32),
               rng.randint(1, cfg.vocab_size, size=9).astype(np.int32)]
    refs = _refs(params, cfg, prompts, 5, 1)
    observe.enable(clear=True)
    try:
        eng = _engine(params, cfg, n_layers=1, mesh=2)
        reqs = [eng.submit(p, 5) for p in prompts]
        eng.drain()
        snap = observe.snapshot()
    finally:
        observe.disable()
    for r, ref in zip(reqs, refs):
        np.testing.assert_array_equal(r.output(), ref)
    # the mesh really is a 2-way tp mesh, and the pool is head-sharded
    assert eng.mesh is not None and eng.mesh.tp == 2
    sh = _pool_sharding(eng)
    assert isinstance(sh, jax.sharding.NamedSharding)
    assert sh.mesh.size == 2
    assert _spec_axes(sh) == (eng.mesh.axis,)   # dim 0 = kv-head, rest repl
    # announced: gauge + typed serving_mesh event with the mesh descriptor
    assert snap["gauges"]["serving.tp_degree"] == 2
    ev = [e for e in snap["events"] if e["kind"] == "serving_mesh"]
    assert ev and ev[0]["phase"] == "build" and ev[0]["tp_degree"] == 2
    assert ev[0]["mesh_shape"] == [2]
    assert eng.describe_state()["mesh"]["tp_degree"] == 2
    eng.assert_quiescent()


def test_mesh_descriptor_shapes():
    tpm = TensorParallelMesh(tp=4)
    assert mesh_descriptor(tpm) == {"mesh_shape": [4], "tp_degree": 4}
    assert mesh_descriptor(None) == {"mesh_shape": [1], "tp_degree": 1}


# ---------------------------------------------------------------------------
# 8-device token identity (the acceptance gate)
# ---------------------------------------------------------------------------

def test_tp8_decode_token_identical_to_single_device(tp_model, tp8_engine):
    """The full-width gate: tiny-tp (everything divides 8) decoded over
    the 8-device mesh is token-identical to the same engine on one device
    AND to the dense ``generate()`` reference, across mixed prompt lengths
    including a chunk-spanning prompt."""
    cfg, params = tp_model
    rng = np.random.RandomState(1)
    prompts = [np.asarray([7], np.int32),
               rng.randint(1, cfg.vocab_size, size=11).astype(np.int32),
               rng.randint(1, cfg.vocab_size, size=37).astype(np.int32)]
    refs = _refs(params, cfg, prompts, 6, 2)
    meshed = tp8_engine
    single = _engine(params, cfg, n_layers=2)
    mreqs = [meshed.submit(p, 6) for p in prompts]
    sreqs = [single.submit(p, 6) for p in prompts]
    meshed.drain()
    single.drain()
    for m, s, ref in zip(mreqs, sreqs, refs):
        np.testing.assert_array_equal(m.output(), s.output())
        np.testing.assert_array_equal(m.output(), ref)
    sh = _pool_sharding(meshed)
    assert sh.mesh.size == 8
    meshed.assert_quiescent()
    single.assert_quiescent()


def test_tp8_bestof_fork_and_prefix_warm_hit_identical(tp_model):
    """The COW-fork and prefix-cache paths survive sharding: a seeded
    best-of-3 fork group and a warm prefix-cache hit produce the same
    tokens on the 8-device mesh as on one device (the fork's page copies
    and the admission probe's skipped prefill both operate on the
    head-sharded pool)."""
    cfg, params = tp_model
    rng = np.random.RandomState(2)
    sysp = rng.randint(1, cfg.vocab_size, size=16).astype(np.int32)
    tails = [rng.randint(1, cfg.vocab_size, size=5).astype(np.int32)
             for _ in range(2)]
    prompts = [np.concatenate([sysp, t]) for t in tails]
    sp = SamplingParams(temperature=0.8, top_k=20, seed=11)

    def run(mesh):
        eng = _engine(params, cfg, n_layers=2, max_slots=4,
                      prefix_cache=True, num_pages=48, mesh=mesh)
        # cold then warm: the second submission of each prompt probe-hits
        # the donated system pages
        cold = [eng.submit(p, 4) for p in prompts]
        eng.drain()
        warm = [eng.submit(p, 4) for p in prompts]
        eng.drain()
        prim = eng.submit(prompts[0], 4, best_of=3, sampling=sp)
        eng.drain()
        forked = [list(r.output()) for r in prim.fork_group]
        hit = sum(r.prefix_hit_tokens for r in warm)
        outs = ([list(r.output()) for r in cold],
                [list(r.output()) for r in warm])
        eng.assert_quiescent()
        return outs, hit, forked, eng

    (m_cold, m_warm), m_hit, m_fork, meng = run(8)
    (s_cold, s_warm), s_hit, s_fork, _ = run(None)
    assert m_cold == m_warm == s_cold == s_warm   # warm hits change nothing
    assert m_hit > 0 and m_hit == s_hit           # and they really were hits
    assert m_fork == s_fork                       # seeded fork group matches
    assert len(m_fork) == 3
    assert _pool_sharding(meng).mesh.size == 8


# ---------------------------------------------------------------------------
# the collective budget (CENSUS_BUDGETS.json regression gate)
# ---------------------------------------------------------------------------

def test_tp8_decode_census_within_committed_budget(tp8_engine):
    """The meshed decode program must stay collective-lean: exactly 2
    all-reduces per layer (attention out-projection + MLP down-projection)
    and NO gather of the sharded pool — drifting outside the committed
    tiny-tp-decode-tp8 bounds fails tier-1."""
    eng = tp8_engine
    eng.submit(np.arange(1, 6, dtype=np.int32), 3)
    eng.drain()
    c = tt.hlo_census(eng.runner.decode_jit)
    assert c is not None and not c.get("hlo_unavailable")
    with open(os.path.join(REPO, "CENSUS_BUDGETS.json")) as f:
        budget = json.load(f)["configs"]["tiny-tp-decode-tp8"]
    violations = census.check_budget(c, budget)
    assert not violations, violations
    # the gate is live, not a tautology
    assert census.check_budget(c, {"max_total_collectives": 0})
    assert census.check_budget(c, {"forbid_kinds": ["all-reduce"]})
    # the census itself carries the mesh descriptor (flight/bench stamps)
    assert c["mesh_shape"] == [8] and c["tp_degree"] == 8
    assert c["n_dev"] == 8


# ---------------------------------------------------------------------------
# typed sharding-geometry rejection
# ---------------------------------------------------------------------------

def test_kv_heads_not_divisible_rejected_typed():
    geom = PageGeometry(n_layers=1, kv_heads=2, head_dim=16, page_size=8,
                        num_pages=12, pages_per_request=4)
    with pytest.raises(ShardingGeometryError, match="kv_heads=2") as ei:
        PagedKVCache(geom, jnp.float32, sharding=TensorParallelMesh(tp=8))
    assert ei.value.kv_heads == 2 and ei.value.tp == 8
    # the typed error is both a ServingError and a ValueError
    assert isinstance(ei.value, ServingError)
    assert isinstance(ei.value, ValueError)


def test_engine_rejects_indivisible_head_geometry(gqa_model):
    """The engine-level check names the first indivisible dimension:
    tiny-gqa has 4 q-heads / 2 kv-heads, neither divides 8."""
    cfg, params = gqa_model
    with pytest.raises(ShardingGeometryError):
        _engine(params, cfg, n_layers=1, mesh=8)


# ---------------------------------------------------------------------------
# crash recovery restores the shardings
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_supervisor_rebuild_restores_sharding(gqa_model):
    """A ``serving:engine`` crash consumes the sharded pools; the
    supervisor rebuilds from the fault's typed ``RestartState`` — the new
    pool carries the SAME NamedSharding the compiled SPMD step was built
    around (a replicated rebuild would poison the next dispatch), outputs
    stay token-identical, and the rebuild announces itself."""
    from thunder_tpu.runtime.retry import RetryPolicy

    cfg, params = gqa_model
    rng = np.random.RandomState(3)
    prompts = [rng.randint(1, cfg.vocab_size, size=L).astype(np.int32)
               for L in (5, 9)]
    refs = _refs(params, cfg, prompts, 6, 1)
    observe.enable(clear=True)
    try:
        eng = _engine(params, cfg, n_layers=1, mesh=2,
                      retry_policy=RetryPolicy(max_attempts=3,
                                               base_delay_s=0.001,
                                               max_delay_s=0.01))
        axes_before = _spec_axes(_pool_sharding(eng))
        sup = EngineSupervisor(eng, max_restarts=2, restart_window_s=600.0)
        reqs = [sup.submit(p, 6) for p in prompts]
        with faults.active(FaultPlan([FaultSpec("serving:engine",
                                                at_steps={3})])):
            sup.drain()
        snap = observe.snapshot()
    finally:
        observe.disable()
    assert sup.restarts == 1
    for r, ref in zip(reqs, refs):
        np.testing.assert_array_equal(r.output(), ref)
    sh = _pool_sharding(eng)
    assert isinstance(sh, jax.sharding.NamedSharding)
    assert sh.mesh.size == 2 and _spec_axes(sh) == axes_before == ("tp",)
    phases = [e["phase"] for e in snap["events"]
              if e["kind"] == "serving_mesh"]
    assert phases == ["build", "rebuild"]
    eng.assert_quiescent()


def test_rebuild_rejects_mismatched_restart_state(gqa_model):
    """Rebuilding from a RestartState describing a DIFFERENT sharding is a
    lifecycle bug (it would silently change the mesh under the compiled
    program) and raises the typed error instead."""
    cfg, params = gqa_model
    eng = _engine(params, cfg, n_layers=1, mesh=2)
    foreign = RestartState(geometry=eng.geom, dtype=cfg.dtype.jax,
                           mesh=None)
    with pytest.raises(ShardingGeometryError, match="restart state"):
        eng.rebuild_after_fault(foreign)
    # its own state is, of course, accepted
    eng.rebuild_after_fault(eng._restart_state)
    assert _pool_sharding(eng).mesh.size == 2
    eng.assert_quiescent()


def test_engine_fault_carries_restart_state(gqa_model):
    """The typed RestartState rides the EngineFault itself, so a
    supervisor holding only the exception can rebuild sharding-identical
    (the describe() view is what postmortems print)."""
    cfg, params = gqa_model
    eng = _engine(params, cfg, n_layers=1, mesh=2)
    rs = eng._restart_state
    assert rs.mesh is eng.mesh
    d = rs.describe()
    assert d["tp_degree"] == 2 and d["mesh_shape"] == [2]
    assert d["kv_heads"] == cfg.kv_heads
    from thunder_tpu.serving.errors import EngineFault

    e = EngineFault("boom", domain="serving:engine", restart_state=rs)
    assert e.restart_state is rs


# ---------------------------------------------------------------------------
# the megakernel planner's one-rung mesh cap
# ---------------------------------------------------------------------------

def test_mesh_caps_megakernel_one_rung(monkeypatch):
    """Under ``decode_tp_shards`` the planner stops ONE rung down: the
    attention/MLP sub-block kernels still claim (Pallas interpret on CPU),
    the decode-layer chain does NOT, the cap is recorded as a typed
    ``mesh-rung-capped`` decision, and outputs match the unfused program —
    never a silent collapse to per-op XLA."""
    from thunder_tpu.serving.runner import PagedLlamaRunner

    monkeypatch.setenv("THUNDER_TPU_PALLAS_INTERPRET", "1")
    cfg = llama.CONFIGS["tiny-gqa"]
    params = jax.device_put(llama.init_params(cfg, seed=3, scale_layers=2))
    geom = PageGeometry(n_layers=2, kv_heads=cfg.kv_heads, head_dim=16,
                        page_size=8, num_pages=16, pages_per_request=4)
    # the mesh object is only a planner input here (tp rides the compile
    # options); inputs stay on one device, so interpret-Pallas is safe
    tpm = TensorParallelMesh(tp=2)
    capped = PagedLlamaRunner(cfg, geom, n_layers=2, block_fusion=True,
                              mesh=tpm)
    plain = PagedLlamaRunner(cfg, geom, n_layers=2, block_fusion=False)
    S = 2
    rng = np.random.RandomState(5)
    tokens = rng.randint(1, cfg.vocab_size, size=(S, 1)).astype(np.int32)
    bt = np.zeros((S, 4), np.int32)
    bt[0, 0], bt[1, 0] = 1, 2
    lengths = np.asarray([3, 5], np.int32)
    write_pos = np.asarray([bt[b, 0] * 8 + int(lengths[b]) - 1
                            for b in range(S)], np.int32)
    kd = cfg.dim // cfg.n_heads

    def pools():
        return [{"k": jnp.zeros((geom.kv_heads, geom.num_pages,
                                 geom.page_size, kd), jnp.float32),
                 "v": jnp.zeros((geom.kv_heads, geom.num_pages,
                                 geom.page_size, kd), jnp.float32)}
                for _ in range(2)]

    sampling = (np.zeros(S, np.float32), np.zeros(S, np.int32),
                np.ones(S, np.float32), np.zeros((S, 2), np.uint32))
    tc, lc, _ = capped.decode_jit(params, tokens, bt, lengths, write_pos,
                                  pools(), *sampling)
    tp_, lp, _ = plain.decode_jit(params, tokens, bt, lengths, write_pos,
                                  pools(), *sampling)
    np.testing.assert_allclose(np.asarray(lc), np.asarray(lp),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_array_equal(np.asarray(tc), np.asarray(tp_))

    def names(trc):
        out = set()

        def walk(bsyms):
            for b in bsyms:
                out.add(b.sym.codegen_name())
                walk(b.subsymbols)

        walk(trc.bound_symbols)
        return out

    got = names(tt.last_execution_trace(capped.decode_jit))
    assert "pallas_decode_layer" not in got      # the capped rung
    assert "pallas_attn_subblock" in got         # ONE rung down, not per-op
    assert "pallas_mlp_subblock" in got
    dec = [d for d in tt.compile_stats(capped.decode_jit).last_decisions
           if d["kind"] == "block" and d["decision"] == "mesh-rung-capped"]
    assert dec and dec[0]["op"] == "nn.decode_layer"
    assert "tp=2" in dec[0]["reason"]
    # the runner stamped the mesh descriptor for the census/flight stamps
    assert tt.compile_stats(capped.decode_jit).census_context[
        "tp_degree"] == 2


@pytest.mark.parametrize("block_fusion", [None, False],
                         ids=["subblocks", "paged-attention"])
def test_pallas_claims_run_under_their_partitioning_plans(monkeypatch,
                                                          gqa_model,
                                                          block_fusion):
    """A REAL tp=2 mesh with Pallas claims on (interpret mode): Mosaic
    kernels cannot be auto-partitioned, so inside the GSPMD program the
    planned impls run in a shard_map under the Megatron layout — the
    attention and MLP sub-blocks by default, the standalone paged-attention
    kernel with the planner off — token-identical to ``generate`` and
    still 2 all-reduces per layer with the pool never gathered."""
    monkeypatch.setenv("THUNDER_TPU_PALLAS_INTERPRET", "1")
    cfg, params = gqa_model
    rng = np.random.RandomState(21)
    prompts = [rng.randint(1, cfg.vocab_size, size=n).astype(np.int32)
               for n in (5, 11)]
    refs = _refs(params, cfg, prompts, 4, 1)
    eng = _engine(params, cfg, n_layers=1, mesh=2, block_fusion=block_fusion)
    reqs = [eng.submit(p, 4) for p in prompts]
    eng.drain()
    for r, ref in zip(reqs, refs):
        np.testing.assert_array_equal(r.output(), ref)
    names = set()

    def walk(bsyms):
        for b in bsyms:
            names.add(b.sym.codegen_name())
            walk(b.subsymbols)

    walk(tt.last_execution_trace(eng.runner.decode_jit).bound_symbols)
    if block_fusion is None:
        assert {"pallas_attn_subblock", "pallas_mlp_subblock"} <= names
    else:
        assert "pallas_paged_decode_attention" in names
        # plan-less kernels do not claim inside a meshed program
        assert "pallas_linear_act" not in names
    c = tt.hlo_census(eng.runner.decode_jit)
    kinds = {k: v["count"] for k, v in c["collectives"]["per_kind"].items()
             if v["count"]}
    assert kinds == {"all-reduce": 2}, kinds
    assert _spec_axes(_pool_sharding(eng)) == ("tp",)
    assert len(quarantine.get_quarantine()) == 0


# ---------------------------------------------------------------------------
# shard_params geometry checks
# ---------------------------------------------------------------------------

def test_shard_params_rejects_indivisible_dim():
    tpm = TensorParallelMesh(tp=8, column_patterns=(r"\bw\b",))
    with pytest.raises(ValueError, match="divisible"):
        shard_params({"w": jnp.zeros((12, 4))}, tpm)
