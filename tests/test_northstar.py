"""North-star regression net (verdict r3 #1): AOT-compile the REAL
BASELINE.md configs against real TPU topologies and assert the evidence —
memory fit, async-collective overlap, flop sanity, projected MFU.

Each compile takes ~15-20 minutes of XLA time (a full 32-layer 7B-class
fwd+bwd+AdamW program for a 16-chip target), so the file is gated:

    RUN_NORTHSTAR=1 python -m pytest tests/test_northstar.py -v

The committed NORTHSTAR.md / NORTHSTAR.json artifacts are produced by
``python -m thunder_tpu.benchmarks.northstar`` from the same code paths.
Ungated, this file only checks the machinery imports and the topology
handles resolve (so a libtpu regression still fails fast).
"""

import os

import pytest

from thunder_tpu.benchmarks import northstar as ns

RUN = os.environ.get("RUN_NORTHSTAR") == "1"


def test_topologies_resolve():
    if ns.get_topology(ns.TOPO_V5P_32) is None:
        pytest.skip("TPU compiler unavailable")
    assert len(ns.get_topology(ns.TOPO_V5P_32).devices) == 16
    assert len(ns.get_topology(ns.TOPO_V5P_16).devices) == 8


def test_analytic_param_count_matches_llama2_7b():
    from thunder_tpu.models import llama

    n = ns.n_params_llama(llama.CONFIGS["llama2-7b"])
    assert abs(n - 6.74e9) / 6.74e9 < 0.01  # the published 7B count


needs_run = pytest.mark.skipif(
    not RUN or ns.get_topology(ns.TOPO_V5P_32) is None,
    reason="RUN_NORTHSTAR=1 + TPU compiler required (each config is a "
           "15-20 min XLA compile)")


@pytest.fixture(scope="module")
def llama7b():
    from thunder_tpu.models import llama

    cfg = llama.CONFIGS["llama2-7b"]
    n = ns.n_params_llama(cfg)
    return ns.run_config(
        "llama2-7b-fsdp-v5p32",
        lambda: ns.abstract_llama_step("llama2-7b", batch=16, seq=4096,
                                       n_dev=16, zero=2),
        ns.TOPO_V5P_32, 16, 16 * 4096, n,
        ns.analytic_train_flops(n, 16 * 4096, cfg, 4096))


@pytest.fixture(scope="module")
def llama8b():
    from thunder_tpu.models import llama

    cfg = llama.CONFIGS["llama3-8b"]
    n = ns.n_params_llama(cfg)
    return ns.run_config(
        "llama3-8b-fsdp-v5p32",
        lambda: ns.abstract_llama_step("llama3-8b", batch=16, seq=8192,
                                       n_dev=16, zero=3, remat=True),
        ns.TOPO_V5P_32, 16, 16 * 8192, n,
        ns.analytic_train_flops(n, 16 * 8192, cfg, 8192))


@pytest.fixture(scope="module")
def mixtral_ep():
    from thunder_tpu.models import mixtral

    mcfg = mixtral.CONFIGS["mixtral-8x7b"]
    kv_dim = mcfg.kv_heads * mcfg.head_dim
    att = mcfg.n_layers * (2 * mcfg.dim * mcfg.dim + 2 * kv_dim * mcfg.dim
                           + 2 * mcfg.dim)
    expert = 3 * mcfg.intermediate_size * mcfg.dim
    n_active = (2 * mcfg.vocab_size * mcfg.dim + mcfg.dim + att
                + mcfg.n_layers * (mcfg.n_experts * mcfg.dim
                                   + mcfg.top_k * expert))
    return ns.run_config(
        "mixtral-8x7b-ep-v5p16",
        lambda: ns.abstract_mixtral_ep_step(batch=8, seq=2048, n_dev=8),
        ns.TOPO_V5P_16, 8, 8 * 2048, n_active,
        ns.analytic_train_flops(n_active, 8 * 2048, mcfg, 2048))


@needs_run
class TestLlama27BFsdpV5p32:
    def test_fits_hbm(self, llama7b):
        assert llama7b["fits_hbm"], llama7b["live_bytes_per_device"]

    def test_async_all_gather_scheduled(self, llama7b):
        assert llama7b["overlap"]["async_all_gather"] > 0

    def test_xla_flops_match_analytic(self, llama7b):
        rel = abs(llama7b["xla_flops_per_device"]
                  - llama7b["analytic_flops_per_device"]) \
            / llama7b["analytic_flops_per_device"]
        assert rel < 0.25

    def test_projected_mfu_clears_north_star(self, llama7b):
        # the >=45% MFU bar (BASELINE.md): with the async overlap the HLO
        # demonstrably schedules, the roofline must be MXU-bound at >=45%
        assert llama7b["mfu_projected_overlapped"] >= 0.45
        # and even with NOTHING overlapped the floor stays above 45%%
        assert llama7b["mfu_projected_serial"] >= 0.45


@needs_run
class TestLlama38BGqaV5p32:
    def test_fits_hbm(self, llama8b):
        assert llama8b["fits_hbm"], llama8b["live_bytes_per_device"]

    def test_async_all_gather_scheduled(self, llama8b):
        assert llama8b["overlap"]["async_all_gather"] > 0

    def test_projected_mfu(self, llama8b):
        assert llama8b["mfu_projected_overlapped"] >= 0.45


@needs_run
class TestMixtral8x7BEp:
    def test_fits_hbm(self, mixtral_ep):
        assert mixtral_ep["fits_hbm"], mixtral_ep["live_bytes_per_device"]

    def test_all_to_all_present(self, mixtral_ep):
        # dropless EP routes tokens with all-to-all over the ep axis
        assert mixtral_ep["overlap"]["all_to_all_total"] > 0


# ---------------------------------------------------------------------------
# ungated smoke tier (VERDICT r4 #8): the full evidence pipeline — abstract
# build, AOT compile, memory/cost/HLO-collective analysis, roofline
# projection — exercised on a TINY config against the hermetic 8-device CPU
# mesh every suite run, so a regression in the pipeline itself (not just in
# libtpu) fails fast.
# ---------------------------------------------------------------------------

def test_evidence_pipeline_smoke_cpu(fsdp_smoke_step):
    from thunder_tpu.models import llama
    from thunder_tpu.observe import census

    n_dev = 8
    cfg = llama.CONFIGS["tiny"]
    jstep, entry = fsdp_smoke_step
    # the shared memoized accessor: ONE AOT compile per suite run, shared
    # with test_census (and with tt.last_hlo / examine on this entry)
    compiled = census.compiled_for_entry(entry)

    n = ns.n_params_llama(cfg)
    m = ns.analyze(compiled, n_dev=n_dev,
                   analytic_flops=ns.analytic_train_flops(n, 8 * 16, cfg, 16))
    # memory analysis produced real numbers
    assert m["live_bytes_per_device"] > 0
    # the HLO census found the FSDP collectives with denominators. The
    # zero-2 grad reduction MUST survive as reduce-scatter on the CPU
    # path (NORTHSTAR.md: the TPU AOT pipeline rewrites it to all-reduce
    # — this assert is the negative control proving the framework emits
    # the cheaper collective and the rewrite is XLA's)
    hc = m["hlo_collectives"]
    kinds = set(hc["per_kind"])
    assert "reduce-scatter" in kinds and "all-gather" in kinds, kinds
    assert hc["recv_bytes_per_device_total"] > 0
    for k, e in hc["per_kind"].items():
        assert 0 <= e["async_count"] <= e["count"]
        assert e["recv_bytes_per_dev"] > 0
    # roofline projection composes with the comm term
    comm = ns.comm_bytes_per_device(jstep)
    recv = max(hc["recv_bytes_per_device_total"], ns._recv_bytes(comm, n_dev))
    proj = ns.project(m, {"total_in_bytes": recv})
    assert 0 < proj["mfu_projected_serial"] <= proj["mfu_projected_overlapped"] <= 1.0


def test_hlo_collectives_parser_pinned():
    """The census parses sync ops, async start tuples, and applies the ring
    cost model per kind (bytes are hand-computed for this snippet)."""
    hlo = """
  %ar = f32[1024,8]{1,0} all-reduce(f32[1024,8]{1,0} %p0), replica_groups={}
  %ag = (bf16[128,8]{1,0}, bf16[1024,8]{1,0}) all-gather-start(bf16[128,8]{1,0} %p1), dimensions={0}
  %rs = f32[128,8]{1,0} reduce-scatter(f32[1024,8]{1,0} %p2), dimensions={0}
  %cp = bf16[64]{0} collective-permute(bf16[64]{0} %p3), source_target_pairs={{0,1}}
"""
    c = ns.hlo_collectives(hlo, n_dev=8)
    pk = c["per_kind"]
    assert pk["all-reduce"]["count"] == 1 and pk["all-reduce"]["async_count"] == 0
    assert pk["all-reduce"]["recv_bytes_per_dev"] == 2 * 1024 * 8 * 4 * 7 // 8
    assert pk["all-gather"]["count"] == 1 and pk["all-gather"]["async_count"] == 1
    assert pk["all-gather"]["recv_bytes_per_dev"] == 1024 * 8 * 2 * 7 // 8
    assert pk["reduce-scatter"]["recv_bytes_per_dev"] == 128 * 8 * 4 * 7
    assert pk["collective-permute"]["recv_bytes_per_dev"] == 64 * 2
    assert c["async_fraction"]["all-gather"] == 1.0
