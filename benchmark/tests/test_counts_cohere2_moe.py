"""The ``cohere2_moe`` family's operation and byte counts at
``command-a-plus-05-2026-l4e16``'s widths equal numbers worked out by hand
(D 4096, expert width 4096, 128 heads / 8 KV heads x 128, window 4096, 16
experts held + 4 shared, 32,768 rows of the vocabulary, 4 layers of which 3
are window layers)."""

import json
import os

import run as bench_run
from conftest import BENCH

fam = bench_run.load_module("families", "cohere2_moe")


def spec():
    with open(os.path.join(
            BENCH, "configs", "command-a-plus-05-2026-l4e16.json")) as f:
        return fam.spec_from_config(json.load(f))


# q 4096 x 16384, k and v 4096 x 1024 each, o 16384 x 4096
ATTN_W = 4096 * (128 + 2 * 8) * 128 + 128 * 128 * 4096      # 142,606,336
EXPERT_W = 3 * 4096 * 4096                                  # 50,331,648


def test_num_params_is_the_cut_the_issue_reckoned():
    # a layer: attention + router 128 x 4096 + 20 experts + one norm
    layer = ATTN_W + 524_288 + 20 * EXPERT_W + 4096
    assert layer == 1_149_767_680
    # four layers + the tied embedding + the final norm: 4.73 B, 9.47 GB bf16
    assert fam.num_params(spec()) == 4 * layer + 32768 * 4096 + 4096 \
        == 4_733_292_544


def test_decode_page_walk_counts_by_kind():
    # 32 slots at 8,192 tokens each: the global layer reads 262,144 tokens, a
    # window layer 32 x 4,096; a layer's mean over 1 global : 3 window
    a = fam.decode_attn_block_counts(spec(), 32, 32 * 8192)
    tokens = 0.25 * 262_144 + 0.75 * 131_072
    assert tokens == 163_840
    # QK^T and PV: 2 * 2 * H * hd a token
    assert a["flops"] == 4 * 128 * 128 * tokens == 10_737_418_240
    # K and V of those tokens (2 * KV * hd each) + q and o rows, bf16
    assert a["bytes"] == (2 * 1024 * tokens + 2 * 32 * 16384) * 2 \
        == 673_185_792
    # below the window every layer reads the live context
    short = fam.decode_attn_block_counts(spec(), 32, 32 * 1000)
    assert short["flops"] == 4 * 128 * 128 * 32_000


def test_decode_step_bytes_at_32_slots_and_13_hit_experts():
    s = spec()
    # the step without its routed experts: 4 layers of (walk + attention
    # weights + router + 4 shared experts) + the tied head + 32 embedding rows
    dense = ATTN_W + 524_288 + 4 * EXPERT_W
    assert dense == 344_457_216
    base = fam.decode_step_counts(s, 32, 32 * 8192)
    assert base["bytes"] == 4 * (673_185_792 + dense * 2) \
        + (32768 * 4096 + 32 * 4096) * 2 == 5_717_098_496
    assert base["flops"] == 4 * (10_737_418_240 + 2 * dense * 32) \
        + 2 * 32768 * 4096 * 32 == 139_720_654_848
    # 13 of 16 held experts hit by 64 local picks, one layer: three 4096 x
    # 4096 matrices an expert, read once; 2 FLOPs a weight a pick
    r = fam.routed_counts(s, 13, 64)
    assert r["bytes"] == 13 * EXPERT_W * 2 == 1_308_622_848
    assert r["flops"] == 2 * EXPERT_W * 64 == 6_442_450_944
    # the whole step at 13 hit a layer: 10.95 GB, 13.4 ms at 819 GB/s, and
    # bandwidth bounds it (the issue's "~10 GB a step with the cache, 12 ms")
    step = base["bytes"] + 4 * r["bytes"]
    assert step == 10_951_589_888 and 13.3e-3 < step / 819e9 < 13.5e-3
    assert step / 819e9 > (base["flops"] + 4 * r["flops"]) / 197e12
    # of the step's WEIGHT bytes (the step less its cache, rows and
    # embedding rows) the experts are ~83%: 13 hit + 4 shared of a layer's
    # 2.0 GB
    weights = step - 4 * 673_185_792 - 32 * 4096 * 2
    assert 0.82 < 4 * (r["bytes"] + 4 * EXPERT_W * 2) / weights < 0.84


def test_moe_block_counts_are_the_hit_and_the_shared_experts():
    m = fam.moe_block_counts(spec(), 32, 13, 64)
    # 13 hit + 4 shared experts' weights, 32 rows in and out
    assert m["bytes"] == 17 * EXPERT_W * 2 + 2 * 32 * 4096 * 2 == 1_711_800_320
    # 64 routed picks + 32 rows x 4 shared experts
    assert m["flops"] == 2 * EXPERT_W * (64 + 128) == 19_327_352_832
    # an expert no row hit costs nothing; the shared ones always run
    idle = fam.moe_block_counts(spec(), 32, 0, 0)
    assert idle["bytes"] == 4 * EXPERT_W * 2 + 2 * 32 * 4096 * 2


def test_the_shares_count_the_experts_the_kernel_streamed():
    """Reader ``moe_route``: the program gives every held expert a
    zero-weight row, so a step streams all 16 where the routing hit 13; the
    whole step's share counts the ``streamed`` ones' bytes (an event without
    the field, the hit ones), the picks' operations either way."""
    import types

    from thunder_tpu import observe

    reader = bench_run.load_module("readers", "moe_route")
    s = spec()
    base = fam.decode_step_counts(s, 32, 32 * 8192)
    ctx = types.SimpleNamespace(
        family=fam, spec=s, chips=1, clock_sync=(0.0, 0.0), t_trace_open=0.0,
        t_trace_close=1e12, traffic={"engine": {"max_slots": 32}},
        peaks={"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9},
        readings={"counts": {"step_decode": base, "decode_s": 0.040}},
        load=lambda kind, name: bench_run.load_module(kind, name))

    def mfu(**fields):
        observe.enable(clear=True)
        try:
            for layer in range(4):
                observe.event("moe_route", step=1, layer=layer, hit=13,
                              local_picks=64, max_load=7, **fields)
            return reader.read(ctx, "mfu")
        finally:
            observe.disable()
            observe.reset()

    of = lambda experts: 100.0 * (base["bytes"] + 4 * experts * EXPERT_W * 2) \
        / 819e9 / 0.040
    assert abs(mfu(streamed=16) - of(16)) < 1e-9
    assert abs(mfu() - of(13)) < 1e-9
    assert of(16) - of(13) > 3.6        # 1.2 GB a step more, of 40 ms
