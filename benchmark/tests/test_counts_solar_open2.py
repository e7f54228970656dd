"""The ``solar_open2`` family's operation and byte counts at
``solar-open2-250b-l4e40``'s widths equal numbers worked out by hand (D
4096; GQA 64 heads / 8 KV heads x 128; KDA 64 heads of 128, convolution 4,
low rank 128; 40 experts held + 1 shared of width 1280, router 320; 24,576
rows of the vocabulary; 4 layers, of which layer 0 is GQA)."""

import json
import os

import run as bench_run
from conftest import BENCH

fam = bench_run.load_module("families", "solar_open2")


def spec():
    with open(os.path.join(BENCH, "configs",
                           "solar-open2-250b-l4e40.json")) as f:
        return fam.spec_from_config(json.load(f))


# GQA: q 4096 x 8192, k and v 4096 x 1024 each, o and the gate 8192 x 4096
GQA_W = 4096 * 10240 + 2 * 8192 * 4096                      # 109,051,904
# KDA: q, k, v, o; two rank-128 pairs; beta; convolution; A_log, dt_bias,
# the head norm
KDA_W = (4 * 4096 * 8192 + 2 * (4096 * 128 + 128 * 8192) + 4096 * 64
         + 3 * 8192 * 4 + 64 + 8192 + 128)                  # 137,732,288
EXPERT_W = 3 * 4096 * 1280                                  # 15,728,640


def test_num_params_is_the_cut_reckoned_by_hand():
    assert (GQA_W, KDA_W) == (109_051_904, 137_732_288)
    # a layer's experts: router 320 x 4096 and its bias, 41 experts, 2 norms
    moe = 320 * 4097 + 41 * EXPERT_W + 2 * 4096
    # 1 GQA + 3 KDA layers + embedding and untied head + the final norm:
    # 3.31 B parameters, 6.62 GB in bf16
    assert fam.num_params(spec()) == GQA_W + 3 * KDA_W + 4 * moe \
        + 2 * 24576 * 4096 + 4096 == 3_308_353_344


def test_kda_decode_counts_are_the_state_read_and_written_once():
    # 3 layers x 128 slots x 64 heads of a 128 x 128 float32 state, read and
    # written: 3.22 GB; the five vectors in and o out add 75.5 MB
    c = fam.kda_decode_counts(spec(), 128)
    state = 3 * 128 * 64 * 2 * 128 * 128 * 4
    assert state == 3_221_225_472
    assert c["bytes"] == state + 3 * 128 * 64 * 6 * 128 * 4 == 3_296_722_944
    assert c["flops"] == 3 * 128 * 64 * 7 * 128 * 128
    # memory-bound by two decades
    assert c["bytes"] / 819e9 > 100 * c["flops"] / 197e12


def test_decode_page_walk_is_the_one_gqa_layer_over_four():
    # 128 slots at 3,584 tokens: the GQA layer reads every live token, and
    # the harness multiplies the mean over the 4 layers by 4
    live = 128 * 3584
    a = fam.decode_attn_block_counts(spec(), 128, live)
    assert 4 * a["flops"] == 4 * 64 * 128 * live
    assert 4 * a["bytes"] == (2 * 1024 * live + 2 * 128 * 8192) * 2 \
        == 1_883_242_496


def test_decode_step_bytes_at_128_slots_and_every_held_expert_streamed():
    s = spec()
    dense = GQA_W + 3 * KDA_W + 4 * (320 * 4096 + EXPERT_W)
    base = fam.decode_step_counts(s, 128, 128 * 3584)
    assert base["bytes"] == 1_883_242_496 + 3_296_722_944 + dense * 2 \
        + (24576 * 4096 + 128 * 4096) * 2 == 6_563_153_024
    # every held expert streams (the program gives each a row): 40 a layer
    routed = 4 * fam.routed_counts(s, 40, 128 * 8 * 40 / 320)["bytes"]
    assert routed == 4 * 40 * EXPERT_W * 2 == 5_033_164_800
    # the cut's byte count (PERF.md §4): ~11.5 GB a step, ~14 ms at
    # 819 GB/s, the experts ~44% of it and the KDA state ~28%
    step = base["bytes"] + routed
    assert 11.5e9 < step < 11.7e9 and 14.0e-3 < step / 819e9 < 14.3e-3
    assert 0.43 < routed / step < 0.44
    assert 0.27 < 3_221_225_472 / step < 0.28


def test_moe_block_counts_are_the_streamed_and_the_shared_experts():
    m = fam.moe_block_counts(spec(), 128, 40, 128)
    assert m["bytes"] == 41 * EXPERT_W * 2 + 2 * 128 * 4096 * 2
    assert m["flops"] == 2 * EXPERT_W * (128 + 128)
