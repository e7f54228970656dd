"""The operation and byte counts at Mistral-7B widths equal numbers worked
out by hand (D 4096, F 14336, 32 heads / 8 KV heads x 128, vocab 32768)."""

import json
import os

import run as bench_run
from conftest import BENCH

llama = bench_run.load_module("families", "llama")


def spec(layers):
    with open(os.path.join(BENCH, "configs", f"mistral-7b-v0.3-l{layers}.json")) as f:
        return llama.spec_from_config(json.load(f))


# attention weights a layer: D*(H+2KV)*hd + H*hd*D = 4096*48*128 + 4096*4096
ATTN_W = 25_165_824 + 16_777_216            # 41,943,040
MLP_W = 3 * 4096 * 14336                    # 176,160,768


def test_num_params():
    # a layer: attn + mlp + two norms = 218,112,000; embedding + head:
    # 2*32768*4096 = 268,435,456; final norm 4096
    assert llama.num_params(spec(2)) == 2 * 218_112_000 + 268_435_456 + 4096 \
        == 704_663_552
    assert llama.num_params(spec(8)) == 2_013_335_552


def test_train_flops_per_token():
    # dense fwd: 2*(2*(ATTN_W+MLP_W) + 32768*4096) = 2*570,425,344
    # causal attention fwd: 2 layers * (QK, PV) * 2 * 4096 * 4097/2 = 67,125,248
    # fwd + bwd = 3 x fwd
    assert llama.train_flops_per_token(spec(2), 4096) == \
        3 * (1_140_850_688 + 67_125_248) == 3_623_927_808


def test_mlp_block_train_counts():
    # 16,384 rows: 3 GEMMs fwd, 6 bwd = 2*MLP_W*rows*3 FLOPs
    # bytes: weights read once + their gradients written once (2*MLP_W), rows
    # in/out fwd (2) and dout, h, dh bwd (3): 5*rows*D; bf16
    c = llama.mlp_block_train_counts(spec(2), 16384)
    assert c["flops"] == 6 * MLP_W * 16384 == 17_317_308_137_472
    assert c["bytes"] == (2 * MLP_W + 5 * 16384 * 4096) * 2 == 1_375_731_712
    # compute-bound on a v5e: 87.9 ms of FLOPs against 1.7 ms of bytes
    assert c["flops"] / 197e12 > 50 * c["bytes"] / 819e9


def test_decode_counts():
    # 32 slots holding 8,192 live tokens together
    a = llama.decode_attn_block_counts(spec(8), 32, 8192)
    # QKV + out-projection: 2*ATTN_W*32; attention: 2*2*H*hd*live
    assert a["flops"] == 2_684_354_560 + 134_217_728
    # weights + K and V of the live context (2*KV*hd a token) + rows in/out
    assert a["bytes"] == (ATTN_W + 2 * 1024 * 8192 + 2 * 32 * 4096) * 2 \
        == 117_964_800
    s = llama.decode_step_counts(spec(8), 32, 8192)
    # 8 layers of (attention block + MLP GEMMs 2*MLP_W*32) + the head
    assert s["flops"] == 8 * (2_818_572_288 + 11_274_289_152) + 8_589_934_592
    # 8 layers of (attention bytes + MLP weights) + head + 32 embedding rows
    assert s["bytes"] == 8 * (117_964_800 + 352_321_536) \
        + (134_217_728 + 131_072) * 2 == 4_030_988_288
    # bandwidth bounds the step: ~4.9 ms at 819 GB/s
    assert 4.8e-3 < s["bytes"] / 819e9 < 5.0e-3
    assert s["bytes"] / 819e9 > s["flops"] / 197e12


def test_window_counts_scale_with_steps_and_layers():
    w = llama.train_window_counts(spec(2), 6, 4, 4096)
    assert w["mlp_block_train"]["flops"] == 17_317_308_137_472 * 2 * 6
    assert w["step_train"]["flops"] == 3_623_927_808 * 6 * 16384
