"""The reduction from a trace to numbers, on a hand-made trace whose values
are worked out in the comments, and on a small trace recorded on the chip."""

import json
import os

import pytest

import run as bench_run
from conftest import HERE

dt = bench_run.load_module("readers", "device_trace")


def test_op_names():
    assert dt.op_name("%pallas_mlp_subblock_bwd_1.3 = (bf16[14336,4096]{1,0}) "
                      "custom-call(...)") == "pallas_mlp_subblock_bwd"
    assert dt.op_name("%fusion.49 = bf16[4,4096]{1,0} fusion(...)") == "fusion.49"
    assert dt.op_name("copy.24") == "copy.24"


def test_hand_made_trace():
    # window 0..1000 ns, two devices.
    # device 0: a 100-300, b 250-400 (overlaps a: busy 100-400 = 300),
    #           all-reduce.1 500-700, c 600-650 (inside the collective),
    #           d 900-1100 (clipped at 1000: 100)      -> busy 300+200+100 = 600
    #   collective 500-700 less compute 600-650        -> exposed 150
    #   idle gaps: 0-100, 400-500, 700-900
    # device 1: e 0-500                                 -> busy 500, exposed 0
    dev = {"/device:TPU:0": [("a", 100, 200), ("b", 250, 150),
                             ("all-reduce.1", 500, 200), ("c", 600, 50),
                             ("d", 900, 200)],
           "/device:TPU:1": [("e", 0, 500)]}
    # host spans: step 0-450 with prepare 0-50 inside it; wait 650-1000
    #   gap 0-100:   prepare 50 (innermost first), step 50
    #   gap 400-500: step 50, nothing covers 450-500 -> unattributed 50
    #   gap 700-900: wait 200
    spans = [("step", 0, 450), ("prepare", 0, 50), ("wait", 650, 1000)]
    out = dt.reduce(dev, spans, (0, 1000))
    assert out["window_s"] == pytest.approx(1000e-9)
    assert out["busy_s"] == pytest.approx((600 + 500) / 2 * 1e-9)
    assert out["collective_exposed_s"] == pytest.approx(150 / 2 * 1e-9)
    assert out["ops_s"]["a"] == pytest.approx(200 / 2 * 1e-9)
    assert out["ops_s"]["d"] == pytest.approx(100 / 2 * 1e-9)
    gaps = dict(out["breakdown"]["idle_gaps"])
    assert gaps == pytest.approx({"wait": 200e-9, "step": 100e-9,
                                  "prepare": 50e-9, "unattributed": 50e-9})
    assert out["breakdown"]["device_ops"][0] == ["e", pytest.approx(250e-9)]


def test_recorded_trace():
    """The first 60 ops of a fenced train step on a TPU v5 lite. The busy time
    is checked against a nanosecond grid; the window opens 25,879 ns before
    ``bench:dispatch`` begins, with the device idle, and that is all that no
    span covers."""
    np = pytest.importorskip("numpy")
    with open(os.path.join(HERE, "recorded_trace.json")) as f:
        rec = json.load(f)
    dev = {k: [tuple(e) for e in v] for k, v in rec["device"].items()}
    w0, w1 = rec["window"]
    out = dt.reduce(dev, [tuple(s) for s in rec["spans"]], (w0, w1))
    grid = np.zeros(w1 - w0, bool)
    sums = {}
    for name, s, d in next(iter(dev.values())):
        grid[s:s + d] = True
        sums[name] = sums.get(name, 0) + d
    assert out["busy_s"] == pytest.approx(grid.sum() * 1e-9)
    for name, ns in sums.items():
        if ns == 0:                         # a marker of no duration
            assert name not in out["ops_s"]
            continue
        assert out["ops_s"][name] == pytest.approx(ns * 1e-9)
    gaps = dict(out["breakdown"]["idle_gaps"])
    assert gaps["unattributed"] == pytest.approx(25_879e-9)
    assert sum(gaps.values()) == pytest.approx((~grid).sum() * 1e-9)
    assert out["breakdown"]["device_ops"][0][0] == "pallas_sdpa_fwd"
    assert out["collective_exposed_s"] == 0.0


def test_roofline_is_least_time_over_kernel_time():
    class Ctx:
        peaks = {"flops_bf16": 100.0, "hbm_bytes_per_s": 10.0}
        readings = {"trace": {"ops_s": {"pallas_k": 4.0, "fusion.1": 1.0},
                              "busy_s": 5.0, "window_s": 10.0},
                    "counts": {"steps": 2, "k": {"flops": 100.0, "bytes": 20.0}}}
    # least time = max(100/100, 20/10) = 2 s of bytes against 4 s measured
    assert dt.read(Ctx, "roofline", kernels=["^pallas_k"], counts="k") == 50.0
    assert dt.read(Ctx, "kernel_ms", kernels=["^pallas_"]) == 2000.0
    assert dt.read(Ctx, "idle_share") == 50.0
    # a kernel that is not on the path is silent, never 0
    assert dt.read(Ctx, "roofline", kernels=["^pallas_gone"], counts="k") is None
