"""Driver ``serve_closed_state``: the routed driver's two gaps and the share
of the compared slots' state entries that bfloat16 holds exactly. A run of
the cell's rehearsal as built reads a float32 state; the same run with the
PROGRAM's state rounded to bfloat16 after every step (the precision cut the
two gaps cannot see) comes out not correct on that number alone."""

import argparse
import types

import numpy as np

import run as bench_run
from run import load_module

CELL = "solaropen2_serve_reason_sat"


def run_cell(tmp_path, seed=2**31 + 17):
    args = argparse.Namespace(workload=CELL, seed=seed, seconds=1.0,
                              trace=0, rehearse=True, control=False, set=[],
                              out=str(tmp_path))
    return bench_run.run_cell(args)


def failing(line):
    return [k for k, c in line["compared"].items()
            if c["value"] is None or c["value"] > c["limit"]]


def test_a_float32_state_is_read_and_correct(tmp_path):
    line = run_cell(tmp_path)
    assert line["correct"] is True and not failing(line)
    assert set(line["compared"]) == {"served_logit_gap",
                                     "served_logit_gap_mean",
                                     "served_state_bf16_share"}
    # a computed float32 value has its low 16 mantissa bits zero a few
    # times in 100,000
    assert line["compared"]["served_state_bf16_share"]["value"] < 1e-3


def test_a_state_held_in_bfloat16_is_not_correct(tmp_path, monkeypatch):
    from thunder_tpu import ops
    from thunder_tpu.core import dtypes
    from thunder_tpu.models import solar_open2 as so

    def rounded(step):
        def wrapped(self, *args):
            out, st = step(self, *args)
            s = ops.convert_element_type(
                ops.convert_element_type(st["s"], dtypes.bfloat16),
                dtypes.float32)
            return out, dict(st, s=s)
        return wrapped

    for name in ("_kda_decode", "_kda_prefill"):
        monkeypatch.setattr(so.SolarOpen2Description, name,
                            rounded(getattr(so.SolarOpen2Description, name)))
    line = run_cell(tmp_path)
    assert line["correct"] is False
    assert "served_state_bf16_share" in failing(line)
    assert line["compared"]["served_state_bf16_share"]["value"] == 1.0


def test_the_share_reads_the_sampled_requests_slots():
    """A made-up engine: the sampled requests' rows are read (not the other
    residents'), their nonzero entries counted, and a state not held in
    float32 reads as nothing compared."""
    drv = load_module("drivers", "serve_closed_state")
    rng = np.random.default_rng(3)
    exact = np.array([1.0, -2.5, 0.0, 3.0], np.float32)       # bf16 holds
    inexact = (rng.random(6).astype(np.float32) + 1) * np.float32(1 + 2**-20)
    rows = {0: exact, 1: inexact, 2: exact}

    def req(i):
        return types.SimpleNamespace(prompt=np.arange(i, i + 3), generated=[7])

    slots = [req(0), None, req(1), req(2)]
    sample = [{"prompt": np.arange(1, 4), "tokens": [7]},
              {"prompt": np.arange(0, 3), "tokens": [7]}]
    eng = types.SimpleNamespace(slots=slots, request_state=lambda r: [
        {"s": rows[int(r.prompt[0])], "conv": np.ones(2, np.float16)}])
    ctx = types.SimpleNamespace(family=types.SimpleNamespace(
        STATE_FLOAT32=("s",)), traffic={"check_requests": 2},
        log=lambda msg: None)
    serving = types.SimpleNamespace(sample_finished=lambda *a: sample)
    sv = types.SimpleNamespace(eng=eng)
    got = drv.state_bf16_share(ctx, serving, sv, {"t_open": 0, "t_close": 1})
    assert got == 3 / (6 + 3)           # requests 1 and 0; the zero left out
    rows[0] = exact.astype(np.float16)
    assert drv.state_bf16_share(ctx, serving, sv,
                                {"t_open": 0, "t_close": 1}) is None
