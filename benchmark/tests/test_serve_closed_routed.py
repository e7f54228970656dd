"""Driver ``serve_closed_routed``: what it compares. A made-up family whose
reference is a table look-up, so every token's gap is known by hand: the worst
and the MEAN over every served token of every sampled request, the control's
tokens the lower precision's own, and each token's gap kept in the run's
directory."""

import json
import types

import numpy as np

from run import load_module

V = 8
TABLE = np.random.default_rng(5).normal(size=(V, V)).astype(np.float32)
LOW = TABLE + np.random.default_rng(6).normal(size=(V, V)).astype(np.float32)


def fake_family():
    import jax.numpy as jnp

    def ref_logits(params, seq, spec, precision="float32"):
        # position i's logits depend on token i alone
        return jnp.asarray(TABLE if precision == "float32" else LOW)[seq]

    return types.SimpleNamespace(ref_logits=ref_logits,
                                 init_params=lambda spec, seed: None)


def ctx_of(tmp_path):
    written = {}
    return types.SimpleNamespace(
        family=fake_family(), spec=types.SimpleNamespace(dtype="bfloat16"),
        seed=3, log=lambda msg: None, written=written,
        write_json=lambda name, obj: written.update({name: json.loads(
            json.dumps(obj))}))


def sample():
    rng = np.random.default_rng(7)
    return [{"prompt": rng.integers(0, V, size=n_p, dtype=np.int32),
             "tokens": rng.integers(0, V, size=n_t).tolist()}
            for n_p, n_t in ((5, 9), (12, 4), (3, 20))]


def by_hand(sample, table, served_of):
    gaps = []
    for s in sample:
        seq = np.concatenate([s["prompt"], s["tokens"]])
        for i, tok in enumerate(s["tokens"]):
            row = table[seq[len(s["prompt"]) - 1 + i]]  # logits before token i
            gaps.append(row.max() - row[served_of(seq, len(s["prompt"]) - 1 + i,
                                                  tok)])
    return np.array(gaps)


def test_worst_and_mean_over_every_served_token(tmp_path):
    drv = load_module("drivers", "serve_closed_routed")
    ctx, smp = ctx_of(tmp_path), sample()
    got = drv.served_gaps(ctx, smp)
    want = by_hand(smp, TABLE, lambda seq, pos, tok: tok)
    assert want.size == 33 and (want > 0).sum() > 10
    np.testing.assert_allclose(got["served_logit_gap"], want.max(), rtol=1e-6)
    np.testing.assert_allclose(got["served_logit_gap_mean"], want.mean(),
                               rtol=1e-6)
    np.testing.assert_allclose(ctx.written["served_gaps.json"], np.sort(want),
                               rtol=1e-6)


def test_control_compares_the_lower_precisions_own_tokens(tmp_path):
    drv = load_module("drivers", "serve_closed_routed")
    ctx, smp = ctx_of(tmp_path), sample()
    got = drv.served_gaps(ctx, smp, control="fp8")
    want = by_hand(smp, TABLE, lambda seq, pos, tok: LOW[seq[pos]].argmax())
    np.testing.assert_allclose(got["served_logit_gap_mean"], want.mean(),
                               rtol=1e-6)
    assert "served_gaps_fp8.json" in ctx.written


def test_nothing_served_compares_as_not_correct(tmp_path):
    drv = load_module("drivers", "serve_closed_routed")
    got = drv.served_gaps(ctx_of(tmp_path), [])
    assert got == {"served_logit_gap": None, "served_logit_gap_mean": None}


def test_attempted_is_the_requests_the_window_served():
    drv = load_module("drivers", "serve_closed_routed")
    rec = lambda stamps, failed=False: {"stamps": stamps, "failed": failed}
    records = [rec([1.0, 5.0, 6.0]),            # admitted in the ramp, served
               rec([1.0, 2.0]),                 # done before the window opened
               rec([12.0]),                     # first token after the close
               rec([4.0], failed=True),         # failed: attempted and failed
               rec([], failed=True),
               rec([9.99])]
    assert drv.served_in_window(records, 4.0, 10.0) == {"attempted": 4,
                                                        "failed": 2}
    assert drv.served_in_window([], 4.0, 10.0) == {"attempted": 0, "failed": 0}


def test_a_window_in_which_no_request_falls_due_still_attempts(tmp_path):
    """The cell's own shape at the rehearsal's size: one request a client,
    every one admitted in the ramp and longer than ramp + window. None falls
    due inside the window (``serving.window_metrics`` counts 0 there) and none
    finishes; the line still counts the four requests the window served."""
    import argparse

    import run as bench_run

    def patch(ctx):
        ctx.traffic.update(
            requests_per_client=1,
            engine=dict(ctx.traffic["engine"], max_context=8192),
            output={"dist": "uniform", "min": 6000, "max": 8000})

    args = argparse.Namespace(
        workload="commandaplus_serve_agent_sat", seed=2**31 + 77, seconds=1.0,
        trace=0, rehearse=True, control=False, set=[], out=str(tmp_path))
    line = bench_run.run_cell(args, patch=patch)
    window = json.load(open(
        tmp_path / "commandaplus_serve_agent_sat" / f"seed{args.seed}_trace0"
        / "window.json"))
    steps = json.load(open(
        tmp_path / "commandaplus_serve_agent_sat" / f"seed{args.seed}_trace0"
        / "steps.json"))
    assert len(steps) * 4 == window["tokens"]   # every step a row a request
    assert window["attempted"] == 0 and window["finished"] == 0
    assert window["requests"] == 4
    assert line["attempted"] == 4 and line["failed"] == 0
    assert line["correct"] is True, line["compared"]
