"""A run with the timed path broken underneath comes out not correct. The
look for a chip is skipped (``--rehearse``); everything else is the run's own
code, in this process, with the PROGRAM patched: a step that returns its
state unchanged, half of the batch left out (the mean taken over the rest),
a served token altered where it is produced."""

import argparse

import pytest

import run as bench_run


def run_cell(workload, tmp_path, seed=31):
    args = argparse.Namespace(workload=workload, seed=seed, seconds=1.0,
                              trace=0, rehearse=True, control=False, set=[],
                              out=str(tmp_path))
    return bench_run.run_cell(args)


def failing(line):
    return [k for k, c in line["compared"].items()
            if c["value"] is None or c["value"] > c["limit"]]


def test_sound_train_run_is_correct(tmp_path):
    line = run_cell("mistral7b_train", tmp_path)
    assert line["correct"] is True and not failing(line)


def test_state_returned_unchanged(tmp_path, monkeypatch):
    from thunder_tpu.optim import AdamW

    monkeypatch.setattr(AdamW, "update",
                        lambda self, params, grads, state: (params, state))
    line = run_cell("mistral7b_train", tmp_path)
    assert line["correct"] is False
    # no gradient reached the optimizer's state, no parameter moved: both
    # norms read 0 against the reference's, a gap of 1
    assert line["compared"]["grad1_gap"]["value"] == pytest.approx(1.0)
    assert line["compared"]["dparam3_gap"]["value"] == pytest.approx(1.0)


def test_half_of_the_batch_left_out(tmp_path, monkeypatch):
    from thunder_tpu.models import llama

    whole = llama.fused_loss_fn

    def half(params, tokens, targets, cfg, **kw):
        n = tokens.shape[0] // 2
        return whole(params, tokens[:n], targets[:n], cfg, **kw)

    monkeypatch.setattr(llama, "fused_loss_fn", half)
    line = run_cell("mistral7b_train", tmp_path)
    assert line["correct"] is False and failing(line)


@pytest.mark.parametrize("workload", ["mistral7b_serve_decode_sat",
                                      "mistral7b_serve_chat"])
def test_served_token_altered(tmp_path, monkeypatch, workload):
    from thunder_tpu.serving.scheduler import ServingEngine

    on_token, count = ServingEngine._on_token, [0]

    def altered(self, req, tok):
        count[0] += 1
        if count[0] % 2 == 0:
            tok = (tok + 1) % self.cfg.vocab_size
        return on_token(self, req, tok)

    monkeypatch.setattr(ServingEngine, "_on_token", altered)
    line = run_cell(workload, tmp_path)
    assert line["correct"] is False
    assert failing(line) == ["served_logit_gap"]


def test_exchange_between_chips_left_out(tmp_path, ep4_checkout):
    """Four chips: every chip keeps the slots it would have sent and gets
    none (``jax.lax.all_to_all`` patched under the program). Four virtual
    devices need a process of their own."""
    import subprocess
    import sys

    from conftest import BENCH, ROOT

    code = f"""
import argparse, json, sys
sys.path.insert(0, {BENCH!r}); sys.path.insert(0, {ROOT!r})
import run as bench_run

def patch(ctx):
    import jax, jax.numpy as jnp
    def kept(x, axis_name, split_axis, concat_axis, **kw):
        n = ctx.chips
        chunk = x.shape[split_axis] // n
        own = jax.lax.dynamic_slice_in_dim(
            x, jax.lax.axis_index(axis_name) * chunk, chunk, split_axis)
        return jnp.concatenate([own] * n, axis=concat_axis)
    jax.lax.all_to_all = kept

args = argparse.Namespace(workload="mixtral8x7b_train_ep4", seed=31, seconds=1.0,
                          trace=0, rehearse=True, control=False, set=[],
                          out={str(tmp_path)!r})
root = {str(ep4_checkout)!r}
print(json.dumps(bench_run.run_cell(args, root + "/benchmark", root, patch=patch)))
"""
    import os

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", code], env=env, text=True,
                       capture_output=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    import json

    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["correct"] is False and failing(line)
