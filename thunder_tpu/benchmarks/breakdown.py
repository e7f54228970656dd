"""Per-component timing attribution for the bench step (verdict r3 #2).

The reference harness times per-region kernels inside a step
(``thunder/benchmarks/__init__.py:241-460``, pre/post-region hooks).
Attribution here is by **program knockout** (no profiler trace needed): time
nested sub-programs of the train step —

    fwd                  (loss only)
    fwd+bwd              (value_and_grad, no optimizer)
    full                 (fwd+bwd+AdamW)
    attention fwd+bwd    (isolated at the bench shape, x n_layers)
    lm_head + CE fwd+bwd (isolated at the bench shape)

— and report the differences: bwd = (fwd+bwd) - fwd, optimizer = full -
(fwd+bwd), "everything else" (linears/norms/rope/embed) = (fwd+bwd) -
attention - CE. Differences of medians carry the noise of both terms;
they answer "which component eats the gap to peak", which is the question
the round needed answered (not ns-exact kernel times).

Run: BENCH_BREAKDOWN=1 python bench.py   (writes BENCH_BREAKDOWN.json)
"""

from __future__ import annotations

import json
import sys
import time


def _force(x):
    import jax.numpy as jnp
    import jax

    leaves = [l for l in jax.tree_util.tree_leaves(x) if hasattr(l, "shape")]
    return float(jnp.sum(leaves[0].astype(jnp.float32))) if leaves else None


def time_fn(fn, *args, steps: int = 5, trials: int = 3) -> float:
    """Best-of-trials mean seconds per call (compile excluded)."""
    out = fn(*args)
    _force(out)
    best = float("inf")
    for _ in range(trials):
        t0 = time.perf_counter()
        for _ in range(steps):
            out = fn(*args)
        _force(out)
        best = min(best, (time.perf_counter() - t0) / steps)
    return best


def run_breakdown(*, cfg, n_layers, params, tokens, targets,
                  model_loss, t_full: float, steps: int, opt=None) -> dict:
    import jax
    import numpy as np

    import thunder_tpu as tt
    from thunder_tpu import ops
    from thunder_tpu.ops import nn as ops_nn

    B, T = tokens.shape
    # inputs for the ISOLATED sub-programs live on device up front: at the
    # bench shape q/k/v and the (B·T, dim) hidden are ~256 MB each — feeding
    # them as host numpy would re-ship them host-to-device every call and
    # the transfer, not the kernel, would be measured
    # (r4's toy-scale run hid this; the r5 chip run surfaced 36 s/call)
    params = jax.device_put(params)

    # fwd only
    jfwd = tt.jit(lambda p: model_loss(p, tokens, targets, cfg))
    t_fwd = time_fn(jfwd, params, steps=steps)

    # fwd + bwd (no optimizer)
    jfb = tt.jit(lambda p: tt.value_and_grad(
        lambda q: model_loss(q, tokens, targets, cfg))(p))
    t_fb = time_fn(jfb, params, steps=steps)

    # attention alone at the bench shape (per layer), fwd+bwd
    hd = cfg.head_dim
    rng = np.random.RandomState(0)
    q = jax.device_put((rng.randn(B, cfg.n_heads, T, hd).astype(np.float32) * 0.1)
                       .astype(cfg.dtype.jax))
    k = q  # read-only inputs (no donation): one device buffer serves all three
    v = q

    def att_loss(qkv):
        qq, kk, vv = qkv
        return ops.sum(ops_nn.scaled_dot_product_attention(qq, kk, vv, is_causal=True))

    jatt = tt.jit(lambda qkv: tt.value_and_grad(att_loss)(qkv))
    t_att1 = time_fn(jatt, (q, k, v), steps=steps)

    # lm_head matmul + CE at the bench shape, fwd+bwd
    h = jax.device_put((rng.randn(B * T, cfg.dim).astype(np.float32) * 0.1)
                       .astype(cfg.dtype.jax))
    w = params["lm_head"]
    tg = jax.device_put(targets.reshape(-1))

    def ce_loss(args):
        hh, ww = args
        out = ops_nn.fused_linear_cross_entropy(hh, ww, tg)
        return out[0] if isinstance(out, tuple) else out

    jce = tt.jit(lambda a: tt.value_and_grad(ce_loss)(a))
    t_ce = time_fn(jce, (h, w), steps=steps)

    # MLP sub-block fwd+bwd at the bench shape (per layer, x n_layers),
    # compiled with the block planner FORCED on so the chain runs as the
    # claimed nn.mlp_subblock megakernel — the isolated number the Fusion 3.0
    # planner is accountable to against the linears_norms_rest residual
    # (PR 9). block_fusion=True (not the cost-model default) because this
    # row measures the planned kernel, not the planning decision.
    layer0 = params["layers"][0]
    hres = jax.device_put((rng.randn(B, T, cfg.dim).astype(np.float32) * 0.1)
                          .astype(cfg.dtype.jax))
    xattn = jax.device_put((rng.randn(B, T, cfg.dim).astype(np.float32) * 0.1)
                           .astype(cfg.dtype.jax))
    sub_w = jax.device_put({k: layer0[k] for k in
                            ("mlp_norm", "w_gate", "w_up", "w_down")})

    def sub_loss(args):
        hh, xx, w = args
        h2 = ops.add(hh, xx)
        n = ops.rms_norm(h2, w["mlp_norm"], eps=cfg.norm_eps)
        gate = ops.silu(ops.linear(n, w["w_gate"]))
        up = ops.linear(n, w["w_up"])
        out = ops.add(h2, ops.linear(ops.mul(gate, up), w["w_down"]))
        return ops.sum(out)

    jsub = tt.jit(lambda a: tt.value_and_grad(sub_loss)(a), block_fusion=True)
    t_sub = time_fn(jsub, (hres, xattn, sub_w), steps=steps) * n_layers

    t_att = t_att1 * n_layers
    t_bwd = max(0.0, t_fb - t_fwd)
    t_opt = max(0.0, t_full - t_fb)
    t_rest = max(0.0, t_fb - t_att - t_ce)

    rows = {
        "full_step_ms": t_full * 1e3,
        "forward_ms": t_fwd * 1e3,
        "backward_ms(delta)": t_bwd * 1e3,
        "optimizer_ms(delta)": t_opt * 1e3,
        "attention_fwdbwd_ms(isolated x layers)": t_att * 1e3,
        "lmhead_ce_fwdbwd_ms(isolated)": t_ce * 1e3,
        "linears_norms_rest_ms(residual)": t_rest * 1e3,
        # planned MLP sub-block megakernel, fwd+bwd, x n_layers — compare
        # against linears_norms_rest_ms: the planner's target chain
        "subblock_fused_ms(isolated)": t_sub * 1e3,
    }

    # isolated optimizer update fed by REAL gradients: the knockout delta
    # above includes XLA's cross-phase scheduling interplay — this is the
    # kernel-only number the fused multi-tensor optimizer (PR 5) is
    # measured against. No donation: time_fn re-feeds the same buffers each
    # trial, and donated inputs are consumed on first use.
    if opt is not None:
        _, grads = jfb(params)
        opt_state = jax.device_put(opt.init(params))
        jupd = tt.jit(lambda p, g, s: opt.update(p, g, s))
        rows["adamw_update_ms(isolated)"] = time_fn(
            jupd, params, grads, opt_state, steps=steps) * 1e3
    print("--- breakdown (knockout attribution, ±10% shared-chip noise) ---",
          file=sys.stderr)
    for k_, v_ in rows.items():
        share = v_ / (t_full * 1e3) * 100.0
        print(f"{k_:45s} {v_:8.1f} ms  {share:5.1f}% of step", file=sys.stderr)
    return rows


def save(rows: dict, meta: dict, path: str = "BENCH_BREAKDOWN.json") -> None:
    with open(path, "w") as f:
        json.dump({"meta": meta, "rows": rows}, f, indent=1)
