"""Per-kernel microbenchmark table: each Pallas kernel vs its XLA lowering.

VERDICT r1 item 5: "a committed per-kernel table showing each Pallas kernel
beats its XLA lowering (else the kernel shouldn't claim)". Run on a real TPU:

    python -m thunder_tpu.benchmarks.kernel_table          # prints markdown
    python -m thunder_tpu.benchmarks.kernel_table --json   # JSON lines

Workloads mirror the claim surface: SDPA fwd and fwd+bwd (flash streaming
kernels vs XLA softmax-matmul), fused cross-entropy rows, fused RMSNorm.
Timing is interleaved A/B rounds, each fenced with ``block_until_ready``.
"""

from __future__ import annotations

import json
import math
import sys
import time

import numpy as np


def _sync(out):
    import jax

    return jax.block_until_ready(out)


def _time_pair(fa, fb, args, rounds=8, iters=20):
    """Interleaved A/B timing: alternating the two sides each round puts
    both under the same machine conditions; min-of-rounds per side."""
    ta, tb = [], [float("inf")]
    _sync(fa(*args))
    if fb is not None:
        _sync(fb(*args))
        tb = []
    for _r in range(rounds):
        t0 = time.perf_counter()
        for _i in range(iters):
            out = fa(*args)
        _sync(out)
        ta.append((time.perf_counter() - t0) / iters)
        if fb is not None:
            t0 = time.perf_counter()
            for _i in range(iters):
                out = fb(*args)
            _sync(out)
            tb.append((time.perf_counter() - t0) / iters)
    return min(ta), min(tb)


def run_table():
    import jax
    import jax.numpy as jnp

    from thunder_tpu.executors.pallasex import (
        pallas_ce_fwd, pallas_rms_norm, pallas_sdpa_bwd, pallas_sdpa_fwd,
    )

    rows = []

    def xla_sdpa(q, k, v):
        hd = q.shape[-1]
        T = q.shape[-2]
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) / math.sqrt(hd)
        mask = jnp.tril(jnp.ones((T, T), bool))
        s = jnp.where(mask, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        return jnp.einsum("bhqk,bhkd->bhqd", p, v)

    # -- SDPA forward --------------------------------------------------------
    for (B, H, T, hd) in [(8, 32, 2048, 128), (1, 8, 8192, 128)]:
        mk = lambda s: jax.random.normal(jax.random.PRNGKey(s), (B, H, T, hd), jnp.bfloat16)
        q, k, v = mk(0), mk(1), mk(2)
        fp = jax.jit(lambda q, k, v: pallas_sdpa_fwd(q, k, v, True)[0])
        fx = jax.jit(xla_sdpa)
        try:
            tp, tx = _time_pair(fp, fx, (q, k, v))
        except Exception:
            tp, tx = _time_pair(fp, None, (q, k, v))
        rows.append({"kernel": "sdpa_fwd", "shape": f"({B},{H},{T},{hd}) bf16 causal",
                     "pallas_ms": round(tp * 1e3, 2),
                     "xla_ms": round(tx * 1e3, 2) if tx != float("inf") else None,
                     "speedup": round(tx / tp, 2) if tx != float("inf") else None})

    # -- SDPA fwd+bwd --------------------------------------------------------
    for (B, H, T, hd) in [(8, 32, 2048, 128)]:
        mk = lambda s: jax.random.normal(jax.random.PRNGKey(s), (B, H, T, hd), jnp.bfloat16)
        q, k, v, g = mk(0), mk(1), mk(2), mk(3)
        fp = jax.jit(lambda q, k, v, g: pallas_sdpa_bwd(
            g, q, k, v, *pallas_sdpa_fwd(q, k, v, True), True))

        def xla_fwd_bwd(q, k, v, g):
            def loss(q, k, v):
                return (xla_sdpa(q, k, v).astype(jnp.float32) * g.astype(jnp.float32)).sum()

            return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

        fx = jax.jit(xla_fwd_bwd)
        try:
            tp, tx = _time_pair(fp, fx, (q, k, v, g))
        except Exception:
            tp, tx = _time_pair(fp, None, (q, k, v, g))
        rows.append({"kernel": "sdpa_fwd+bwd", "shape": f"({B},{H},{T},{hd}) bf16 causal",
                     "pallas_ms": round(tp * 1e3, 2),
                     "xla_ms": round(tx * 1e3, 2) if tx != float("inf") else None,
                     "speedup": round(tx / tp, 2) if tx != float("inf") else None})

    # -- fused cross-entropy -------------------------------------------------
    for (N, V) in [(16384, 32000)]:
        logits = jax.random.normal(jax.random.PRNGKey(0), (N, V), jnp.bfloat16)
        tgt = jax.random.randint(jax.random.PRNGKey(1), (N,), 0, V, jnp.int32)
        fp = jax.jit(lambda l, t: pallas_ce_fwd(l, t)[0])

        def xla_ce(l, t):
            lf = l.astype(jnp.float32)
            lse = jax.nn.logsumexp(lf, axis=-1)
            picked = jnp.take_along_axis(lf, t[:, None], 1)[:, 0]
            return lse - picked

        fx = jax.jit(xla_ce)
        tp, tx = _time_pair(fp, fx, (logits, tgt))
        rows.append({"kernel": "ce_fwd", "shape": f"({N},{V}) bf16",
                     "pallas_ms": round(tp * 1e3, 2), "xla_ms": round(tx * 1e3, 2),
                     "speedup": round(tx / tp, 2)})

    # -- fused rms_norm ------------------------------------------------------
    for (N, D) in [(16384, 4096)]:
        x = jax.random.normal(jax.random.PRNGKey(0), (N, D), jnp.bfloat16)
        w = jax.random.normal(jax.random.PRNGKey(1), (D,), jnp.bfloat16)
        fp = jax.jit(lambda x, w: pallas_rms_norm(x, w))

        def xla_rms(x, w):
            xf = x.astype(jnp.float32)
            ms = jnp.mean(xf * xf, -1, keepdims=True)
            return (xf * jax.lax.rsqrt(ms + 1e-5)).astype(x.dtype) * w

        fx = jax.jit(xla_rms)
        tp, tx = _time_pair(fp, fx, (x, w))
        rows.append({"kernel": "rms_norm", "shape": f"({N},{D}) bf16",
                     "pallas_ms": round(tp * 1e3, 2), "xla_ms": round(tx * 1e3, 2),
                     "speedup": round(tx / tp, 2)})

    return rows


def main():
    import jax

    rows = run_table()
    if "--json" in sys.argv:
        for r in rows:
            print(json.dumps(r))
        return
    print(f"# Pallas kernels vs XLA lowering ({jax.devices()[0].device_kind})\n")
    print("| kernel | shape | pallas ms | xla ms | speedup |")
    print("|---|---|---|---|---|")
    for r in rows:
        print(f"| {r['kernel']} | {r['shape']} | {r['pallas_ms']} | "
              f"{r['xla_ms']} | {r['speedup']}x |")


if __name__ == "__main__":
    main()
