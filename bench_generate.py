"""Inference benchmark: KV-cache prefill latency + decode throughput
(verdict r3 #6 — the committed performance story for ``generate()``).

Geometry matches bench.py (Llama-2-7B width, BENCH_LAYERS layers on one
chip). Two metrics, each vs a hand-written ``jax.jit`` decode loop a
perf-aware user would write (same cache layout, donated buffers):

    prefill: one (B, Tp) forward populating the KV cache  -> latency
    decode:  N sequential (B, 1) steps reusing the cache  -> tokens/s

Prints one JSON line per metric. Env: BENCH_LAYERS, BENCH_BATCH,
BENCH_PROMPT, BENCH_DECODE, BENCH_MODEL. --smoke for a tiny CPU run.
"""

from __future__ import annotations

import json
import os
import sys
import time


def build_jax_ref(cfg, batch, max_len, n_layers):
    """Independent hand-written jax.jit KV-cache step (the baseline a
    perf-aware jax user would write: donated cache, grouped GQA, full-cache
    masked attention)."""
    import functools
    import math

    import jax
    import jax.numpy as jnp

    hd, n_rep = cfg.head_dim, cfg.n_heads // cfg.kv_heads

    def jax_rope_at(x, pos):
        B, H, T, d = x.shape
        p = (jnp.arange(T, dtype=jnp.float32) + pos)
        idx = jnp.arange(d // 2, dtype=jnp.float32)
        inv = cfg.rope_theta ** (idx * -2.0 / d)
        ang = p[:, None] * inv[None, :]
        cos, sin = jnp.cos(ang).astype(x.dtype), jnp.sin(ang).astype(x.dtype)
        x1, x2 = x[..., : d // 2], x[..., d // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    def rmsn(h, w):
        return (h / jnp.sqrt(jnp.mean((h * h).astype(jnp.float32), -1,
                                      keepdims=True) + cfg.norm_eps).astype(h.dtype)) * w

    @functools.partial(jax.jit, donate_argnums=(2,))
    def jax_step(p, toks, cache, pos):
        B, T = toks.shape
        h = p["tok_embedding"][toks]
        col = jnp.arange(max_len)
        row = jnp.arange(T) + pos
        valid = col[None, :] <= row[:, None]
        new_cache = []
        for layer, c in zip(p["layers"], cache):
            x = rmsn(h, layer["attn_norm"])
            q = (x @ layer["wq"].T).reshape(B, T, cfg.n_heads, hd).transpose(0, 2, 1, 3)
            k = (x @ layer["wk"].T).reshape(B, T, cfg.kv_heads, hd).transpose(0, 2, 1, 3)
            v = (x @ layer["wv"].T).reshape(B, T, cfg.kv_heads, hd).transpose(0, 2, 1, 3)
            q, k = jax_rope_at(q, pos), jax_rope_at(k, pos)
            ck = jax.lax.dynamic_update_slice(c["k"], k, (0, 0, pos, 0))
            cv = jax.lax.dynamic_update_slice(c["v"], v, (0, 0, pos, 0))
            new_cache.append({"k": ck, "v": cv})
            qg = q.reshape(B, cfg.kv_heads, n_rep * T, hd)
            scores = (qg.astype(jnp.float32) @ ck.astype(jnp.float32).swapaxes(-1, -2)) / math.sqrt(hd)
            scores = scores.reshape(B, cfg.n_heads, T, max_len)
            scores = jnp.where(valid, scores, -jnp.inf)
            w = jax.nn.softmax(scores, -1).astype(h.dtype)
            attn = (w.reshape(B, cfg.kv_heads, n_rep * T, max_len) @ cv)
            attn = attn.reshape(B, cfg.n_heads, T, hd).transpose(0, 2, 1, 3).reshape(B, T, cfg.dim)
            h = h + attn @ layer["wo"].T
            x = rmsn(h, layer["mlp_norm"])
            h = h + (jax.nn.silu(x @ layer["w_gate"].T) * (x @ layer["w_up"].T)) @ layer["w_down"].T
        h = rmsn(h, p["norm_f"])
        logits = h[:, -1:] @ p["lm_head"].T
        return logits[:, 0], new_cache

    def jax_init_cache():
        return [{"k": jnp.zeros((batch, cfg.kv_heads, max_len, hd), cfg.dtype.jax),
                 "v": jnp.zeros((batch, cfg.kv_heads, max_len, hd), cfg.dtype.jax)}
                for _ in range(n_layers)]

    return jax_step, jax_init_cache


def main():
    import jax

    if "--smoke" in sys.argv:
        os.environ.setdefault("BENCH_LAYERS", "1")
        os.environ.setdefault("BENCH_BATCH", "2")
        os.environ.setdefault("BENCH_PROMPT", "32")
        os.environ.setdefault("BENCH_DECODE", "8")
    # --smoke runs on whatever platform JAX resolves: a CPU rehearsal is
    # asked for from outside (JAX_PLATFORMS=cpu), never chosen here
    import jax.numpy as jnp
    import numpy as np

    import thunder_tpu as tt
    from thunder_tpu.models import llama

    print(f"device: {jax.devices()[0].platform} {jax.devices()[0].device_kind} "
          f"x{len(jax.devices())}; compile cache: "
          f"{tt.enable_compilation_cache()}", file=sys.stderr)

    n_layers = int(os.environ.get("BENCH_LAYERS", "2"))
    batch = int(os.environ.get("BENCH_BATCH", "8"))
    t_prompt = int(os.environ.get("BENCH_PROMPT", "512"))
    n_decode = int(os.environ.get("BENCH_DECODE", "128"))
    model = os.environ.get("BENCH_MODEL", "llama2-7b-bench")
    cfg = llama.CONFIGS[model]
    max_len = t_prompt + n_decode

    rng = np.random.RandomState(0)
    prompt = jax.device_put(rng.randint(0, cfg.vocab_size,
                                        (batch, t_prompt)).astype(np.int32))
    # params MUST live on device up front: feeding host numpy would re-ship
    # ~1.3 GB host-to-device on every step and the transfer, not the model,
    # would be measured (same lesson as benchmarks/breakdown.py, r5)
    params = jax.device_put(llama.init_params(cfg, seed=0, scale_layers=n_layers))

    sync = jax.block_until_ready

    # ---- thunder_tpu: the public generate() machinery ----------------------
    from thunder_tpu.models.llama import _get_step_fns, init_kv_cache

    step_fn, _ = _get_step_fns(cfg, n_layers)

    def interleaved_decode(impls: dict, *, block: int | None = None,
                           rounds: int | None = None):
        """{name: (prefill_fn, decode_fn, fresh_cache_fn)} -> {name: best s/token}.

        Alternating short blocks round-robin puts every impl under the same
        machine conditions (host load, clocks) instead of attributing a
        drift between sequential per-impl loops to the impl."""
        if block is None:
            block = 4 if "--smoke" in sys.argv else 32
        if rounds is None:
            rounds = 2 if "--smoke" in sys.argv else 6
        state = {}
        for name, (prefill_fn, step, mk_cache) in impls.items():
            cache = mk_cache()
            last, cache = prefill_fn(params, prompt, cache, jnp.int32(0))
            tok = jnp.argmax(last, -1).astype(jnp.int32)[:, None]
            state[name] = [step, tok, cache, 0, float("inf")]
        for _ in range(rounds):
            for name in impls:
                step, tok, cache, off, best = state[name]
                t0 = time.perf_counter()
                for i in range(block):
                    last, cache = step(params, tok, cache,
                                       jnp.int32(t_prompt + (off + i) % n_decode))
                    tok = jnp.argmax(last, -1).astype(jnp.int32)[:, None]
                sync(last)
                state[name] = [step, tok, cache, (off + block) % n_decode,
                               min(best, (time.perf_counter() - t0) / block)]
        return {name: st[4] for name, st in state.items()}

    # ---- hand-written jax.jit decode loop (defined below, built first so
    # every impl can be measured in the same interleaved rounds) -----------
    jax_step, jax_init_cache = build_jax_ref(cfg, batch, max_len, n_layers)

    # warmup/compile both shapes, all impls
    cache = init_kv_cache(cfg, batch, max_len, n_layers=n_layers)
    last, cache = step_fn(params, prompt, cache, jnp.int32(0))
    _ = step_fn(params, jnp.zeros((batch, 1), jnp.int32), cache, jnp.int32(t_prompt))
    jcache = jax_init_cache()
    last, jcache = jax_step(params, prompt, jcache, jnp.int32(0))
    _ = jax_step(params, jnp.zeros((batch, 1), jnp.int32), jcache, jnp.int32(t_prompt))
    bound = step_fn.bind(params, jnp.zeros((batch, 1), jnp.int32),
                         init_kv_cache(cfg, batch, max_len, n_layers=n_layers),
                         jnp.int32(t_prompt))

    # prefill: alternate ours/ref so machine conditions hit both equally
    pre_ours, pre_ref = float("inf"), float("inf")
    for _ in range(2 if "--smoke" in sys.argv else 4):
        cache = init_kv_cache(cfg, batch, max_len, n_layers=n_layers)
        t0 = time.perf_counter()
        last, cache = step_fn(params, prompt, cache, jnp.int32(0))
        sync(last)
        pre_ours = min(pre_ours, time.perf_counter() - t0)
        jcache = jax_init_cache()
        t0 = time.perf_counter()
        last, jcache = jax_step(params, prompt, jcache, jnp.int32(0))
        sync(last)
        pre_ref = min(pre_ref, time.perf_counter() - t0)
    print(f"prefill: thunder {pre_ours*1e3:.1f} ms vs jax.jit {pre_ref*1e3:.1f} ms",
          file=sys.stderr)

    # decode: round-robin 32-step blocks across all three impls
    dec = interleaved_decode({
        "ours": (step_fn, step_fn,
                 lambda: init_kv_cache(cfg, batch, max_len, n_layers=n_layers)),
        "bound": (step_fn, bound,  # bound is pinned to the (B,1) decode shape
                  lambda: init_kv_cache(cfg, batch, max_len, n_layers=n_layers)),
        "jax": (jax_step, jax_step, jax_init_cache),
    })
    dec_ours, dec_bound, dec_ref = dec["ours"], dec["bound"], dec["jax"]
    print(f"decode tok/s: thunder {batch/dec_ours:.0f}, bound {batch/dec_bound:.0f}, "
          f"jax.jit {batch/dec_ref:.0f}", file=sys.stderr)

    # fused loop: the whole decode as ONE lax.scan program (one dispatch
    # per generation — the TPU-native serving shape; generate_fused docstring)
    llama.generate_fused(params, cfg, prompt, n_decode + 1,
                         max_len=max_len + 1, n_layers=n_layers)  # compile
    best_f = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        toks = llama.generate_fused(params, cfg, prompt, n_decode + 1,
                                    max_len=max_len + 1, n_layers=n_layers)
        np.asarray(toks)
        best_f = min(best_f, time.perf_counter() - t0)
    dec_fused = max(best_f - pre_ours, 1e-9) / n_decode
    print(f"thunder_tpu fused-loop: decode {batch/dec_fused:.0f} tok/s "
          f"(whole generation = one dispatch)", file=sys.stderr)

    # metrics_schema matches bench.py's current version: every bench in this
    # repo emits JSON lines of {metrics_schema, metric, value, unit,
    # vs_baseline, ...extras} so CI parses all of them with one reader
    # (previously these lines were unversioned). --smoke emits the same
    # schema — only the geometry in the metric name differs.
    from bench import METRICS_SCHEMA

    print(json.dumps({
        "metrics_schema": METRICS_SCHEMA,
        "metric": f"{model.replace('-bench','')}-geometry({n_layers}L,b{batch}) "
                  f"prefill latency Tp={t_prompt}",
        "value": round(pre_ours * 1e3, 2), "unit": "ms",
        "vs_baseline": round(pre_ref / pre_ours, 4)}))
    print(json.dumps({
        "metrics_schema": METRICS_SCHEMA,
        "metric": f"{model.replace('-bench','')}-geometry({n_layers}L,b{batch}) "
                  f"decode tokens/s",
        "value": round(batch / dec_ours, 1), "unit": "tokens/s",
        "vs_baseline": round(dec_ref / dec_ours, 4)}))
    print(json.dumps({
        "metrics_schema": METRICS_SCHEMA,
        "metric": f"{model.replace('-bench','')}-geometry({n_layers}L,b{batch}) "
                  f"decode tokens/s (bound fast path)",
        "value": round(batch / dec_bound, 1), "unit": "tokens/s",
        "vs_baseline": round(dec_ref / dec_bound, 4)}))
    print(json.dumps({
        "metrics_schema": METRICS_SCHEMA,
        "metric": f"{model.replace('-bench','')}-geometry({n_layers}L,b{batch}) "
                  f"decode tokens/s (fused loop)",
        "value": round(batch / dec_fused, 1), "unit": "tokens/s",
        "vs_baseline": round(dec_ref / dec_fused, 4)}))


if __name__ == "__main__":
    main()
    # what was timed is the program the planner chose, not a degraded one
    from thunder_tpu.runtime import quarantine

    quarantine.assert_clean()
