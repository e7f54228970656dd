#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, phases in sequence, through the entry points a user calls:

1. **device gate** — ``jax.devices()[0].platform == "tpu"`` or exit non-zero
   before any work (a CPU run is a rehearsal and proves nothing here);
2. **train** — ``tt.jit(train_step, donate_argnums=(0, 1))`` with default
   options at Llama-2-7B widths (dim 4096, 32 heads x 128, MLP 11008, vocab
   32,000, bf16), depth cut to 2 layers, batch 8 x seq 2048: step-0 loss
   against a plain float32 ``jax.numpy`` reference, loss falling on the
   repeated batch;
3. **serve** — ``ServingEngine`` on the same widths: eight requests with
   prompts from tens to ~1,500 tokens through ``submit()`` / ``drain()``,
   then the decode program's LOGITS for one request against the float32
   reference's full forward at the same positions;
4. with >= 4 chips, the same train step under ``fsdp(..., fsdp=4)`` and the
   same requests under ``ServingEngine(mesh=4)``, with parameters, optimizer
   state and the KV pool checked to be spread over all four devices.

Every phase prints its claim table — Pallas kernel the planner chose ->
present in the program that ran — and fails if a chosen kernel is absent,
if anything was quarantined, if ``runtime.fallbacks`` or
``compile.census_errors`` moved, or (train) if flash attention forward and
backward did not claim. No phase is wrapped in try/except: a failure is a
traceback and a non-zero exit.

Compile seconds and ms/step are printed as OBSERVATIONS of this run, named
with the device — they are not benchmark metrics. The last line of stdout is
``{"ok": true, "device": {...}}``; the full report goes to
``chiprun_out/chip_smoke.json``.

Llama geometry is used because it is the only model ``serving/runner.py``
serves and the only width with an earlier chip record; it is not a
benchmark cell (ROADMAP Reach).
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
import statistics
import sys
import time

EXIT_NO_ACCELERATOR = 2

FLASH_CLAIMS = ("pallas.sdpa_fwd", "pallas.sdpa_bwd")


@dataclasses.dataclass(frozen=True)
class Preset:
    """One size of the smoke. ``FULL`` is what ``main()`` runs on the chip;
    ``tests/test_chip_smoke.py`` drives the same phase functions on the CPU
    at a tiny preset with interpret-mode kernels."""

    model: str
    n_layers: int
    batch: int
    seq: int
    steps: int                      # timed train steps after the compile
    max_slots: int
    page_size: int
    max_context: int
    prefill_chunk: int
    prompt_lens: tuple              # chosen to land on few ladder rungs
    new_tokens: int
    parity_prompt: int
    parity_tokens: int
    # |step-0 loss - float32 reference| bound. bf16 rounds every matmul
    # operand and stored activation to 8 mantissa bits, moving each token's
    # NLL by ~1e-2 with either sign; the mean over batch x seq = 16,384
    # tokens averages that to ~1e-4. Seen on the chip: 2e-5 (one chip and
    # fsdp=4; PERF.md Findings) — 2e-3 leaves 100x for another seed or
    # compiler, and is still 250x tighter than the ln(vocab) sanity check.
    # float32 (the CPU preset) differs only by summation order.
    loss_atol: float
    # max |decode logit - reference logit| over the parity rows x vocab, and
    # the RMS of the same difference. Logits here are ~N(0, 1); a bf16
    # residual stream carries ~0.4% error per element into a 4096-wide dot
    # product, ~1e-2 RMS on the logit, and the max over 256,000 draws sits
    # near 5 sigma. Seen on the chip: max 0.048 / rms 0.0105 on one chip,
    # 0.061 / 0.0119 under mesh=4 (another reduction order) — the bounds
    # are ~2.5x the worse of the two.
    logits_atol: float
    logits_rms: float
    require_flash: bool             # train program must carry flash fwd+bwd


FULL = Preset(
    model="llama2-7b-bench", n_layers=2, batch=8, seq=2048, steps=4,
    max_slots=8, page_size=16, max_context=2048, prefill_chunk=512,
    # tails land on ladder rungs {64, 256, 512}: three prefill programs
    prompt_lens=(40, 60, 200, 250, 562, 1224, 1480, 1500),
    new_tokens=24, parity_prompt=60, parity_tokens=8,
    loss_atol=2e-3, logits_atol=0.15, logits_rms=0.03, require_flash=True)


# ---------------------------------------------------------------------------
# device gate + run-wide meters
# ---------------------------------------------------------------------------

def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def versions() -> dict:
    import importlib.metadata as md

    import jax
    import jaxlib

    return {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "libtpu": md.version("libtpu")}


class CompileMeter:
    """Sums JAX's own compile events (``jax.monitoring``): seconds inside
    the backend compile — which, on a persistent-cache hit, is only the
    retrieval — and the cache's hit/miss counts. ``take()`` returns and
    clears the window, so each program reports its own share."""

    def __init__(self):
        import jax.monitoring as mon

        self._w = self._zero()
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    @staticmethod
    def _zero():
        return {"backend_compile_s": 0.0, "programs": 0, "cache_hits": 0,
                "cache_misses": 0}

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self._w["backend_compile_s"] += duration
            self._w["programs"] += 1

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self._w["cache_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self._w["cache_misses"] += 1

    def take(self) -> dict:
        w, self._w = self._w, self._zero()
        w["backend_compile_s"] = round(w["backend_compile_s"], 2)
        return w


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


# ---------------------------------------------------------------------------
# the float32 reference: plain jax.numpy, nothing of thunder_tpu on this side
# ---------------------------------------------------------------------------

def reference_forward(params, tokens, cfg):
    """tokens (B, T) -> logits (B, T, V) in float32 (call under
    ``jax.default_matmul_precision("highest")``)."""
    import jax
    import jax.numpy as jnp

    f32 = lambda a: a.astype(jnp.float32)
    B, T = tokens.shape
    hd, H, KV = cfg.head_dim, cfg.n_heads, cfg.kv_heads
    pos = jnp.arange(T, dtype=jnp.float32)
    inv = cfg.rope_theta ** (jnp.arange(hd // 2, dtype=jnp.float32) * -2.0 / hd)
    ang = pos[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)

    def rope(x):
        x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    def norm(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                 + cfg.norm_eps) * f32(w)

    def heads(x, n):
        return x.reshape(B, T, n, hd).transpose(0, 2, 1, 3)

    mask = jnp.tril(jnp.ones((T, T), bool))
    h = f32(params["tok_embedding"])[tokens]
    for layer in params["layers"]:
        x = norm(h, layer["attn_norm"])
        q = rope(heads(x @ f32(layer["wq"]).T, H))
        k = rope(heads(x @ f32(layer["wk"]).T, KV))
        v = heads(x @ f32(layer["wv"]).T, KV)
        if H != KV:
            k = jnp.repeat(k, H // KV, axis=1)
            v = jnp.repeat(v, H // KV, axis=1)
        s = (q @ k.swapaxes(-1, -2)) / math.sqrt(hd)
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1)
        attn = (p @ v).transpose(0, 2, 1, 3).reshape(B, T, H * hd)
        h = h + attn @ f32(layer["wo"]).T
        x = norm(h, layer["mlp_norm"])
        h = h + (jax.nn.silu(x @ f32(layer["w_gate"]).T)
                 * (x @ f32(layer["w_up"]).T)) @ f32(layer["w_down"]).T
    return norm(h, params["norm_f"]) @ f32(params["lm_head"]).T


def reference_loss(params, tokens, targets, cfg) -> float:
    """Mean next-token NLL over the whole batch, one sequence at a time —
    the slice the reference can hold (its (T, T) scores and (T, V) logits
    are float32)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def seq_nll(p, t, y):
        logp = jax.nn.log_softmax(reference_forward(p, t, cfg)[0], -1)
        return -jnp.take_along_axis(logp, y[0][:, None], 1).sum()

    total = 0.0
    with jax.default_matmul_precision("highest"):
        for b in range(tokens.shape[0]):
            total += float(seq_nll(params, tokens[b:b + 1], targets[b:b + 1]))
    return total / tokens.size


# ---------------------------------------------------------------------------
# planned == executed
# ---------------------------------------------------------------------------

def claim_table(jfn, label: str, required=()) -> dict:
    """Pallas kernels the claim pass CHOSE (decision log of the program's
    last compile) against those PRESENT in the execution trace that ran.
    Prints the table; raises if a chosen kernel is absent, a required one
    was not chosen, or the census of that program hit guarded errors."""
    import thunder_tpu as tt
    from thunder_tpu.observe import census

    stats = tt.compile_stats(jfn)
    # decision records name the CLAIMED op; the executed trace carries the
    # executor's own symbol id — join them through the executor's impl map
    claim_id = {key.split(".")[-1]: impl.symbol.id
                for key, impl in tt.get_executor("pallas").implmap.items()}
    planned: dict[str, int] = {}
    for d in stats.last_decisions:
        if (d.get("kind") == "claim" and d.get("executor") == "pallas"
                and d.get("decision") == "claimed"):
            cid = claim_id[d["op"]]
            planned[cid] = planned.get(cid, 0) + 1
    tc = census.trace_census(tt.last_execution_trace(jfn))
    executed = tc["pallas_claims"]
    rows = sorted(set(planned) | set(executed) | set(required))
    log(f"claim table [{label}]  (pallas launches in the executed trace: "
        f"{tc['pallas_launches']}, xla regions: {tc['xla_regions']})")
    log(f"  {'claim id':<34}{'planned':>8}{'executed':>9}")
    for cid in rows:
        log(f"  {cid:<34}{planned.get(cid, 0):>8}{executed.get(cid, 0):>9}")
    if not rows:
        log("  (no pallas claims)")
    absent = [c for c in planned if not executed.get(c)]
    if absent:
        raise RuntimeError(f"[{label}] planned kernels absent from the "
                           f"executed trace: {absent}")
    missing = [c for c in required if not executed.get(c)]
    if missing:
        raise RuntimeError(f"[{label}] required kernels did not claim: "
                           f"{missing} — the executor vanished?")
    if tc["errors"]:
        raise RuntimeError(f"[{label}] trace census errors: {tc['errors']}")
    hlo = stats.last_census or {}
    if hlo.get("census_errors"):
        raise RuntimeError(f"[{label}] executable census errors: "
                           f"{hlo.get('errors')}")
    return {"planned": planned, "executed": executed,
            "pallas_launches": tc["pallas_launches"],
            "xla_regions": tc["xla_regions"],
            "hlo_collectives": (hlo.get("async") or {}).get("count", 0),
            "hlo_fusions": hlo.get("hlo_fusions")}


def assert_clean(where: str) -> None:
    """Nothing quarantined, no fallback, no census error — at ``where``."""
    from thunder_tpu import observe
    from thunder_tpu.runtime import quarantine

    quarantine.assert_clean()
    errs = observe.get_registry().counters.get("compile.census_errors", 0)
    if errs:
        raise RuntimeError(f"compile.census_errors = {errs} at {where}")


def spread_over(tree, n_dev: int, what: str) -> dict:
    """Every matrix of ``tree`` must live on ``n_dev`` distinct devices, and
    at least one of them as 1/n_dev shards — state is spread, not sitting
    on device 0."""
    import jax

    big = [l for l in jax.tree_util.tree_leaves(tree)
           if hasattr(l, "addressable_shards") and l.ndim >= 2]
    if not big:
        raise RuntimeError(f"{what}: no matrices to check")
    sharded = 0
    for leaf in big:
        shards = leaf.addressable_shards
        devs = {s.device for s in shards}
        if len(devs) != n_dev:
            raise RuntimeError(f"{what}: a {leaf.shape} leaf lives on "
                               f"{len(devs)} device(s), expected {n_dev}")
        if shards[0].data.size * n_dev == leaf.size:
            sharded += 1
    if sharded == 0:
        raise RuntimeError(f"{what}: every leaf is replicated, none sharded")
    return {"leaves": len(big), "sharded_1_over_n": sharded}


def hbm_in_use() -> list | None:
    """``bytes_in_use`` per device where the backend reports it."""
    import jax

    stats = [d.memory_stats() for d in jax.devices()]
    if any(s is None for s in stats):
        return None
    return [int(s["bytes_in_use"]) for s in stats]


# ---------------------------------------------------------------------------
# train phase
# ---------------------------------------------------------------------------

def make_train_step(cfg, opt):
    import thunder_tpu as tt
    from thunder_tpu.models import llama

    def train_step(params, opt_state, tokens, targets):
        loss, grads = tt.value_and_grad(
            lambda p: llama.fused_loss_fn(p, tokens, targets, cfg))(params)
        new_params, new_state = opt.update(params, grads, opt_state)
        return loss, new_params, new_state

    return train_step


def train_batch(cfg, preset: Preset):
    import numpy as np

    rng = np.random.RandomState(0)
    tokens = rng.randint(0, cfg.vocab_size,
                         size=(preset.batch, preset.seq)).astype(np.int32)
    return tokens, np.roll(tokens, -1, axis=1).astype(np.int32)


def run_steps(jstep, params, opt_state, tokens, targets, preset, meter, dev):
    """Compile + step 0, then ``preset.steps`` more on the SAME batch, each
    fenced. Returns (report dict, final params, final optimizer state)."""
    import jax

    t0 = time.perf_counter()
    loss, params, opt_state = jstep(params, opt_state, tokens, targets)
    jax.block_until_ready((loss, params, opt_state))
    first_s = time.perf_counter() - t0
    compile_w = meter.take()
    losses, step_ms = [float(loss)], []
    for _ in range(preset.steps):
        t0 = time.perf_counter()
        loss, params, opt_state = jstep(params, opt_state, tokens, targets)
        jax.block_until_ready((loss, params, opt_state))
        step_ms.append((time.perf_counter() - t0) * 1e3)
        losses.append(float(loss))
    log(f"  first call (trace + compile + step 0): {first_s:.1f} s  "
        f"[{compile_w}]")
    log(f"  steady step on {dev['kind']} x{dev['count']}: "
        f"{statistics.median(step_ms):.1f} ms median of {preset.steps} "
        f"({', '.join(f'{m:.1f}' for m in step_ms)})")
    log(f"  loss: {' -> '.join(f'{l:.4f}' for l in losses)}")
    return ({"losses": losses, "first_call_s": first_s, "compile": compile_w,
             "step_ms": step_ms}, params, opt_state)


def check_losses(losses, ref, cfg, preset: Preset, label: str) -> None:
    ln_v = math.log(cfg.vocab_size)
    if not all(math.isfinite(l) for l in losses):
        raise RuntimeError(f"[{label}] non-finite loss: {losses}")
    if abs(losses[0] - ref) > preset.loss_atol:
        raise RuntimeError(
            f"[{label}] step-0 loss {losses[0]:.5f} vs float32 reference "
            f"{ref:.5f}: |diff| {abs(losses[0] - ref):.5f} > "
            f"{preset.loss_atol}")
    if abs(losses[0] - ln_v) > 1.0:
        raise RuntimeError(f"[{label}] step-0 loss {losses[0]:.3f} is not "
                           f"near ln(vocab) = {ln_v:.3f}")
    if not losses[-1] < losses[0]:
        raise RuntimeError(f"[{label}] loss did not fall on the repeated "
                           f"batch: {losses}")


def train_phase(preset: Preset, meter: CompileMeter, dev: dict) -> dict:
    import jax

    import thunder_tpu as tt
    from thunder_tpu.core import dtypes
    from thunder_tpu.models import llama
    from thunder_tpu.optim import AdamW

    log(f"train phase: {preset.model} widths, {preset.n_layers} layers, "
        f"batch {preset.batch} x seq {preset.seq}")
    cfg = llama.CONFIGS[preset.model]
    tokens, targets = train_batch(cfg, preset)
    params = jax.device_put(
        llama.init_params(cfg, seed=0, scale_layers=preset.n_layers))
    t0 = time.perf_counter()
    ref = reference_loss(params, tokens, targets, cfg)
    log(f"  float32 reference loss {ref:.5f} "
        f"(ln vocab = {math.log(cfg.vocab_size):.3f}; "
        f"{time.perf_counter() - t0:.1f} s)")
    meter.take()                    # the reference's compiles are not ours

    # bf16 first moment, f32 second: the step the benchmark's train cell times
    opt = AdamW(lr=1e-4, state_dtype=dtypes.bfloat16, v_dtype=dtypes.float32)
    jstep = tt.jit(make_train_step(cfg, opt), donate_argnums=(0, 1))
    out, params, opt_state = run_steps(
        jstep, params, opt.init(params), tokens, targets, preset, meter, dev)
    check_losses(out["losses"], ref, cfg, preset, "train")
    out.update(reference_loss=ref, claims=claim_table(
        jstep, "train", required=FLASH_CLAIMS if preset.require_flash else ()))
    assert_clean("end of train phase")
    del params, opt_state           # release the training buffers
    return out


def fsdp_phase(preset: Preset, meter: CompileMeter, dev: dict,
               n_dev: int) -> dict:
    import thunder_tpu as tt  # noqa: F401  (registers executors)
    from thunder_tpu.core import dtypes
    from thunder_tpu.core.devices import MeshSpec
    from thunder_tpu.distributed import fsdp
    from thunder_tpu.models import llama
    from thunder_tpu.optim import AdamW

    log(f"fsdp phase: the same train step under fsdp={n_dev}")
    cfg = llama.CONFIGS[preset.model]
    tokens, targets = train_batch(cfg, preset)
    params = llama.init_params(cfg, seed=0, scale_layers=preset.n_layers)
    ref = reference_loss(params, tokens, targets, cfg)
    meter.take()
    opt = AdamW(lr=1e-4, state_dtype=dtypes.bfloat16, v_dtype=dtypes.float32)
    jstep = fsdp(make_train_step(cfg, opt), MeshSpec.make(fsdp=n_dev))
    before = hbm_in_use()
    out, params, opt_state = run_steps(
        jstep, params, opt.init(params), tokens, targets, preset, meter, dev)
    check_losses(out["losses"], ref, cfg, preset, "fsdp")
    spread = {"params": spread_over(params, n_dev, "fsdp params"),
              "opt_state": spread_over(opt_state, n_dev, "fsdp optimizer state")}
    after = hbm_in_use()
    log(f"  state spread over {n_dev} devices: {spread}; "
        f"bytes_in_use per device before {before} after {after}")
    out.update(reference_loss=ref, claims=claim_table(jstep, "fsdp"),
               spread=spread, bytes_in_use=after)
    assert_clean("end of fsdp phase")
    del params, opt_state
    return out


# ---------------------------------------------------------------------------
# serve phase
# ---------------------------------------------------------------------------

def serve_phase(preset: Preset, meter: CompileMeter, dev: dict,
                mesh: int | None = None) -> dict:
    import jax
    import numpy as np

    from thunder_tpu.models import llama
    from thunder_tpu.serving import ServingEngine

    label = "serve" if mesh is None else f"serve mesh={mesh}"
    log(f"{label} phase: {preset.model} widths, {preset.n_layers} layers, "
        f"{preset.max_slots} slots, page {preset.page_size}, context "
        f"{preset.max_context}, prefill chunk {preset.prefill_chunk}")
    cfg = llama.CONFIGS[preset.model]
    params = jax.device_put(
        llama.init_params(cfg, seed=0, scale_layers=preset.n_layers))
    eng = ServingEngine(params, cfg, n_layers=preset.n_layers,
                        max_slots=preset.max_slots, page_size=preset.page_size,
                        max_context=preset.max_context,
                        prefill_chunk=preset.prefill_chunk, mesh=mesh)
    rng = np.random.RandomState(1)
    prompts = [rng.randint(1, cfg.vocab_size, size=n).astype(np.int32)
               for n in preset.prompt_lens]
    meter.take()                    # earlier phases' compiles are not ours
    t0 = time.perf_counter()
    reqs = [eng.submit(p, preset.new_tokens) for p in prompts]
    eng.drain()
    drain_s = time.perf_counter() - t0
    compile_w = meter.take()
    for r, p in zip(reqs, prompts):
        if not r.done or len(r.generated) != preset.new_tokens:
            raise RuntimeError(
                f"[{label}] request {r.request_id} (prompt {len(p)}): state "
                f"{r.state}, {len(r.generated)}/{preset.new_tokens} tokens, "
                f"error {r.error!r}")
    eng.assert_quiescent()
    log(f"  {len(reqs)} requests (prompts {list(preset.prompt_lens)}) x "
        f"{preset.new_tokens} new tokens complete; pools quiescent; "
        f"first drain {drain_s:.1f} s  [{compile_w}]")

    # logits parity: one more request, stepped by hand so each decode
    # step's logits row (kept on device by the engine) can be read
    prompt = rng.randint(1, cfg.vocab_size,
                         size=preset.parity_prompt).astype(np.int32)
    req = eng.submit(prompt, preset.parity_tokens)
    rows, decode_ms = [], []
    slot = None
    while not req.done:
        decoding = req.state == "decode"
        n_before = len(req.generated)
        t0 = time.perf_counter()
        if not eng.step():
            raise RuntimeError(f"[{label}] parity request stalled")
        dt = (time.perf_counter() - t0) * 1e3
        if req in eng.slots:
            # admitted, prefilled and decoded within one step: the slot is
            # known only now (and is gone again once the request is done)
            slot = eng.slots.index(req)
        if len(req.generated) > n_before:
            rows.append(np.asarray(eng.last_decode_logits[slot], np.float32))
            if decoding and n_before > 0:   # a pure decode step, warm
                decode_ms.append(dt)
    eng.assert_quiescent()
    if len(rows) != preset.parity_tokens:
        raise RuntimeError(f"[{label}] captured {len(rows)} logits rows for "
                           f"{preset.parity_tokens} tokens")
    got = np.stack(rows)                                    # (n, V)
    seq = np.concatenate([prompt, req.output()[:-1]])[None]
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(jax.jit(
            lambda p, t: reference_forward(p, t, cfg))(eng.params, seq))[0]
    ref = ref[len(prompt) - 1:]
    diff = got - ref
    max_abs, rms = float(np.abs(diff).max()), float(np.sqrt((diff ** 2).mean()))
    agree = int((got.argmax(-1) == ref.argmax(-1)).sum())
    log(f"  decode logits vs float32 reference over {got.shape[0]} positions "
        f"x {got.shape[1]} vocab: max|diff| {max_abs:.4f} (bound "
        f"{preset.logits_atol}), rms {rms:.4f} (bound {preset.logits_rms}), "
        f"logit std {float(ref.std()):.3f}, argmax agrees {agree}/{len(rows)}")
    if not np.isfinite(got).all():
        raise RuntimeError(f"[{label}] non-finite decode logits")
    if max_abs > preset.logits_atol or rms > preset.logits_rms:
        raise RuntimeError(f"[{label}] decode logits off the float32 "
                           f"reference: max {max_abs}, rms {rms}")
    if decode_ms:
        log(f"  decode step on {dev['kind']} x{dev['count']} "
            f"({preset.max_slots} slots): {statistics.median(decode_ms):.2f} "
            f"ms median of {len(decode_ms)}")
    out = {"requests": len(reqs), "first_drain_s": drain_s,
           "compile": compile_w, "decode_step_ms": decode_ms,
           "logits_max_abs": max_abs, "logits_rms": rms,
           "argmax_agree": f"{agree}/{len(rows)}",
           "claims": {"decode": claim_table(eng.runner.decode_jit,
                                            f"{label}: decode"),
                      "prefill": claim_table(eng.runner.prefill_jit,
                                             f"{label}: prefill (last rung)")}}
    if mesh is not None:
        out["spread"] = {
            "params": spread_over(eng.params, mesh, "meshed params"),
            "kv_pool": spread_over(eng.cache.pools, mesh, "meshed KV pool")}
        out["bytes_in_use"] = hbm_in_use()
        log(f"  state spread over {mesh} devices: {out['spread']}; "
            f"bytes_in_use per device {out['bytes_in_use']}")
    assert_clean(f"end of {label} phase")
    return out


# ---------------------------------------------------------------------------
# entry
# ---------------------------------------------------------------------------

def run(preset: Preset, dev: dict, meter: CompileMeter,
        multi_chip: int = 0) -> dict:
    """All phases in sequence. ``multi_chip``: device count for the FSDP and
    meshed-serving phases (0 = single-chip phases only)."""
    from thunder_tpu import observe

    observe.enable(clear=True)      # counters (fallbacks, census errors) on
    assert_clean("start")
    report = {"train": train_phase(preset, meter, dev),
              "serve": serve_phase(preset, meter, dev)}
    if multi_chip:
        report["fsdp"] = fsdp_phase(preset, meter, dev, multi_chip)
        report["serve_mesh"] = serve_phase(preset, meter, dev, mesh=multi_chip)
    assert_clean("end")
    return report


def main(argv=None) -> int:
    dev = device_info()
    if dev["platform"] != "tpu":
        print(f"chip_smoke: JAX found no accelerator (platform "
              f"{dev['platform']!r}, {dev['kind']}); this script proves the "
              f"system on a TPU and does nothing anywhere else",
              file=sys.stderr)
        return EXIT_NO_ACCELERATOR

    import thunder_tpu as tt

    t_start = time.perf_counter()
    cache_dir = tt.enable_compilation_cache()
    vers = versions()
    log(f"device: {dev}  versions: {vers}")
    log(f"compile cache: {cache_dir}  (JAX_COMPILATION_CACHE_DIR "
        f"{'set' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'not set'})")
    meter = CompileMeter()
    multi = 4 if dev["count"] >= 4 else 0
    report = run(FULL, dev, meter, multi_chip=multi)
    if not multi:
        log("four chips: not run (this process sees "
            f"{dev['count']} device)")
    report.update(device=dev, versions=vers, cache_dir=cache_dir,
                  total_s=round(time.perf_counter() - t_start, 1))
    out_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "chip_smoke.json"), "w") as f:
        json.dump(report, f, indent=1, default=str)
    log(f"all phases passed in {report['total_s']} s")
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
