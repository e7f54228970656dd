"""Family ``llama``: the dense decoder every dense model here shares
(RMSNorm, rotary GQA attention, SwiGLU MLP, untied head).

Three things live here, and only the first touches the program:

1. ``program_config`` / ``make_loss``: the program's own config object and
   loss, built from the benchmark's config file (``llama.CONFIGS`` is not
   read);
2. ``init_params`` and the plain float32 reference (``ref_*``): the
   architecture as published, in ``jax.numpy``, importing nothing of the
   program. Weights are made on the device from the seed in one jitted call,
   in the type the configuration states;
3. the operation and byte counts of the work, from shapes alone.
"""

from __future__ import annotations

import math
from types import SimpleNamespace

def spec_from_config(conf: dict, rehearse: bool = False) -> SimpleNamespace:
    c = dict(conf)
    if rehearse:
        c.update(conf.get("rehearse", {}))
        c["dtype"] = "float32"      # a tiny bfloat16 model is all round-off
    return SimpleNamespace(
        conf=c, D=c["hidden_size"], F=c["intermediate_size"],
        H=c["num_attention_heads"], KV=c["num_key_value_heads"],
        hd=c["head_dim"], L=c["num_hidden_layers"], V=c["vocab_size"],
        theta=float(c["rope_theta"]), eps=float(c["rms_norm_eps"]),
        dtype=c["dtype"], name=c.get("name", "cfg"))


# ---------------------------------------------------------------------------
# the program's side
# ---------------------------------------------------------------------------

def program_config(spec, max_seq_len: int):
    from thunder_tpu.core import dtypes
    from thunder_tpu.models import llama

    if spec.H * spec.hd != spec.D:
        raise ValueError("models/llama.py derives head_dim as dim / n_heads")
    return llama.LlamaConfig(
        name=spec.name, vocab_size=spec.V, dim=spec.D, n_layers=spec.L,
        n_heads=spec.H, n_kv_heads=spec.KV, intermediate_size=spec.F,
        max_seq_len=max_seq_len, rope_theta=spec.theta, norm_eps=spec.eps,
        dtype=getattr(dtypes, spec.dtype))


def make_loss(spec, cfg):
    from thunder_tpu.models import llama

    return lambda p, tokens, targets: llama.fused_loss_fn(p, tokens, targets, cfg)


# ---------------------------------------------------------------------------
# weights from the seed
# ---------------------------------------------------------------------------

def key_from_seed(seed: int, stream: int = 0):
    """A JAX key from any whole number (the driver's seeds pass 2**31)."""
    import jax
    import numpy as np

    data = np.random.SeedSequence([int(seed), stream]).generate_state(2)
    return jax.random.wrap_key_data(data.astype(np.uint32))


def layer_shapes(spec) -> dict:
    qd, kvd = spec.H * spec.hd, spec.KV * spec.hd
    return {"wq": (qd, spec.D), "wk": (kvd, spec.D), "wv": (kvd, spec.D),
            "wo": (spec.D, qd), "w_gate": (spec.F, spec.D),
            "w_up": (spec.F, spec.D), "w_down": (spec.D, spec.F)}


def _init(spec, key, layer_extra=None):
    import jax
    import jax.numpy as jnp

    jd = jnp.dtype(spec.dtype)

    def dense(k, shape):
        return (jax.random.normal(k, shape, jnp.float32)
                / math.sqrt(shape[-1])).astype(jd)

    k_emb, k_head, k_layers = jax.random.split(key, 3)
    params = {"tok_embedding": dense(k_emb, (spec.V, spec.D)),
              "norm_f": jnp.ones((spec.D,), jd),
              "lm_head": dense(k_head, (spec.V, spec.D)), "layers": []}
    for kl in jax.random.split(k_layers, spec.L):
        shapes = layer_shapes(spec)
        ks = jax.random.split(kl, len(shapes) + 1)
        layer = {n: dense(k, s) for k, (n, s) in zip(ks, shapes.items())}
        layer["attn_norm"] = jnp.ones((spec.D,), jd)
        layer["mlp_norm"] = jnp.ones((spec.D,), jd)
        if layer_extra is not None:
            layer_extra(layer, ks[-1], dense)
        params["layers"].append(layer)
    return params


def init_params(spec, seed: int):
    """Every weight on the device from the seed, one jitted call."""
    import jax

    return jax.jit(lambda k: _init(spec, k))(key_from_seed(seed))


# ---------------------------------------------------------------------------
# the plain float32 reference (call under default_matmul_precision("highest"))
# ---------------------------------------------------------------------------

def _fp8(x):
    """Round to float8_e4m3 under one scale a tensor: the control's
    precision, the step below bfloat16. Gradients pass straight through (a
    cast's own transpose would round the unscaled cotangent to fp8, which
    flushes it to nought)."""
    import jax
    import jax.numpy as jnp

    s = jnp.max(jnp.abs(x)) / 448.0 + 1e-30
    q = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return x + jax.lax.stop_gradient(q - x)


def matmul(precision: str):
    """``mm(x, w)`` = x @ w.T in float32, operands rounded first where the
    control asks for a lower precision."""
    import jax.numpy as jnp

    f32 = lambda a: a.astype(jnp.float32)
    if precision == "float32":
        return lambda x, w: x @ f32(w).T
    if precision == "bfloat16":
        r = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)
        return lambda x, w: r(x) @ r(f32(w)).T
    if precision == "fp8":
        return lambda x, w: _fp8(x) @ _fp8(f32(w)).T
    raise ValueError(f"no reference precision {precision!r}")


def _norm(x, w, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def ref_attention(x, layer, spec, mm, remat: bool):
    """x (T, D) normed -> attention output (T, D) before the residual."""
    import jax
    import jax.numpy as jnp

    T, hd, H, KV = x.shape[0], spec.hd, spec.H, spec.KV
    pos = jnp.arange(T, dtype=jnp.float32)
    inv = spec.theta ** (jnp.arange(hd // 2, dtype=jnp.float32) * -2.0 / hd)
    ang = pos[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)

    def rope(a):                                     # (n, T, hd)
        a1, a2 = a[..., : hd // 2], a[..., hd // 2:]
        return jnp.concatenate([a1 * cos - a2 * sin, a2 * cos + a1 * sin], -1)

    heads = lambda a, n: a.reshape(T, n, hd).transpose(1, 0, 2)
    q = rope(heads(mm(x, layer["wq"]), H)).reshape(KV, H // KV, T, hd)
    k = rope(heads(mm(x, layer["wk"]), KV))
    v = heads(mm(x, layer["wv"]), KV)
    mask = jnp.tril(jnp.ones((T, T), bool))

    def group(qkv):                                  # one KV head's queries
        qg, kg, vg = qkv
        s = (qg @ kg.T) / math.sqrt(hd)
        return jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1) @ vg

    if remat:
        group = jax.checkpoint(group)
    o = jax.lax.map(group, (q, k, v))                # (KV, rep, T, hd)
    return mm(o.reshape(H, T, hd).transpose(1, 0, 2).reshape(T, H * hd),
              layer["wo"])


def ref_mlp(x, layer, spec, mm):
    import jax

    return mm(jax.nn.silu(mm(x, layer["w_gate"])) * mm(x, layer["w_up"]),
              layer["w_down"])


def ref_logits(params, tokens, spec, precision="float32", remat=False,
               mlp=ref_mlp):
    """tokens (T,) -> logits (T, V) in float32: one sequence, no cache."""
    import jax
    import jax.numpy as jnp

    mm = matmul(precision)

    def block(h, layer):
        h = h + ref_attention(_norm(h, layer["attn_norm"], spec.eps), layer,
                              spec, mm, remat)
        return h + mlp(_norm(h, layer["mlp_norm"], spec.eps), layer, spec, mm)

    if remat:
        block = jax.checkpoint(block)
    h = params["tok_embedding"].astype(jnp.float32)[tokens]
    for layer in params["layers"]:
        h = block(h, layer)
    return mm(_norm(h, params["norm_f"], spec.eps), params["lm_head"])


def ref_nll_sum(params, tokens, targets, spec, precision="float32",
                mlp=ref_mlp):
    """Summed next-token NLL of one sequence (rematerialised so that its
    gradient fits beside the optimizer's state)."""
    import jax
    import jax.numpy as jnp

    logp = jax.nn.log_softmax(
        ref_logits(params, tokens, spec, precision, remat=True, mlp=mlp), -1)
    return -jnp.take_along_axis(logp, targets[:, None], 1).sum()


# ---------------------------------------------------------------------------
# operations and bytes, from shapes (the work, whoever does it)
# ---------------------------------------------------------------------------

def _attn_weights(spec) -> int:
    return spec.D * (spec.H + 2 * spec.KV) * spec.hd + spec.H * spec.hd * spec.D


def _mlp_weights(spec) -> int:
    return 3 * spec.D * spec.F


def num_params(spec) -> int:
    return (spec.L * (_attn_weights(spec) + _mlp_weights(spec) + 2 * spec.D)
            + 2 * spec.V * spec.D + spec.D)


def train_flops_per_token(spec, seq: int) -> float:
    """Forward + backward (3 x forward), no recomputation: 2 FLOPs a weight
    of every matrix but the embedding table, plus causal attention (each
    token attends to (seq + 1) / 2 keys on average; QK^T and PV)."""
    dense = 2.0 * (spec.L * (_attn_weights(spec) + _mlp_weights(spec))
                   + spec.V * spec.D)
    attn = spec.L * 2 * 2.0 * spec.H * spec.hd * (seq + 1) / 2
    return 3.0 * (dense + attn)


def mlp_block_train_counts(spec, tokens: int, itemsize: int = 2) -> dict:
    """One MLP sub-block (norm, gate/up/down GEMMs, residual), forward and
    backward, over ``tokens`` rows. Weights are read once and their gradients
    written once; the rows go in and out once each way."""
    flops = 3 * 2.0 * _mlp_weights(spec) * tokens
    bytes_ = (2 * _mlp_weights(spec) + 5 * tokens * spec.D) * itemsize
    return {"flops": flops, "bytes": float(bytes_)}


def train_window_counts(spec, steps: int, batch: int, seq: int) -> dict:
    """The work of ``steps`` train steps, by the names the metrics use."""
    mlp = mlp_block_train_counts(spec, batch * seq)
    return {"mlp_block_train": {k: v * spec.L * steps for k, v in mlp.items()},
            "step_train": {"flops": train_flops_per_token(spec, seq)
                           * steps * batch * seq}}


def decode_attn_block_counts(spec, slots: int, live_tokens: float,
                             itemsize: int = 2) -> dict:
    """One decode attention sub-block (norm, QKV, rope, attention over the
    live context, out-projection) for ``slots`` rows whose contexts hold
    ``live_tokens`` tokens together. Weights and cache are read once."""
    flops = (2.0 * _attn_weights(spec) * slots
             + 2 * 2.0 * spec.H * spec.hd * live_tokens)
    bytes_ = (_attn_weights(spec) + 2 * spec.KV * spec.hd * live_tokens
              + 2 * slots * spec.D) * itemsize
    return {"flops": flops, "bytes": float(bytes_)}


def decode_step_counts(spec, slots: int, live_tokens: float,
                       itemsize: int = 2) -> dict:
    """A whole decode step: every layer's weights and the head read once,
    one embedding row a slot, the live cache read once."""
    a = decode_attn_block_counts(spec, slots, live_tokens, itemsize)
    flops = spec.L * (a["flops"] + 2.0 * _mlp_weights(spec) * slots) \
        + 2.0 * spec.V * spec.D * slots
    bytes_ = spec.L * (a["bytes"] + _mlp_weights(spec) * itemsize) \
        + (spec.V * spec.D + slots * spec.D) * itemsize
    return {"flops": flops, "bytes": float(bytes_)}
