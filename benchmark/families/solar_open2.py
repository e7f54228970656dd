"""Family ``solar_open2``: Solar-Open2 class hybrid decoders, served.

One layer, ``x`` the residual, every norm an RMSNorm (``rms_norm_eps``)::

    h  = RMSNorm(x);  x' = x + Mix(h)
    u  = RMSNorm(x');  x'' = x' + SharedE(u) + sum_{e in P} w_e E_e(u)
    E(u) = (silu(u G) * (u U)) D                  (width moe_intermediate_size)
    router: s = sigmoid(u Wr) (``router_width``); P = top_k of s + b_corr
        (the bias picks only); w_e = s_e / sum_P s  (routed_scaling_factor 1)
    GQA layer (index in ``gqa_layers``): no positions, elementwise gate
        a = softmax(q k^T / sqrt(hd) + causal) v;  Mix = (a * sigmoid(h Wg)) Wo
    KDA layer (every other), per head (dk = dv = linear_attn_config head_dim):
        q, k, v = silu(causal_depthwise_conv4(h Wq | Wk | Wv))
        q, k = L2norm(q), L2norm(k);  q *= 1 / sqrt(dk)
        beta = 2 sigmoid(h Wb);  g = -exp(A_log) softplus((h Wf_a) Wf_b + dt_bias)
        S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
        o_t = S_t^T q_t;  Mix = (RMSNorm_head(o) * sigmoid((h Wg_a) Wg_b)) Wo
    logits = RMSNorm_f(x_L) Wlm^T                 (untied head)

**The chip's share** (the configuration's ``deployment``): the router keeps
its published width and picks; this chip holds the experts
``held_experts_start .. + n_routed_experts - 1`` and adds only their part of
the routed sum, here and in the program alike. ``vocab_size`` rows of the
embedding and of the head are held; logits are over them.

Three things live here, and only the first touches the program:

1. ``program_config``: the program's own config object;
2. ``init_params`` and the plain float32 reference ``ref_logits``: the
   equations above in ``jax.numpy``, importing nothing of the program. The
   delta rule runs a token at a time in a ``lax.scan`` (the program runs it
   in chunks); attention runs a KV head and a block of queries at a time;
   each held expert runs over every row;
3. the operation and byte counts of the work, from shapes alone.

Parameter tree (the program's ``models/solar_open2.py`` reads the same)::

    tok_embedding (V, D), norm_f (D,), lm_head (V, D),
    layers[i]: attn_norm, ffn_norm (D,), router (router_width, D),
               router_bias (router_width,) float32,
               w_gate, w_up (E + Sh, F, D), w_down (E + Sh, D, F)  # shared last
      gqa:     wq (H*hd, D), wk, wv (KV*hd, D), wo (D, H*hd), wg (H*hd, D)
      kda:     wq, wk, wv (Hk*dk, D), wo (D, Hk*dk), conv (3*Hk*dk, 4),
               wb (Hk, D), wf_a (dk, D), wf_b (Hk*dk, dk), wg_a (dk, D),
               wg_b (Hk*dk, dk), a_log (Hk,), dt_bias (Hk*dk,), o_norm (dk,)
"""

from __future__ import annotations

import math
from types import SimpleNamespace

Q_BLOCK = 512       # queries a block of the reference's attention
# the arrays of a slot's state that the configuration holds in float32 (its
# ``assumed``; the driver ``serve_closed_state`` reads them at the close)
STATE_FLOAT32 = ("s",)


def spec_from_config(conf: dict, rehearse: bool = False) -> SimpleNamespace:
    c = dict(conf)
    if rehearse:
        c.update(conf.get("rehearse", {}))
        c["dtype"] = "float32"      # a tiny bfloat16 model is all round-off
    la = c["linear_attn_config"]
    L = c["num_hidden_layers"]
    gqa = set(c["gqa_layers"])
    return SimpleNamespace(
        conf=c, D=c["hidden_size"], F=c["moe_intermediate_size"],
        H=c["num_attention_heads"], KV=c["num_key_value_heads"],
        hd=c["head_dim"], L=L, V=c["vocab_size"],
        layer_types=tuple("gqa" if i in gqa else "kda" for i in range(L)),
        Hk=la["num_heads"], dk=la["head_dim"],
        conv=la["short_conv_kernel_size"],
        E=c["n_routed_experts"], E0=c["held_experts_start"],
        router_width=c["router_width"], k=c["num_experts_per_tok"],
        Sh=c["n_shared_experts"], eps=float(c["rms_norm_eps"]),
        dtype=c["dtype"], name=c.get("name", "cfg"))


# ---------------------------------------------------------------------------
# the program's side
# ---------------------------------------------------------------------------

def program_config(spec, max_seq_len: int):
    from thunder_tpu.core import dtypes
    from thunder_tpu.models import solar_open2

    return solar_open2.SolarOpen2Config(
        name=spec.name, vocab_size=spec.V, dim=spec.D, n_layers=spec.L,
        layer_types=spec.layer_types, n_heads=spec.H, n_kv_heads=spec.KV,
        head_dim=spec.hd, kda_heads=spec.Hk, kda_head_dim=spec.dk,
        kda_conv=spec.conv, kda_rank=spec.dk, expert_dim=spec.F,
        n_experts=spec.router_width, top_k=spec.k, held_start=spec.E0,
        n_held=spec.E, n_shared=spec.Sh, norm_eps=spec.eps,
        max_seq_len=max_seq_len, dtype=getattr(dtypes, spec.dtype))


# ---------------------------------------------------------------------------
# weights from the seed
# ---------------------------------------------------------------------------

def key_from_seed(seed: int, stream: int = 0):
    """A JAX key from any whole number (the driver's seeds pass 2**31)."""
    import jax
    import numpy as np

    data = np.random.SeedSequence([int(seed), stream]).generate_state(2)
    return jax.random.wrap_key_data(data.astype(np.uint32))


def layer_shapes(spec, layer_type: str) -> dict:
    """The layer's matrices (seeded normal, std 1 / sqrt(fan_in))."""
    n = spec.E + spec.Sh
    out = {"router": (spec.router_width, spec.D),
           "w_gate": (n, spec.F, spec.D), "w_up": (n, spec.F, spec.D),
           "w_down": (n, spec.D, spec.F)}
    if layer_type == "gqa":
        qd, kvd = spec.H * spec.hd, spec.KV * spec.hd
        out.update(wq=(qd, spec.D), wk=(kvd, spec.D), wv=(kvd, spec.D),
                   wo=(spec.D, qd), wg=(qd, spec.D))
    else:
        c, r = spec.Hk * spec.dk, spec.dk
        out.update(wq=(c, spec.D), wk=(c, spec.D), wv=(c, spec.D),
                   wo=(spec.D, c), wb=(spec.Hk, spec.D), wf_a=(r, spec.D),
                   wf_b=(c, r), wg_a=(r, spec.D), wg_b=(c, r))
    return out


def _init(spec, key):
    import jax
    import jax.numpy as jnp

    jd = jnp.dtype(spec.dtype)

    def dense(k, shape):        # std 1 / sqrt(fan_in), fan_in the last dim
        return (jax.random.normal(k, shape, jnp.float32)
                / math.sqrt(shape[-1])).astype(jd)

    k_emb, k_head, k_layers = jax.random.split(key, 3)
    params = {"tok_embedding": dense(k_emb, (spec.V, spec.D)),
              "lm_head": dense(k_head, (spec.V, spec.D)),
              "norm_f": jnp.ones((spec.D,), jd), "layers": []}
    for kl, t in zip(jax.random.split(k_layers, spec.L), spec.layer_types):
        shapes = layer_shapes(spec, t)
        ks = jax.random.split(kl, len(shapes) + 5)
        layer = {n: dense(k, s) for k, (n, s) in zip(ks, shapes.items())}
        layer["attn_norm"] = jnp.ones((spec.D,), jd)
        layer["ffn_norm"] = jnp.ones((spec.D,), jd)
        layer["router_bias"] = 0.05 * jax.random.normal(
            ks[-1], (spec.router_width,), jnp.float32)
        if t == "kda":
            c = spec.Hk * spec.dk
            layer["conv"] = (0.5 * jax.random.normal(
                ks[-2], (3 * c, spec.conv), jnp.float32)).astype(jd)
            layer["a_log"] = jnp.log(jax.random.uniform(
                ks[-3], (spec.Hk,), jnp.float32, 1.0, 16.0)).astype(jd)
            dt = jnp.exp(jax.random.uniform(
                ks[-4], (c,), jnp.float32, math.log(1e-3), math.log(1e-1)))
            layer["dt_bias"] = (dt + jnp.log(-jnp.expm1(-dt))).astype(jd)
            layer["o_norm"] = jnp.ones((spec.dk,), jd)
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
    precision, the step below bfloat16."""
    import jax.numpy as jnp

    s = jnp.max(jnp.abs(x)) / 448.0 + 1e-30
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


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


def _rms(x, g, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(jnp.float32)


def ref_gqa(h, layer, spec, mm):
    """h (T, D) normed -> the gated NoPE attention's output (T, D)."""
    import jax
    import jax.numpy as jnp

    T, hd, H, KV = h.shape[0], spec.hd, spec.H, spec.KV
    heads = lambda a, n: a.reshape(T, n, hd).transpose(1, 0, 2)
    q, k = heads(mm(h, layer["wq"]), H), heads(mm(h, layer["wk"]), KV)
    v = heads(mm(h, layer["wv"]), KV)
    qb = min(Q_BLOCK, T)
    assert T % qb == 0, (T, qb)
    G = H // KV
    q = q.reshape(KV, G, T // qb, qb, hd).transpose(0, 2, 1, 3, 4)
    cols = jnp.arange(T)

    def group(args):                    # one KV head
        qh, kh, vh = args

        def block(carry, xs):           # one block of queries, G heads
            qg, b = xs
            rows = b * qb + jnp.arange(qb)
            ok = cols[None, :] <= rows[:, None]
            s = jnp.einsum("gqd,kd->gqk", qg, kh) / math.sqrt(hd)
            p = jax.nn.softmax(jnp.where(ok[None], s, -jnp.inf), -1)
            return carry, jnp.einsum("gqk,kd->gqd", p, vh)

        _, o = jax.lax.scan(block, 0, (qh, jnp.arange(T // qb)))
        return o

    o = jax.lax.map(group, (q, k, v))   # (KV, T/qb, G, qb, hd)
    o = o.transpose(1, 3, 0, 2, 4).reshape(T, H * hd)
    return mm(o * jax.nn.sigmoid(mm(h, layer["wg"])), layer["wo"])


def ref_kda(h, layer, spec, mm):
    """h (T, D) normed -> the delta rule's mix (T, D), a token at a time."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    T, H, dk, K = h.shape[0], spec.Hk, spec.dk, spec.conv
    x = jnp.concatenate([mm(h, layer["wq"]), mm(h, layer["wk"]),
                         mm(h, layer["wv"])], -1)            # (T, 3*H*dk)
    xp = jnp.pad(x, ((K - 1, 0), (0, 0)))
    w = layer["conv"].astype(f32)
    y = jax.nn.silu(sum(xp[i:i + T] * w[:, i] for i in range(K)))
    q, k, v = (y[:, i * H * dk:(i + 1) * H * dk].reshape(T, H, dk)
               for i in range(3))
    l2 = lambda a: a / jnp.sqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)
    q, k = l2(q) / math.sqrt(dk), l2(k)
    beta = 2.0 * jax.nn.sigmoid(mm(h, layer["wb"]))          # (T, H)
    rate = jax.nn.softplus(mm(mm(h, layer["wf_a"]), layer["wf_b"])
                           + layer["dt_bias"].astype(f32)).reshape(T, H, dk)
    g = -jnp.exp(layer["a_log"].astype(f32))[None, :, None] * rate

    def step(S, xs):                    # S (H, dk, dv)
        qt, kt, vt, gt, bt = xs
        S = S * jnp.exp(gt)[:, :, None]
        kv = jnp.einsum("hkd,hk->hd", S, kt)
        S = S + kt[:, :, None] * (bt[:, None] * (vt - kv))[:, None, :]
        return S, jnp.einsum("hkd,hk->hd", S, qt)

    _, o = jax.lax.scan(step, jnp.zeros((H, dk, dk), f32), (q, k, v, g, beta))
    o = _rms(o, layer["o_norm"], spec.eps)
    gate = jax.nn.sigmoid(mm(mm(h, layer["wg_a"]), layer["wg_b"]))
    return mm((o * gate.reshape(T, H, dk)).reshape(T, H * dk), layer["wo"])


def ref_routing(u, layer, spec, mm):
    """The routed experts' combine weights over ALL ``router_width``
    experts (T, router_width): the bias picks, the scores weigh."""
    import jax
    import jax.numpy as jnp

    s = jax.nn.sigmoid(mm(u, layer["router"]))
    _, idx = jax.lax.top_k(s + layer["router_bias"].astype(jnp.float32),
                           spec.k)
    vals = jnp.take_along_axis(s, idx, 1)
    w = vals / jnp.sum(vals, -1, keepdims=True)
    return jnp.zeros_like(s).at[jnp.arange(u.shape[0])[:, None], idx].set(w)


def ref_experts(u, layer, spec, mm):
    """routed (the held experts' share) + shared, for rows u (T, D)."""
    import jax
    import jax.numpy as jnp

    combine = ref_routing(u, layer, spec, mm)
    combine = jnp.concatenate(
        [combine[:, spec.E0: spec.E0 + spec.E],
         jnp.full((u.shape[0], spec.Sh), 1.0 / spec.Sh, jnp.float32)], 1)

    def one(acc, ew):                   # one held (or shared) expert
        g, up, d, c = ew
        y = mm(jax.nn.silu(mm(u, g)) * mm(u, up), d)
        return acc + c[:, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(u),
                          (layer["w_gate"], layer["w_up"], layer["w_down"],
                           combine.T))
    return out


def ref_layer(x, layer, spec, mm, layer_type: str):
    h = _rms(x, layer["attn_norm"], spec.eps)
    mix = ref_gqa if layer_type == "gqa" else ref_kda
    x = x + mix(h, layer, spec, mm)
    return x + ref_experts(_rms(x, layer["ffn_norm"], spec.eps), layer, spec,
                           mm)


def ref_logits(params, tokens, spec, precision="float32"):
    """tokens (T,) -> logits (T, V) in float32: one sequence, no cache."""
    import jax.numpy as jnp

    mm = matmul(precision)
    x = params["tok_embedding"].astype(jnp.float32)[tokens]
    for layer, t in zip(params["layers"], spec.layer_types):
        x = ref_layer(x, layer, spec, mm, t)
    return mm(_rms(x, params["norm_f"], spec.eps), params["lm_head"])


# ---------------------------------------------------------------------------
# operations and bytes, from shapes (the work, whoever does it)
# ---------------------------------------------------------------------------

def _gqa_weights(spec) -> int:
    return spec.D * (spec.H + 2 * spec.KV) * spec.hd \
        + 2 * spec.H * spec.hd * spec.D


def _kda_weights(spec) -> int:
    c, r = spec.Hk * spec.dk, spec.dk
    return (4 * spec.D * c + 2 * (spec.D * r + r * c) + spec.D * spec.Hk
            + 3 * c * spec.conv + spec.Hk + c + spec.dk)


def _expert_weights(spec) -> int:
    return 3 * spec.D * spec.F


def _n(spec, t: str) -> int:
    return spec.layer_types.count(t)


def num_params(spec) -> int:
    moe = spec.router_width * (spec.D + 1) \
        + (spec.E + spec.Sh) * _expert_weights(spec) + 2 * spec.D
    return (_n(spec, "gqa") * _gqa_weights(spec)
            + _n(spec, "kda") * _kda_weights(spec) + spec.L * moe
            + 2 * spec.V * spec.D + spec.D)


def decode_attn_block_counts(spec, slots: int, live_tokens: float,
                             itemsize: int = 2) -> dict:
    """The page walk of the GQA layers' decode attention (QK^T, softmax, PV
    over the pages it reads, the rows in and out), averaged over ALL the
    layers (the harness multiplies by ``L``; a KDA layer walks no page),
    for ``slots`` rows whose contexts hold ``live_tokens`` together."""
    share = _n(spec, "gqa") / spec.L
    flops = 2 * 2.0 * spec.H * spec.hd * live_tokens
    bytes_ = (2 * spec.KV * spec.hd * live_tokens
              + 2 * slots * spec.H * spec.hd) * itemsize
    return {"flops": share * flops, "bytes": share * float(bytes_)}


def kda_decode_counts(spec, rows: float) -> dict:
    """One decode step of every KDA layer's delta rule for ``rows`` slots:
    each head's float32 state read and written once (the decay, k^T S, the
    rank-one update and S^T q on it), its five float32 vectors in (q, k,
    beta k, exp g, v) and o out."""
    n = _n(spec, "kda") * rows * spec.Hk
    state = spec.dk * spec.dk
    return {"flops": n * 7.0 * state,
            "bytes": n * 4.0 * (2 * state + 6 * spec.dk)}


def routed_counts(spec, hit: float, picks: float, itemsize: int = 2) -> dict:
    """The routed experts' work: ``hit`` held experts' three matrices read
    once each (the experts the kernel streams), ``picks`` (row, expert)
    assignments computed."""
    return {"flops": 2.0 * _expert_weights(spec) * picks,
            "bytes": float(_expert_weights(spec) * hit * itemsize)}


def moe_block_counts(spec, slots: int, hit: float, picks: float,
                     itemsize: int = 2) -> dict:
    """One layer's expert kernel at decode: the ``hit`` experts it streams
    and the shared one, the rows in and out."""
    r = routed_counts(spec, hit + spec.Sh, picks + slots * spec.Sh, itemsize)
    r["bytes"] += 2.0 * slots * spec.D * itemsize
    return r


def decode_step_counts(spec, slots: int, live_tokens: float,
                       itemsize: int = 2) -> dict:
    """A whole decode step WITHOUT its routed experts (the harness hands
    this function no routing; the reader ``moe_route`` adds what the
    ``moe_route`` events report as streamed): every layer's mixing weights,
    router and shared expert read once; the GQA walk; every KDA layer's
    state for ``slots`` rows; the head and one embedding row a slot."""
    a = decode_attn_block_counts(spec, slots, live_tokens, itemsize)
    s = kda_decode_counts(spec, slots)
    dense = (_n(spec, "gqa") * _gqa_weights(spec)
             + _n(spec, "kda") * _kda_weights(spec)
             + spec.L * (spec.router_width * spec.D
                         + spec.Sh * _expert_weights(spec)))
    flops = spec.L * a["flops"] + s["flops"] + 2.0 * dense * slots \
        + 2.0 * spec.V * spec.D * slots
    bytes_ = spec.L * a["bytes"] + s["bytes"] + dense * itemsize \
        + (spec.V * spec.D + slots * spec.D) * itemsize
    return {"flops": flops, "bytes": float(bytes_)}
