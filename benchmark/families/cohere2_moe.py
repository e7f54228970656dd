"""Family ``cohere2_moe``: Command A+ class decoders, served.

One layer, ``x`` of shape (T, D); one mean-centred LayerNorm a layer, no
bias (``use_parallel_block``)::

    h  = LN(x)              LN(v) = (v - mean(v)) / sqrt(var(v) + eps) * g
    q  = h Wq (H heads x hd)    k = h Wk (KV x hd)    v = h Wv (KV x hd)
    window layer (``sliding_attention``): q, k rotated, theta ``rope_theta``,
        interleaved pairs (2i, 2i+1) (``rope_gptj``); key j visible to
        query i iff i - W < j <= i
    global layer (``full_attention``): no positional encoding; j <= i
    a  = softmax(q k^T / sqrt(hd) + mask) v, H / KV queries a KV head
    attn = concat(a) Wo
    s  = sigmoid(h Wr) (``router_width`` scores);  P = the ``top_k`` largest
    w_e = s_e / sum_P s                                  (``norm_topk_prob``)
    E(h; G, U, D) = (silu(h G) * (h U)) D
    routed = sum_{e in P} w_e E_e(h);  shared = mean_j E(h; shared_j)
    x' = x + attn + routed + shared
    logits = LN_f(x_L) Emb^T * logit_scale               (tied embedding)

**The chip's share** (the configuration's ``deployment``): the router keeps
its published width and picks and normalises over all of them; this chip
holds the experts ``held_experts_start .. + num_experts - 1`` and adds only
their part of ``routed``. What the others would have added is left out, here
and in the program alike, and the partial sum goes on to the next layer.
``vocab_size`` rows of the embedding are held; logits are over them.

Three things live here, and only the first touches the program:

1. ``program_config``: the program's own config object;
2. ``init_params`` and the plain float32 reference ``ref_logits``: the
   equations above in ``jax.numpy``, importing nothing of the program.
   Attention runs a KV head and a block of queries at a time, so that
   16,384 positions x 128 heads fit; the experts held run one at a time
   over every row, weighted by the router's (mostly zero) combine weights;
3. the operation and byte counts of the work, from shapes alone.

Parameter tree (the program's ``models/cohere2_moe.py`` reads the same)::

    tok_embedding (V, D), norm_f (D,),
    layers[i]: norm (D,), wq (H*hd, D), wk, wv (KV*hd, D), wo (D, H*hd),
               router (router_width, D),
               w_gate, w_up (num_experts + num_shared, F, D),
               w_down (num_experts + num_shared, D, F)   # shared ones last
"""

from __future__ import annotations

import math
from types import SimpleNamespace

Q_BLOCK = 512       # queries a block of the reference's attention


def spec_from_config(conf: dict, rehearse: bool = False) -> SimpleNamespace:
    c = dict(conf)
    if rehearse:
        c.update(conf.get("rehearse", {}))
        c["dtype"] = "float32"      # a tiny bfloat16 model is all round-off
    L = c["num_hidden_layers"]
    return SimpleNamespace(
        conf=c, D=c["hidden_size"], F=c["intermediate_size"],
        H=c["num_attention_heads"], KV=c["num_key_value_heads"],
        hd=c["head_dim"], L=L, V=c["vocab_size"], W=c["sliding_window"],
        layer_types=tuple(c["layer_types"][:L]),
        E=c["num_experts"], E0=c["held_experts_start"],
        router_width=c["router_width"], k=c["num_experts_per_tok"],
        Sh=c["num_shared_experts"], theta=float(c["rope_theta"]),
        eps=float(c["layer_norm_eps"]), logit_scale=float(c["logit_scale"]),
        dtype=c["dtype"], name=c.get("name", "cfg"))


# ---------------------------------------------------------------------------
# the program's side
# ---------------------------------------------------------------------------

def program_config(spec, max_seq_len: int):
    from thunder_tpu.core import dtypes
    from thunder_tpu.models import cohere2_moe

    kinds = {"sliding_attention": "window", "full_attention": "full"}
    return cohere2_moe.Cohere2MoeConfig(
        name=spec.name, vocab_size=spec.V, dim=spec.D, n_layers=spec.L,
        n_heads=spec.H, n_kv_heads=spec.KV, head_dim=spec.hd, window=spec.W,
        layer_types=tuple(kinds[t] for t in spec.layer_types),
        expert_dim=spec.F, n_experts=spec.router_width, top_k=spec.k,
        held_start=spec.E0, n_held=spec.E, n_shared=spec.Sh,
        rope_theta=spec.theta, norm_eps=spec.eps,
        logit_scale=spec.logit_scale, max_seq_len=max_seq_len,
        dtype=getattr(dtypes, spec.dtype))


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
    qd, kvd, n = spec.H * spec.hd, spec.KV * spec.hd, spec.E + spec.Sh
    return {"wq": (qd, spec.D), "wk": (kvd, spec.D), "wv": (kvd, spec.D),
            "wo": (spec.D, qd), "router": (spec.router_width, spec.D),
            "w_gate": (n, spec.F, spec.D), "w_up": (n, spec.F, spec.D),
            "w_down": (n, spec.D, spec.F)}


def _init(spec, key):
    import jax
    import jax.numpy as jnp

    jd = jnp.dtype(spec.dtype)

    def dense(k, shape):        # std 1 / sqrt(fan_in), fan_in the last dim
        return (jax.random.normal(k, shape, jnp.float32)
                / math.sqrt(shape[-1])).astype(jd)

    k_emb, k_layers = jax.random.split(key)
    params = {"tok_embedding": dense(k_emb, (spec.V, spec.D)),
              "norm_f": jnp.ones((spec.D,), jd), "layers": []}
    for kl in jax.random.split(k_layers, spec.L):
        shapes = layer_shapes(spec)
        ks = jax.random.split(kl, len(shapes))
        layer = {n: dense(k, s) for k, (n, s) in zip(ks, shapes.items())}
        layer["norm"] = jnp.ones((spec.D,), jd)
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


def _norm(x, g, eps):
    import jax
    import jax.numpy as jnp

    c = x - jnp.mean(x, -1, keepdims=True)
    return c * jax.lax.rsqrt(jnp.mean(c * c, -1, keepdims=True) + eps) \
        * g.astype(jnp.float32)


def _rope_gptj(a, theta: float):
    """a (n, T, hd): pairs (2i, 2i+1) rotated by pos * theta^(-2i/hd)."""
    import jax.numpy as jnp

    n, T, hd = a.shape
    inv = theta ** (jnp.arange(hd // 2, dtype=jnp.float32) * -2.0 / hd)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    even, odd = a[..., 0::2], a[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     -1).reshape(n, T, hd)


def ref_attention(h, layer, spec, mm, window: int | None):
    """h (T, D) normed -> attention output (T, D) before the residual."""
    import jax
    import jax.numpy as jnp

    T, hd, H, KV = h.shape[0], spec.hd, spec.H, spec.KV
    heads = lambda a, n: a.reshape(T, n, hd).transpose(1, 0, 2)
    q, k = heads(mm(h, layer["wq"]), H), heads(mm(h, layer["wk"]), KV)
    v = heads(mm(h, layer["wv"]), KV)
    if window is not None:
        q, k = _rope_gptj(q, spec.theta), _rope_gptj(k, spec.theta)
    qb = min(Q_BLOCK, T)
    assert T % qb == 0, (T, qb)
    G = H // KV
    q = q.reshape(KV, G, T // qb, qb, hd).transpose(0, 2, 1, 3, 4)
    cols = jnp.arange(T)

    def group(args):                    # one KV head
        qh, kh, vh = args               # (T/qb, G, qb, hd), (T, hd), (T, hd)

        def block(carry, xs):           # one block of queries, G heads
            qg, b = xs
            rows = b * qb + jnp.arange(qb)
            ok = cols[None, :] <= rows[:, None]
            if window is not None:
                ok = ok & (cols[None, :] > rows[:, None] - window)
            s = jnp.einsum("gqd,kd->gqk", qg, kh) / math.sqrt(hd)
            p = jax.nn.softmax(jnp.where(ok[None], s, -jnp.inf), -1)
            return carry, jnp.einsum("gqk,kd->gqd", p, vh)

        _, o = jax.lax.scan(block, 0, (qh, jnp.arange(T // qb)))
        return o                        # (T/qb, G, qb, hd)

    o = jax.lax.map(group, (q, k, v))   # (KV, T/qb, G, qb, hd)
    o = o.transpose(1, 3, 0, 2, 4).reshape(T, H * hd)
    return mm(o, layer["wo"])


def ref_experts(h, layer, spec, mm):
    """routed (the held experts' share) + shared, for rows h (T, D)."""
    import jax
    import jax.numpy as jnp

    s = jax.nn.sigmoid(mm(h, layer["router"]))              # (T, width)
    vals, idx = jax.lax.top_k(s, spec.k)
    w = vals / jnp.sum(vals, -1, keepdims=True)
    combine = jnp.zeros_like(s).at[jnp.arange(h.shape[0])[:, None], idx].set(w)
    combine = jnp.concatenate(
        [combine[:, spec.E0: spec.E0 + spec.E],
         jnp.full((h.shape[0], spec.Sh), 1.0 / spec.Sh, jnp.float32)], 1)

    def one(acc, ew):                   # one held (or shared) expert
        g, u, d, c = ew
        y = mm(jax.nn.silu(mm(h, g)) * mm(h, u), d)
        return acc + c[:, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h),
                          (layer["w_gate"], layer["w_up"], layer["w_down"],
                           combine.T))
    return out


def ref_layer(x, layer, spec, mm, layer_type: str):
    h = _norm(x, layer["norm"], spec.eps)
    window = spec.W if layer_type == "sliding_attention" else None
    return x + ref_attention(h, layer, spec, mm, window) \
        + ref_experts(h, layer, spec, mm)


def ref_logits(params, tokens, spec, precision="float32"):
    """tokens (T,) -> logits (T, V) in float32: one sequence, no cache."""
    import jax.numpy as jnp

    mm = matmul(precision)
    x = params["tok_embedding"].astype(jnp.float32)[tokens]
    for layer, t in zip(params["layers"], spec.layer_types):
        x = ref_layer(x, layer, spec, mm, t)
    return mm(_norm(x, params["norm_f"], spec.eps),
              params["tok_embedding"]) * spec.logit_scale


# ---------------------------------------------------------------------------
# operations and bytes, from shapes (the work, whoever does it)
# ---------------------------------------------------------------------------

def _attn_weights(spec) -> int:
    return spec.D * (spec.H + 2 * spec.KV) * spec.hd + spec.H * spec.hd * spec.D


def _expert_weights(spec) -> int:
    return 3 * spec.D * spec.F


def num_params(spec) -> int:
    layer = (_attn_weights(spec) + spec.router_width * spec.D
             + (spec.E + spec.Sh) * _expert_weights(spec) + spec.D)
    return spec.L * layer + spec.V * spec.D + spec.D


def _window_share(spec) -> float:
    return spec.layer_types.count("sliding_attention") / spec.L


def decode_attn_block_counts(spec, slots: int, live_tokens: float,
                             itemsize: int = 2) -> dict:
    """The page walk of ONE layer's decode attention (what the Pallas kernel
    does of the attention sub-block: QK^T, softmax, PV over the pages it
    reads; the projections are XLA's and are in ``decode_step_counts``),
    averaged over the layers' kinds, for ``slots`` rows whose contexts hold
    ``live_tokens`` tokens together. A global layer reads the live context;
    a window layer at most ``W`` tokens a slot (``min(live, slots * W)``:
    exact where every context is at least the window, as in the cell that
    reads this, and where none is)."""
    ws = _window_share(spec)
    tokens = (1 - ws) * live_tokens + ws * min(live_tokens, slots * spec.W)
    flops = 2 * 2.0 * spec.H * spec.hd * tokens
    bytes_ = (2 * spec.KV * spec.hd * tokens
              + 2 * slots * spec.H * spec.hd) * itemsize
    return {"flops": flops, "bytes": float(bytes_)}


def routed_counts(spec, hit: float, picks: float, itemsize: int = 2) -> dict:
    """The routed experts' work: ``hit`` held experts' three matrices read
    once each (the experts the kernel streams), ``picks`` (row, expert)
    assignments computed."""
    return {"flops": 2.0 * _expert_weights(spec) * picks,
            "bytes": float(_expert_weights(spec) * hit * itemsize)}


def moe_block_counts(spec, slots: int, hit: float, picks: float,
                     itemsize: int = 2) -> dict:
    """One layer's expert kernel at decode: the ``hit`` experts it streams
    and the shared ones, the rows in and out."""
    r = routed_counts(spec, hit + spec.Sh, picks + slots * spec.Sh, itemsize)
    r["bytes"] += 2.0 * slots * spec.D * itemsize
    return r


def decode_step_counts(spec, slots: int, live_tokens: float,
                       itemsize: int = 2) -> dict:
    """A whole decode step WITHOUT its routed experts: the fewest a step can
    hit of those held here is none (every pick may fall on an expert held
    elsewhere), and the harness hands this function no routing. Per layer:
    the attention weights and the router read once, the page walk, the
    shared experts; then the tied head and one embedding row a slot. The
    reader ``moe_route`` adds ``routed_counts`` of the experts the traced
    window's ``moe_route`` events report as streamed."""
    a = decode_attn_block_counts(spec, slots, live_tokens, itemsize)
    dense = _attn_weights(spec) + spec.router_width * spec.D \
        + spec.Sh * _expert_weights(spec)
    flops = spec.L * (a["flops"] + 2.0 * dense * slots) \
        + 2.0 * spec.V * spec.D * slots
    bytes_ = spec.L * (a["bytes"] + dense * itemsize) \
        + (spec.V * spec.D + slots * spec.D) * itemsize
    return {"flops": flops, "bytes": float(bytes_)}
