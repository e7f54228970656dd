"""Family ``mixtral``: the dense family's attention, norms, loss and optimizer
with a sparse-expert feed-forward (top-k of E SwiGLU experts a token).

Attention, rope, norms, the matmul precisions and the weight init are the
``llama`` family's, loaded from its file. What is here: the expert layer's
weights, the program's config and expert-parallel step, the plain reference
of the routed feed-forward with the load-balancing term, and the counts.

The published model routes every token to its top-k experts and drops none.
The program dispatches by capacity (GShard slots; ``dispatch`` in the config
file), and with seeded random weights some experts overflow, so the reference
follows the rule the file states: on each chip's rows, in order, a token's
j-th choice is kept while its expert has a slot left. Which assignments are
kept is worked out once a step from the router's choices (``ref_step_context``)
and handed to each row. The load-balancing term joins the loss only
where the config sets ``output_router_logits`` (the published file does not);
where it does, it follows the rule the program runs (fraction of tokens whose
FIRST choice is an expert, times the mean router probability, over the whole
batch; the published rule counts all k choices).
"""

from __future__ import annotations

import importlib.util
import math
import os
import sys


def _sibling(name: str):
    key = f"bench_families_{name}"
    if key in sys.modules:
        return sys.modules[key]
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), f"{name}.py")
    spec = importlib.util.spec_from_file_location(key, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[key] = mod
    spec.loader.exec_module(mod)
    return mod


dense = _sibling("llama")
key_from_seed = dense.key_from_seed


def spec_from_config(conf: dict, rehearse: bool = False):
    spec = dense.spec_from_config(conf, rehearse)
    c = spec.conf
    spec.E, spec.k = c["num_local_experts"], c["num_experts_per_tok"]
    # as published: the load-balancing term joins the loss only where the
    # config asks for the router's logits
    spec.aux_coef = float(c["router_aux_loss_coef"]) \
        if c["output_router_logits"] else 0.0
    spec.dispatch = c["dispatch"]
    return spec


# ---------------------------------------------------------------------------
# the program's side
# ---------------------------------------------------------------------------

def program_config(spec, max_seq_len: int):
    from thunder_tpu.core import dtypes
    from thunder_tpu.models import mixtral

    d = spec.dispatch
    return mixtral.MixtralConfig(
        name=spec.name, vocab_size=spec.V, dim=spec.D, n_layers=spec.L,
        n_heads=spec.H, n_kv_heads=spec.KV, intermediate_size=spec.F,
        n_experts=spec.E, top_k=spec.k,
        capacity_factor=float(d.get("capacity_factor", 2.0)),
        dropless=d["rule"] == "dropless", max_seq_len=max_seq_len,
        rope_theta=spec.theta, norm_eps=spec.eps,
        router_aux_coef=spec.aux_coef, dtype=getattr(dtypes, spec.dtype))


def make_loss(spec, cfg):
    from thunder_tpu.models import mixtral

    return lambda p, tokens, targets: mixtral.fused_loss_fn(p, tokens, targets, cfg)


def parallel_step(train_step, parallel: dict):
    """The step under expert parallelism: expert weights sharded over ``ep``
    chips, the batch over the same axis, the rest replicated."""
    from thunder_tpu.core.devices import MeshSpec
    from thunder_tpu.distributed import expert_parallel
    from thunder_tpu.models import mixtral

    return expert_parallel(train_step, MeshSpec.make(ep=parallel["ep"]),
                           expert_patterns=mixtral.EP_PATTERNS,
                           donate_argnums=(0, 1))


# ---------------------------------------------------------------------------
# weights from the seed, placed as expert parallelism holds them
# ---------------------------------------------------------------------------

EXPERT_LEAVES = ("we_gate", "we_up", "we_down")


def _init(spec, key):
    def experts(layer, k, dense_init):
        import jax

        for name in ("w_gate", "w_up", "w_down"):
            del layer[name]
        kr, kg, ku, kd = jax.random.split(k, 4)
        layer["router"] = dense_init(kr, (spec.E, spec.D))
        layer["we_gate"] = dense_init(kg, (spec.E, spec.F, spec.D))
        layer["we_up"] = dense_init(ku, (spec.E, spec.F, spec.D))
        layer["we_down"] = dense_init(kd, (spec.E, spec.D, spec.F))

    return dense._init(spec, key, layer_extra=experts)


def shardings(spec, n_dev: int):
    """Expert-stacked leaves split on the expert axis over ``n_dev`` chips,
    everything else replicated (a pytree of shardings like the params)."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:n_dev]), ("ep",))
    shapes = jax.eval_shape(lambda k: _init(spec, k), key_from_seed(0))
    return jax.tree_util.tree_map_with_path(
        lambda path, _: NamedSharding(
            mesh, P("ep") if path[-1].key in EXPERT_LEAVES else P()), shapes)


def init_params(spec, seed: int):
    import jax

    n_dev = len(jax.devices())
    if n_dev == 1 or spec.E % n_dev:
        return jax.jit(lambda k: _init(spec, k))(key_from_seed(seed))
    return jax.jit(lambda k: _init(spec, k),
                   out_shardings=shardings(spec, n_dev))(key_from_seed(seed))


def init_state(opt, params):
    """The optimizer's state, laid out as ``AdamW.init`` lays it out, each
    moment placed where its parameter lives (``opt.init`` would put every
    zero on the first chip)."""
    import jax
    import jax.numpy as jnp

    zeros = lambda dt: jax.tree_util.tree_map(
        lambda p: jnp.zeros_like(p, dtype=dt.jax), params)
    return {"m": zeros(opt.state_dtype), "v": zeros(opt.v_dtype),
            "step": jnp.zeros((), jnp.float32)}


# ---------------------------------------------------------------------------
# the plain float32 reference
# ---------------------------------------------------------------------------

def route(x, layer, spec, mm):
    """x (T, D) normed -> (router probabilities (T, E), the k choices'
    weights (T, k) and experts (T, k))."""
    import jax

    probs = jax.nn.softmax(mm(x, layer["router"]), -1)
    topv, topi = jax.lax.top_k(probs, spec.k)
    return probs, topv / topv.sum(-1, keepdims=True), topi


def ref_moe(x, layer, spec, mm, kept=None, held=None):
    """x (T, D) normed -> (feed-forward output (T, D), mean router
    probability (E,), first-choice one-hot mean (E,)). ``kept`` (T, k) marks
    the assignments that found a slot (all, where None). ``held`` (E,) marks
    the experts these rows can reach: all of them, unless a test plants the
    fault of an exchange between chips left out."""
    import jax
    import jax.numpy as jnp

    probs, topv, topi = route(x, layer, spec, mm)
    if kept is not None:
        topv = topv * kept
    weight = sum(jax.nn.one_hot(topi[:, j], spec.E) * topv[:, j:j + 1]
                 for j in range(spec.k))                           # (T, E)
    if held is not None:
        weight = weight * held
    over = lambda w: jax.vmap(lambda we: mm(x, we))(w)             # (E, T, .)
    hidden = jax.nn.silu(over(layer["we_gate"])) * over(layer["we_up"])
    out = jax.vmap(mm)(hidden, layer["we_down"])                   # (E, T, D)
    first = jax.nn.one_hot(topi[:, 0], spec.E).mean(0)
    return jnp.einsum("te,etd->td", weight, out), probs.mean(0), first


def _forward(params, tokens, spec, precision, remat, kept=None, held=None):
    """tokens (T,) -> (final hidden (T, D), [(P, first) a layer]); ``kept``
    is a list, a layer, of the assignments that found a slot."""
    import jax
    import jax.numpy as jnp

    mm = dense.matmul(precision)

    def block(h, layer, kept_l):
        h = h + dense.ref_attention(
            dense._norm(h, layer["attn_norm"], spec.eps), layer, spec, mm, remat)
        out, p, first = ref_moe(dense._norm(h, layer["mlp_norm"], spec.eps),
                                layer, spec, mm, kept_l, held)
        return h + out, (p, first)

    if remat:
        block = jax.checkpoint(block)
    h = params["tok_embedding"].astype(jnp.float32)[tokens]
    stats = []
    for i, layer in enumerate(params["layers"]):
        h, s = block(h, layer, None if kept is None else kept[i])
        stats.append(s)
    return dense._norm(h, params["norm_f"], spec.eps), stats, mm


def ref_logits(params, tokens, spec, precision="float32", remat=False):
    h, _, mm = _forward(params, tokens, spec, precision, remat)
    return mm(h, params["lm_head"])


def capacity(spec, tokens_a_chip: int) -> int:
    d = spec.dispatch
    if d["rule"] == "dropless":
        return tokens_a_chip
    return max(1, min(tokens_a_chip, math.ceil(
        tokens_a_chip * d["capacity_factor"] * spec.k / spec.E)))


def slots_kept(choices, spec, shards: int):
    """choices (rows, T, k) experts -> (rows, T, k) 1.0 where the assignment
    finds a slot. Each chip's rows form one queue, in order; the j-th choices
    queue behind whatever the earlier choices kept (GShard)."""
    import numpy as np

    rows, T, k = choices.shape
    per = max(1, rows // shards)
    cap = capacity(spec, per * T)
    kept = np.zeros(choices.shape, np.float32)
    for r in range(rows // per):
        ch = choices[r * per:(r + 1) * per].reshape(per * T, k)
        counts = np.zeros(spec.E, np.int64)
        out = np.zeros((per * T, k), np.float32)
        for j in range(k):
            onehot = np.eye(spec.E, dtype=np.int64)[ch[:, j]]
            pos = (np.cumsum(onehot, 0) - onehot + counts)[np.arange(per * T),
                                                            ch[:, j]]
            out[:, j] = pos < cap
            counts += (onehot * out[:, j:j + 1].astype(np.int64)).sum(0)
        kept[r * per:(r + 1) * per] = out.reshape(per, T, k)
    return kept


def ref_step_context(params, tokens, spec, precision="float32", shards=1):
    """What of a step spans its rows (tokens (rows, T)), a row: the
    assignments that find a slot on the row's chip, and the batch's
    first-choice fractions (the part of the load-balancing term that no
    gradient flows through), a layer each."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    mm = dense.matmul(precision)
    rows = len(tokens)
    embed = jax.jit(lambda p, t: p["tok_embedding"].astype(jnp.float32)[t])

    @jax.jit
    def choose(layer, h):
        h = h + dense.ref_attention(
            dense._norm(h, layer["attn_norm"], spec.eps), layer, spec, mm, False)
        _, _, topi = route(dense._norm(h, layer["mlp_norm"], spec.eps),
                           layer, spec, mm)
        return h, topi

    @jax.jit
    def feed_forward(layer, h, kept):
        out, _, _ = ref_moe(dense._norm(h, layer["mlp_norm"], spec.eps),
                            layer, spec, mm, kept)
        return h + out

    hs = [embed(params, t) for t in tokens]
    kept, first = [], []
    for li, layer in enumerate(params["layers"]):
        hs, choices = map(list, zip(*[choose(layer, h) for h in hs]))
        choices = np.stack([np.asarray(c) for c in choices])
        kept.append(slots_kept(choices, spec, shards))
        first.append(jnp.asarray(
            np.eye(spec.E, dtype=np.float32)[choices[..., 0]].mean((0, 1))))
        if li + 1 < spec.L:
            hs = [feed_forward(layer, h, kept[-1][i]) for i, h in enumerate(hs)]
    return [{"kept": [jnp.asarray(k[i]) for k in kept], "first": first}
            for i in range(rows)]


def ref_nll_sum(params, tokens, targets, spec, precision="float32",
                context=None, chip=None):
    """One row's part of the step's loss, times the step's token count:
    summed NLL plus the row's share of the load-balancing term. ``chip`` =
    (this row's chip, chips) plants the fault of the exchange left out: the
    row reaches only the experts its own chip holds."""
    import jax
    import jax.numpy as jnp

    held = None
    if chip is not None:
        rank, n = chip
        held = (jnp.arange(spec.E) // (spec.E // n) == rank).astype(jnp.float32)
    h, stats, mm = _forward(params, tokens, spec, precision, True,
                            context["kept"], held)
    logp = jax.nn.log_softmax(mm(h, params["lm_head"]), -1)
    nll = -jnp.take_along_axis(logp, targets[:, None], 1).sum()
    aux = sum(spec.E * spec.aux_coef * jnp.sum(f * p)
              for f, (p, _) in zip(context["first"], stats))
    return nll + tokens.shape[0] * aux


# ---------------------------------------------------------------------------
# operations, from shapes
# ---------------------------------------------------------------------------

def num_params(spec) -> int:
    layer = (dense._attn_weights(spec) + spec.E * spec.D
             + spec.E * dense._mlp_weights(spec) + 2 * spec.D)
    return spec.L * layer + 2 * spec.V * spec.D + spec.D


def train_flops_per_token(spec, seq: int) -> float:
    """Forward + backward, no recomputation, ACTIVE weights only: attention,
    the router, k experts a token (not the capacity the dispatch pads to),
    the head, and causal attention."""
    active = (dense._attn_weights(spec) + spec.E * spec.D
              + spec.k * dense._mlp_weights(spec))
    dense_flops = 2.0 * (spec.L * active + spec.V * spec.D)
    attn = spec.L * 2 * 2.0 * spec.H * spec.hd * (seq + 1) / 2
    return 3.0 * (dense_flops + attn)


def train_window_counts(spec, steps: int, batch: int, seq: int) -> dict:
    return {"step_train": {"flops": train_flops_per_token(spec, seq)
                           * steps * batch * seq}}
