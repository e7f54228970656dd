"""The ``solar_open2`` family on the serving path (Solar-Open2 class): a
hybrid of Kimi-delta-rule linear-attention layers and softmax GQA layers
without positions, every layer with sigmoid-routed experts.

One layer, ``x`` the residual, every norm an RMSNorm::

    h  = RMSNorm(x);  x' = x + Mix(h)
    u  = RMSNorm(x');  x'' = x' + SharedE(u) + sum_{e in P} w_e E_e(u)
    E(u) = (silu(u G) * (u U)) D                 (shared and routed alike)
    router: s = sigmoid(u Wr) (n_experts); P = top_k of s + b_corr (the
        bias picks only); w_e = s_e / sum_P s    (routed_scaling_factor 1)

    GQA layer (``gqa``): no positions, an elementwise output gate
        a   = softmax(q k^T / sqrt(hd) + causal) v   (H q / KV heads)
        Mix = (a * sigmoid(h Wgate)) Wo
    KDA layer (``kda``), per head (dk = dv):
        q, k, v = silu(causal_depthwise_conv4(h Wq | Wk | Wv))
        q, k    = L2norm(q), L2norm(k);  q *= 1 / sqrt(dk)
        beta    = 2 sigmoid(h Wb)                 (negative eigenvalues: 0..2)
        g       = -exp(A_log) softplus((h Wf_a) Wf_b + dt_bias)  (log decay)
        S_t     = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1}
                  + beta_t k_t v_t^T;   o_t = S_t^T q_t
        Mix     = (RMSNorm_head(o) * sigmoid((h Wg_a) Wg_b)) Wo
    logits = RMSNorm_f(x_L) Wlm^T                 (untied head)

(Kimi Linear, arXiv:2510.26692; the gated delta rule, arXiv:2412.06464.)

**Caches.** A GQA layer keeps its whole context in pages (kind ``full``);
a KDA layer keeps a float32 state ``S`` (H, dk, dv) and the last four
inputs of its convolution, one row a slot (kind ``state``,
``serving/description.py``). A prefill chunk reads the slot's row (zero on
a request's first chunk), takes in its prompt tokens and writes it back; a
decode step takes its token into the rows that decode. A slot's first
decode row re-feeds the last prompt token, whose state prefill already
holds: its ``o = S^T q`` is read from the state as it stands, the
convolution from the four inputs kept, and neither is written.

**The chip's share**, as in ``models/cohere2_moe.py``: the router keeps its
published width and ``top_k`` picks; this chip holds the experts
``held_start .. held_start + n_held - 1`` and adds only their part; the
vocabulary rows held here are the embedding's and the head's. The routing
and the expert kernel are ``cohere2_moe.route`` and ``nn.moe_experts``.

Parameter tree (``benchmark/families/solar_open2.py::init_params`` lays the
same one out)::

    tok_embedding (V, D), norm_f (D,), lm_head (V, D),
    layers[i]: attn_norm, ffn_norm (D,), router (n_experts, D),
               router_bias (n_experts,) float32,
               w_gate, w_up (n_held + n_shared, F, D),
               w_down (n_held + n_shared, D, F)          # the shared last
      gqa:     wq (H*hd, D), wk, wv (KV*hd, D), wo (D, H*hd), wg (H*hd, D)
      kda:     wq, wk, wv (Hk*dk, D), wo (D, Hk*dk), conv (3*Hk*dk, 4),
               wb (Hk, D), wf_a (r, D), wf_b (Hk*dk, r), wg_a (r, D),
               wg_b (Hk*dk, r), a_log (Hk,), dt_bias (Hk*dk,), o_norm (dk,)
"""

from __future__ import annotations

import math
from dataclasses import dataclass

from thunder_tpu import ops
from thunder_tpu.core import dtypes, prims
from thunder_tpu.models.cohere2_moe import (Cohere2MoeDescription, _heads,
                                            _write_rows, route)
from thunder_tpu.ops import nn as tnn
from thunder_tpu.serving.description import (FULL, STATE, ModelDescription,
                                             write_pages)
from thunder_tpu.serving.sampling import sample_tokens


@dataclass(frozen=True)
class SolarOpen2Config:
    name: str = "tiny-solar-open2"
    vocab_size: int = 512               # the rows of the vocabulary held here
    dim: int = 64
    n_layers: int = 4
    # per-layer kinds, "gqa" | "kda"; the published period is gqa, kda x 3
    layer_types: tuple = ("gqa", "kda", "kda", "kda")
    n_heads: int = 8                    # GQA query heads
    n_kv_heads: int = 2
    head_dim: int = 16
    kda_heads: int = 4
    kda_head_dim: int = 16              # dk = dv
    kda_conv: int = 4
    kda_rank: int = 8                   # the decay's and gate's low rank
    expert_dim: int = 32
    n_experts: int = 16                 # the router's width (published)
    top_k: int = 4
    held_start: int = 0
    n_held: int = 4
    n_shared: int = 1
    norm_eps: float = 1e-5
    max_seq_len: int = 256
    dtype: dtypes.dtype = dtypes.float32

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads

    def serving_description(self, n_layers: int | None = None):
        return SolarOpen2Description(self, n_layers)


def _moe(u, layer, cfg):
    """Shared + this chip's routed experts for rows ``u`` (B, T, D), and
    the routing's counts."""
    B, T, D = u.shape
    u2 = ops.reshape(u, (B * T, D))
    ids, weights, counts = route(u2, layer, cfg)
    y = tnn.moe_experts(u2, layer["w_gate"], layer["w_up"], layer["w_down"],
                        ids, weights, act="silu")
    return ops.reshape(y, (B, T, D)), counts


def _kda_inputs(cfg, x2, layer, window):
    """Per-head q, k, v (N, H, d), log decay g (N, H, dk) and beta (N, H),
    all float32, from rows ``x2`` (N, D) and their convolution windows
    ``window`` (N, conv, 3*H*dk) of pre-convolution inputs."""
    f32 = dtypes.float32
    N = x2.shape[0]
    H, dk = cfg.kda_heads, cfg.kda_head_dim
    w = ops.convert_element_type(ops.transpose(layer["conv"], (1, 0)), f32)
    y = ops.silu(ops.sum(ops.mul(ops.convert_element_type(window, f32),
                                 ops.unsqueeze(w, 0)), 1))      # (N, 3*H*dk)
    split = lambda i: ops.reshape(ops.narrow(y, 1, i * H * dk, H * dk),
                                  (N, H, dk))
    l2 = lambda a: ops.mul(a, ops.rsqrt(ops.add(
        ops.sum(ops.mul(a, a), -1, keepdim=True), 1e-6)))
    q = ops.mul(l2(split(0)), 1.0 / math.sqrt(dk))
    k, v = l2(split(1)), split(2)
    beta = ops.mul(ops.sigmoid(ops.convert_element_type(
        ops.linear(x2, layer["wb"]), f32)), 2.0)
    decay = ops.convert_element_type(
        ops.linear(ops.linear(x2, layer["wf_a"]), layer["wf_b"]), f32)
    rate = ops.softplus(ops.add(decay, ops.convert_element_type(
        layer["dt_bias"], f32)))
    a = ops.exp(ops.convert_element_type(layer["a_log"], f32))
    g = ops.neg(ops.mul(ops.reshape(rate, (N, H, dk)),
                        ops.reshape(a, (1, H, 1))))
    return q, k, v, g, beta


def _kda_out(cfg, x2, layer, o):
    """(RMSNorm_head(o) * sigmoid(gate)) Wo for rows ``x2`` (N, D) and the
    delta rule's ``o`` (N, H, dv) float32 -> (N, D)."""
    N = x2.shape[0]
    H, dk = cfg.kda_heads, cfg.kda_head_dim
    gate = ops.sigmoid(ops.convert_element_type(
        ops.linear(ops.linear(x2, layer["wg_a"]), layer["wg_b"]),
        dtypes.float32))
    o = ops.mul(ops.rms_norm(o, ops.convert_element_type(
        layer["o_norm"], dtypes.float32), eps=cfg.norm_eps),
        ops.reshape(gate, (N, H, dk)))
    o = ops.convert_element_type(ops.reshape(o, (N, H * dk)), x2.dtype)
    return ops.linear(o, layer["wo"])


def _qkv_rows(x2, layer):
    return ops.cat([ops.linear(x2, layer["wq"]), ops.linear(x2, layer["wk"]),
                    ops.linear(x2, layer["wv"])], -1)           # (N, 3*H*dk)


class SolarOpen2Description(ModelDescription):
    """GQA layers keep their whole context in pages (kind ``full``); KDA
    layers a state row a slot (kind ``state``)."""

    def __init__(self, cfg: SolarOpen2Config, n_layers: int | None = None):
        self.cfg = cfg
        self.n_layers = n_layers if n_layers is not None else cfg.n_layers
        types = cfg.layer_types[: self.n_layers]
        if "gqa" not in types:
            raise ValueError(f"{cfg.name}: the layers served hold no GQA "
                             f"layer; the engine's first cache kind is paged")
        # the paged kind first: the engine's legacy single-cache views
        # (``engine.cache``, ``Request.pages``) are the first kind's
        self.cache_kinds = (FULL, STATE) if "kda" in types else (FULL,)
        self.layer_kinds = tuple(self.cache_kinds.index(
            FULL if t == "gqa" else STATE) for t in types)

    def state_shapes(self) -> dict:
        cfg = self.cfg
        H, dk = cfg.kda_heads, cfg.kda_head_dim
        return {"s": ((H, dk, dk), dtypes.float32.jax),
                "conv": ((cfg.kda_conv, 3 * H * dk), cfg.dtype.jax)}

    # -- decode -------------------------------------------------------------
    def _gqa_decode(self, x, layer, kv, bt, lengths, write_pos):
        cfg = self.cfg
        S = x.shape[0]
        q = _heads(x, layer["wq"], cfg.n_heads, cfg.head_dim)
        k = _heads(x, layer["wk"], cfg.kv_heads, cfg.head_dim)
        v = _heads(x, layer["wv"], cfg.kv_heads, cfg.head_dim)
        kp = _write_rows(kv["k"], k, write_pos)
        vp = _write_rows(kv["v"], v, write_pos)
        attn = tnn.paged_decode_attention(q, kp, vp, bt, lengths)
        attn = ops.reshape(ops.transpose(attn, (0, 2, 1, 3)),
                           (S, 1, cfg.n_heads * cfg.head_dim))
        gate = ops.sigmoid(ops.linear(x, layer["wg"]))
        return ops.linear(ops.mul(attn, gate), layer["wo"]), \
            {"k": kp, "v": vp}

    def _kda_decode(self, x, layer, st, update):
        """``update`` (S,) int32: 1 where the row takes its token in."""
        cfg = self.cfg
        S, D = x.shape[0], x.shape[2]
        x2 = ops.reshape(x, (S, D))
        tail = st["conv"]                                  # (S, conv, 3*H*dk)
        shifted = ops.cat([ops.narrow(tail, 1, 1, cfg.kda_conv - 1),
                           ops.unsqueeze(ops.convert_element_type(
                               _qkv_rows(x2, layer), tail.dtype), 1)], 1)
        live = ops.ne(update, 0)
        window = ops.where(ops.expand_to(ops.reshape(live, (S, 1, 1)),
                                         tail.shape), shifted, tail)
        q, k, v, g, beta = _kda_inputs(cfg, x2, layer, window)
        o, s1 = tnn.kda_decode(q, k, v, g, beta, st["s"], update)
        out = _kda_out(cfg, x2, layer, o)
        return ops.reshape(out, (S, 1, D)), {"s": s1, "conv": window}

    def decode(self, geoms, params, tokens, block_tables, lengths, write_pos,
               pools, temps, top_ks, top_ps, rng):
        """As :meth:`LlamaDescription.decode`, with a block table and a
        write position per cache kind (the state kind's ``write_pos`` is
        its update mask); returns ``aux["moe_route"]`` (L, 4) int32 beside
        the tokens, as ``cohere2_moe`` does."""
        cfg = self.cfg
        h = ops.embedding(tokens, params["tok_embedding"])            # (S,1,D)
        new_pools, routes = [], []
        for layer, kv, ki in zip(params["layers"], pools, self.layer_kinds):
            x = ops.rms_norm(h, layer["attn_norm"], eps=cfg.norm_eps)
            if self.cache_kinds[ki] is STATE:
                mix, kv = self._kda_decode(x, layer, kv, write_pos[ki])
            else:
                mix, kv = self._gqa_decode(x, layer, kv, block_tables[ki],
                                           lengths, write_pos[ki])
            new_pools.append(kv)
            h = ops.add(h, mix)
            moe, counts = _moe(ops.rms_norm(h, layer["ffn_norm"],
                                            eps=cfg.norm_eps), layer, cfg)
            routes.append(counts)
            h = ops.add(h, moe)
        h = ops.rms_norm(h, params["norm_f"], eps=cfg.norm_eps)
        logits = ops.squeeze(ops.linear(h, params["lm_head"]), 1)
        toks = sample_tokens(logits, temps, top_ks, top_ps, rng)
        return toks, logits, new_pools, {"moe_route": ops.stack(routes, 0)}

    # -- a prefill chunk ----------------------------------------------------
    def _gqa_prefill(self, x, layer, g, kv, bt, pos0, page_writes):
        """The chunk's pages written, then its rows against the whole
        table gathered in position order."""
        cfg = self.cfg
        ps, hd = g.page_size, cfg.head_dim
        C = x.shape[1]
        q = ops.squeeze(_heads(x, layer["wq"], cfg.n_heads, hd), 0)   # (H,C,hd)
        k = ops.squeeze(_heads(x, layer["wk"], cfg.kv_heads, hd), 0)
        v = ops.squeeze(_heads(x, layer["wv"], cfg.kv_heads, hd), 0)
        flat = (g.kv_heads, g.num_pages * ps, g.head_dim)
        paged = (g.kv_heads, g.num_pages, ps, g.head_dim)
        write = lambda pool, rows: ops.reshape(
            write_pages(ops.reshape(pool, flat), rows, page_writes, ps), paged)
        kp, vp = write(kv["k"], k), write(kv["v"], v)
        table = ops.getitem(bt, 0)
        gather = lambda pool: ops.reshape(
            prims.take(pool, table, 1),
            (g.kv_heads, g.pages_per_request * ps, hd))
        attn = tnn.banded_attention(q, gather(kp), gather(vp), pos0, 0)
        attn = ops.reshape(ops.transpose(attn, (1, 0, 2)),
                           (1, C, cfg.n_heads * hd))
        gate = ops.sigmoid(ops.linear(x, layer["wg"]))
        return ops.linear(ops.mul(attn, gate), layer["wo"]), \
            {"k": kp, "v": vp}

    def _kda_prefill(self, x, layer, st, control):
        """``control`` (3,) int32: the slot, the chunk's prompt tokens, and
        whether the slot's row carries over (0: a request's first chunk,
        from zero)."""
        cfg = self.cfg
        C, D = x.shape[1], x.shape[2]
        H, dk, K = cfg.kda_heads, cfg.kda_head_dim, cfg.kda_conv
        slot = ops.reshape(ops.getitem(control, 0), (1,))
        n_valid = ops.getitem(control, 1)
        carried = ops.ne(ops.getitem(control, 2), 0)
        row = lambda a: ops.squeeze(prims.take(a, slot, 0), 0)
        s0 = row(st["s"])
        s0 = ops.where(ops.expand_to(carried, s0.shape), s0,
                       ops.zeros_like(s0))
        tail = row(st["conv"])                                   # (K, 3*H*dk)
        tail = ops.where(ops.expand_to(carried, tail.shape), tail,
                         ops.zeros_like(tail))
        x2 = ops.reshape(x, (C, D))
        full = ops.cat([tail, ops.convert_element_type(_qkv_rows(x2, layer),
                                                       tail.dtype)], 0)
        # token j's window: inputs j-K+1 .. j, at rows 1+j .. K+j of ``full``
        window = ops.stack([ops.narrow(full, 0, 1 + i, C) for i in range(K)],
                           1)                                    # (C,K,3Hdk)
        q, k, v, g, beta = _kda_inputs(cfg, x2, layer, window)
        heads = lambda a: ops.transpose(a, (1, 0, 2))
        o, s1 = tnn.kda_chunk(heads(q), heads(k), heads(v), heads(g),
                              ops.transpose(beta, (1, 0)), s0, n_valid)
        out = _kda_out(cfg, x2, layer, heads(o))
        # the last K inputs up to the chunk's last prompt token
        new_tail = prims.take(full, ops.add(ops.arange(K), n_valid), 0)
        zero = ops.full((), 0, dtype=dtypes.int32)
        put = lambda pool, v: prims.dynamic_update_slice(
            pool, ops.unsqueeze(v, 0),
            (ops.getitem(control, 0),) + (zero,) * v.ndim)
        return ops.reshape(out, (1, C, D)), \
            {"s": put(st["s"], s1), "conv": put(st["conv"], new_tail)}

    def prefill(self, geoms, params, tokens, block_tables, lengths,
                page_writes, pools):
        """As :meth:`LlamaDescription.prefill`; the state kind's
        ``page_writes`` entry is its control ``[slot, tokens, carried]``."""
        cfg = self.cfg
        C = tokens.shape[1]
        h = ops.embedding(tokens, params["tok_embedding"])            # (1,C,D)
        pos0 = ops.sub(ops.getitem(lengths, 0), C)
        new_pools = []
        for layer, kv, ki in zip(params["layers"], pools, self.layer_kinds):
            x = ops.rms_norm(h, layer["attn_norm"], eps=cfg.norm_eps)
            if self.cache_kinds[ki] is STATE:
                mix, kv = self._kda_prefill(x, layer, kv, page_writes[ki])
            else:
                mix, kv = self._gqa_prefill(x, layer, geoms[ki], kv,
                                            block_tables[ki], pos0,
                                            page_writes[ki])
            new_pools.append(kv)
            h = ops.add(h, mix)
            moe, _ = _moe(ops.rms_norm(h, layer["ffn_norm"], eps=cfg.norm_eps),
                          layer, cfg)
            h = ops.add(h, moe)
        return new_pools

    on_decode_aux = Cohere2MoeDescription.on_decode_aux
