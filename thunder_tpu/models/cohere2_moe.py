"""The ``cohere2_moe`` family on the serving path (Command A+ class).

One layer, ``x`` of shape (T, D); ``LN`` is mean-centred, without bias, and
there is ONE norm a layer (``use_parallel_block``)::

    h  = LN(x)              LN(v) = (v - mean(v)) / sqrt(var(v) + eps) * g
    q  = h Wq (H heads x hd)    k = h Wk (KV x hd)    v = h Wv (KV x hd)
    window layer (types 0, 1, 2 of each 4): q, k rotated, theta 50,000,
        interleaved pairs (2i, 2i+1) ("rope_gptj"); key j visible to query
        i iff i - W < j <= i
    global layer (type 3 of each 4): no positional encoding; j <= i
    a  = softmax(q k^T / sqrt(hd) + mask) v, H / KV queries a KV head
    attn = concat(a) Wo
    s  = sigmoid(h Wr)  (n_experts scores);  P = the top_k largest;
    w_e = s_e / sum_P s                                   (norm_topk_prob)
    E(h; G, U, D) = (silu(h G) * (h U)) D
    routed = sum_{e in P} w_e E_e(h)
    shared = (1 / n_shared) sum_j E(h; shared_j)
    x' = x + attn + routed + shared
    logits = LN_f(x_L) Emb^T * logit_scale                (tied embedding)

**The chip's share.** The router keeps its published width and its
``top_k`` picks and normalises over all of them; this chip HOLDS the experts
``held_start .. held_start + n_held - 1`` and adds only their part of
``routed``. What the experts held elsewhere would have added is left out
(the deployment's exchange would bring it; no code stands in for it), and
the partial sum goes on to the next layer. The embedding is the slice of
the vocabulary held here; logits and sampling are over that slice.

Parameter tree (``benchmark/families/cohere2_moe.py::init_params`` lays the
same one out)::

    tok_embedding (V, D), norm_f (D,),
    layers[i]: norm (D,), wq (H*hd, D), wk, wv (KV*hd, D), wo (D, H*hd),
               router (n_experts, D),
               w_gate, w_up (n_held + n_shared, F, D),
               w_down (n_held + n_shared, D, F)    # the shared experts last

The experts ride in ONE stack because one grouped kernel
(``nn.moe_experts``) runs them: a shared expert is an expert every row is
assigned to with weight ``1 / n_shared``.
"""

from __future__ import annotations

from dataclasses import dataclass

from thunder_tpu import ops
from thunder_tpu.core import dtypes, prims
from thunder_tpu.core.fusion_passes import _record_block
from thunder_tpu.ops import nn as tnn
from thunder_tpu.serving.description import (FULL, CacheKind,
                                             ModelDescription, write_pages)
from thunder_tpu.serving.sampling import sample_tokens


@dataclass(frozen=True)
class Cohere2MoeConfig:
    name: str = "tiny-cohere2-moe"
    vocab_size: int = 512               # the rows of the vocabulary held here
    dim: int = 64
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: int = 2
    head_dim: int = 16
    window: int = 8
    # per-layer kinds, "window" | "full"; the published pattern is three
    # window layers to one global layer
    layer_types: tuple = ("window", "window", "window", "full")
    expert_dim: int = 32
    n_experts: int = 16                 # the router's width (published)
    top_k: int = 4
    held_start: int = 0                 # first expert held here
    n_held: int = 4
    n_shared: int = 2
    rope_theta: float = 50000.0
    norm_eps: float = 1e-5
    logit_scale: float = 1.0
    max_seq_len: int = 256
    dtype: dtypes.dtype = dtypes.float32

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads

    def serving_description(self, n_layers: int | None = None):
        return Cohere2MoeDescription(self, n_layers)


def _norm(x, g, eps):
    return ops.layer_norm(x, (x.shape[-1],), weight=g, eps=eps)


def _rope_interleaved(x, cos, sin):
    """x (..., hd), pairs (2i, 2i+1) rotated by angle i; cos/sin broadcast
    against (..., hd/2)."""
    hd = x.shape[-1]
    pairs = ops.reshape(x, tuple(x.shape[:-1]) + (hd // 2, 2))
    even, odd = pairs[..., 0], pairs[..., 1]
    out = ops.stack([ops.sub(ops.mul(even, cos), ops.mul(odd, sin)),
                     ops.add(ops.mul(odd, cos), ops.mul(even, sin))], -1)
    return ops.reshape(out, x.shape)


def _rope_tables(cfg, pos, dtype):
    """cos/sin of shape ``pos.shape + (hd/2,)``."""
    hd = cfg.head_dim
    idx = ops.convert_element_type(ops.arange(hd // 2), dtypes.float32)
    inv = ops.pow(cfg.rope_theta, ops.true_divide(ops.mul(idx, -2.0), float(hd)))
    ang = ops.mul(ops.unsqueeze(ops.convert_element_type(pos, dtypes.float32),
                                -1), inv)
    return (ops.convert_element_type(ops.cos(ang), dtype),
            ops.convert_element_type(ops.sin(ang), dtype))


def _write_rows(pool, rows, flat_positions):
    """Every slot's K/V row into the paged pool, one ROW a slot and KV head.

    ``pool``: (KV, P, ps, hd); ``rows``: (S, KV, 1, hd); ``flat_positions``:
    (S,) int32 of page*ps+offset (0, the scratch page, for idle and replay
    slots: duplicates there are benign). The pool is indexed as (KV*P*ps)
    rows of ``hd``, so the backend scatters S*KV windows of ``hd`` elements.
    ``ops.nn.decode_row_write`` (the Llama path, where the decode megakernel
    absorbs it) scatters element by element: on the chip that is 32,768
    serial updates a pool, 2.9 ms (PR 35's first traced run), where this
    form issues 256."""
    KV, P, ps, hd = pool.shape
    S = rows.shape[0]
    idx = ops.add(ops.unsqueeze(flat_positions, 0),
                  ops.unsqueeze(ops.mul(ops.arange(KV), P * ps), 1))  # (KV,S)
    src = ops.transpose(ops.squeeze(rows, 2), (1, 0, 2))             # (KV,S,hd)
    out = ops.index_put(ops.reshape(pool, (KV * P * ps, hd)),
                        (ops.reshape(idx, (KV * S,)),),
                        ops.reshape(src, (KV * S, hd)))
    return ops.reshape(out, (KV, P, ps, hd))


def _heads(x, w, n, hd):
    """(B, T, D) -> (B, n, T, hd)."""
    B, T = x.shape[0], x.shape[1]
    return ops.transpose(ops.reshape(ops.linear(x, w), (B, T, n, hd)),
                         (0, 2, 1, 3))


def route(x2, layer, cfg):
    """Sigmoid top-k routing of rows ``x2`` (N, D) over ALL the experts, in
    float32, then the assignments as ``nn.moe_experts`` takes them: LOCAL
    ids (N, top_k + n_shared + 1) — a pick held elsewhere keeps an id outside
    ``[0, n_held)`` and adds nothing — and combine weights, the shared
    experts' columns at ``1 / n_shared`` each, and a last column of weight
    0 that gives every held expert a row (see below). A layer with a
    ``router_bias`` (n_experts,) picks the top_k of ``scores + bias`` and
    weighs them by their scores alone. Also the routing's
    counts (int32 scalars): held experts hit, local picks, the largest load
    of one held expert, and the held experts that STREAM (hit, or given a
    row by the last column: what the kernel's bytes follow)."""
    N = x2.shape[0]
    f32 = dtypes.float32
    scores = ops.sigmoid(ops.linear(ops.convert_element_type(x2, f32),
                                    ops.convert_element_type(layer["router"],
                                                             f32)))
    bias = layer.get("router_bias")
    if bias is None:
        vals, idx = ops.topk(scores, cfg.top_k, -1)
    else:
        # a correction bias (DeepSeek-V3's) chooses the picks and weighs
        # none of them: the weights are the picks' own scores
        _, idx = ops.topk(ops.add(scores, ops.convert_element_type(bias, f32)),
                          cfg.top_k, -1)
        vals = ops.take_along_axis(scores, idx, 1)
    weights = ops.true_divide(vals, ops.sum(vals, -1, keepdim=True))
    local = ops.sub(ops.convert_element_type(idx, dtypes.int32),
                    cfg.held_start)
    held = ops.logical_and(ops.ge(local, 0), ops.lt(local, cfg.n_held))
    # a pick held elsewhere must not alias a shared expert's stack index
    local = ops.where(held, local, ops.full_like(local, -1))
    load = ops.sum(ops.convert_element_type(
        ops.eq(ops.unsqueeze(local, 2),
               ops.reshape(ops.arange(cfg.n_held), (1, 1, cfg.n_held))),
        dtypes.int32), (0, 1))                                    # (n_held,)
    warmed = ops.lt(ops.arange(cfg.n_held), N)
    n_of = lambda mask: ops.sum(ops.convert_element_type(mask, dtypes.int32))
    counts = ops.stack([
        n_of(ops.gt(load, 0)), ops.sum(load), ops.amax(load),
        n_of(ops.logical_or(ops.gt(load, 0), warmed))], 0)
    shared_ids = ops.expand_to(
        ops.reshape(ops.add(ops.arange(cfg.n_shared), cfg.n_held),
                    (1, cfg.n_shared)), (N, cfg.n_shared))
    shared_w = ops.full((N, cfg.n_shared), 1.0 / cfg.n_shared, dtype=f32)
    # one more column, weight 0: row r is also assigned held expert r, so
    # EVERY held expert has a row and streams every step. At a decode
    # step's 32 rows the routing alone hits 13-14 of 16, and how many
    # follows the weights (the seed): the step's bytes then moved 1.4-1.7%
    # between seeds (PERF.md §6, PR 35). A step that always streams what
    # the chip holds is also what the deployment's 256 rows a step do.
    # The price falls on a batch below the deployment's load: a lone row
    # hits one held expert in two steps and streams min(N, n_held) here.
    rows = ops.arange(N)
    warm = ops.unsqueeze(ops.where(ops.lt(rows, cfg.n_held), rows,
                                   ops.full_like(rows, -1)), 1)
    return (ops.cat([local, shared_ids, warm], 1),
            ops.cat([weights, shared_w, ops.full((N, 1), 0.0, dtype=f32)], 1),
            counts)


def experts(x, layer, cfg):
    """routed (this chip's share) + shared, for rows ``x`` (B, T, D); and
    the routing's counts. ``x`` is the layer's one norm, which the attention
    reads too: the ``block`` decision record names that here, where the
    layer is built (no fused form exists for a planner to choose)."""
    B, T, D = x.shape
    stacks = (layer["w_gate"], layer["w_up"], layer["w_down"])
    _record_block(
        "parallel-block",
        "one layer_norm feeds the attention composite and moe_experts; one "
        "residual add of three terms; kept as two launches",
        {"shared_row_bytes": int(x.numel * x.dtype.bytes),
         "weight_bytes": int(sum(w.numel * w.dtype.bytes for w in stacks))},
        op="nn.moe_experts")
    x2 = ops.reshape(x, (B * T, D))
    ids, weights, counts = route(x2, layer, cfg)
    y = tnn.moe_experts(x2, layer["w_gate"], layer["w_up"], layer["w_down"],
                        ids, weights, act="silu")
    return ops.reshape(y, (B, T, D)), counts


def _lane_pad_pages(tokens: int, ps: int) -> int:
    """Pages to add below a gathered context of ``tokens`` keys so that its
    length is whole 128-key blocks (what the flash forward streams); the
    added keys lie below the window and are masked like any other."""
    if 128 % ps:
        return 0
    return ((-tokens) % 128) // ps


class Cohere2MoeDescription(ModelDescription):
    """Window layers keep a ring of ``ceil(W / page) + 1`` pages, global
    layers their whole context; the engine gives each kind its own pool and
    block table."""

    def __init__(self, cfg: Cohere2MoeConfig, n_layers: int | None = None):
        self.cfg = cfg
        self.n_layers = n_layers if n_layers is not None else cfg.n_layers
        types = cfg.layer_types[: self.n_layers]
        kinds = []
        for t in types:
            kind = FULL if t == "full" else CacheKind("window", cfg.window)
            if kind not in kinds:
                kinds.append(kind)
        # a full kind first: the engine's legacy single-cache views
        # (``engine.cache``, ``Request.pages``) are the first kind's
        kinds.sort(key=lambda k: k.window is not None)
        self.cache_kinds = tuple(kinds)
        self.layer_kinds = tuple(
            kinds.index(FULL if t == "full" else CacheKind("window", cfg.window))
            for t in types)

    # -- one layer's attention, decode ----------------------------------------
    def _decode_attn(self, x, layer, kind, g, kv, bt, lengths, write_pos,
                     rope):
        cfg = self.cfg
        S = x.shape[0]
        q = _heads(x, layer["wq"], cfg.n_heads, cfg.head_dim)
        k = _heads(x, layer["wk"], cfg.kv_heads, cfg.head_dim)
        v = _heads(x, layer["wv"], cfg.kv_heads, cfg.head_dim)
        if kind.window is not None:
            # the pairs' stride-2 split is made on the projections. Without
            # the barrier the TPU's layout assignment moves it into the
            # dot's weight, and every step copies wq (134 MB at the published
            # widths, 0.52 ms on a v5e) and wk into row pairs; the ops, and
            # so the values, are the same either way. A prefill chunk's rows
            # keep the split unaided. tests/test_mosaic_aot.py checks the
            # compiled step.
            q, k = prims.opt_barrier(q, k)
            q = _rope_interleaved(q, *rope)
            k = _rope_interleaved(k, *rope)
        kp = _write_rows(kv["k"], k, write_pos)
        vp = _write_rows(kv["v"], v, write_pos)
        attn = tnn.paged_decode_attention(q, kp, vp, bt, lengths,
                                          window=kind.window)
        attn = ops.reshape(ops.transpose(attn, (0, 2, 1, 3)),
                           (S, 1, cfg.n_heads * cfg.head_dim))
        return ops.linear(attn, layer["wo"]), {"k": kp, "v": vp}

    def decode(self, geoms, params, tokens, block_tables, lengths, write_pos,
               pools, temps, top_ks, top_ps, rng):
        """As :meth:`LlamaDescription.decode`, with a block table and a
        write position per cache kind; returns ``aux["moe_route"]`` (L, 4)
        int32 beside the tokens: a layer's held experts hit, local picks,
        largest load and held experts streamed."""
        cfg = self.cfg
        h = ops.embedding(tokens, params["tok_embedding"])            # (S,1,D)
        cos, sin = _rope_tables(cfg, ops.sub(lengths, 1), h.dtype)    # (S,hd/2)
        S = tokens.shape[0]
        shape = (S, 1, 1, cfg.head_dim // 2)
        rope = (ops.reshape(cos, shape), ops.reshape(sin, shape))
        new_pools, routes = [], []
        for layer, kv, ki in zip(params["layers"], pools, self.layer_kinds):
            x = _norm(h, layer["norm"], cfg.norm_eps)
            attn, kv = self._decode_attn(
                x, layer, self.cache_kinds[ki], geoms[ki], kv,
                block_tables[ki], lengths, write_pos[ki], rope)
            new_pools.append(kv)
            moe, counts = experts(x, layer, cfg)
            routes.append(counts)
            h = ops.add(ops.add(h, attn), moe)
        h = _norm(h, params["norm_f"], cfg.norm_eps)
        logits = ops.squeeze(ops.linear(h, params["tok_embedding"]), 1)
        if cfg.logit_scale != 1.0:
            logits = ops.mul(logits, cfg.logit_scale)
        toks = sample_tokens(logits, temps, top_ks, top_ps, rng)
        return toks, logits, new_pools, {"moe_route": ops.stack(routes, 0)}

    # -- one layer's attention, a prefill chunk -------------------------------
    def _prefill_attn(self, x, layer, kind, g, kv, bt, pos0, page_writes,
                      rope):
        """The chunk's rows against keys gathered in position order. A
        global layer writes the chunk's pages, then gathers the whole table;
        a window layer gathers the ``ceil(W / page)`` pages below the chunk
        from the ring FIRST (the chunk's pages take recycled ring slots),
        attends them with the chunk's own K/V appended, then writes."""
        cfg = self.cfg
        ps, hd = g.page_size, cfg.head_dim
        C = x.shape[1]
        q = ops.squeeze(_heads(x, layer["wq"], cfg.n_heads, hd), 0)   # (H,C,hd)
        k = ops.squeeze(_heads(x, layer["wk"], cfg.kv_heads, hd), 0)
        v = ops.squeeze(_heads(x, layer["wv"], cfg.kv_heads, hd), 0)
        if kind.window is not None:
            q = _rope_interleaved(q, *rope)
            k = _rope_interleaved(k, *rope)
        flat = (g.kv_heads, g.num_pages * ps, g.head_dim)
        paged = (g.kv_heads, g.num_pages, ps, g.head_dim)
        write = lambda pool, rows: ops.reshape(
            write_pages(ops.reshape(pool, flat), rows, page_writes, ps), paged)
        table = ops.getitem(bt, 0)                                     # (npg,)
        if kind.window is None:
            kp, vp = write(kv["k"], k), write(kv["v"], v)
            n_ctx, k_pos0 = g.pages_per_request, 0
            pages = table
            gather = lambda pool: ops.reshape(
                prims.take(pool, pages, 1), (g.kv_heads, n_ctx * ps, hd))
            keys, vals = gather(kp), gather(vp)
        else:
            R = g.pages_per_request
            n_ctx = -(-kind.window // ps)
            n_ctx += _lane_pad_pages(n_ctx * ps + C, ps)
            # logical pages [pos0 / ps - n_ctx, pos0 / ps), each in ring
            # column (page mod R); below page 0 the column holds no key of
            # this request, and nn.banded_attention masks positions < 0
            first = ops.sub(ops.floor_divide(pos0, ps), n_ctx)
            cols = ops.remainder(
                ops.add(ops.add(ops.arange(n_ctx), first), R * (n_ctx // R + 1)),
                R)
            pages = prims.take(table, cols, 0)
            gather = lambda pool, rows: ops.cat(
                [ops.reshape(prims.take(pool, pages, 1),
                             (g.kv_heads, n_ctx * ps, hd)), rows], 1)
            keys, vals = gather(kv["k"], k), gather(kv["v"], v)
            k_pos0 = ops.sub(pos0, n_ctx * ps)
            kp, vp = write(kv["k"], k), write(kv["v"], v)
        attn = tnn.banded_attention(q, keys, vals, pos0, k_pos0,
                                    window=kind.window)
        attn = ops.reshape(ops.transpose(attn, (1, 0, 2)),
                           (1, C, cfg.n_heads * hd))
        return ops.linear(attn, layer["wo"]), {"k": kp, "v": vp}

    def prefill(self, geoms, params, tokens, block_tables, lengths,
                page_writes, pools):
        """As :meth:`LlamaDescription.prefill`; ``page_writes[kind]`` holds
        the scratch position 0 for a chunk page that kind does not keep (a
        window kind skips pages of pure padding and pages the window has
        already left)."""
        cfg = self.cfg
        C = tokens.shape[1]
        h = ops.embedding(tokens, params["tok_embedding"])            # (1,C,D)
        pos0 = ops.sub(ops.getitem(lengths, 0), C)
        rope = _rope_tables(cfg, ops.add(ops.arange(C), pos0), h.dtype)
        new_pools = []
        for layer, kv, ki in zip(params["layers"], pools, self.layer_kinds):
            x = _norm(h, layer["norm"], cfg.norm_eps)
            attn, kv = self._prefill_attn(
                x, layer, self.cache_kinds[ki], geoms[ki], kv,
                block_tables[ki], pos0, page_writes[ki], rope)
            new_pools.append(kv)
            moe, _ = experts(x, layer, cfg)
            h = ops.add(ops.add(h, attn), moe)
        return new_pools

    def on_decode_aux(self, obs, aux: dict, step: int) -> None:
        for i, (hit, picks, top, streamed) in enumerate(
                aux["moe_route"].tolist()):
            obs.event("moe_route", step=step, layer=i, hit=hit,
                      local_picks=picks, max_load=top, streamed=streamed)
            obs.inc("moe.experts_hit", hit)
            obs.inc("moe.local_picks", picks)
