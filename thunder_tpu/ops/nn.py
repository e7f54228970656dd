"""NN composite operations.

Each composite is a Symbol with a stable ``nn.*`` id and a prim
decomposition, so operator executors can claim it whole — the Pallas
flash-attention executor claims ``nn.scaled_dot_product_attention`` exactly
like the reference's cudnnex/sdpaex claim torch SDPA
(``thunder/executors/sdpaex.py:239``, ``cudnnex.py:425``), and the fused
cross-entropy kernel claims ``nn.cross_entropy`` (apex/triton analog).
"""

from __future__ import annotations

import math

from thunder_tpu.core import dtypes, prims
from thunder_tpu.core.baseutils import check, canonicalize_dim
from thunder_tpu.core.proxies import TensorProxy, pyval
import thunder_tpu.ops as ops
from thunder_tpu.ops import _tensor_like, opsymbol


@opsymbol(id="nn.embedding")
def embedding(ids, weight, padding_idx=None):
    check(weight.ndim == 2, lambda: (
        f"embedding: weight must be (num_embeddings, dim), got "
        f"{weight.ndim}-D {tuple(weight.shape)}"))
    out = prims.take(weight, ids, 0)
    return out


@opsymbol(id="nn.one_hot")
def one_hot(ids, num_classes: int):
    check(int(num_classes) > 0,
          lambda: f"one_hot: num_classes must be positive, got {num_classes}")
    classes = prims.iota(num_classes, dtype=dtypes.int32, device=ids.device)
    classes = ops.expand_to(classes, ids.shape + (num_classes,))
    expanded = ops.expand_to(ops.unsqueeze(ids, -1), ids.shape + (num_classes,))
    return ops.convert_element_type(ops.eq(expanded, classes), dtypes.int32)


@opsymbol(id="nn.layer_norm")
def layer_norm(a, normalized_shape, weight=None, bias=None, eps: float = 1e-5):
    _tensor_like(a, "layer_norm")
    nd = len(normalized_shape)
    check(tuple(a.shape[-nd:]) == tuple(normalized_shape),
          lambda: f"layer_norm: normalized_shape {normalized_shape} != trailing dims of {a.shape}")
    dims = tuple(range(a.ndim - nd, a.ndim))
    x = ops.convert_element_type(a, dtypes.float32) if a.dtype in (dtypes.float16, dtypes.bfloat16) else a
    m = ops.mean(x, dims, keepdim=True)
    centered = ops.sub(x, m)
    v = ops.mean(ops.mul(centered, centered), dims, keepdim=True)
    out = ops.mul(centered, ops.rsqrt(ops.add(v, eps)))
    if weight is not None:
        out = ops.mul(out, weight)
    if bias is not None:
        out = ops.add(out, bias)
    return ops.convert_element_type(out, a.dtype)


@opsymbol(id="nn.rms_norm")
def rms_norm(a, weight=None, eps: float = 1e-5, dim: int = -1):
    d = canonicalize_dim(a.ndim, dim)
    x = ops.convert_element_type(a, dtypes.float32) if a.dtype in (dtypes.float16, dtypes.bfloat16) else a
    ms = ops.mean(ops.mul(x, x), d, keepdim=True)
    out = ops.mul(x, ops.rsqrt(ops.add(ms, eps)))
    out = ops.convert_element_type(out, a.dtype)
    if weight is not None:
        out = ops.mul(out, weight)
    return out


@opsymbol(id="nn.rms_norm_residual")
def rms_norm_residual(residual, a, weight=None, eps: float = 1e-5):
    """Fused residual-add + RMS norm: ``h = residual + a`` followed by
    ``rms_norm(h, weight)``; returns ``(h, normed)``.

    Both values escape in a transformer block — ``h`` is the residual
    stream, ``normed`` feeds the next projection — so the epilogue fusion
    pass rewrites ``add → rms_norm`` chains into this composite (which the
    Pallas executor claims as one kernel, saving an HBM round-trip of the
    residual stream per block). Unclaimed, this decomposition is exactly the
    unfused ops, so numerics are identical either way.
    """
    _tensor_like(a, "rms_norm_residual")
    check(tuple(residual.shape) == tuple(a.shape),
          lambda: f"rms_norm_residual: residual shape {tuple(residual.shape)} "
                  f"!= input shape {tuple(a.shape)}")
    h = ops.add(residual, a)
    return h, rms_norm(h, weight, eps=eps)


_LINEAR_ACT_FNS = {
    "relu": lambda y: ops.relu(y),
    "silu": lambda y: ops.silu(y),
    "gelu": lambda y: ops.gelu(y),
    "gelu_tanh": lambda y: ops.gelu(y, approximate="tanh"),
}


@opsymbol(id="nn.linear_act")
def linear_act(a, w, bias=None, act: str = "relu"):
    """Fused ``act(a @ w.T + bias)`` — the GEMM-epilogue composite the
    pattern pass builds from ``nn.linear → activation`` chains, claimable by
    the Pallas executor as a single kernel (activation applied to the f32
    accumulator tile while it is still in VMEM). ``act`` is one of
    ``relu | silu | gelu | gelu_tanh``."""
    check(act in _LINEAR_ACT_FNS,
          lambda: f"linear_act: unknown activation {act!r}; known: {sorted(_LINEAR_ACT_FNS)}")
    return _LINEAR_ACT_FNS[act](ops.linear(a, w, bias))


_SUBBLOCK_ACTS = ("silu", "relu", "gelu", "gelu_tanh")


@opsymbol(id="nn.mlp_subblock")
def mlp_subblock(residual, x, w_norm, w_gate, w_up, w_down, *,
                 act: str = "silu", eps: float = 1e-5):
    """Whole transformer MLP sub-block as ONE claimable composite — the
    block planner's megakernel unit (``core/fusion_passes.block_fusion_pass``)::

        h   = residual + x          # attention-out residual add
        n   = rms_norm(h, w_norm)
        y   = act(n @ w_gate.T) * (n @ w_up.T)
        out = h + y @ w_down.T      # second residual add

    The decomposition below is exactly the unfused chain (that is the
    numerics contract when nothing claims it, and the per-op XLA fallback
    the quarantine/bisection machinery recompiles to); the Pallas executor
    claims it as a single streamed-weight kernel that keeps every interior
    value (n, the gate/up pre-activations, the SwiGLU product) in VMEM.
    It is a serving kernel: the planner builds it on inference traces only,
    and it has no VJP rule — under autodiff it differentiates through this
    decomposition like any other composite (XLA's GEMMs, both directions).
    """
    _tensor_like(x, "mlp_subblock")
    check(tuple(residual.shape) == tuple(x.shape) and residual.dtype == x.dtype,
          lambda: f"mlp_subblock: residual {tuple(residual.shape)}/{residual.dtype} "
                  f"does not match x {tuple(x.shape)}/{x.dtype}")
    check(act in _SUBBLOCK_ACTS,
          lambda: f"mlp_subblock: unknown activation {act!r}; known: {_SUBBLOCK_ACTS}")
    h = ops.add(residual, x)
    n = rms_norm(h, w_norm, eps=eps)
    gate = _LINEAR_ACT_FNS[act](ops.linear(n, w_gate))
    up = ops.linear(n, w_up)
    return ops.add(h, ops.linear(ops.mul(gate, up), w_down))


@opsymbol(id="nn.dropout")
def dropout(a, p: float = 0.5, training: bool = True):
    p = float(pyval(p))
    if not training or p == 0.0:
        return a
    check(0.0 <= p < 1.0, lambda: f"dropout p={p} out of range")
    keep = ops.bernoulli(1.0 - p, a.shape, dtype=a.dtype)
    return ops.mul(ops.mul(a, keep), 1.0 / (1.0 - p))


@opsymbol(id="nn.mse_loss")
def mse_loss(input, target, reduction: str = "mean"):
    d = ops.sub(input, target)
    sq = ops.mul(d, d)
    if reduction == "mean":
        return ops.mean(sq)
    if reduction == "sum":
        return ops.sum(sq)
    return sq


@opsymbol(id="nn.cross_entropy")
def cross_entropy(logits, target, weight=None, ignore_index: int = -100,
                  reduction: str = "mean", label_smoothing: float = 0.0):
    """logits: (N, C) or (N, C, ...) float; target: (N, ...) int class ids."""
    check(weight is None, "cross_entropy: class weights not yet supported")
    C = logits.shape[1] if logits.ndim > 1 else logits.shape[0]
    expect = (logits.shape[0],) + tuple(logits.shape[2:]) if logits.ndim > 1 else ()
    check(tuple(target.shape) == expect, lambda: (
        f"cross_entropy: target shape {tuple(target.shape)} does not match "
        f"logits {tuple(logits.shape)} — expected {expect} "
        f"(N, d1, ...; the class dim C={C} is dim 1 of logits)"))
    if logits.ndim > 2:
        # (N, C, d1..) -> (N*d1.., C)
        perm = (0,) + tuple(range(2, logits.ndim)) + (1,)
        logits = ops.reshape(ops.transpose(logits, perm), (-1, C))
        target = ops.reshape(target, (-1,))
    logp = ops.log_softmax(logits, -1)
    tgt = ops.convert_element_type(target, dtypes.int32)
    safe_tgt = ops.where(ops.eq(tgt, ignore_index), ops.zeros_like(tgt), tgt)
    picked = ops.squeeze(prims.take_along_axis(logp, ops.unsqueeze(safe_tgt, -1), 1), (1,))
    nll = ops.neg(picked)
    if label_smoothing > 0.0:
        smooth = ops.neg(ops.mean(logp, -1))
        nll = ops.add(ops.mul(nll, 1.0 - label_smoothing), ops.mul(smooth, label_smoothing))
    valid = ops.ne(tgt, ignore_index)
    nll = ops.where(valid, nll, ops.zeros_like(nll))
    if reduction == "none":
        return nll
    if reduction == "sum":
        return ops.sum(nll)
    count = ops.sum(ops.convert_element_type(valid, dtypes.float32))
    return ops.true_divide(ops.sum(nll), ops.maximum(count, 1.0))


@opsymbol(id="nn.sdpa_fwd")
def sdpa_fwd(q, k, v, is_causal: bool = False, scale: float | None = None):
    """Attention forward that also returns the row logsumexp — the
    flash-attention forward contract. Claimable by the Pallas executor; the
    decomposition below is the always-available fallback."""
    E = q.shape[-1]
    L, S = q.shape[-2], k.shape[-2]
    scale = scale if scale is not None else 1.0 / math.sqrt(E)
    qf = ops.convert_element_type(q, dtypes.float32)
    kf = ops.convert_element_type(k, dtypes.float32)
    vf = ops.convert_element_type(v, dtypes.float32)
    scores = ops.mul(ops.matmul(qf, kf.mT), scale)
    if is_causal:
        causal = ops.tril_mask(L, S, 0, device=q.device)
        scores = ops.where(ops.expand_to(causal, scores.shape), scores,
                           ops.full_like(scores, -float("inf")))
    m = ops.amax(scores, -1, keepdim=True)
    e = ops.exp(ops.sub(scores, m))
    l = ops.sum(e, -1, keepdim=True)
    out = ops.matmul(ops.true_divide(e, l), vf)
    lse = ops.add(ops.squeeze(m, -1), ops.log(ops.squeeze(l, -1)))
    return ops.convert_element_type(out, q.dtype), lse


@opsymbol(id="nn.ce_fwd")
def ce_fwd(logits, target, ignore_index: int = -100):
    """Per-row negative log-likelihood + logsumexp (fused-CE forward
    contract; Pallas-claimable). logits: (N, C); target: (N,) int."""
    lf = ops.convert_element_type(logits, dtypes.float32)
    m = ops.amax(lf, -1, keepdim=True)
    lse = ops.add(ops.squeeze(m, -1), ops.log(ops.sum(ops.exp(ops.sub(lf, m)), -1)))
    tgt = ops.convert_element_type(target, dtypes.int32)
    safe_tgt = ops.where(ops.eq(tgt, ignore_index), ops.zeros_like(tgt), tgt)
    picked = ops.squeeze(prims.take_along_axis(lf, ops.unsqueeze(safe_tgt, -1), 1), (1,))
    nll = ops.sub(lse, picked)
    valid = ops.ne(tgt, ignore_index)
    nll = ops.where(valid, nll, ops.zeros_like(nll))
    return nll, lse


@opsymbol(id="nn.sdpa_bwd")
def sdpa_bwd(g, q, k, v, out, lse, is_causal: bool = False, scale: float | None = None):
    """Flash-attention backward contract: recompute probabilities from
    (q, k, lse), produce (dq, dk, dv). Claimable by the Pallas executor;
    this decomposition is the always-available fallback."""
    E = q.shape[-1]
    L, S = q.shape[-2], k.shape[-2]
    scale_v = scale if scale is not None else 1.0 / math.sqrt(E)
    gf = ops.convert_element_type(g, dtypes.float32)
    qf = ops.convert_element_type(q, dtypes.float32)
    kf = ops.convert_element_type(k, dtypes.float32)
    vf = ops.convert_element_type(v, dtypes.float32)
    of = ops.convert_element_type(out, dtypes.float32)
    scores = ops.mul(ops.matmul(qf, kf.mT), scale_v)
    if is_causal:
        causal = ops.tril_mask(L, S, 0, device=q.device)
        scores = ops.where(ops.expand_to(causal, scores.shape), scores,
                           ops.full_like(scores, -float("inf")))
    p = ops.exp(ops.sub(scores, ops.unsqueeze(lse, -1)))
    dv = ops.matmul(p.mT, gf)
    dp = ops.matmul(gf, vf.mT)
    delta = ops.sum(ops.mul(gf, of), -1, keepdim=True)  # rowsum(dO * O)
    ds = ops.mul(ops.mul(p, ops.sub(dp, delta)), scale_v)
    dq = ops.matmul(ds, kf)
    dk = ops.matmul(ds.mT, qf)
    return (ops.convert_element_type(dq, q.dtype),
            ops.convert_element_type(dk, k.dtype),
            ops.convert_element_type(dv, v.dtype))


@opsymbol(id="nn.paged_decode_attention")
def paged_decode_attention(q, k_pages, v_pages, block_tables, lengths,
                           scale: float | None = None,
                           window: int | None = None):
    """Ragged-batch attention over a block-allocated paged KV cache — the
    serving engine's decode attention (``thunder_tpu/serving/``): every
    request in the batch reads its OWN context length through its OWN block
    table, in one launch, from one shared page pool.

    - ``q``: ``(B, n_heads, T, hd)`` — the T newest positions per request
      (decode T=1; chunked prefill passes the whole chunk).
    - ``k_pages`` / ``v_pages``: ``(kv_heads, num_pages, page_size, hd)`` —
      the shared per-layer page pools.
    - ``block_tables``: ``(B, pages_per_request)`` int32 page ids; entries
      beyond a request's allocation must still be valid pool indices (the
      allocator reserves page 0 as the never-read scratch page).
    - ``lengths``: ``(B,)`` int32 context length per request INCLUDING the
      T new rows — row ``r`` sits at absolute position ``lengths - T + r``
      and attends keys ``j <= lengths - T + r`` (ragged causal masking).

    Head grouping is GQA-contiguous, matching ``models/llama.forward_step``:
    query head ``h`` reads kv head ``h // (n_heads // kv_heads)``.

    ``window=W`` (decode rows only, T == 1) makes the table a RING: logical
    page ``p`` of a request sits in column ``p % pages_per_request``, the
    host having recycled the pages that fell wholly out of the window, and
    key ``j`` is visible to the row at position ``i = lengths - 1`` iff
    ``i - W < j <= i``. The walk starts at the first page the window still
    reaches, so its cost does not grow with the context.

    The decomposition below (gather pages through the block table, mask,
    softmax) is the always-available XLA fallback — the Pallas executor
    claims the T==1 decode case as a single scalar-prefetch kernel that
    streams each request's pages by block-table lookup, and the kernel
    quarantine / bisection machinery falls back here per-op with equal
    numerics.
    """
    _tensor_like(q, "paged_decode_attention")
    check(q.ndim == 4 and k_pages.ndim == 4 and v_pages.ndim == 4,
          lambda: f"paged_decode_attention: q must be (B, H, T, hd) and pages "
                  f"(kv_heads, P, page, hd); got q {tuple(q.shape)}, "
                  f"k_pages {tuple(k_pages.shape)}")
    B, H, T, hd = q.shape
    KV, P, ps, hd2 = k_pages.shape
    check(hd2 == hd and tuple(v_pages.shape) == tuple(k_pages.shape),
          lambda: f"paged_decode_attention: page pools {tuple(k_pages.shape)} / "
                  f"{tuple(v_pages.shape)} do not match head_dim {hd}")
    check(H % KV == 0,
          lambda: f"paged_decode_attention: n_heads {H} not divisible by "
                  f"kv_heads {KV}")
    check(block_tables.ndim == 2 and block_tables.shape[0] == B
          and lengths.ndim == 1 and lengths.shape[0] == B,
          lambda: f"paged_decode_attention: block_tables {tuple(block_tables.shape)}"
                  f" / lengths {tuple(lengths.shape)} do not match batch {B}")
    n_rep = H // KV
    npg = block_tables.shape[1]
    L = npg * ps
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    check(window is None or (T == 1 and int(window) >= 1),
          lambda: f"paged_decode_attention: window={window} takes decode "
                  f"rows (T == 1) over a ring table; got T={T} (a prefill "
                  f"chunk attends through nn.banded_attention)")

    # gather each request's context from the shared pools via its block
    # table: (KV, P, ps, hd) indexed along the page dim by the flattened
    # (B*npg,) table -> (KV, B, npg*ps, hd) -> (B, KV, L, hd)
    idx = ops.reshape(block_tables, (B * npg,))
    k = ops.transpose(ops.reshape(prims.take(k_pages, idx, 1),
                                  (KV, B, L, hd)), (1, 0, 2, 3))
    v = ops.transpose(ops.reshape(prims.take(v_pages, idx, 1),
                                  (KV, B, L, hd)), (1, 0, 2, 3))
    # grouped-query attention without materializing the expanded cache:
    # fold the group dim into q's row dim (forward_step's GQA recipe)
    qg = ops.reshape(q, (B, KV, n_rep * T, hd))
    qf = ops.convert_element_type(qg, dtypes.float32)
    kf = ops.convert_element_type(k, dtypes.float32)
    vf = ops.convert_element_type(v, dtypes.float32)
    scores = ops.mul(ops.matmul(qf, kf.mT), scale)        # (B, KV, n_rep*T, L)
    scores = ops.reshape(scores, (B, H, T, L))
    if window is None:
        # ragged causal mask: key j valid for row r iff j <= lengths - T + r
        col = ops.arange(L)                               # (L,)
        row_pos = ops.add(ops.unsqueeze(ops.sub(lengths, T), 1),
                          ops.unsqueeze(ops.arange(T), 0))  # (B, T)
        valid = ops.le(ops.unsqueeze(ops.unsqueeze(col, 0), 0),
                       ops.unsqueeze(row_pos, 2))         # (B, T, L)
    else:
        valid = ops.unsqueeze(
            _ring_valid(lengths, npg, ps, int(window)), 1)  # (B, 1, L)
    neg = ops.full((), float("-inf"), dtype=dtypes.float32)
    scores = ops.where(ops.expand_to(ops.unsqueeze(valid, 1), scores.shape),
                       scores, neg)
    probs = ops.softmax(scores, -1)
    attn = ops.matmul(ops.reshape(probs, (B, KV, n_rep * T, L)), vf)
    return ops.convert_element_type(ops.reshape(attn, (B, H, T, hd)), q.dtype)


def _ring_valid(lengths, npg: int, ps: int, window: int):
    """(B, npg * ps) mask of the ring slots a decode row may read. Column
    ``c`` of the ring holds the newest logical page ``p <= last`` with
    ``p % npg == c``; its token ``o`` sits at position ``p * ps + o``, and
    the row at ``lengths - 1`` sees positions in ``[lengths - window,
    lengths)`` (never below 0: a column no page has reached yet maps to a
    negative position)."""
    last = ops.unsqueeze(ops.floor_divide(ops.sub(lengths, 1), ps), 1)  # (B,1)
    col = ops.unsqueeze(ops.arange(npg), 0)                          # (1,npg)
    back = ops.remainder(ops.add(ops.sub(last, col), npg), npg)
    page = ops.sub(last, back)                                       # (B,npg)
    pos = ops.add(ops.unsqueeze(ops.mul(page, ps), 2),
                  ops.reshape(ops.arange(ps), (1, 1, ps)))           # (B,npg,ps)
    pos = ops.reshape(pos, (lengths.shape[0], npg * ps))
    ln = ops.unsqueeze(lengths, 1)
    lo = ops.maximum(ops.sub(ln, window), 0)
    return ops.logical_and(ops.ge(pos, lo), ops.lt(pos, ln))


def decode_row_write(pool_flat, rows, flat_positions):
    """Scatter every decode slot's K/V row into a flattened page pool in ONE
    replace-semantics scatter — the serving runner's K/V append, shared here
    so the ``nn.attn_subblock`` decomposition and ``serving/runner.py`` emit
    the IDENTICAL op sequence (the block planner's chain matcher and the
    per-op quarantine fallback both depend on that identity).

    ``pool_flat``: (KV, P*ps, hd); ``rows``: (S, KV, 1, hd);
    ``flat_positions``: (S,) int32 of ``page*ps + offset``. Idle slots all
    target position 0 (the reserved scratch page); duplicate indices there
    are benign (any write wins, nobody reads)."""
    S = rows.shape[0]
    src = ops.transpose(ops.squeeze(rows, 2), (1, 0, 2))       # (KV, S, hd)
    idx = ops.expand_to(ops.reshape(flat_positions, (1, S, 1)), src.shape)
    return prims.scatter(pool_flat, idx, src, 1)


_DECODE_T1 = ("decode-only composite (T == 1): every slot contributes one "
              "new row; the chunked-prefill path keeps the unfused ops")


@opsymbol(id="nn.attn_subblock")
def attn_subblock(h, w_norm, wq, wk, wv, wo, cos, sin, k_pages, v_pages,
                  block_tables, lengths, write_pos, *, eps: float = 1e-5,
                  scale: float | None = None):
    """Whole serving attention sub-block of one T==1 decode step as ONE
    claimable composite — the block planner's attention unit
    (``core/fusion_passes.block_fusion_pass`` attention walk)::

        x    = rms_norm(h, w_norm)
        q,k,v= rope(split_heads(x @ wq/wk/wv.T))   # v un-roped
        kp,vp= pools with this step's k/v rows scattered at write_pos
        attn = paged_decode_attention(q, kp, vp, block_tables, lengths)
        out  = merge_heads(attn) @ wo.T            # residual add stays outside

    Returns ``(out, kp, vp)`` — the out-projection (pre-residual; the
    ``h + out`` add belongs to the adjoining MLP sub-block, which is how
    the chaining stage fuses the two into ``nn.decode_layer``) and the
    updated page pools. The decomposition below is EXACTLY the op sequence
    ``serving/runner.py`` emits per layer (that is the numerics contract
    when nothing claims it, and the per-op XLA fallback quarantine/bisection
    recompiles to); the Pallas executor claims it as a single launch with
    the weights streamed through VMEM, the fresh K/V rows patched in from
    VMEM scratch, and block tables / lengths scalar-prefetched.
    """
    _tensor_like(h, "attn_subblock")
    B, T = h.shape[0], h.shape[1]
    check(T == 1, lambda: f"attn_subblock: {_DECODE_T1}; got T={T}")
    KV, P, ps, hd = k_pages.shape
    check(tuple(v_pages.shape) == tuple(k_pages.shape),
          lambda: f"attn_subblock: page pools {tuple(k_pages.shape)} / "
                  f"{tuple(v_pages.shape)} differ")
    H = wq.shape[0] // hd
    check(wq.shape[0] == H * hd and wk.shape[0] == KV * hd
          and tuple(wv.shape) == tuple(wk.shape)
          and wo.shape[1] == H * hd,
          lambda: f"attn_subblock: projection shapes wq {tuple(wq.shape)} / "
                  f"wk {tuple(wk.shape)} / wo {tuple(wo.shape)} do not agree "
                  f"with head_dim {hd}")
    from thunder_tpu.models.llama import _apply_rope

    x = rms_norm(h, w_norm, eps=eps)
    q = ops.transpose(ops.reshape(ops.linear(x, wq), (B, T, H, hd)),
                      (0, 2, 1, 3))
    k = ops.transpose(ops.reshape(ops.linear(x, wk), (B, T, KV, hd)),
                      (0, 2, 1, 3))
    v = ops.transpose(ops.reshape(ops.linear(x, wv), (B, T, KV, hd)),
                      (0, 2, 1, 3))
    q = _apply_rope(q, cos, sin)
    k = _apply_rope(k, cos, sin)
    flat = (KV, P * ps, hd)
    paged = (KV, P, ps, hd)
    kp = ops.reshape(decode_row_write(ops.reshape(k_pages, flat), k,
                                      write_pos), paged)
    vp = ops.reshape(decode_row_write(ops.reshape(v_pages, flat), v,
                                      write_pos), paged)
    attn = paged_decode_attention(q, kp, vp, block_tables, lengths,
                                  scale=scale)
    attn = ops.reshape(ops.transpose(attn, (0, 2, 1, 3)), (B, T, H * hd))
    return ops.linear(attn, wo), kp, vp


@opsymbol(id="nn.decode_layer")
def decode_layer(h, attn_norm, wq, wk, wv, wo, cos, sin, k_pages, v_pages,
                 block_tables, lengths, write_pos, mlp_norm, w_gate, w_up,
                 w_down, *, act: str = "silu", eps: float = 1e-5,
                 scale: float | None = None):
    """One whole transformer decode layer (T==1 serving path) as ONE
    claimable composite — the block planner's chaining unit: the attention
    sub-block plus the MLP sub-block, one Pallas launch per layer per
    decoded token when claimed. Returns ``(out, kp, vp)``.

    The decomposition is the two sub-block composites, which gives the
    quarantine/bisection machinery a LAYERED fallback: a quarantined
    ``pallas.decode_layer`` decomposes into ``nn.attn_subblock`` +
    ``nn.mlp_subblock`` (two launches, still fused); quarantining those too
    reaches the fully per-op XLA chain with equal numerics."""
    proj, kp, vp = attn_subblock(h, attn_norm, wq, wk, wv, wo, cos, sin,
                                 k_pages, v_pages, block_tables, lengths,
                                 write_pos, eps=eps, scale=scale)
    out = mlp_subblock(h, proj, mlp_norm, w_gate, w_up, w_down,
                       act=act, eps=eps)
    return out, kp, vp


@opsymbol(id="nn.fp8_linear")
def fp8_linear(a, w, x_scale=None, w_scale=None, bias=None, slot: int = -1):
    """FP8 linear (TransformerEngine analog, reference
    ``thunder/executors/transformer_engineex.py:181,351``): e4m3 quantized
    ``a @ w.T`` with f32 accumulation, dequantized by the scale product.
    Returns ``(out, amax_x, amax_w)`` — the amaxes feed the caller's
    delayed-scaling state update (``thunder_tpu.fp8``). ``x_scale``/
    ``w_scale`` of None selects just-in-time scaling."""
    from thunder_tpu.fp8 import E4M3_MAX

    amax_x = ops.amax(ops.abs(a))
    amax_w = ops.amax(ops.abs(w))
    sx = x_scale if x_scale is not None else ops.true_divide(E4M3_MAX, ops.maximum(amax_x, 1e-12))
    sw = w_scale if w_scale is not None else ops.true_divide(E4M3_MAX, ops.maximum(amax_w, 1e-12))
    aq = ops.convert_element_type(
        ops.clamp(ops.mul(ops.convert_element_type(a, dtypes.float32), sx), -E4M3_MAX, E4M3_MAX),
        dtypes.float8_e4m3fn)
    wq = ops.convert_element_type(
        ops.clamp(ops.mul(ops.convert_element_type(w, dtypes.float32), sw), -E4M3_MAX, E4M3_MAX),
        dtypes.float8_e4m3fn)
    out = prims.dot_general(aq, wq, contract_dims=((a.ndim - 1,), (1,)),
                            preferred_element_type=dtypes.float32)
    out = ops.true_divide(out, ops.mul(sx, sw))
    out = ops.convert_element_type(out, a.dtype)
    if bias is not None:
        out = ops.add(out, bias)
    # every (re)trace of this composite — initial emission, autograd replay,
    # checkpoint recompute — re-records its live amax proxies with the active
    # delayed-scaling context (last write wins)
    from thunder_tpu.fp8 import current_fp8

    ctx = current_fp8()
    if ctx is not None and slot >= 0:
        ctx._record(slot, amax_x, amax_w)
    return out, amax_x, amax_w


@opsymbol(id="nn.scaled_dot_product_attention")
def scaled_dot_product_attention(q, k, v, attn_mask=None, dropout_p: float = 0.0,
                                 is_causal: bool = False, scale: float | None = None):
    """q,k,v: (..., L, E) / (..., S, E). Decomposes to softmax(q k^T / sqrt(E)) v;
    the Pallas flash-attention executor claims this symbol on TPU. Under an
    active context-parallel scope, lowers to ring attention over the mesh
    axis (sequence sharded; K/V rotate via ppermute)."""
    _tensor_like(q, "scaled_dot_product_attention")
    from thunder_tpu.distributed import current_cp

    cp = current_cp()
    if cp is not None and attn_mask is None and dropout_p == 0.0:
        from thunder_tpu.distributed.ring import ring_attention

        axis, size = cp
        return ring_attention(q, k, v, axis, size, is_causal, scale)
    E = q.shape[-1]
    L, S = q.shape[-2], k.shape[-2]
    scale = scale if scale is not None else 1.0 / math.sqrt(E)
    qf = ops.convert_element_type(q, dtypes.float32)
    kf = ops.convert_element_type(k, dtypes.float32)
    vf = ops.convert_element_type(v, dtypes.float32)
    scores = ops.mul(ops.matmul(qf, kf.mT), scale)
    if is_causal:
        check(attn_mask is None, "cannot pass both is_causal and attn_mask")
        causal = ops.tril_mask(L, S, 0, device=q.device)
        scores = ops.where(ops.expand_to(causal, scores.shape), scores,
                           ops.full_like(scores, -float("inf")))
    if attn_mask is not None:
        if attn_mask.dtype.is_bool:
            scores = ops.where(ops.expand_to(attn_mask, scores.shape), scores,
                               ops.full_like(scores, -float("inf")))
        else:
            scores = ops.add(scores, attn_mask)
    probs = ops.softmax(scores, -1)
    if dropout_p > 0.0:
        probs = dropout(probs, dropout_p)
    out = ops.matmul(probs, vf)
    return ops.convert_element_type(out, q.dtype)


# ---------------------------------------------------------------------------
# flash-style custom VJP rules: save (q, k, v, out, lse) and recompute the
# attention matrix / softmax in backward instead of saving (B,H,L,S) probs.
# This is the memory contract of the reference's fused-attention executors
# (sdpaex/cudnnex fwd+bwd pairs, ``thunder/executors/sdpaex.py:239,312``),
# expressed as a trace-level grad rule; the fwd symbol is Pallas-claimable.
# ---------------------------------------------------------------------------

from thunder_tpu.core.transforms import register_vjp  # noqa: E402
from thunder_tpu.core.proxies import TensorProxy  # noqa: E402


@register_vjp("nn.scaled_dot_product_attention")
def _sdpa_vjp(q, k, v, attn_mask=None, dropout_p: float = 0.0, is_causal: bool = False,
              scale: float | None = None):
    from thunder_tpu.distributed import current_cp

    if attn_mask is not None or dropout_p > 0.0 or current_cp() is not None:
        return NotImplemented  # fall back to differentiating the decomposition
    out, lse = sdpa_fwd(q, k, v, is_causal, scale)

    def pullback(g):
        dq, dk, dv = sdpa_bwd(g, q, k, v, out, lse, is_causal, scale)
        return [(q, dq), (k, dk), (v, dv)]

    return out, pullback


@register_vjp("nn.rms_norm")
def _rms_norm_vjp(a, weight=None, eps: float = 1e-5, dim: int = -1):
    """Keep ``nn.rms_norm`` a composite in training traces (the autodiff
    replay otherwise decomposes it to prims, which hides it from both the
    Pallas claim and the epilogue fusion pattern). Saves only (a, weight) —
    the backward recomputes the row statistics, like the flash-attention
    rules recompute the softmax."""
    if dim not in (-1, a.ndim - 1):
        return NotImplemented
    out = rms_norm(a, weight, eps=eps, dim=dim)

    def pullback(g):
        # same dtype policy as the forward composite: widen to f32 only for
        # half precision — f32 stays f32, and f64 (x64 mode) keeps full
        # precision instead of silently narrowing
        wide = dtypes.float32 if a.dtype in (dtypes.float16, dtypes.bfloat16) else a.dtype
        x = ops.convert_element_type(a, wide)
        g32 = ops.convert_element_type(g, wide)
        ms = ops.mean(ops.mul(x, x), -1, keepdim=True)
        r = ops.rsqrt(ops.add(ms, eps))
        xhat = ops.mul(x, r)
        if weight is not None:
            gxhat = ops.mul(g32, ops.convert_element_type(weight, wide))
        else:
            gxhat = g32
        # d/dx of x·(mean(x²)+eps)^(-1/2): r·(ĝ − x̂·mean(ĝ·x̂))
        proj = ops.mean(ops.mul(gxhat, xhat), -1, keepdim=True)
        da = ops.mul(r, ops.sub(gxhat, ops.mul(xhat, proj)))
        pairs = [(a, ops.convert_element_type(da, a.dtype))]
        if weight is not None and isinstance(weight, TensorProxy):
            lead = tuple(range(a.ndim - 1))
            dw = ops.mul(g32, xhat) if not lead else ops.sum(ops.mul(g32, xhat), lead)
            pairs.append((weight, ops.convert_element_type(dw, weight.dtype)))
        return pairs

    return out, pullback


@register_vjp("nn.fp8_linear")
def _fp8_linear_vjp(a, w, x_scale=None, w_scale=None, bias=None, slot: int = -1):
    """TE-recipe backward (reference ``transformer_engineex.py:397-447``):
    dgrad = e5m2-quantized cotangent x e4m3 weight; wgrad accumulated in
    f32 from unquantized operands (TE's higher-precision wgrad default)."""
    from thunder_tpu.fp8 import E4M3_MAX, E5M2_MAX

    out, amax_x, amax_w = fp8_linear(a, w, x_scale, w_scale, bias, slot)
    sw = w_scale if w_scale is not None else ops.true_divide(E4M3_MAX, ops.maximum(amax_w, 1e-12))

    def pullback(g):
        gy = g[0] if isinstance(g, (tuple, list)) else g
        if gy is None:
            return []
        gf = ops.convert_element_type(gy, dtypes.float32)
        # dgrad in fp8: e5m2 cotangent (JIT scale) x e4m3 weight
        amax_g = ops.amax(ops.abs(gf))
        sg = ops.true_divide(E5M2_MAX, ops.maximum(amax_g, 1e-12))
        gq = ops.convert_element_type(
            ops.clamp(ops.mul(gf, sg), -E5M2_MAX, E5M2_MAX), dtypes.float8_e5m2)
        wq = ops.convert_element_type(
            ops.clamp(ops.mul(ops.convert_element_type(w, dtypes.float32), sw),
                      -E4M3_MAX, E4M3_MAX), dtypes.float8_e4m3fn)
        da = prims.dot_general(gq, wq, contract_dims=((gy.ndim - 1,), (0,)),
                               preferred_element_type=dtypes.float32)
        da = ops.true_divide(da, ops.mul(sg, sw))
        # wgrad in f32: flatten leading dims, g2^T @ a2
        N = 1
        for d in gy.shape[:-1]:
            N *= d
        g2 = ops.reshape(gf, (N, gy.shape[-1]))
        a2 = ops.reshape(ops.convert_element_type(a, dtypes.float32), (N, a.shape[-1]))
        dw = prims.dot_general(g2, a2, contract_dims=((0,), (0,)),
                               preferred_element_type=dtypes.float32)
        pairs = [(a, ops.convert_element_type(da, a.dtype)),
                 (w, ops.convert_element_type(dw, w.dtype))]
        if bias is not None and isinstance(bias, TensorProxy):
            db = ops.sum(g2, 0)
            pairs.append((bias, ops.convert_element_type(db, bias.dtype)))
        return pairs

    return (out, amax_x, amax_w), pullback


@register_vjp("nn.cross_entropy")
def _cross_entropy_vjp(logits, target, weight=None, ignore_index: int = -100,
                       reduction: str = "mean", label_smoothing: float = 0.0):
    if weight is not None or label_smoothing > 0.0 or logits.ndim != 2:
        return NotImplemented
    nll, lse = ce_fwd(logits, target, ignore_index)
    tgt = ops.convert_element_type(target, dtypes.int32)
    valid = ops.ne(tgt, ignore_index)
    validf = ops.convert_element_type(valid, dtypes.float32)
    count = ops.maximum(ops.sum(validf), 1.0)
    if reduction == "mean":
        loss = ops.true_divide(ops.sum(nll), count)
    elif reduction == "sum":
        loss = ops.sum(nll)
    elif reduction == "none":
        loss = nll
    else:
        return NotImplemented

    def pullback(g):
        C = logits.shape[-1]
        lf = ops.convert_element_type(logits, dtypes.float32)
        p = ops.exp(ops.sub(lf, ops.unsqueeze(lse, -1)))  # softmax rows
        safe_tgt = ops.where(ops.eq(tgt, ignore_index), ops.zeros_like(tgt), tgt)
        onehot = ops.convert_element_type(one_hot(safe_tgt, C), dtypes.float32)
        if reduction == "mean":
            row_scale = ops.mul(ops.true_divide(validf, count), g)
        elif reduction == "sum":
            row_scale = ops.mul(validf, g)
        else:
            row_scale = ops.mul(validf, g)
        dlogits = ops.mul(ops.sub(p, onehot), ops.unsqueeze(row_scale, -1))
        return [(logits, ops.convert_element_type(dlogits, logits.dtype))]

    return loss, pullback


# ---------------------------------------------------------------------------
# additional losses (reference: thunder/torch/__init__.py loss section)
# ---------------------------------------------------------------------------

def _reduce_loss(per_elem, reduction: str):
    if reduction == "none":
        return per_elem
    if reduction == "sum":
        return ops.sum(per_elem)
    check(reduction == "mean", lambda: f"unknown reduction {reduction!r}")
    return ops.mean(per_elem)


@opsymbol(id="nn.l1_loss")
def l1_loss(input, target, reduction: str = "mean"):
    return _reduce_loss(ops.abs(ops.sub(input, target)), reduction)


@opsymbol(id="nn.smooth_l1_loss")
def smooth_l1_loss(input, target, reduction: str = "mean", beta: float = 1.0):
    d = ops.abs(ops.sub(input, target))
    per = ops.where(ops.lt(d, beta),
                    ops.true_divide(ops.mul(ops.mul(d, d), 0.5), beta),
                    ops.sub(d, 0.5 * beta))
    return _reduce_loss(per, reduction)


@opsymbol(id="nn.huber_loss")
def huber_loss(input, target, reduction: str = "mean", delta: float = 1.0):
    d = ops.abs(ops.sub(input, target))
    per = ops.where(ops.lt(d, delta),
                    ops.mul(ops.mul(d, d), 0.5),
                    ops.mul(delta, ops.sub(d, 0.5 * delta)))
    return _reduce_loss(per, reduction)


@opsymbol(id="nn.binary_cross_entropy")
def binary_cross_entropy(input, target, weight=None, reduction: str = "mean"):
    eps = 1e-12
    per = ops.neg(ops.add(ops.mul(target, ops.log(ops.clamp(input, min=eps))),
                          ops.mul(ops.sub(1.0, target),
                                  ops.log(ops.clamp(ops.sub(1.0, input), min=eps)))))
    if weight is not None:
        per = ops.mul(per, weight)
    return _reduce_loss(per, reduction)


@opsymbol(id="nn.binary_cross_entropy_with_logits")
def binary_cross_entropy_with_logits(input, target, weight=None, pos_weight=None,
                                     reduction: str = "mean"):
    # stable: max(x,0) - x*t + log(1+exp(-|x|)), with optional pos_weight
    neg_abs = ops.neg(ops.abs(input))
    softplus_term = ops.log1p(ops.exp(neg_abs))
    if pos_weight is not None:
        log_weight = ops.add(1.0, ops.mul(ops.sub(pos_weight, 1.0), target))
        per = ops.add(ops.sub(ops.clamp(input, min=0.0), ops.mul(input, target)),
                      ops.mul(log_weight, softplus_term))
    else:
        per = ops.add(ops.sub(ops.clamp(input, min=0.0), ops.mul(input, target)),
                      softplus_term)
    if weight is not None:
        per = ops.mul(per, weight)
    return _reduce_loss(per, reduction)


@opsymbol(id="nn.kl_div")
def kl_div(input, target, reduction: str = "mean", log_target: bool = False):
    """input is log-probabilities (torch convention)."""
    if log_target:
        per = ops.mul(ops.exp(target), ops.sub(target, input))
    else:
        per = ops.xlogy(target, target)
        per = ops.sub(per, ops.mul(target, input))
    return _reduce_loss(per, reduction)


@opsymbol(id="nn.nll_loss")
def nll_loss(logp, target, weight=None, ignore_index: int = -100,
             reduction: str = "mean"):
    _tensor_like(logp, "nll_loss")
    check(weight is None, "nll_loss: class weights unsupported")
    tgt = ops.reshape(target, (-1,)) if target.ndim > 1 else target
    lp = ops.reshape(logp, (-1, logp.shape[-1])) if logp.ndim > 2 else logp
    safe = ops.where(ops.ne(tgt, ignore_index), tgt, ops.zeros_like(tgt))
    picked = ops.neg(ops.squeeze(ops.gather(lp, 1, ops.unsqueeze(safe, 1)), 1))
    valid = ops.ne(tgt, ignore_index)
    picked = ops.where(valid, picked, ops.zeros_like(picked))
    if reduction == "none":
        return ops.reshape(picked, tuple(target.shape))
    total = ops.sum(picked)
    if reduction == "sum":
        return total
    return ops.true_divide(total, ops.sum(ops.convert_element_type(valid, picked.dtype)))


# ---------------------------------------------------------------------------
# pooling — decomposed into static strided slices + elementwise reductions
# (fully differentiable through existing prims; XLA fuses the k*k slice
# reads into one windowed reduce on TPU)
# ---------------------------------------------------------------------------

def _pool_windows(a, kernel_size, stride, padding, pad_value, nd=2):
    """Sliding windows over the last ``nd`` spatial dims (1-, 2- or 3-d
    pooling share this decomposition)."""
    import itertools

    ks = (kernel_size,) * nd if isinstance(kernel_size, int) else tuple(kernel_size)
    if stride is None:
        stride = ks
    ss = (stride,) * nd if isinstance(stride, int) else tuple(stride)
    ps = (padding,) * nd if isinstance(padding, int) else tuple(padding)
    if any(ps):
        cfg = tuple((0, 0, 0) for _ in range(a.ndim - nd)) + tuple((p, p, 0) for p in ps)
        a = ops.pad(a, cfg, value=pad_value)
    outs = [(a.shape[a.ndim - nd + i] - ks[i]) // ss[i] + 1 for i in range(nd)]
    windows = []
    for offs in itertools.product(*(range(k) for k in ks)):
        idx = (Ellipsis,) + tuple(
            slice(offs[i], offs[i] + (outs[i] - 1) * ss[i] + 1, ss[i]) for i in range(nd))
        windows.append(ops.getitem(a, idx))
    return windows, math.prod(ks)


@opsymbol(id="nn.max_pool2d")
def max_pool2d(a, kernel_size, stride=None, padding=0):
    _tensor_like(a, "max_pool2d")
    windows, _ = _pool_windows(a, kernel_size, stride, padding, float("-inf"))
    out = windows[0]
    for w in windows[1:]:
        out = ops.maximum(out, w)
    return out


@opsymbol(id="nn.avg_pool2d")
def avg_pool2d(a, kernel_size, stride=None, padding=0, count_include_pad: bool = True):
    _tensor_like(a, "avg_pool2d")
    check(count_include_pad or padding == 0, "avg_pool2d: count_include_pad=False unsupported")
    windows, n = _pool_windows(a, kernel_size, stride, padding, 0.0)
    out = windows[0]
    for w in windows[1:]:
        out = ops.add(out, w)
    return ops.true_divide(out, float(n))


@opsymbol(id="nn.max_pool1d")
def max_pool1d(a, kernel_size, stride=None, padding=0):
    _tensor_like(a, "max_pool1d")
    windows, _ = _pool_windows(a, kernel_size, stride, padding, float("-inf"), nd=1)
    out = windows[0]
    for w in windows[1:]:
        out = ops.maximum(out, w)
    return out


@opsymbol(id="nn.max_pool3d")
def max_pool3d(a, kernel_size, stride=None, padding=0):
    _tensor_like(a, "max_pool3d")
    windows, _ = _pool_windows(a, kernel_size, stride, padding, float("-inf"), nd=3)
    out = windows[0]
    for w in windows[1:]:
        out = ops.maximum(out, w)
    return out


@opsymbol(id="nn.avg_pool1d")
def avg_pool1d(a, kernel_size, stride=None, padding=0, count_include_pad: bool = True):
    _tensor_like(a, "avg_pool1d")
    check(count_include_pad or padding == 0, "avg_pool1d: count_include_pad=False unsupported")
    windows, n = _pool_windows(a, kernel_size, stride, padding, 0.0, nd=1)
    out = windows[0]
    for w in windows[1:]:
        out = ops.add(out, w)
    return ops.true_divide(out, float(n))


@opsymbol(id="nn.avg_pool3d")
def avg_pool3d(a, kernel_size, stride=None, padding=0, count_include_pad: bool = True):
    _tensor_like(a, "avg_pool3d")
    check(count_include_pad or padding == 0, "avg_pool3d: count_include_pad=False unsupported")
    windows, n = _pool_windows(a, kernel_size, stride, padding, 0.0, nd=3)
    out = windows[0]
    for w in windows[1:]:
        out = ops.add(out, w)
    return ops.true_divide(out, float(n))


@opsymbol(id="nn.adaptive_avg_pool2d")
def adaptive_avg_pool2d(a, output_size):
    _tensor_like(a, "adaptive_avg_pool2d")
    oh, ow = (output_size, output_size) if isinstance(output_size, int) else tuple(output_size)
    H, W = a.shape[-2], a.shape[-1]
    check(H % oh == 0 and W % ow == 0,
          lambda: f"adaptive_avg_pool2d: input {H}x{W} not divisible by output {oh}x{ow}")
    r = ops.reshape(a, tuple(a.shape[:-2]) + (oh, H // oh, ow, W // ow))
    return ops.mean(r, dim=(-3, -1))


@opsymbol(id="nn.instance_norm")
def instance_norm(a, weight=None, bias=None, eps: float = 1e-5):
    _tensor_like(a, "instance_norm")
    dims = tuple(range(2, a.ndim))
    var, mean = ops.var_mean(a, dim=dims, correction=0, keepdim=True)
    out = ops.true_divide(ops.sub(a, mean), ops.sqrt(ops.add(var, eps)))
    bshape = (1, a.shape[1]) + (1,) * (a.ndim - 2)
    if weight is not None:
        out = ops.mul(out, ops.reshape(weight, bshape))
    if bias is not None:
        out = ops.add(out, ops.reshape(bias, bshape))
    return out


@opsymbol(id="nn.pixel_shuffle")
def pixel_shuffle(a, upscale_factor: int):
    _tensor_like(a, "pixel_shuffle")
    r = upscale_factor
    B_dims = tuple(a.shape[:-3])
    C, H, W = a.shape[-3], a.shape[-2], a.shape[-1]
    check(C % (r * r) == 0, "pixel_shuffle: channels not divisible by r^2")
    oc = C // (r * r)
    x = ops.reshape(a, B_dims + (oc, r, r, H, W))
    nb = len(B_dims)
    x = ops.transpose(x, tuple(range(nb)) + (nb, nb + 3, nb + 1, nb + 4, nb + 2))
    return ops.reshape(x, B_dims + (oc, H * r, W * r))


@opsymbol(id="nn.interpolate_nearest")
def interpolate_nearest(a, scale_factor: int):
    """Nearest-neighbor upsampling by an integer factor over the last two dims."""
    _tensor_like(a, "interpolate_nearest")
    s = int(scale_factor)
    check(s >= 1, lambda: f"interpolate_nearest: scale_factor must be >= 1, got {s}")
    out = a
    out = ops.movedim(out, -2, 0)
    out = ops.repeat_interleave_dim0(out, s)
    out = ops.movedim(out, 0, -2)
    out = ops.movedim(out, -1, 0)
    out = ops.repeat_interleave_dim0(out, s)
    return ops.movedim(out, 0, -1)


def _default_ce_chunk(V: int) -> int:
    """Fewer, larger matmuls pipeline better on the MXU (measured r5:
    113.8 -> 99.7 ms fwd+bwd at N=16k, V=32k); big vocabs keep the smaller
    chunk so live f32 logits stay ~0.5 GB at bench N. Forward and VJP must
    agree (the VJP recomputes per chunk against the forward's lse)."""
    return 16384 if V <= 65536 else 8192


@opsymbol(id="nn.fused_linear_cross_entropy")
def fused_linear_cross_entropy(h, w, target, *, chunk: int | None = None,
                               ignore_index: int = -100):
    """Mean softmax-cross-entropy of ``h @ w.T`` computed one vocab chunk at
    a time — the (N, V) logits are NEVER materialized (live memory is
    O(N * chunk)); the custom VJP below recomputes per chunk in backward.

    Beyond the reference: its fused-CE executors (apex/triton,
    ``thunder/executors/apex_entropyex.py:99``) still take materialized
    logits; fusing the lm_head projection removes the dominant activation
    of large-vocab training (N*V f32 — e.g. 1 GB at N=2048, V=128k).

    h: (N, D) hidden states; w: (V, D) head weight; target: (N,) int ids.
    """
    N, D = h.shape
    V = w.shape[0]
    if chunk is None:
        chunk = _default_ce_chunk(V)
    tgt = ops.convert_element_type(target, dtypes.int32)

    m = ops.full((N,), float("-inf"), dtype=dtypes.float32)
    s = ops.full((N,), 0.0, dtype=dtypes.float32)
    picked = ops.full((N,), 0.0, dtype=dtypes.float32)
    for c0 in range(0, V, chunk):
        cw = min(chunk, V - c0)
        wc = ops.narrow(w, 0, c0, cw)
        # operands stay in the MODEL dtype (bf16 in training — full MXU
        # rate; f32 operands would halve v5e matmul throughput, measured
        # r5 breakdown: the CE region sat at ~58% MFU), accumulation is
        # f32 via preferred_element_type — the standard large-vocab recipe
        lg = prims.dot_general(h, wc, contract_dims=((1,), (1,)),
                               preferred_element_type=dtypes.float32)
        mc = ops.amax(lg, -1)
        m_new = ops.maximum(m, mc)
        alpha = ops.exp(ops.sub(m, m_new))
        e = ops.exp(ops.sub(lg, ops.unsqueeze(m_new, 1)))
        s = ops.add(ops.mul(s, alpha), ops.sum(e, -1))
        m = m_new
        idx = ops.sub(tgt, c0)
        valid = ops.logical_and(ops.ge(idx, 0), ops.lt(idx, cw))
        safe = ops.clamp(idx, 0, cw - 1)
        pc = ops.squeeze(prims.take_along_axis(lg, ops.unsqueeze(safe, 1), 1), (1,))
        picked = ops.add(picked, ops.where(valid, pc, ops.zeros_like(pc)))

    lse = ops.add(m, ops.log(s))
    nll = ops.sub(lse, picked)
    ok = ops.ne(tgt, ignore_index)
    nll = ops.where(ok, nll, ops.zeros_like(nll))
    count = ops.maximum(ops.sum(ops.convert_element_type(ok, dtypes.float32)), 1.0)
    return ops.true_divide(ops.sum(nll), count), lse


@register_vjp("nn.fused_linear_cross_entropy")
def _flce_vjp(h, w, target, *, chunk: int | None = None, ignore_index: int = -100):
    loss, lse = fused_linear_cross_entropy(h, w, target, chunk=chunk,
                                           ignore_index=ignore_index)
    N, D = h.shape
    V = w.shape[0]
    if chunk is None:
        chunk = _default_ce_chunk(V)  # MUST mirror the forward (shared lse)

    def pullback(g):
        gl, glse = (g[0], g[1]) if isinstance(g, (tuple, list)) else (g, None)
        if gl is None and glse is None:
            return []
        tgt = ops.convert_element_type(target, dtypes.int32)
        hf = ops.convert_element_type(h, dtypes.float32)
        ok = ops.ne(tgt, ignore_index)
        okf = ops.convert_element_type(ok, dtypes.float32)
        count = ops.maximum(ops.sum(okf), 1.0)
        # per-row scale for the nll term: d(mean nll)/d(logit) rows;
        # ignored rows contribute 0
        if gl is not None:
            gs = ops.true_divide(ops.convert_element_type(gl, dtypes.float32), count)
            srow = ops.mul(okf, gs)                                 # (N,)
        else:
            srow = ops.full((N,), 0.0, dtype=dtypes.float32)
        # the lse output is differentiable too (z-loss etc.): d lse/d logit
        # is the softmax row, so its cotangent simply adds to the softmax
        # coefficient (the one-hot term belongs to the nll alone)
        coef = srow if glse is None else             ops.add(srow, ops.convert_element_type(glse, dtypes.float32))
        dh = ops.full((N, D), 0.0, dtype=dtypes.float32)
        dw_chunks = []
        for c0 in range(0, V, chunk):
            cw = min(chunk, V - c0)
            wc = ops.narrow(w, 0, c0, cw)
            lg = prims.dot_general(h, wc, contract_dims=((1,), (1,)),
                                   preferred_element_type=dtypes.float32)
            p = ops.exp(ops.sub(lg, ops.unsqueeze(lse, 1)))         # (N, cw) softmax
            ps = ops.mul(p, ops.unsqueeze(coef, 1))
            # d(logits) cast to the model dtype before the grad matmuls
            # (bf16 operands, f32 accumulation — same recipe as forward;
            # the end results are cast to h/w dtype anyway)
            psc = ops.convert_element_type(ps, w.dtype)
            # softmax part: dh += ps @ wc; dw_c = ps^T @ h_scaled? No —
            # dw_c = ps^T @ h (h unscaled: ps already carries the row scale)
            dh = ops.add(dh, prims.dot_general(psc, wc, contract_dims=((1,), (0,)),
                                               preferred_element_type=dtypes.float32))
            dw_c = prims.dot_general(psc, h, contract_dims=((0,), (0,)),
                                     preferred_element_type=dtypes.float32)  # (cw, D)
            # one-hot part: rows whose target lives in this chunk
            idx = ops.sub(tgt, c0)
            valid = ops.logical_and(ops.ge(idx, 0), ops.lt(idx, cw))
            safe = ops.clamp(idx, 0, cw - 1)
            vrow = ops.mul(srow, ops.convert_element_type(valid, dtypes.float32))
            # dh -= wc[target] * srow   (rows with target in chunk)
            dh = ops.sub(dh, ops.mul(prims.take(wc, safe, 0), ops.unsqueeze(vrow, 1)))
            # dw_c[target] -= h * srow
            neg_rows = ops.mul(hf, ops.unsqueeze(ops.neg(vrow), 1))
            dw_c = prims.index_add(dw_c, safe, neg_rows, 0)
            dw_chunks.append(dw_c)
        dw = ops.cat(dw_chunks, 0)
        return [(h, ops.convert_element_type(dh, h.dtype)),
                (w, ops.convert_element_type(dw, w.dtype))]

    return (loss, lse), pullback


@opsymbol(id="nn.group_norm")
def group_norm(a, num_groups: int, weight=None, bias=None, eps: float = 1e-5):
    """GroupNorm over (N, C, *spatial) — reference
    ``thunder/torch/__init__.py`` group_norm; first-class nn id so executors
    can claim a fused kernel for it."""
    _tensor_like(a, "group_norm")
    n, c = a.shape[0], a.shape[1]
    check(c % num_groups == 0, "group_norm: channels not divisible by groups")
    grouped = ops.reshape(a, (n, num_groups, c // num_groups) + tuple(a.shape[2:]))
    dims = tuple(range(2, grouped.ndim))
    var, mean = ops.var_mean(grouped, dim=dims, correction=0, keepdim=True)
    out = ops.true_divide(ops.sub(grouped, mean), ops.sqrt(ops.add(var, eps)))
    out = ops.reshape(out, tuple(a.shape))
    bshape = (1, c) + (1,) * (a.ndim - 2)
    if weight is not None:
        out = ops.mul(out, ops.reshape(weight, bshape))
    if bias is not None:
        out = ops.add(out, ops.reshape(bias, bshape))
    return out


@opsymbol(id="nn.batch_norm")
def batch_norm(a, running_mean=None, running_var=None, weight=None, bias=None,
               training: bool = False, momentum: float = 0.1, eps: float = 1e-5):
    """Functional BatchNorm: returns ``(out, new_stats)`` where ``new_stats``
    is ``(new_running_mean, new_running_var)`` in training mode with stats
    provided, else None — running statistics are explicit state (no module
    mutation; the torch dialect's F.batch_norm adapter rebinds buffer
    wrappers from this return)."""
    _tensor_like(a, "batch_norm")
    C = int(a.shape[1]) if a.ndim > 1 else int(a.shape[0])
    for nm, st in (("running_mean", running_mean), ("running_var", running_var),
                   ("weight", weight), ("bias", bias)):
        check(st is None or (getattr(st, "ndim", 0) == 1
                             and int(st.shape[0]) == C),
              lambda nm=nm, st=st: f"batch_norm: {nm} must be shape ({C},), "
              f"got {tuple(getattr(st, 'shape', ()))}")
    dims = (0,) + tuple(range(2, a.ndim))
    if training or running_mean is None:
        var, mean = ops.var_mean(a, dim=dims, correction=0, keepdim=False)
    else:
        mean, var = running_mean, running_var
    bshape = (1, a.shape[1]) + (1,) * (a.ndim - 2)
    out = ops.true_divide(ops.sub(a, ops.reshape(mean, bshape)),
                          ops.sqrt(ops.add(ops.reshape(var, bshape), eps)))
    if weight is not None:
        out = ops.mul(out, ops.reshape(weight, bshape))
    if bias is not None:
        out = ops.add(out, ops.reshape(bias, bshape))
    new_stats = None
    if training and running_mean is not None:
        n = 1
        for d in dims:
            n *= a.shape[d]
        unbiased_var = ops.mul(var, float(n) / max(n - 1, 1))
        new_mean = ops.add(ops.mul(running_mean, 1 - momentum), ops.mul(mean, momentum))
        new_var = ops.add(ops.mul(running_var, 1 - momentum), ops.mul(unbiased_var, momentum))
        new_stats = (new_mean, new_var)
    return out, new_stats


# ---------------------------------------------------------------------------
# round 3: grid_sample + ctc_loss (reference thunder/torch F.* coverage)
# ---------------------------------------------------------------------------

@opsymbol(id="nn.grid_sample")
def grid_sample(input, grid, mode: str = "bilinear", padding_mode: str = "zeros",
                align_corners: bool = False):
    """4-D ``F.grid_sample``: sample ``input`` (N,C,H,W) at normalized
    ``grid`` (N,Ho,Wo,2) coordinates. TPU-first: the four corner reads are
    flat gathers over H*W (one fused gather per corner, no scatter/loops);
    differentiable in both ``input`` and ``grid`` (bilinear mode)."""
    check(input.ndim == 4 and grid.ndim == 4 and grid.shape[-1] == 2,
          lambda: f"grid_sample: expected input (N,C,H,W) and grid (N,Ho,Wo,2), "
                  f"got {tuple(input.shape)} and {tuple(grid.shape)}")
    check(mode in ("bilinear", "nearest"),
          lambda: f"grid_sample: unsupported mode {mode!r}")
    check(padding_mode in ("zeros", "border"),
          lambda: f"grid_sample: unsupported padding_mode {padding_mode!r}")
    check(input.shape[0] == grid.shape[0],
          lambda: f"grid_sample: batch mismatch {input.shape[0]} vs {grid.shape[0]}")
    N, C, H, W = input.shape
    _, Ho, Wo, _ = grid.shape
    gx = ops.squeeze(ops.narrow(grid, 3, 0, 1), 3)  # (N,Ho,Wo) x in [-1,1]
    gy = ops.squeeze(ops.narrow(grid, 3, 1, 1), 3)

    def unnorm(g, size):
        if align_corners:
            return ops.mul(ops.add(g, 1.0), (size - 1) / 2.0)
        return ops.true_divide(ops.sub(ops.mul(ops.add(g, 1.0), float(size)), 1.0), 2.0)

    x = unnorm(gx, W)
    y = unnorm(gy, H)
    inp_flat = ops.reshape(input, (N, C, H * W))

    def read(ix, iy):
        """Gather input at integer (iy, ix); returns ((N,C,Ho,Wo), inbounds)."""
        inb = ops.logical_and(
            ops.logical_and(ops.ge(ix, 0), ops.le(ix, W - 1)),
            ops.logical_and(ops.ge(iy, 0), ops.le(iy, H - 1)))
        cx = ops.clamp(ix, 0, W - 1)
        cy = ops.clamp(iy, 0, H - 1)
        flat = ops.reshape(ops.add(ops.mul(cy, W), cx), (N, 1, Ho * Wo))
        idx = ops.expand(flat, (N, C, Ho * Wo))
        vals = ops.reshape(ops.gather(inp_flat, 2, idx), (N, C, Ho, Wo))
        return vals, ops.reshape(inb, (N, 1, Ho, Wo))

    def masked(vals, inb):
        if padding_mode == "zeros":
            return ops.mul(vals, ops.convert_element_type(inb, vals.dtype))
        return vals  # border: clamped read is already the border value

    to_i = lambda v: ops.convert_element_type(v, dtypes.int32)
    if mode == "nearest":
        # torch's kernel uses std::nearbyint — round half to even; ops.round
        # (lax round-to-nearest-even) matches it exactly on .5 boundaries
        vals, inb = read(to_i(ops.round(x)), to_i(ops.round(y)))
        return masked(vals, inb)
    x0f, y0f = ops.floor(x), ops.floor(y)
    wx = ops.reshape(ops.sub(x, x0f), (N, 1, Ho, Wo))
    wy = ops.reshape(ops.sub(y, y0f), (N, 1, Ho, Wo))
    x0, y0 = to_i(x0f), to_i(y0f)
    x1, y1 = ops.add(x0, 1), ops.add(y0, 1)
    v00 = masked(*read(x0, y0))
    v01 = masked(*read(x1, y0))
    v10 = masked(*read(x0, y1))
    v11 = masked(*read(x1, y1))
    one = 1.0
    return ops.add(
        ops.add(ops.mul(v00, ops.mul(ops.sub(one, wx), ops.sub(one, wy))),
                ops.mul(v01, ops.mul(wx, ops.sub(one, wy)))),
        ops.add(ops.mul(v10, ops.mul(ops.sub(one, wx), wy)),
                ops.mul(v11, ops.mul(wx, wy))))


# log-space "impossible" marker: a large FINITE negative (optax-style).
# A true -inf would NaN the VJP (0 * inf in the where/exp pullbacks);
# exp(_CTC_LOG_EPS - x) is exactly 0.0 in f32 for any realistic x.
_CTC_LOG_EPS = -1e5


def _safe_lse(parts):
    """logsumexp over same-shape tensors padded with _CTC_LOG_EPS."""
    m = parts[0]
    for p in parts[1:]:
        m = ops.maximum(m, p)
    s = None
    for p in parts:
        e = ops.exp(ops.sub(p, m))
        s = e if s is None else ops.add(s, e)
    return ops.add(m, ops.log(s))


@opsymbol(id="nn.ctc_loss")
def ctc_loss(log_probs, targets, input_lengths, target_lengths, blank: int = 0,
             reduction: str = "mean", zero_infinity: bool = False):
    """CTC loss (``F.ctc_loss``): the standard alpha recursion over the
    blank-extended target, expressed as a statically-unrolled scan of
    batched gather/logsumexp steps — every step is a (B, 2S+1) vector op,
    so XLA fuses the whole recursion; gradients are exact soft alignments
    via autodiff of the recursion (torch uses a hand-written backward).

    ``targets`` must be the padded 2-D (B, S) form (the 1-D concatenated
    form is data-dependent and unsupported under static shapes).
    ``log_probs`` is (T, B, C) and must already be log-softmaxed."""
    check(log_probs.ndim == 3,
          lambda: f"ctc_loss: log_probs must be (T,B,C), got {log_probs.ndim}-D")
    check(targets.ndim == 2,
          "ctc_loss: only the padded 2-D targets form is supported (the 1-D "
          "concatenated form has data-dependent layout; pad to (B, S))")
    check(reduction in ("none", "mean", "sum"),
          lambda: f"ctc_loss: unknown reduction {reduction!r}")
    T, B, C = log_probs.shape
    S = targets.shape[1]
    check(int(pyval(blank)) >= 0 and int(pyval(blank)) < C,
          lambda: f"ctc_loss: blank={blank} out of range for {C} classes")
    blank = int(pyval(blank))
    S2 = 2 * S + 1
    f32 = dtypes.float32
    neg_inf = ops.full((), _CTC_LOG_EPS, dtype=f32)

    # blank-extended targets ext (B, S2): [blank, t0, blank, t1, ..., blank]
    pos = ops.arange(S2)                                   # (S2,)
    tgt_idx = ops.clamp(ops.true_divide(ops.sub(pos, 1), 2), min=0)
    tgt_idx = ops.convert_element_type(tgt_idx, dtypes.int32)
    tgt_gathered = ops.gather(targets, 1,
                              ops.expand(ops.reshape(tgt_idx, (1, S2)), (B, S2)))
    is_label = ops.eq(ops.remainder(pos, 2), 1)            # (S2,) odd = label
    ext = ops.where(ops.reshape(is_label, (1, S2)), tgt_gathered,
                    ops.full((), blank, dtype=targets.dtype))

    # skip transition s-2 -> s allowed when ext[s] is a label differing from
    # ext[s-2]
    ext_m2 = ops.cat([ops.full((B, 2), blank, dtype=ext.dtype),
                      ops.narrow(ext, 1, 0, S2 - 2)], 1)
    allow_skip = ops.logical_and(ops.reshape(is_label, (1, S2)),
                                 ops.ne(ext, ext_m2))      # (B, S2)

    def emit(t):
        """log_probs[t] gathered at the extended targets: (B, S2)."""
        lp_t = ops.squeeze(ops.narrow(log_probs, 0, t, 1), 0)  # (B, C)
        return ops.gather(ops.convert_element_type(lp_t, f32), 1,
                          ops.convert_element_type(ext, dtypes.int32))

    # alpha_0: only s=0 (blank) and s=1 (first label) can start
    start_mask = ops.reshape(ops.le(pos, 1), (1, S2))
    alpha = ops.where(start_mask, emit(0), neg_inf)

    ilen = ops.convert_element_type(input_lengths, dtypes.int32)
    for t in range(1, T):
        a1 = ops.cat([ops.full((B, 1), _CTC_LOG_EPS, dtype=f32),
                      ops.narrow(alpha, 1, 0, S2 - 1)], 1)
        a2 = ops.cat([ops.full((B, 2), _CTC_LOG_EPS, dtype=f32),
                      ops.narrow(alpha, 1, 0, S2 - 2)], 1)
        a2 = ops.where(allow_skip, a2, neg_inf)
        new_alpha = ops.add(_safe_lse([alpha, a1, a2]), emit(t))
        active = ops.reshape(ops.gt(ilen, t), (B, 1))  # t < input_length
        alpha = ops.where(active, new_alpha, alpha)

    # total log-likelihood: alpha at s = 2*target_len (final blank) and
    # s = 2*target_len - 1 (final label; absent when target_len == 0)
    tlen = ops.convert_element_type(target_lengths, dtypes.int32)
    idx_blank = ops.reshape(ops.mul(tlen, 2), (B, 1))
    l_blank = ops.squeeze(ops.gather(alpha, 1, idx_blank), 1)
    idx_label = ops.clamp(ops.sub(idx_blank, 1), min=0)
    l_label = ops.squeeze(ops.gather(alpha, 1, idx_label), 1)
    l_label = ops.where(ops.gt(tlen, 0), l_label, neg_inf)
    ll = _safe_lse([l_blank, l_label])
    loss = ops.neg(ll)
    if zero_infinity:
        impossible = ops.gt(loss, -0.5 * _CTC_LOG_EPS)
        loss = ops.where(impossible, ops.full((), 0.0, dtype=f32), loss)
    if reduction == "none":
        return loss
    if reduction == "sum":
        return ops.sum(loss, None)
    denom = ops.convert_element_type(ops.maximum(tlen, 1), f32)
    return ops.mean(ops.true_divide(loss, denom), None)


@opsymbol(id="nn.banded_attention")
def banded_attention(q, k, v, q_pos0, k_pos0, *, window: int | None = None,
                     scale: float | None = None):
    """Attention of a prefill chunk over keys gathered in position order —
    the serving engine's chunk attention for both cache kinds.

    - ``q``: ``(n_heads, Tq, hd)``, row ``i`` at absolute position
      ``q_pos0 + i``; ``k`` / ``v``: ``(kv_heads, Lk, hd)``, key ``j`` at
      absolute position ``k_pos0 + j`` (GQA-contiguous grouping);
    - ``q_pos0`` / ``k_pos0``: int32 scalars, traced (one program serves
      every chunk of every prompt);
    - key at position ``b`` is visible to the row at position ``a`` iff
      ``0 <= b <= a`` and, with ``window=W``, ``b > a - W``. Keys gathered
      from below position 0 (a window that reaches past the start) or from
      beyond the chunk (pages not written yet) are masked by those bounds.

    The decomposition below is the masked softmax; the Pallas executor
    claims it as a flash forward that skips the key blocks wholly outside
    the band."""
    _tensor_like(q, "banded_attention")
    check(q.ndim == 3 and k.ndim == 3 and tuple(k.shape) == tuple(v.shape)
          and k.shape[-1] == q.shape[-1] and q.shape[0] % k.shape[0] == 0,
          lambda: f"banded_attention: q (H, Tq, hd) {tuple(q.shape)} / "
                  f"k, v (KV, Lk, hd) {tuple(k.shape)}, {tuple(v.shape)}")
    H, Tq, hd = q.shape
    KV, Lk, _ = k.shape
    n_rep = H // KV
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    qf = ops.convert_element_type(ops.reshape(q, (KV, n_rep * Tq, hd)),
                                  dtypes.float32)
    kf = ops.convert_element_type(k, dtypes.float32)
    vf = ops.convert_element_type(v, dtypes.float32)
    scores = ops.reshape(ops.mul(ops.matmul(qf, kf.mT), scale),
                         (KV, n_rep, Tq, Lk))
    a = ops.unsqueeze(ops.add(ops.arange(Tq), q_pos0), 1)            # (Tq, 1)
    b = ops.unsqueeze(ops.add(ops.arange(Lk), k_pos0), 0)            # (1, Lk)
    valid = ops.logical_and(ops.ge(b, 0), ops.le(b, a))
    if window is not None:
        valid = ops.logical_and(valid, ops.gt(b, ops.sub(a, int(window))))
    neg = ops.full((), float("-inf"), dtype=dtypes.float32)
    scores = ops.where(ops.expand_to(ops.reshape(valid, (1, 1, Tq, Lk)),
                                     scores.shape), scores, neg)
    probs = ops.softmax(scores, -1)
    out = ops.matmul(ops.reshape(probs, (KV, n_rep * Tq, Lk)), vf)
    return ops.convert_element_type(ops.reshape(out, (H, Tq, hd)), q.dtype)


@opsymbol(id="nn.moe_experts")
def moe_experts(x, w_gate, w_up, w_down, expert_ids, expert_weights, *,
                act: str = "silu"):
    """The gated experts an expert layer HOLDS, applied to the rows routed
    to them, dropless::

        out[n] = sum_k  expert_weights[n, k] * E_{expert_ids[n, k]}(x[n])
        E_e(h) = (act(h @ w_gate[e].T) * (h @ w_up[e].T)) @ w_down[e].T

    - ``x``: ``(N, D)``; ``w_gate`` / ``w_up``: ``(E, F, D)``; ``w_down``:
      ``(E, D, F)`` — the experts held here, however many the router
      chooses among;
    - ``expert_ids``: ``(N, K)`` int32 LOCAL ids; an id outside ``[0, E)``
      is an assignment to an expert held elsewhere and adds nothing;
    - ``expert_weights``: ``(N, K)`` float32 combine weights.

    The decomposition runs every held expert over every row and combines
    with a dense ``(N, E)`` weight matrix (exact, E x the work); the Pallas
    executor claims it as sort-by-expert, one grouped matmul kernel over
    the ragged groups (an expert no row hit costs nothing, an expert's
    weights stream once a row tile), unsort."""
    _tensor_like(x, "moe_experts")
    check(act in _SUBBLOCK_ACTS, lambda: f"moe_experts: unknown act {act!r}")
    check(x.ndim == 2 and w_gate.ndim == 3
          and tuple(w_up.shape) == tuple(w_gate.shape)
          and tuple(w_down.shape) == (w_gate.shape[0], x.shape[1],
                                      w_gate.shape[1])
          and w_gate.shape[2] == x.shape[1],
          lambda: f"moe_experts: x {tuple(x.shape)}, w_gate "
                  f"{tuple(w_gate.shape)}, w_down {tuple(w_down.shape)}")
    check(expert_ids.ndim == 2 and expert_ids.shape[0] == x.shape[0]
          and tuple(expert_weights.shape) == tuple(expert_ids.shape),
          lambda: f"moe_experts: ids {tuple(expert_ids.shape)} / weights "
                  f"{tuple(expert_weights.shape)} for {x.shape[0]} rows")
    N, D = x.shape
    E = w_gate.shape[0]
    hit = ops.eq(ops.unsqueeze(expert_ids, 2),
                 ops.reshape(ops.arange(E), (1, 1, E)))              # (N,K,E)
    combine = ops.sum(ops.where(
        hit, ops.expand_to(ops.unsqueeze(expert_weights, 2), hit.shape),
        ops.full((), 0.0, dtype=dtypes.float32)), 1)                 # (N, E)
    xe = ops.unsqueeze(x, 0)                                         # (1,N,D)
    g = _LINEAR_ACT_FNS[act](ops.matmul(xe, w_gate.mT))              # (E,N,F)
    y = ops.matmul(ops.mul(g, ops.matmul(xe, w_up.mT)), w_down.mT)   # (E,N,D)
    yf = ops.convert_element_type(y, dtypes.float32)
    out = ops.sum(ops.mul(yf, ops.unsqueeze(ops.transpose(combine, (1, 0)),
                                            2)), 0)
    return ops.convert_element_type(out, x.dtype)


# ---------------------------------------------------------------------------
# gated delta rule with per-channel decay (Kimi Delta Attention): one head's
# state S (dk x dv, float32) per sequence, updated a token at a time as
#
#     S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
#     o_t = S_t^T q_t
#
# (arXiv:2510.26692; the gated delta rule of arXiv:2412.06464 with a decay a
# key channel). ``g`` is the log decay (<= 0). The serving engine keeps S
# per slot beside the page pools (``serving/description.py``, kind "state").
# ---------------------------------------------------------------------------

KDA_CHUNK = 64


@opsymbol(id="nn.kda_chunk")
def kda_chunk(q, k, v, g, beta, state, n_valid, *, chunk: int = KDA_CHUNK):
    """A prefill chunk of the delta rule above, in the chunked (WY / UT)
    form: ``T`` tokens of one sequence from ``state`` on.

    - ``q`` / ``k``: ``(H, T, dk)``, ``v``: ``(H, T, dv)``; ``g``:
      ``(H, T, dk)`` float32 log decay; ``beta``: ``(H, T)`` float32;
    - ``state``: ``(H, dk, dv)`` float32, what the tokens before the chunk
      left;
    - ``n_valid``: int32 scalar; the positions from it on are padding and
      leave the state as it is (their decay is 1 and their beta 0).

    Returns ``(o (H, T, dv) float32, state (H, dk, dv) float32)``.

    Inner chunks of ``chunk`` tokens, with ``G`` the decay summed inside
    one: the chunk's own pairs ``A[t, s] = beta_t sum_c k_t k_s
    exp(G_t - G_s)`` (s < t) are read from exponents <= 0 only, so a decay
    near 0 underflows instead of overflowing; ``(I + A)^-1`` is taken by
    forward substitution; then ``U = (I + A)^-1 diag(beta) (V - (k exp G)
    S)``, ``O = (q exp G) S + P U`` and ``S' = exp(G_C) S + (k exp(G_C -
    G))^T U`` carry the state from one inner chunk to the next. The Pallas
    executor claims it as one kernel a (head, inner chunk) that keeps the
    state in VMEM."""
    _tensor_like(q, "kda_chunk")
    check(q.ndim == 3 and tuple(k.shape) == tuple(q.shape)
          and tuple(g.shape) == tuple(q.shape) and v.ndim == 3
          and tuple(v.shape[:2]) == tuple(q.shape[:2])
          and tuple(beta.shape) == tuple(q.shape[:2])
          and tuple(state.shape) == (q.shape[0], q.shape[2], v.shape[2]),
          lambda: f"kda_chunk: q/k/g (H, T, dk) {tuple(q.shape)}, v "
                  f"{tuple(v.shape)}, beta {tuple(beta.shape)}, state "
                  f"{tuple(state.shape)}")
    f32 = dtypes.float32
    H, T, dk = q.shape
    dv = v.shape[2]
    C = min(int(chunk), T)
    Tp = -(-T // C) * C
    cast = lambda a: ops.convert_element_type(a, f32)
    q, k, v, g, beta = map(cast, (q, k, v, g, beta))
    if Tp != T:
        pad3 = ((0, 0, 0), (0, Tp - T, 0), (0, 0, 0))
        q, k, v, g = (ops.pad(a, pad3) for a in (q, k, v, g))
        beta = ops.pad(beta, ((0, 0, 0), (0, Tp - T, 0)))
    valid = ops.lt(ops.arange(Tp), n_valid)                          # (Tp,)
    zero = ops.full((), 0.0, dtype=f32)
    g = ops.where(ops.expand_to(ops.reshape(valid, (1, Tp, 1)), g.shape),
                  g, zero)
    beta = ops.where(ops.expand_to(ops.unsqueeze(valid, 0), beta.shape),
                     beta, zero)
    n = Tp // C
    blk = lambda a: ops.reshape(a, (H, n, C, a.shape[-1]))
    q, k, v, g = map(blk, (q, k, v, g))
    beta = ops.reshape(beta, (H, n, C, 1))
    G = ops.cumsum(g, 2)                                          # (H,n,C,dk)
    # every pair's decay from exponents <= 0 (the masked half clamps to 0)
    dec = ops.exp(ops.minimum(
        ops.sub(ops.unsqueeze(G, 3), ops.unsqueeze(G, 2)), 0.0))  # (H,n,C,C,dk)
    pair = lambda a: ops.sum(ops.mul(ops.mul(ops.unsqueeze(a, 3),
                                             ops.unsqueeze(k, 2)), dec), -1)
    rows = ops.unsqueeze(ops.arange(C), 1)
    cols = ops.unsqueeze(ops.arange(C), 0)
    bcast = lambda m: ops.expand_to(ops.reshape(m, (1, 1, C, C)), (H, n, C, C))
    A = ops.where(bcast(ops.gt(rows, cols)), ops.mul(beta, pair(k)), zero)
    P = ops.where(bcast(ops.ge(rows, cols)), pair(q), zero)
    # (I + A)^-1, A strictly lower: row t = e_t - A[t] (rows above final)
    eye = ops.convert_element_type(ops.eq(rows, cols), f32)
    Tm = ops.expand_to(ops.reshape(eye, (1, 1, C, C)), (H, n, C, C))
    for t in range(1, C):
        row = ops.sub(ops.narrow(Tm, 2, t, 1),
                      ops.matmul(ops.narrow(A, 2, t, 1), Tm))
        at_t = ops.expand_to(ops.reshape(ops.eq(rows, t), (1, 1, C, 1)),
                             Tm.shape)
        Tm = ops.where(at_t, ops.expand_to(row, Tm.shape), Tm)
    eG = ops.exp(G)
    last = ops.narrow(G, 2, C - 1, 1)                              # (H,n,1,dk)
    W = ops.matmul(Tm, ops.mul(beta, ops.mul(k, eG)))
    Uv = ops.matmul(Tm, ops.mul(beta, v))
    qg = ops.mul(q, eG)
    kd = ops.mul(k, ops.exp(ops.sub(last, G)))
    S = cast(state)
    outs = []
    for c in range(n):
        at = lambda a: ops.squeeze(ops.narrow(a, 1, c, 1), 1)
        U = ops.sub(at(Uv), ops.matmul(at(W), S))
        outs.append(ops.add(ops.matmul(at(qg), S), ops.matmul(at(P), U)))
        S = ops.add(ops.mul(ops.exp(ops.transpose(at(last), (0, 2, 1))), S),
                    ops.matmul(ops.transpose(at(kd), (0, 2, 1)), U))
    o = ops.cat(outs, 1) if n > 1 else outs[0]
    if Tp != T:
        o = ops.narrow(o, 1, 0, T)
    return o, S


@opsymbol(id="nn.kda_decode")
def kda_decode(q, k, v, g, beta, state, update):
    """One token of the delta rule above for every slot of a decode batch.

    - ``q`` / ``k``: ``(S, H, dk)``, ``v``: ``(S, H, dv)``; ``g``:
      ``(S, H, dk)`` float32 log decay; ``beta``: ``(S, H)`` float32;
    - ``state``: ``(S, H, dk, dv)`` float32, a row a slot;
    - ``update``: ``(S,)`` int32. A row with 1 takes the token into its
      state and reads ``o = S'^T q``; a row with 0 keeps its state and reads
      ``o = S^T q`` (a slot's first decode row re-feeds the last prompt
      token, which prefill already took in; an idle row's output is
      discarded).

    Returns ``(o (S, H, dv) float32, state)``. The Pallas executor claims it
    as one kernel that reads and writes each row's state once, the output
    aliased to the input (the pool is donated, so the step copies none of
    it)."""
    _tensor_like(q, "kda_decode")
    check(q.ndim == 3 and tuple(k.shape) == tuple(q.shape)
          and tuple(g.shape) == tuple(q.shape) and v.ndim == 3
          and tuple(v.shape[:2]) == tuple(q.shape[:2])
          and tuple(beta.shape) == tuple(q.shape[:2])
          and tuple(state.shape) == (*q.shape, v.shape[2])
          and tuple(update.shape) == (q.shape[0],),
          lambda: f"kda_decode: q/k/g (S, H, dk) {tuple(q.shape)}, v "
                  f"{tuple(v.shape)}, beta {tuple(beta.shape)}, state "
                  f"{tuple(state.shape)}, update {tuple(update.shape)}")
    f32 = dtypes.float32
    cast = lambda a: ops.convert_element_type(a, f32)
    q, k, v, g, beta = map(cast, (q, k, v, g, beta))
    col = lambda a: ops.unsqueeze(a, 3)                           # (S,H,dk,1)
    S0 = state
    Sd = ops.mul(S0, col(ops.exp(g)))
    kv = ops.sum(ops.mul(Sd, col(k)), 2)                          # (S,H,dv)
    u = ops.mul(ops.unsqueeze(beta, 2), ops.sub(v, kv))
    S1 = ops.add(Sd, ops.mul(col(k), ops.unsqueeze(u, 2)))
    live = ops.expand_to(ops.reshape(ops.ne(update, 0),
                                     (update.shape[0], 1, 1, 1)), S1.shape)
    S1 = ops.where(live, S1, S0)
    o = ops.sum(ops.mul(S1, col(q)), 2)
    return o, S1
