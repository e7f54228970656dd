"""Optimizer composite operations.

``optim.adamw_step`` is the per-parameter AdamW update chain as ONE
claimable composite (its decomposition is exactly the pointwise chain
``thunder_tpu.optim.AdamW.update`` used to inline), and
``optim.fused_adamw`` is the multi-tensor form the optimizer fusion pass
(``core/fusion_passes.optimizer_fusion_pass``) builds from dtype-bucketed
groups of those chains — the trace-level analog of the reference
ecosystem's "foreach"/multi-tensor optimizer paths (apex
``multi_tensor_apply``): one kernel launch per dtype bucket instead of one
fused pointwise chain per parameter.

Neither symbol is ever differentiated: both run on detached gradients and
optimizer state strictly after the backward, so no VJP rules exist (see
``tests/test_grad_coverage.py`` for the recorded exemption).
"""

from __future__ import annotations

import math

from thunder_tpu.core import dtypes
from thunder_tpu.core.baseutils import check
import thunder_tpu.ops as ops
from thunder_tpu.ops import opsymbol

# Slab geometry shared by the Pallas multi-tensor kernel, the slab-persistent
# optimizer state, and checkpoint layout conversion: ONE definition, so a
# slab packed at init is bit-compatible with the slab the kernel would build
# from the same bucket (that identity is what makes slab-persistent updates
# bit-identical to the pack-per-step path).
SLAB_LANE = 128        # last-dim tile width (v5e lane count)
SLAB_ROW_BLOCK = 512   # rows per kernel grid step


def slab_geometry(total_elems: int) -> tuple[int, int]:
    """``(rows_padded, row_block)`` of the ``(rows, 128)`` slab holding
    ``total_elems`` flattened elements (zero-padded tail)."""
    rows = max(-(-total_elems // SLAB_LANE), 1)
    bn = min(SLAB_ROW_BLOCK, -(-rows // 8) * 8)
    rows_pad = -(-rows // bn) * bn
    return rows_pad, bn


@opsymbol(id="optim.adamw_step")
def adamw_step(p, g, m, v, bc1, bc2, *, lr: float = 1e-3, beta1: float = 0.9,
               beta2: float = 0.999, eps: float = 1e-8, weight_decay: float = 0.0,
               state_dtype=None, v_dtype=None):
    """One parameter's AdamW update: ``(p, g, m, v, bias_corrections) ->
    (p_new, m_new, v_new)``.

    ``bc1``/``bc2`` are the traced bias-correction scalars ``1 - betaᵢ^step``
    (computed once per update and shared by every parameter, so the fusion
    pass can bucket chains that agree on them). Arithmetic is f32 (upcast,
    update, store rounded). ``state_dtype``/``v_dtype`` are the CONFIGURED
    storage dtypes for m/v (None keeps each input's own dtype): resuming
    from a checkpoint whose moments were saved wider than the optimizer is
    configured for must re-coerce on the first step, exactly as
    ``AdamW.update`` always did — not silently keep the wider state.
    """
    gf = ops.convert_element_type(g, dtypes.float32)
    mf = ops.convert_element_type(m, dtypes.float32)
    vf = ops.convert_element_type(v, dtypes.float32)
    m_new = ops.add(ops.mul(mf, beta1), ops.mul(gf, 1.0 - beta1))
    v_new = ops.add(ops.mul(vf, beta2), ops.mul(ops.mul(gf, gf), 1.0 - beta2))
    m_hat = ops.true_divide(m_new, bc1)
    v_hat = ops.true_divide(v_new, bc2)
    upd = ops.true_divide(m_hat, ops.add(ops.sqrt(v_hat), eps))
    pf = ops.convert_element_type(p, dtypes.float32)
    if weight_decay:
        upd = ops.add(upd, ops.mul(pf, weight_decay))
    p_new = ops.sub(pf, ops.mul(upd, lr))
    return (ops.convert_element_type(p_new, p.dtype),
            ops.convert_element_type(m_new, state_dtype if state_dtype is not None else m.dtype),
            ops.convert_element_type(v_new, v_dtype if v_dtype is not None else v.dtype))


@opsymbol(id="optim.fused_adamw")
def fused_adamw(params, grads, ms, vs, bc1, bc2, *, lr: float = 1e-3,
                beta1: float = 0.9, beta2: float = 0.999, eps: float = 1e-8,
                weight_decay: float = 0.0, state_dtype=None, v_dtype=None):
    """Multi-tensor AdamW over one dtype bucket: applies ``adamw_step`` to
    every (p, g, m, v) quadruple and returns ``(new_params, new_ms, new_vs)``
    as parallel tuples.

    Built POST-autodiff by ``optimizer_fusion_pass`` and claimed by the
    Pallas executor as ONE flattened kernel launch per bucket
    (``executors/pallasex.py::pallas_fused_adamw``). Unclaimed, this
    decomposition is exactly the per-parameter chains, so numerics are
    identical either way.
    """
    params, grads, ms, vs = tuple(params), tuple(grads), tuple(ms), tuple(vs)
    check(len(params) > 0, "fused_adamw: empty bucket")
    check(len(params) == len(grads) == len(ms) == len(vs),
          lambda: f"fused_adamw: mismatched bucket lengths "
                  f"{(len(params), len(grads), len(ms), len(vs))}")
    triples = [adamw_step(p, g, m, v, bc1, bc2, lr=lr, beta1=beta1, beta2=beta2,
                          eps=eps, weight_decay=weight_decay,
                          state_dtype=state_dtype, v_dtype=v_dtype)
               for p, g, m, v in zip(params, grads, ms, vs)]
    return (tuple(t[0] for t in triples),
            tuple(t[1] for t in triples),
            tuple(t[2] for t in triples))


@opsymbol(id="optim.fused_adamw_slab")
def fused_adamw_slab(params, grads, m_slab, v_slab, bc1, bc2, *,
                     sizes, lr: float = 1e-3, beta1: float = 0.9,
                     beta2: float = 0.999, eps: float = 1e-8,
                     weight_decay: float = 0.0):
    """Multi-tensor AdamW over one dtype bucket whose m/v moments LIVE in
    ``(rows, 128)`` slabs between steps (``optim.AdamW(slab_persistent=True)``):
    ``(params, grads, m_slab, v_slab, bias_corrections) ->
    (new_params, new_m_slab, new_v_slab)``.

    The Pallas claim (``executors/pallasex.py::pallas_fused_adamw_slab``)
    reads/writes the slabs directly — the m/v pack/unpack around the kernel
    (the ``pack_bytes_if_unabsorbed`` risk the r6 cost model recorded) does not exist
    on this path. Unclaimed, this decomposition unpacks each parameter's
    moment rows from the slab, runs the exact per-parameter ``adamw_step``
    chain, and repacks — numerics are identical either way. The slab's
    zero-padded tail is invariant under the update (g=0, p=0 ⇒
    m,v decay toward 0 from 0), so decomposition and kernel agree on the
    pad lanes too.
    """
    params, grads = tuple(params), tuple(grads)
    sizes = tuple(int(s) for s in sizes)
    check(len(params) > 0, "fused_adamw_slab: empty bucket")
    check(len(params) == len(grads) == len(sizes),
          lambda: f"fused_adamw_slab: mismatched bucket lengths "
                  f"{(len(params), len(grads), len(sizes))}")
    total = sum(sizes)
    rows_pad, _ = slab_geometry(total)
    check(tuple(m_slab.shape) == (rows_pad, SLAB_LANE)
          and tuple(v_slab.shape) == (rows_pad, SLAB_LANE),
          lambda: f"fused_adamw_slab: slab shape "
                  f"{tuple(m_slab.shape)}/{tuple(v_slab.shape)} does not match "
                  f"the bucket geometry ({rows_pad}, {SLAB_LANE}) for "
                  f"{total} elements")
    m_flat = ops.reshape(m_slab, (rows_pad * SLAB_LANE,))
    v_flat = ops.reshape(v_slab, (rows_pad * SLAB_LANE,))
    new_ps, new_ms, new_vs = [], [], []
    off = 0
    for p, g, n in zip(params, grads, sizes):
        m_i = ops.reshape(ops.narrow(m_flat, 0, off, n), tuple(p.shape))
        v_i = ops.reshape(ops.narrow(v_flat, 0, off, n), tuple(p.shape))
        p_new, m_new, v_new = adamw_step(
            p, g, m_i, v_i, bc1, bc2, lr=lr, beta1=beta1, beta2=beta2,
            eps=eps, weight_decay=weight_decay,
            state_dtype=dtypes.to_dtype(m_slab.dtype),
            v_dtype=dtypes.to_dtype(v_slab.dtype))
        new_ps.append(p_new)
        new_ms.append(ops.reshape(m_new, (n,)))
        new_vs.append(ops.reshape(v_new, (n,)))
        off += n
    pad = rows_pad * SLAB_LANE - total
    if pad:
        # pad lanes stay exactly zero (they start zero and decay from zero),
        # matching what the claimed kernel computes for them
        new_ms.append(ops.full((pad,), 0.0, dtype=dtypes.to_dtype(m_slab.dtype)))
        new_vs.append(ops.full((pad,), 0.0, dtype=dtypes.to_dtype(v_slab.dtype)))
    m_out = ops.reshape(new_ms[0] if len(new_ms) == 1 else ops.cat(new_ms, 0),
                        (rows_pad, SLAB_LANE))
    v_out = ops.reshape(new_vs[0] if len(new_vs) == 1 else ops.cat(new_vs, 0),
                        (rows_pad, SLAB_LANE))
    return tuple(new_ps), m_out, v_out
