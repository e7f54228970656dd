"""Pallas TPU kernel executor.

The cudnnex/sdpaex/apex/triton analog (reference
``thunder/executors/cudnnex.py:425``, ``sdpaex.py:239``,
``apex_entropyex.py:99``, ``cudnn_layernormex.py:141``): hand-written
kernels claim the fused ops above what XLA would emit. Kernels:

- ``sdpa_fwd``: block-row attention forward producing (out, lse) — the
  flash-attention forward contract (per-q-block full-row softmax; K/V tiles
  stream through VMEM). Backward is the recompute-based trace rule in
  ``ops/nn.py``.
- ``ce_fwd``: fused cross-entropy rows (nll + logsumexp without
  materializing log-softmax).
- ``rms_norm``: fused RMS normalization.
- ``fused_adamw``: multi-tensor AdamW — one flattened kernel launch per
  optimizer dtype bucket (claims ``optim.fused_adamw`` built by the
  optimizer fusion pass; the apex ``multi_tensor_apply`` analog).

Claim policy: on real TPU when shapes align to lane/sublane tiling; in
interpret mode (``THUNDER_TPU_PALLAS_INTERPRET=1``) everywhere, which is how
the CPU test suite exercises these kernels.

Fault domains + quarantine: every impl registered below runs under
``runtime.faults.kernel_guard`` (applied by ``register_operator``) — it
hosts the ``kernel:pallas.<op>`` fault-injection domain and re-raises any
failure as ``KernelExecutionError`` with the claim id. That error is LOUD by
default; inside ``runtime.quarantine.containment()`` (the supervisors'
opt-in) the dispatch layer turns it into quarantine-recompile-and-XLA-
fallback instead of a dead job (see KERNELS.md "Kernel quarantine").
"""

from __future__ import annotations

import contextlib
import contextvars
import functools
import math
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from thunder_tpu.core.devices import chip_spec
from thunder_tpu.executors import OperatorExecutor, register_executor
from thunder_tpu.observe import registry as _observe
from thunder_tpu.ops import get_op


def _interpret() -> bool:
    return os.environ.get("THUNDER_TPU_PALLAS_INTERPRET") == "1"


def _pick_block(n: int, budget_elems: int) -> int:
    """Largest block size dividing ``n`` whose f32 tile stays within
    ``budget_elems``; ``n`` itself when it fits (single-shot: measured faster
    than the inner loop on v5e at T<=4096 — fori_loop overhead exceeds the
    causal-skip FLOP saving)."""
    if n <= budget_elems:
        return n
    fitting = [b for b in (2048, 1024, 512, 256, 128, 64, 32, 16, 8)
               if b <= budget_elems and n % b == 0]
    # no fitting divisor: fall back to n whole — caller's checker must have
    # bounded n already (real-TPU claims require n % 128 == 0); interpret
    # mode has no VMEM to blow
    return max(fitting) if fitting else n


def _causal_mask(s, row0, col0):
    """Mask score tile ``s`` to row >= col given the tile's global offsets."""
    row = row0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    col = col0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(row >= col, s, -jnp.inf)


def _on_tpu() -> bool:
    if jax.default_backend() != "tpu":
        return False
    # every tile budget and cost-model figure behind these claims is one
    # chip's (core/devices.py::CHIP_SPECS); a TPU kind the table does not
    # list raises here instead of running another chip's plan
    chip_spec()
    return True


def _enabled() -> bool:
    return _on_tpu() or _interpret()


# ---------------------------------------------------------------------------
# GSPMD: Mosaic kernels cannot be auto-partitioned ("wrap the call in a
# shard_map" is the lowering's own error). When the whole-program jit
# compiles over a multi-device mesh, the driver traces the program inside
# ``gspmd_mesh(mesh)`` and every impl below either wraps itself in a
# shard_map with its PARTITIONING PLAN — the serving stack's Megatron layout
# (distributed/gspmd.py: q/k/v/gate/up column-parallel, out/down
# row-parallel, pool sharded by kv-head, activations replicated) — or, having
# no plan, refuses loudly. Interpret mode takes the same path, so the CPU
# rehearsal runs the program the chip runs.
# ---------------------------------------------------------------------------

_gspmd_mesh: contextvars.ContextVar = contextvars.ContextVar(
    "pallas_gspmd_mesh", default=None)


@contextlib.contextmanager
def gspmd_mesh(mesh):
    tok = _gspmd_mesh.set(mesh)
    try:
        yield
    finally:
        _gspmd_mesh.reset(tok)


def _under_plan(shard_fn, in_specs, out_specs, *args):
    """Run ``shard_fn(axis, *local_args)`` per shard of the scoped mesh.
    Specs are written with the placeholder axis name ``"tp"``; ``out_specs``
    is one spec or a LIST of them (one per output)."""
    mesh = _gspmd_mesh.get()
    if len(mesh.axis_names) != 1:
        raise NotImplementedError(
            f"Pallas partitioning plans cover a 1-D tensor-parallel mesh; "
            f"this program compiles over {mesh.axis_names}")
    ax = mesh.axis_names[0]

    def spec(names):
        return P(*(ax if n == "tp" else None for n in names))

    return jax.shard_map(
        functools.partial(shard_fn, ax), mesh=mesh,
        in_specs=tuple(spec(n) for n in in_specs),
        out_specs=(tuple(spec(n) for n in out_specs)
                   if isinstance(out_specs, list) else spec(out_specs)),
        check_vma=False)(*args)


def _plan_shards() -> int:
    """Claim-time view of the same fact: how many tensor-parallel shards the
    program being compiled will run over (1 = not meshed). Checkers of
    planned kernels validate the PER-SHARD geometry with it."""
    from thunder_tpu.core.compile_data import get_compile_option

    return int(get_compile_option(
        "decode_tp_shards",
        "tensor-parallel shard count of the serving mesh this program is "
        "compiled over", None) or 1)


_REP = ()                                   # replicated, any rank
_COL = ("tp", None)                         # column-parallel weight (dim 0)
_ROW = (None, "tp")                         # row-parallel weight (dim 1)
_POOL = ("tp", None, None, None)            # paged pool, sharded by kv-head


def _single_device_only(name: str, fn):
    """Guard for impls WITHOUT a partitioning plan: under a GSPMD mesh they
    raise instead of lowering a kernel Mosaic would refuse (or, in
    interpret mode, that XLA would partition some other way)."""
    @functools.wraps(fn)
    def guarded(*args, **kwargs):
        if _gspmd_mesh.get() is not None:
            raise NotImplementedError(
                f"pallas.{name} has no partitioning plan: it cannot run "
                f"inside a program compiled over a multi-device GSPMD mesh "
                f"(shard_map-based transforms — fsdp/ddp/tensor_parallel — "
                f"are fine; so is executors=['xla'])")
        return fn(*args, **kwargs)

    return guarded



# impls that carry a partitioning plan; every other kernel registered on
# this executor is guarded single-device-only
_PLANNED_UNDER_GSPMD = frozenset({
    "rms_norm", "rms_norm_residual", "mlp_subblock",
    "paged_decode_attention", "attn_subblock"})


class _PallasExecutor(OperatorExecutor):
    def register_operator(self, name, *, fn, **kwargs):
        if name not in _PLANNED_UNDER_GSPMD:
            fn = _single_device_only(name, fn)
        return super().register_operator(name, fn=fn, **kwargs)

    def register_implementation(self, id_or_sym, op=None, *, checker=None,
                                **kwargs):
        if op is not None and op.name not in _PLANNED_UNDER_GSPMD:
            # no plan: never CLAIM inside a program the serving runner
            # compiles over a mesh (the op stays with XLA, which partitions
            # it); the trace-time guard above covers programs that give no
            # claim-time signal
            def checker(*a, _inner=checker, **k):
                return _plan_shards() == 1 and (_inner is None
                                                or _inner(*a, **k))

        super().register_implementation(id_or_sym, op, checker=checker,
                                        **kwargs)


ex = _PallasExecutor("pallas")
register_executor(ex, default=True)


# ---------------------------------------------------------------------------
# flash attention forward
# ---------------------------------------------------------------------------

def _sdpa_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                 *, scale: float, causal: bool, bq: int, bk: int):
    """Flash-attention forward with K/V streamed by the GRID.

    One (batch·head, q-block) owns a row of the kv grid dimension; Pallas
    double-buffers each (bk, hd) K/V tile from HBM while the previous tile
    computes, so VMEM holds O(bq·hd + bk·hd) regardless of sequence length —
    this removes round 1's whole-sequence staging cap (VERDICT r1 item 6;
    the reference's kernels claim arbitrary T, ``cudnnex.py:425``).

    MXU discipline: all three matmuls take bf16 (input-dtype) operands with
    f32 accumulation (``preferred_element_type``). Causal blocks strictly
    above the diagonal skip their compute via ``pl.when`` — tiles still
    stream, FLOPs (the dominant cost) are halved.
    """
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)

    run = (kj * bk <= qi * bq + bq - 1) if causal else (kj >= 0)

    @pl.when(run)
    def _compute():
        q = q_ref[0]                                  # (bq, hd) input dtype
        k = k_ref[0]                                  # (bk, hd)
        v = v_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale  # (bq, bk) f32
        if causal:
            s = _causal_mask(s, qi * bq, kj * bk)
        m = m_ref[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)                        # (bq, bk) f32
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = m_new

    @pl.when(kj == nk - 1)
    def _finalize():
        l = l_ref[...]
        lsafe = jnp.where(l == 0.0, 1.0, l)           # fully-masked rows
        o_ref[0] = (acc_ref[...] / lsafe).astype(o_ref.dtype)
        # lse carried as (bq, 1): a 2D last-dim-1 layout keeps the block
        # shape legal on TPU
        lse_ref[0] = m_ref[...] + jnp.log(lsafe)


def _grid_params(*semantics, planned_vmem: bool = False):
    """Mosaic compiler params for a pallas grid.

    ``semantics``: mark reduction-free grid dims "parallel" so Mosaic's
    pipeliner doesn't assume a sequential carry. Measured per-kernel
    (interleaved A/B): rms_norm 0.92x -> ~1.05x and ce_fwd 1.48x KEEP it;
    the SDPA kernels LOSE 26% with it (the scratch carry across the kv grid
    dim pipelines better under the default arbitrary semantics), so they
    deliberately don't use it.

    ``planned_vmem``: the kernel's staging was admitted by a cost-model
    VMEM-feasibility gate — compile it with the limit that gate's budget is
    paired with (``cost_model.VMEM_LIMIT_BYTES``) instead of Mosaic's
    default, which the model's uncounted scratch overruns by kilobytes."""
    if _interpret():
        return {}
    from thunder_tpu.core.cost_model import VMEM_LIMIT_BYTES

    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=semantics or None,
        vmem_limit_bytes=VMEM_LIMIT_BYTES if planned_vmem else None)}


def _sdpa_kernel_causal_resident(q_ref, k_ref, v_ref, o_ref, lse_ref,
                                 *, scale: float, bq: int, sub: int, nq: int):
    """Causal forward, one grid invocation per batch·head: the WHOLE
    Q/K/V/O stay resident in VMEM (one DMA set per bh), an unrolled loop
    walks q blocks, and an inner ``fori_loop`` over kv sub-blocks stops at
    the diagonal. The grid-streamed kernel cannot skip above-diagonal work
    when the kv grid has one step (the masked tile still costs full MXU
    time), and a (bh, nq) grid re-pays per-invocation overhead nq times —
    the bh-grid with 512-wide blocks measured fastest (r5 interleaved
    sweep: 13.4 ms vs 15.1 (bh,nq)-grid vs 18.5 grid-streamed at the
    bench shape)."""
    hd = q_ref.shape[-1]
    for qi in range(nq):
        q = q_ref[0, pl.ds(qi * bq, bq), :]            # VMEM slice, no DMA
        hi = (qi * bq + bq + sub - 1) // sub           # sub-blocks to touch

        def body(j, carry, qi=qi, q=q):
            acc, m, l = carry
            k = k_ref[0, pl.ds(j * sub, sub), :]
            v = v_ref[0, pl.ds(j * sub, sub), :]
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
            s = _causal_mask(s, qi * bq, j * sub)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)
            l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
            pv = jax.lax.dot_general(p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            return acc * alpha + pv, m_new, l

        acc, m, l = jax.lax.fori_loop(
            0, hi, body,
            (jnp.zeros((bq, hd), jnp.float32),
             jnp.full((bq, 1), -jnp.inf, jnp.float32),
             jnp.zeros((bq, 1), jnp.float32)))
        lsafe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, pl.ds(qi * bq, bq), :] = (acc / lsafe).astype(o_ref.dtype)
        lse_ref[0, pl.ds(qi * bq, bq), :] = m + jnp.log(lsafe)


def pallas_sdpa_fwd(q, k, v, is_causal=False, scale=None):
    """q,k,v: (..., T, hd) with identical leading dims. Any T/S that tile."""
    orig_shape = q.shape
    T, hd = q.shape[-2], q.shape[-1]
    S = k.shape[-2]
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    bh = int(functools.reduce(lambda a, b: a * b, q.shape[:-2], 1))
    q3 = q.reshape(bh, T, hd)
    k3 = k.reshape(bh, S, hd)
    v3 = v.reshape(bh, S, hd)
    bq = _pick_block(T, 256)
    # large kv blocks: short sequences take ONE kv grid step (no streaming
    # overhead — matches round 1's single-shot speed), long sequences stream
    # 2048-row tiles (0.5MB bf16: well within VMEM double-buffering)
    bk = _pick_block(S, 2048)

    br = 512 if T % 512 == 0 else bq
    if is_causal and T == S and S % br == 0 and T * hd <= 4096 * 128:
        # causal VMEM-resident variant: skips the upper triangle (the
        # grid-streamed kernel would mask it but still pay its MXU time).
        # Capped at T<=4096 so the whole-sequence Q/K/V/O blocks (plus
        # pallas double-buffering) stay within VMEM; longer sequences
        # stream below.
        out, lse = pl.pallas_call(
            functools.partial(_sdpa_kernel_causal_resident, scale=scale,
                              bq=br, sub=br, nq=T // br),
            grid=(bh,),
            in_specs=[
                pl.BlockSpec((1, T, hd), lambda b: (b, 0, 0)),
                pl.BlockSpec((1, S, hd), lambda b: (b, 0, 0)),
                pl.BlockSpec((1, S, hd), lambda b: (b, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, T, hd), lambda b: (b, 0, 0)),
                pl.BlockSpec((1, T, 1), lambda b: (b, 0, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((bh, T, hd), q.dtype),
                jax.ShapeDtypeStruct((bh, T, 1), jnp.float32),
            ],
            interpret=_interpret(),
        )(q3, k3, v3)
        return out.reshape(orig_shape), lse.reshape(orig_shape[:-1])

    out, lse = pl.pallas_call(
        functools.partial(_sdpa_kernel, scale=scale, causal=bool(is_causal), bq=bq, bk=bk),
        grid=(bh, T // bq, S // bk),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, T, hd), q.dtype),
            jax.ShapeDtypeStruct((bh, T, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, hd), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=_interpret(),
    )(q3, k3, v3)
    return out.reshape(orig_shape), lse.reshape(orig_shape[:-1])


def _sdpa_checker(q, k, v, is_causal=False, scale=None):
    if not _enabled():
        return False
    T, hd = q.shape[-2], q.shape[-1]
    if _interpret():
        return True
    # K/V stream through the grid: no sequence-length VMEM cap — any T/S
    # aligned to the 128-lane tiling claims (long-context included; ring
    # attention composes these same kernels for its local blocks)
    return hd % 128 == 0 and T % 128 == 0 and k.shape[-2] % 128 == 0


# ---------------------------------------------------------------------------
# flash attention backward (dq kernel + dkv kernel; probs never materialized
# outside a VMEM tile — the sdpaex/cudnnex backward analog,
# reference thunder/executors/sdpaex.py:312, cudnnex.py:721)
# ---------------------------------------------------------------------------

def _sdpa_dq_kernel(g_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, dq_ref, delta_ref,
                    acc_ref, *, scale: float, causal: bool, bq: int, bk: int):
    """dq + delta. Grid streams K/V tiles (innermost dim); dq accumulates in
    VMEM scratch across the kv grid dimension."""
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        # delta = rowsum(g * o), written once for the dkv kernel
        # (FlashAttention-2 style)
        gf = g_ref[0].astype(jnp.float32)
        delta_ref[0] = jnp.sum(gf * o_ref[0].astype(jnp.float32), axis=-1, keepdims=True)

    run = (kj * bk <= qi * bq + bq - 1) if causal else (kj >= 0)

    @pl.when(run)
    def _compute():
        g = g_ref[0]                          # (bq, hd) input dtype
        q = q_ref[0]
        k = k_ref[0]                          # (bk, hd)
        v = v_ref[0]
        lse = lse_ref[0].astype(jnp.float32)  # (bq, 1)
        delta = delta_ref[0]   # written once in _init; block resident in VMEM
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale  # (bq, bk)
        if causal:
            s = _causal_mask(s, qi * bq, kj * bk)
        p = jnp.exp(s - lse)                          # (bq, bk) f32
        dp = jax.lax.dot_general(g, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # (bq, bk)
        ds = (p * (dp - delta) * scale).astype(k.dtype)
        acc_ref[...] = acc_ref[...] + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(kj == nk - 1)
    def _finalize():
        dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)


def _sdpa_dkv_kernel(g_ref, q_ref, k_ref, v_ref, delta_ref, lse_ref, dk_ref, dv_ref,
                     dk_acc, dv_acc, *, scale: float, causal: bool, bk: int, bq: int):
    """dk/dv. Grid streams Q/G/lse/delta tiles (innermost dim); dk/dv
    accumulate in VMEM scratch across the q grid dimension."""
    kj = pl.program_id(1)
    qi = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    # causal: q rows strictly above the k block's start contribute nothing
    run = (qi * bq + bq - 1 >= kj * bk) if causal else (qi >= 0)

    @pl.when(run)
    def _compute():
        k = k_ref[0]                          # (bk, hd) input dtype
        v = v_ref[0]
        q = q_ref[0]                          # (bq, hd)
        g = g_ref[0]
        lse = lse_ref[0].astype(jnp.float32)  # (bq, 1)
        delta = delta_ref[0].astype(jnp.float32)  # (bq, 1)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale  # (bq, bk)
        if causal:
            s = _causal_mask(s, qi * bq, kj * bk)
        p = jnp.exp(s - lse)                          # (bq, bk) f32
        pb = p.astype(g.dtype)
        dv_acc[...] = dv_acc[...] + jax.lax.dot_general(
            pb, g, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(g, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # (bq, bk)
        ds = (p * (dp - delta) * scale).astype(q.dtype)
        dk_acc[...] = dk_acc[...] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _sdpa_bwd_kernel_causal_resident(g_ref, q_ref, k_ref, v_ref, o_ref,
                                     lse_ref, dq_ref, dk_ref, dv_ref, dq_acc,
                                     delta_acc, *, scale: float, blk: int,
                                     nb: int):
    """Combined causal dq+dk+dv, one grid invocation per batch·head: the
    whole sequence stays resident in VMEM, a ``fori_loop`` walks kv blocks,
    and a triangular ``fori_loop`` walks the q blocks at-or-below the
    diagonal sharing one recomputed probability tile for all three grads —
    the two-kernel (dq then dkv) structure recomputes p (and the mask, the
    exp, dp - delta) twice, seven matmuls a tile for five, and pays
    per-invocation overhead on two grids. At (4 x 32, 4096, 128) bf16 on a
    v5e: 15.8 ms a layer for the pair, 9.2 for this kernel (PERF.md §6,
    PR 34; blk=512 beat 256 by ~8% in r5).

    The diagonal tile (q block == kv block) is peeled off the inner loop:
    it alone has anything to mask, so the 28 of 36 tiles strictly under it
    skip the iotas, the compare and the select (−0.17 ms a layer). The kv
    loop is a ``fori_loop`` and not a Python loop: unrolled it is 0.3 ms a
    layer faster (static trip counts inside) and 8x the code — 6.2 s of
    Mosaic compile against 0.7."""
    hd = q_ref.shape[-1]
    dq_acc[...] = jnp.zeros_like(dq_acc)
    # delta = rowsum(g * o) depends only on the q row: compute ONCE for the
    # whole sequence (the kv loop would otherwise recompute it per block)
    delta_acc[...] = jnp.sum(g_ref[0].astype(jnp.float32)
                             * o_ref[0].astype(jnp.float32),
                             axis=-1, keepdims=True)
    zeros = jnp.zeros((blk, hd), jnp.float32)

    def kv_block(j, _):
        kj = k_ref[0, pl.ds(j * blk, blk), :]
        vj = v_ref[0, pl.ds(j * blk, blk), :]

        def tile(i, carry, diagonal=False):
            dk_j, dv_j = carry
            qi = q_ref[0, pl.ds(i * blk, blk), :]
            gi = g_ref[0, pl.ds(i * blk, blk), :]
            lse_i = lse_ref[0, pl.ds(i * blk, blk), :]
            delta_i = delta_acc[pl.ds(i * blk, blk), :]
            s = jax.lax.dot_general(qi, kj, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
            if diagonal:
                s = _causal_mask(s, 0, 0)         # same offset, rows and cols
            p = jnp.exp(s - lse_i)
            dp = jax.lax.dot_general(gi, vj, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            ds = (p * (dp - delta_i) * scale).astype(kj.dtype)
            dq_i = jax.lax.dot_general(ds, kj, (((1,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32)
            dq_acc[pl.ds(i * blk, blk), :] += dq_i
            dk_j = dk_j + jax.lax.dot_general(ds, qi, (((0,), (0,)), ((), ())),
                                              preferred_element_type=jnp.float32)
            dv_j = dv_j + jax.lax.dot_general(p.astype(gi.dtype), gi,
                                              (((0,), (0,)), ((), ())),
                                              preferred_element_type=jnp.float32)
            return dk_j, dv_j

        dk_j, dv_j = jax.lax.fori_loop(
            j + 1, nb, tile, tile(j, (zeros, zeros), diagonal=True))
        dk_ref[0, pl.ds(j * blk, blk), :] = dk_j.astype(dk_ref.dtype)
        dv_ref[0, pl.ds(j * blk, blk), :] = dv_j.astype(dv_ref.dtype)
        return 0

    jax.lax.fori_loop(0, nb, kv_block, 0)
    dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


# The causal backward is a ladder of three rungs, chosen from the shape and
# the dtype alone (``_sdpa_bwd_rung``):
#   one_pass   the kernel above: every (T, hd) operand of one batch·head
#              resident, one probability tile for dq, dk and dv
#   pair       the resident-K/V dq kernel + resident-Q/G dkv kernel below:
#              2-3 sequence-length tensors resident per kernel, every tile's
#              probabilities (and mask, exp, dp - delta) computed twice
#   streaming  the grid-streaming dq / dkv kernels: no residency, so no
#              sequence cap; causal tiles above the diagonal are skipped per
#              grid step. Non-causal and cross attention (ring attention's
#              local blocks) take this rung.
# The one-pass gate counts the BYTES the kernel stages (below) and holds them
# against the limit the kernel is compiled with (``_grid_params(planned_vmem
# =True)``: cost_model.VMEM_LIMIT_BYTES, 32 MiB). History: the gate was a
# count of elements, 2048*128, set when the kernel compiled under Mosaic's
# default 16 MiB and T*hd = 4096*128 errored on the chip at 17.63M (r5).
# The pair admits the forward's 4096*128 window; it is compiled with the same
# limit (its dk/dv kernel stages 20 MiB in float32 at 4096*128).
_RESIDENT_BWD_KV_ELEMS = 4096 * 128
_RESIDENT_BWD_SUB = 512  # kv/q sub-block width inside the fori_loops


def _one_pass_block(T: int) -> int:
    """The one-pass kernel's tile edge (0: T does not tile, no one-pass)."""
    return 512 if T % 512 == 0 else (256 if T % 256 == 0 else 0)


def _one_pass_staged_bytes(T: int, hd: int, itemsize: int) -> int:
    """VMEM the one-pass backward stages for one batch·head, as Mosaic
    allocates it: 5 inputs and 3 outputs of (T, hd), double-buffered by the
    pipeliner; ``lse`` as a (T, 1) f32 column, which pads every row to a
    128-lane tile, double-buffered; the (T, hd) f32 dq scratch; the (T, 1)
    f32 delta scratch, padded alike. At T=4096, hd=128, bf16 this is the
    compiler's own 24.00 MiB (16 + 4 + 2 + 2)."""
    lanes = -(-hd // 128) * 128
    seq = T * lanes * itemsize
    column = T * 128 * 4
    return 2 * 8 * seq + 2 * column + T * lanes * 4 + column


def _sdpa_bwd_rung(T: int, S: int, hd: int, itemsize: int,
                   is_causal: bool) -> tuple[str, int]:
    """(rung, staged_bytes) of the backward ladder for one shape and dtype;
    ``staged_bytes`` is the one-pass kernel's staging where it engages, else
    0. The margin under the compile limit is what the kernel's loop body
    keeps live beside the staged blocks (Mosaic spills it to VMEM): four
    (blk, blk) f32 tiles — s, p, dp, ds — and the two (blk, hd) f32
    carries. bf16 at 4096*128 stages 24 MiB + 4.5 and engages; f32 there
    stages 40 MiB and takes the pair, as does anything longer."""
    from thunder_tpu.core.cost_model import VMEM_LIMIT_BYTES

    if not (is_causal and T == S):
        return "streaming", 0
    blk = _one_pass_block(T)
    if blk:
        staged = _one_pass_staged_bytes(T, hd, itemsize)
        body = 4 * blk * blk * 4 + 2 * blk * hd * 4
        if staged + body <= VMEM_LIMIT_BYTES:
            return "one_pass", staged
    if T * hd <= _RESIDENT_BWD_KV_ELEMS:
        # _pick_block(T, sub) always divides T: ragged and T=1 shapes land here
        return "pair", 0
    return "streaming", 0


def _sdpa_dq_kernel_causal_kvres(g_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                                 dq_ref, *, scale: float, bq: int, sub: int):
    """Causal dq with the WHOLE K/V resident in VMEM on a (bh, nq) grid: an
    inner ``fori_loop`` walks kv sub-blocks and STOPS at the causal diagonal
    — the grid-streaming dq kernel masks above-diagonal tiles but still pays
    their MXU time, exactly the waste the r5 forward rewrite removed. dq for
    the block is complete when the loop ends (no cross-grid scratch
    accumulation), and delta = rowsum(dO·O) is per-q-row, computed once from
    the streamed g/o blocks."""
    qi = pl.program_id(1)
    g = g_ref[0]                                  # (bq, hd) input dtype
    q = q_ref[0]
    lse = lse_ref[0].astype(jnp.float32)          # (bq, 1)
    delta = jnp.sum(g.astype(jnp.float32) * o_ref[0].astype(jnp.float32),
                    axis=-1, keepdims=True)
    hd = q.shape[-1]
    hi = (qi * bq + bq + sub - 1) // sub          # sub-blocks at/below diagonal

    def body(j, acc):
        kj = k_ref[0, pl.ds(j * sub, sub), :]     # VMEM slice, no DMA
        vj = v_ref[0, pl.ds(j * sub, sub), :]
        s = jax.lax.dot_general(q, kj, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = _causal_mask(s, qi * bq, j * sub)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(g, vj, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * scale).astype(kj.dtype)
        return acc + jax.lax.dot_general(ds, kj, (((1,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)

    dq = jax.lax.fori_loop(0, hi, body, jnp.zeros((bq, hd), jnp.float32))
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _sdpa_dkv_kernel_causal_qres(g_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                                 dk_ref, dv_ref, delta_acc, *, scale: float,
                                 bk: int, sub: int, nsub: int):
    """Causal dk/dv mirror: the WHOLE Q/G (and lse) resident in VMEM on a
    (bh, nk) grid; the inner ``fori_loop`` walks q sub-blocks STARTING at
    the kv block's diagonal (rows strictly above it contribute nothing).
    delta is computed once per batch·head into scratch at kj == 0 and reused
    by every kv block (the grid's innermost dimension is sequential)."""
    kj = pl.program_id(1)

    @pl.when(kj == 0)
    def _init():
        delta_acc[...] = jnp.sum(g_ref[0].astype(jnp.float32)
                                 * o_ref[0].astype(jnp.float32),
                                 axis=-1, keepdims=True)

    k = k_ref[0]                                  # (bk, hd) input dtype
    v = v_ref[0]
    hd = k.shape[-1]
    lo = (kj * bk) // sub                         # first q sub-block touched

    def body(i, carry):
        dk, dv = carry
        qi = q_ref[0, pl.ds(i * sub, sub), :]
        gi = g_ref[0, pl.ds(i * sub, sub), :]
        lse_i = lse_ref[0, pl.ds(i * sub, sub), :].astype(jnp.float32)
        delta_i = delta_acc[pl.ds(i * sub, sub), :]
        s = jax.lax.dot_general(qi, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = _causal_mask(s, i * sub, kj * bk)
        p = jnp.exp(s - lse_i)
        dv = dv + jax.lax.dot_general(p.astype(gi.dtype), gi,
                                      (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(gi, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta_i) * scale).astype(qi.dtype)
        dk = dk + jax.lax.dot_general(ds, qi, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        return dk, dv

    dk, dv = jax.lax.fori_loop(
        lo, nsub, body, (jnp.zeros((bk, hd), jnp.float32),
                         jnp.zeros((bk, hd), jnp.float32)))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def pallas_sdpa_bwd(g, q, k, v, out, lse, is_causal=False, scale=None):
    orig_shape = q.shape
    T, hd = q.shape[-2], q.shape[-1]
    S = k.shape[-2]
    scale_v = scale if scale is not None else 1.0 / math.sqrt(hd)
    bh = int(functools.reduce(lambda a, b: a * b, q.shape[:-2], 1))
    g3 = g.reshape(bh, T, hd)
    q3 = q.reshape(bh, T, hd)
    k3 = k.reshape(bh, S, hd)
    v3 = v.reshape(bh, S, hd)
    o3 = out.reshape(bh, T, hd)
    lse3 = lse.reshape(bh, T, 1)

    rung, staged = _sdpa_bwd_rung(T, S, hd, q.dtype.itemsize, bool(is_causal))
    # recorded at dispatch, which is trace time: once a call site a compile,
    # nothing a step (which kernel RAN is read from the device trace)
    _observe.inc(f"pallas.sdpa_bwd.{rung}")
    _observe.event("kernel_path", op="nn.sdpa_bwd", rung=rung, T=T, hd=hd,
                   staged_bytes=staged)
    if rung == "one_pass":
        blk = _one_pass_block(T)
        dq, dk, dv = pl.pallas_call(
            functools.partial(_sdpa_bwd_kernel_causal_resident, scale=scale_v,
                              blk=blk, nb=T // blk),
            grid=(bh,),
            in_specs=[pl.BlockSpec((1, T, hd), lambda b: (b, 0, 0))] * 5
                     + [pl.BlockSpec((1, T, 1), lambda b: (b, 0, 0))],
            out_specs=[pl.BlockSpec((1, T, hd), lambda b: (b, 0, 0))] * 3,
            out_shape=[jax.ShapeDtypeStruct((bh, T, hd), q.dtype),
                       jax.ShapeDtypeStruct((bh, S, hd), k.dtype),
                       jax.ShapeDtypeStruct((bh, S, hd), v.dtype)],
            scratch_shapes=[pltpu.VMEM((T, hd), jnp.float32),
                            pltpu.VMEM((T, 1), jnp.float32)],
            interpret=_interpret(),
            **_grid_params(planned_vmem=True),
        )(g3, q3, k3, v3, o3, lse3)
        return (dq.reshape(orig_shape), dk.reshape(k.shape), dv.reshape(v.shape))

    if rung == "pair":
        sub = _pick_block(T, _RESIDENT_BWD_SUB)
        # resident-K/V diagonal-stopping pair: the r5 forward recipe applied
        # to both backward kernels. dq keeps K/V whole in VMEM and its inner
        # loop stops AT the diagonal; dk/dv keeps Q/G whole and its loop
        # starts at the diagonal — neither pays for the masked upper
        # triangle, and neither carries scratch across grid steps.
        seq_spec = pl.BlockSpec((1, T, hd), lambda b, i: (b, 0, 0))
        lse_seq_spec = pl.BlockSpec((1, T, 1), lambda b, i: (b, 0, 0))
        blk_spec = pl.BlockSpec((1, sub, hd), lambda b, i: (b, i, 0))
        lse_blk_spec = pl.BlockSpec((1, sub, 1), lambda b, i: (b, i, 0))
        dq = pl.pallas_call(
            functools.partial(_sdpa_dq_kernel_causal_kvres, scale=scale_v,
                              bq=sub, sub=sub),
            grid=(bh, T // sub),
            in_specs=[blk_spec, blk_spec, seq_spec, seq_spec, blk_spec,
                      lse_blk_spec],
            out_specs=blk_spec,
            out_shape=jax.ShapeDtypeStruct((bh, T, hd), q.dtype),
            interpret=_interpret(),
            **_grid_params(planned_vmem=True),
        )(g3, q3, k3, v3, o3, lse3)
        dk, dv = pl.pallas_call(
            functools.partial(_sdpa_dkv_kernel_causal_qres, scale=scale_v,
                              bk=sub, sub=sub, nsub=T // sub),
            grid=(bh, S // sub),
            in_specs=[seq_spec, seq_spec, blk_spec, blk_spec, seq_spec,
                      lse_seq_spec],
            out_specs=[blk_spec, blk_spec],
            out_shape=[jax.ShapeDtypeStruct((bh, S, hd), k.dtype),
                       jax.ShapeDtypeStruct((bh, S, hd), v.dtype)],
            scratch_shapes=[pltpu.VMEM((T, 1), jnp.float32)],
            interpret=_interpret(),
            **_grid_params(planned_vmem=True),
        )(g3, q3, k3, v3, o3, lse3)
        return (dq.reshape(orig_shape), dk.reshape(k.shape), dv.reshape(v.shape))
    # v5e-swept tiles at (8,32,2048,128) bf16 causal: dq 512/512 = 13.2ms vs
    # 18.5 at 256/256; dkv (bq=1024 inner) 15.1ms vs 24.7 — bigger tiles
    # amortize grid/DMA overhead and keep the MXU fed
    bq = _pick_block(T, 512)
    bk = _pick_block(S, 512)
    bq_dkv = _pick_block(T, 1024)

    dq, delta3 = pl.pallas_call(
        functools.partial(_sdpa_dq_kernel, scale=scale_v, causal=bool(is_causal), bq=bq, bk=bk),
        grid=(bh, T // bq, S // bk),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, T, hd), q.dtype),
            jax.ShapeDtypeStruct((bh, T, 1), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bq, hd), jnp.float32)],
        interpret=_interpret(),
    )(g3, q3, k3, v3, o3, lse3)

    dk, dv = pl.pallas_call(
        functools.partial(_sdpa_dkv_kernel, scale=scale_v, causal=bool(is_causal),
                          bk=bk, bq=bq_dkv),
        grid=(bh, S // bk, T // bq_dkv),
        in_specs=[
            pl.BlockSpec((1, bq_dkv, hd), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, bq_dkv, hd), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bq_dkv, 1), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, bq_dkv, 1), lambda b, j, i: (b, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, hd), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, S, hd), k.dtype),
            jax.ShapeDtypeStruct((bh, S, hd), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bk, hd), jnp.float32),
                        pltpu.VMEM((bk, hd), jnp.float32)],
        interpret=_interpret(),
    )(g3, q3, k3, v3, delta3, lse3)

    return (dq.reshape(orig_shape), dk.reshape(k.shape), dv.reshape(v.shape))


def _sdpa_bwd_checker(g, q, k, v, out, lse, is_causal=False, scale=None):
    return _sdpa_checker(q, k, v, is_causal, scale)


# ---------------------------------------------------------------------------
# fused cross-entropy forward
# ---------------------------------------------------------------------------

def _ce_kernel(logits_ref, tgt_ref, nll_ref, lse_ref, *, ignore_index: int):
    x = logits_ref[...].astype(jnp.float32)  # (bn, V)
    tgt = tgt_ref[...]  # (bn, 1) int32
    m = jnp.max(x, axis=-1, keepdims=True)
    e = jnp.exp(x - m)
    lse = (m + jnp.log(jnp.sum(e, axis=-1, keepdims=True)))[:, 0]  # (bn,)
    col = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    safe = jnp.where(tgt == ignore_index, 0, tgt)  # (bn, 1)
    picked = jnp.sum(jnp.where(col == safe, x, 0.0), axis=-1, keepdims=True)  # (bn, 1)
    lse2 = lse[:, None]
    nll = jnp.where(tgt == ignore_index, 0.0, lse2 - picked)  # (bn, 1)
    nll_ref[...] = nll
    lse_ref[...] = lse2


def pallas_ce_fwd(logits, target, ignore_index=-100):
    N, V = logits.shape
    # size the row block by VMEM budget: the (bn, V) f32 tile must fit well
    # under the ~16MB scoped vmem limit alongside double-buffering
    budget_rows = max((4 * 1024 * 1024) // (V * 4), 1)
    bn = _pick_block(N, min(128, budget_rows))
    tgt2 = target.astype(jnp.int32).reshape(N, 1)
    nll, lse = pl.pallas_call(
        functools.partial(_ce_kernel, ignore_index=ignore_index),
        grid=(N // bn,),
        **_grid_params("parallel"),
        in_specs=[
            pl.BlockSpec((bn, V), lambda i: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, 1), jnp.float32),
            jax.ShapeDtypeStruct((N, 1), jnp.float32),
        ],
        interpret=_interpret(),
    )(logits, tgt2)
    return nll.reshape(N), lse.reshape(N)


def _ce_checker(logits, target, ignore_index=-100):
    if not _enabled() or logits.ndim != 2:
        return False
    if _interpret():
        return True
    # min row block is 8; reject vocabularies whose 8-row f32 tile can't fit
    return (logits.shape[-1] % 128 == 0 and logits.shape[0] % 8 == 0
            and 8 * logits.shape[-1] * 4 <= 4 * 1024 * 1024)


# ---------------------------------------------------------------------------
# fused rms_norm
# ---------------------------------------------------------------------------

def _rms_kernel(x_ref, w_ref, o_ref, *, eps: float, cast):
    x = x_ref[...].astype(jnp.float32)
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(ms + eps)
    y = y.astype(cast)
    if w_ref is not None:
        y = y * w_ref[...]
    o_ref[...] = y.astype(o_ref.dtype)


def pallas_rms_norm(a, weight=None, eps=1e-5, dim=-1):
    if _gspmd_mesh.get() is not None:
        # plan: activations and norm weights are replicated — every shard
        # normalizes the same rows
        arrays = (a,) if weight is None else (a, weight)
        return _under_plan(
            lambda ax, x, w=None: _rms_norm_call(x, w, eps),
            (_REP,) * len(arrays), _REP, *arrays)
    return _rms_norm_call(a, weight, eps)


def _rms_norm_call(a, weight, eps):
    orig_shape = a.shape
    D = a.shape[-1]
    N = a.size // D
    x2 = a.reshape(N, D)
    # bn=128 measured fastest on v5e at D=4096 (budget targets a ~2MB f32
    # tile); with the parallel grid hint the kernel is >=1.0x the XLA fusion
    bn = _pick_block(N, max(8, min(256, (2 * 1024 * 1024) // (D * 4))))
    kernel = functools.partial(_rms_kernel, eps=eps, cast=a.dtype)
    extra = _grid_params("parallel")
    if weight is None:
        def kernel_nw(x_ref, o_ref):
            _rms_kernel(x_ref, None, o_ref, eps=eps, cast=a.dtype)

        out = pl.pallas_call(
            kernel_nw, grid=(N // bn,),
            in_specs=[pl.BlockSpec((bn, D), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((bn, D), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((N, D), a.dtype),
            interpret=_interpret(), **extra,
        )(x2)
    else:
        out = pl.pallas_call(
            kernel, grid=(N // bn,),
            in_specs=[pl.BlockSpec((bn, D), lambda i: (i, 0)),
                      pl.BlockSpec((D,), lambda i: (0,))],
            out_specs=pl.BlockSpec((bn, D), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((N, D), a.dtype),
            interpret=_interpret(), **extra,
        )(x2, weight)
    return out.reshape(orig_shape)


def _rms_checker(a, weight=None, eps=1e-5, dim=-1):
    if not _enabled():
        return False
    if dim not in (-1, a.ndim - 1):
        return False
    if weight is not None and weight.ndim != 1:
        return False
    # a wider weight dtype promotes the composite's output (normed·w); the
    # kernel emits a.dtype — reject rather than silently narrow
    if weight is not None and weight.dtype != a.dtype:
        return False
    if _interpret():
        return True
    D = a.shape[-1]
    N = 1
    for d in a.shape[:-1]:
        N *= int(d)
    # rows must tile (min sublane block 8) and the smallest row block's f32
    # tile must fit VMEM alongside double-buffering
    return D % 128 == 0 and N % 8 == 0 and 8 * D * 8 <= 3 * 1024 * 1024


# ---------------------------------------------------------------------------
# fused rms_norm + residual (epilogue fusion: the residual stream is read
# and written ONCE instead of round-tripping HBM between an add kernel and
# the norm kernel; claimed from the nn.rms_norm_residual composite built by
# core.fusion_passes.epilogue_fusion_pass)
# ---------------------------------------------------------------------------

def _rms_res_kernel(r_ref, x_ref, w_ref, h_ref, o_ref, *, eps: float, cast):
    h = r_ref[...] + x_ref[...]     # input dtype: matches the unfused add
    h_ref[...] = h.astype(h_ref.dtype)
    x32 = h.astype(jnp.float32)
    ms = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    y = (x32 * jax.lax.rsqrt(ms + eps)).astype(cast)
    if w_ref is not None:
        y = y * w_ref[...]
    o_ref[...] = y.astype(o_ref.dtype)


def pallas_rms_norm_residual(residual, a, weight=None, eps=1e-5):
    if _gspmd_mesh.get() is not None:
        # plan: the residual stream and norm weights are replicated
        arrays = (residual, a) if weight is None else (residual, a, weight)
        return _under_plan(
            lambda ax, r, x, w=None: _rms_norm_residual_call(r, x, w, eps),
            (_REP,) * len(arrays), [_REP, _REP], *arrays)
    return _rms_norm_residual_call(residual, a, weight, eps)


def _rms_norm_residual_call(residual, a, weight, eps):
    orig_shape = a.shape
    D = a.shape[-1]
    N = a.size // D
    r2 = residual.reshape(N, D)
    x2 = a.reshape(N, D)
    # 2 input + 2 output row streams double-buffer per grid step — half the
    # single-tensor rms_norm budget so the combined VMEM footprint matches
    bn = _pick_block(N, max(8, min(128, (1024 * 1024) // (D * 4))))
    extra = _grid_params("parallel")
    out_shapes = [jax.ShapeDtypeStruct((N, D), a.dtype),
                  jax.ShapeDtypeStruct((N, D), a.dtype)]
    row_spec = pl.BlockSpec((bn, D), lambda i: (i, 0))
    if weight is None:
        def kernel_nw(r_ref, x_ref, h_ref, o_ref):
            _rms_res_kernel(r_ref, x_ref, None, h_ref, o_ref, eps=eps, cast=a.dtype)

        h, out = pl.pallas_call(
            kernel_nw, grid=(N // bn,),
            in_specs=[row_spec, row_spec],
            out_specs=[row_spec, row_spec],
            out_shape=out_shapes, interpret=_interpret(), **extra,
        )(r2, x2)
    else:
        h, out = pl.pallas_call(
            functools.partial(_rms_res_kernel, eps=eps, cast=a.dtype),
            grid=(N // bn,),
            in_specs=[row_spec, row_spec, pl.BlockSpec((D,), lambda i: (0,))],
            out_specs=[row_spec, row_spec],
            out_shape=out_shapes, interpret=_interpret(), **extra,
        )(r2, x2, weight)
    return h.reshape(orig_shape), out.reshape(orig_shape)


def _rms_res_checker(residual, a, weight=None, eps=1e-5):
    if tuple(residual.shape) != tuple(a.shape) or residual.dtype != a.dtype:
        return False
    # the kernel computes row statistics in f32; claiming an f64 composite
    # (x64 mode) would silently narrow — reject, keep the f64 decomposition
    if a.dtype.bytes > 4:
        return False
    if not _rms_checker(a, weight, eps):  # includes the weight-dtype match
        return False
    if _interpret():
        return True
    # the fused kernel stages 2 input + 2 output tiles per grid step —
    # twice pallas_rms_norm's footprint, so halve its admitted D range
    return 2 * 8 * int(a.shape[-1]) * 8 <= 3 * 1024 * 1024


# ---------------------------------------------------------------------------
# fused linear + bias + activation (GEMM epilogue: the activation runs on
# the f32 accumulator tile while it is still in VMEM; claimed from the
# nn.linear_act composite built by the epilogue fusion pass)
# ---------------------------------------------------------------------------

def _erf_f32(x):
    """erf on an f32 tile. Mosaic lowers neither ``erf`` nor ``erfc``, so
    the exact-GELU epilogues carry XLA's own f32 rational approximation
    (clamp to [-4, 4], x·P(x²)/Q(x²)); it agrees with ``lax.erf`` to 5e-7
    absolute — below the f32 resolution of the GELU it feeds."""
    alpha = (-2.72614225801306e-10, 2.77068142495902e-08,
             -2.10102402082508e-06, -5.69250639462346e-05,
             -7.34990630326855e-04, -2.95459980854025e-03,
             -1.60960333262415e-02)
    beta = (-1.45660718464996e-05, -2.13374055278905e-04,
            -1.68282697438203e-03, -7.37332916720468e-03,
            -1.42647390514189e-02)
    x = jnp.clip(x, -4.0, 4.0)
    x2 = x * x
    num = jnp.full_like(x, alpha[0])
    for c in alpha[1:]:
        num = num * x2 + c
    den = jnp.full_like(x, beta[0])
    for c in beta[1:]:
        den = den * x2 + c
    return x * num / den


_ACT_IMPLS = {
    "relu": lambda y: jnp.maximum(y, 0.0),
    "silu": lambda y: y * jax.nn.sigmoid(y),
    "gelu": lambda y: 0.5 * y * (1.0 + _erf_f32(y * math.sqrt(0.5))),
    "gelu_tanh": lambda y: jax.nn.gelu(y, approximate=True),
}


def _linear_act_kernel(x_ref, w_ref, b_ref, o_ref, acc_ref, *, act: str, nk: int):
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # (bm, bk) x (bn, bk)^T with f32 accumulation — torch weight layout
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(kj == nk - 1)
    def _finalize():
        y = acc_ref[...]
        if b_ref is not None:
            y = y + b_ref[...].astype(jnp.float32)
        y = _ACT_IMPLS[act](y)
        o_ref[...] = y.astype(o_ref.dtype)


def pallas_linear_act(a, w, bias=None, act: str = "relu"):
    orig_shape = a.shape
    K = a.shape[-1]
    M = a.size // K
    Nf = w.shape[0]
    x2 = a.reshape(M, K)
    bm = _pick_block(M, 256)
    bn = _pick_block(Nf, 256)
    bk = _pick_block(K, 512)
    grid = (M // bm, Nf // bn, K // bk)
    x_spec = pl.BlockSpec((bm, bk), lambda i, j, k: (i, k))
    w_spec = pl.BlockSpec((bn, bk), lambda i, j, k: (j, k))
    o_spec = pl.BlockSpec((bm, bn), lambda i, j, k: (i, j))
    out_shape = jax.ShapeDtypeStruct((M, Nf), a.dtype)
    scratch = [pltpu.VMEM((bm, bn), jnp.float32)]
    if bias is None:
        def kernel_nb(x_ref, w_ref, o_ref, acc_ref):
            _linear_act_kernel(x_ref, w_ref, None, o_ref, acc_ref, act=act, nk=grid[2])

        out = pl.pallas_call(
            kernel_nb, grid=grid, in_specs=[x_spec, w_spec], out_specs=o_spec,
            out_shape=out_shape, scratch_shapes=scratch, interpret=_interpret(),
        )(x2, w)
    else:
        out = pl.pallas_call(
            functools.partial(_linear_act_kernel, act=act, nk=grid[2]),
            grid=grid,
            in_specs=[x_spec, w_spec, pl.BlockSpec((1, bn), lambda i, j, k: (0, j))],
            out_specs=o_spec, out_shape=out_shape, scratch_shapes=scratch,
            interpret=_interpret(),
        )(x2, w, bias.reshape(1, Nf))
    return out.reshape(orig_shape[:-1] + (Nf,))


def _linear_act_checker(a, w, bias=None, act: str = "relu"):
    if not _enabled() or act not in _ACT_IMPLS:
        return False
    if a.ndim < 2 or w.ndim != 2 or a.shape[-1] != w.shape[1]:
        return False
    if a.dtype != w.dtype or not a.dtype.is_float:
        return False
    # accumulation is f32 (preferred_element_type); claiming an f64 GEMM
    # (x64 mode) would silently narrow — reject, keep the f64 decomposition
    if a.dtype.bytes > 4:
        return False
    # a wider bias dtype promotes the composite's output through the bias
    # add; the kernel emits a.dtype — reject rather than silently narrow
    if bias is not None and (bias.ndim != 1 or bias.shape[0] != w.shape[0]
                             or bias.dtype != a.dtype):
        return False
    if _interpret():
        return True
    K, Nf = a.shape[-1], w.shape[0]
    M = 1
    for d in a.shape[:-1]:
        M *= int(d)
    return K % 128 == 0 and Nf % 128 == 0 and M % 8 == 0


# ---------------------------------------------------------------------------
# transformer MLP sub-block megakernel (Fusion 3.0: claimed from the
# nn.mlp_subblock composite built by core.fusion_passes.block_fusion_pass).
# One launch computes the whole chain
#     h = residual + x; n = rms_norm(h, w_norm);
#     out = h + (act(n @ wg^T) * (n @ wu^T)) @ wd^T
# with the weights STREAMED through the grid in d_ff blocks — h/n/acc live
# in VMEM scratch for the row block, so none of the chain's interior values
# (n, gate/up pre-activations, the SwiGLU product, the down projection)
# ever round-trips HBM. Forward only: a serving kernel (decode steps and
# prefill chunks); a train step's MLP GEMMs are XLA's (ledger, PR 29).
# ---------------------------------------------------------------------------

# tile budgets are owned by core/cost_model.py: the planner's
# VMEM-feasibility gate and this kernel's actual staging must be computed
# from the SAME numbers, or the gate validates a kernel with a different
# footprint than the one that runs (the compiles-then-dies-on-chip failure
# the rule exists to prevent)
from thunder_tpu.core.cost_model import (  # noqa: E402
    SUBBLOCK_FF_BLOCK as _SUBBLOCK_FF_BUDGET,
    SUBBLOCK_ROW_BLOCK as _SUBBLOCK_ROW_BUDGET,
    decode_pages_per_block,
    decode_subblock_pages_per_block,
)


def _mlp_subblock_kernel(r_ref, x_ref, wn_ref, wg_ref, wu_ref, wd_ref, o_ref,
                         h_ref, n_ref, acc_ref, *, act: str, eps: float, nf: int,
                         cast, residual_out: bool = True):
    """Forward megakernel body. Grid (row_blocks, ff_blocks), ff innermost:
    at f == 0 the row block's h and normed rows are computed once into
    scratch; every f step runs the gate/up GEMM slices against the streamed
    weight tiles and accumulates the down-projection into f32 scratch; the
    final f step adds the residual back and stores (``residual_out=False``:
    stores the down-projection alone — the tensor-parallel plan sums the
    shards' partial projections BEFORE the residual joins)."""
    f = pl.program_id(1)

    @pl.when(f == 0)
    def _init():
        h = r_ref[...] + x_ref[...]                 # input dtype, as unfused
        h_ref[...] = h
        x32 = h.astype(jnp.float32)
        ms = jnp.mean(x32 * x32, axis=-1, keepdims=True)
        nh = (x32 * jax.lax.rsqrt(ms + eps)).astype(cast)
        n_ref[...] = nh * wn_ref[...]
        acc_ref[...] = jnp.zeros_like(acc_ref)

    n = n_ref[...]
    gpre = jax.lax.dot_general(n, wg_ref[...], (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)
    ga = _ACT_IMPLS[act](gpre).astype(cast)
    u = jax.lax.dot_general(n, wu_ref[...], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32).astype(cast)
    acc_ref[...] += jax.lax.dot_general(ga * u, wd_ref[...], (((1,), (1,)), ((), ())),
                                        preferred_element_type=jnp.float32)

    @pl.when(f == nf - 1)
    def _finalize():
        y = acc_ref[...].astype(cast)
        o_ref[...] = ((h_ref[...] + y) if residual_out else y).astype(o_ref.dtype)


def _subblock_grid(N: int, D: int, F: int):
    bn = _pick_block(N, _SUBBLOCK_ROW_BUDGET)
    bf = _pick_block(F, _SUBBLOCK_FF_BUDGET)
    return bn, bf


def pallas_mlp_subblock(residual, x, w_norm, w_gate, w_up, w_down,
                        act: str = "silu", eps: float = 1e-5):
    if _gspmd_mesh.get() is None:
        return _mlp_subblock_call(residual, x, w_norm, w_gate, w_up, w_down,
                                  act, eps)

    # plan: gate/up column-parallel, down row-parallel — each shard owns a
    # d_ff slice and produces a PARTIAL down-projection; one all-reduce,
    # then the (replicated) residual joins once
    def shard(ax, r, x_, wn, wg, wu, wd):
        part = _mlp_subblock_call(r, x_, wn, wg, wu, wd, act, eps,
                                  residual_out=False)
        return (r + x_) + jax.lax.psum(part, ax)

    return _under_plan(shard, (_REP, _REP, _REP, _COL, _COL, _ROW), _REP,
                       residual, x, w_norm, w_gate, w_up, w_down)


def _mlp_subblock_call(residual, x, w_norm, w_gate, w_up, w_down, act, eps,
                       residual_out: bool = True):
    orig_shape = x.shape
    D = x.shape[-1]
    N = x.size // D
    F = w_gate.shape[0]
    r2 = residual.reshape(N, D)
    x2 = x.reshape(N, D)
    bn, bf = _subblock_grid(N, D, F)
    grid = (N // bn, F // bf)
    row = pl.BlockSpec((bn, D), lambda i, f: (i, 0))
    wrow = pl.BlockSpec((bf, D), lambda i, f: (f, 0))
    out = pl.pallas_call(
        functools.partial(_mlp_subblock_kernel, act=act, eps=eps, nf=grid[1],
                          cast=x.dtype, residual_out=residual_out),
        grid=grid,
        in_specs=[row, row,
                  pl.BlockSpec((D,), lambda i, f: (0,)),
                  wrow, wrow,
                  pl.BlockSpec((D, bf), lambda i, f: (0, f))],
        out_specs=row,
        out_shape=jax.ShapeDtypeStruct((N, D), x.dtype),
        scratch_shapes=[pltpu.VMEM((bn, D), x.dtype),
                        pltpu.VMEM((bn, D), x.dtype),
                        pltpu.VMEM((bn, D), jnp.float32)],
        interpret=_interpret(), **_grid_params(planned_vmem=True),
    )(r2, x2, w_norm, w_gate, w_up, w_down)
    return out.reshape(orig_shape)


def _mlp_subblock_checker(residual, x, w_norm, w_gate, w_up, w_down,
                          act: str = "silu", eps: float = 1e-5):
    if not _enabled() or act not in _ACT_IMPLS:
        return False
    if w_norm is None or getattr(w_norm, "ndim", 0) != 1:
        return False
    if tuple(residual.shape) != tuple(x.shape) or residual.dtype != x.dtype:
        return False
    D = x.shape[-1]
    if w_norm.shape[0] != D:
        return False
    # the kernel computes norm stats + GEMM accumulation in f32; f64 (x64
    # mode) composites would silently narrow — reject, keep the decomposition
    if not x.dtype.is_float or x.dtype.bytes > 4:
        return False
    if any(w.dtype != x.dtype for w in (w_norm, w_gate, w_up, w_down)):
        return False
    if w_gate.ndim != 2 or tuple(w_up.shape) != tuple(w_gate.shape):
        return False
    F = w_gate.shape[0]
    if w_gate.shape[1] != D or tuple(w_down.shape) != (D, F):
        return False
    if _interpret():
        return True
    from thunder_tpu.core.cost_model import VMEM_BUDGET_BYTES, subblock_vmem_bytes

    N = 1
    for d in x.shape[:-1]:
        N *= int(d)
    # under the tensor-parallel plan each shard's kernel sees d_ff / tp
    tp = _plan_shards()
    if F % tp:
        return False
    F = int(F) // tp
    return (D % 128 == 0 and F % 128 == 0 and N % 8 == 0
            and subblock_vmem_bytes(int(D), F, x.dtype.bytes, N)
            <= VMEM_BUDGET_BYTES)


# ---------------------------------------------------------------------------
# paged decode attention (serving engine): ragged-batch decode attention
# over the block-allocated paged KV cache, WITHOUT a gathered contiguous
# cache (that materialization is exactly what the XLA decomposition of
# nn.paged_decode_attention pays per step). The pools stay in HBM
# (memory_space=ANY); the block table and the per-request context lengths
# ride as SCALAR-PREFETCH operands, and ONE page walk — _walk_live_pages,
# shared by this kernel and by the attention phase of the decode megakernel
# below — copies each request's LIVE pages into VMEM itself:
#
#   grid step = a GROUP of ``nh`` KV heads (cost_model.decode_pages_per_block:
#   every local head the VMEM holds for this kernel, whose grid is
#   (KV // nh,); ONE for the megakernel, whose step streams that head
#   group's wo slice). Inside it a loop over the slots, and for each slot a
#   loop whose trip count is the request's own
#   cdiv(length, page_size * pages_per_block): a page past a request's
#   length costs nothing, an idle slot (length 0) an empty loop, and the
#   grid does not grow with the block-table window. A block is
#   ``pages_per_block`` pages of K and of V (from the page's bytes, the
#   window and the VMEM left). ONE async copy moves a live page of all
#   ``nh`` heads (k_pages.at[kvh0 : kvh0 + nh, bt[b, p]], a strided source
#   over the (KV, P, ps, hd) pool: ``nh`` tiles, one descriptor); a block's
#   copies are started together into one half of a double buffer while the
#   other half is computed — the next block of this request, or the first
#   block of the next slot — and a WHOLE block is waited for once (the
#   semaphore counts bytes), a ragged one a page at a time. The walk's time
#   followed the count of DMA operations, not the bytes: at 4 KB a copy
#   (one head) 22.3 ms a step of commandaplus_serve_agent_sat, 18.0 of them
#   with the arithmetic taken out; at 32 KB (eight heads) 3.8, where the
#   copies alone take 3.7 and the bytes 3.1 (PERF.md, PR 36). The online
#   softmax (f32 m / l / acc) runs over the whole (G, block) score tile of a
#   head; a group's heads are a batch dimension of its two matmuls (the one
#   head's arithmetic under jax.vmap; the megakernel's walk, one head and no
#   head axis, lowers as it did). Rows past the length are masked in the
#   scores and zeroed in V (the buffer's dead rows hold whatever an earlier
#   block left there).
#
# Claims the T == 1 decode case only — prefill chunks (T > 1 rows over the
# paged context) take the decomposition, whose gather XLA fuses into the
# surrounding region once per chunk rather than per token.
# ---------------------------------------------------------------------------


def _walk_live_pages(kvh0, bt_ref, ln_ref, kp_ref, vp_ref, kbuf, vbuf, sem,
                     m_ref, l_ref, acc_ref, *, S: int, npg: int, ps: int,
                     ppb: int, scale: float, q_of, emit, fresh_of=None,
                     window: int | None = None):
    """Online-softmax decode attention over every slot's live pages, of KV
    head ``kvh0`` or, where the staging has a head axis, of the ``nh`` heads
    from ``kvh0``. ``q_of(b)`` gives slot b's (G, hd) grouped query rows,
    ``emit(b, out)`` takes its normalized (G, hd) f32 result (zeros for a
    length-0 slot); ``fresh_of(b)``, when given, is THIS token's (1, hd) K
    and V rows, patched in at position length-1 because the pool still holds
    the pre-append contents — each with ``nh`` in front for a group.
    ``bt_ref`` is the flattened (S * npg,) block table; ``kbuf`` / ``vbuf``
    are (2, ppb * ps, hd) VMEM buffers, or (2, nh, ppb * ps, hd): the group
    is theirs to say; ``sem`` is a (2, 2) DMA semaphore array (pool x
    buffer).

    ``window=W``: the table is a ring (logical page ``p`` in column
    ``p % npg``) and the walk starts at the first page the window still
    reaches, ``max(length - W, 0) // ps``, so it covers at most
    ``cdiv(W, ps) + 1`` pages whatever the context; rows below
    ``length - W`` are masked like the rows past the length."""
    nh = kbuf.shape[1] if len(kbuf.shape) == 4 else None
    bk = ppb * ps

    def first_page(b):
        """The first logical page slot ``b``'s walk reads."""
        if window is None:
            return 0
        return jnp.maximum(ln_ref[b] - window, 0) // ps

    def live_pages(b, j):
        """Live pages of block ``j`` of slot ``b``."""
        return jnp.minimum(ppb, (ln_ref[b] + ps - 1) // ps - first_page(b)
                           - j * ppb)

    def copies(b, j, buf, do):
        """``do`` (start or wait) on the copies of block ``j`` of slot
        ``b``: its live pages only, every head of a page at once, into
        buffer ``buf``."""
        fp = first_page(b)

        def page(p, carry):
            col = fp + j * ppb + p
            if window is not None:
                col = col % npg
            pid = bt_ref[b * npg + col]
            rows = pl.ds(pl.multiple_of(p * ps, ps), ps)
            for i, (pool, stage) in enumerate(((kp_ref, kbuf), (vp_ref, vbuf))):
                if nh is None:
                    src, dst = pool.at[kvh0, pid], stage.at[buf, rows, :]
                else:       # a strided source: nh tiles, one descriptor
                    src = pool.at[pl.ds(kvh0, nh), pid]
                    dst = stage.at[buf, :, rows, :]
                do(pltpu.make_async_copy(src, dst, sem.at[i, buf]))
            return carry

        jax.lax.fori_loop(0, live_pages(b, j), page, 0)

    start = lambda c: c.start()
    wait = lambda c: c.wait()

    def wait_block(b, j, buf):
        """A whole block's copies fill the buffer: one wait a pool for its
        bytes. A request's last block, and a window's when the ring cuts it
        short, are waited for a page at a time."""
        whole = live_pages(b, j) == ppb

        @pl.when(whole)
        def _whole():
            for i, stage in enumerate((kbuf, vbuf)):
                pltpu.make_async_copy(stage.at[buf], stage.at[buf],
                                      sem.at[i, buf]).wait()

        @pl.when(jnp.logical_not(whole))
        def _ragged():
            copies(b, j, buf, wait)

    def slot(b, buf0):
        ln = ln_ref[b]
        base = first_page(b) * ps       # position of the walk's first row
        lo = 0 if window is None else jnp.maximum(ln - window, 0)
        nblk = (ln - base + bk - 1) // bk
        nxt = jnp.minimum(b + 1, S - 1)

        # the first block is already in flight when the slot before had a
        # loop to start it from
        @pl.when((nblk > 0) & ((b == 0) | (ln_ref[jnp.maximum(b - 1, 0)] == 0)))
        def _first():
            copies(b, 0, buf0, start)

        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        q = q_of(b)
        fresh = fresh_of(b) if fresh_of is not None else ()

        def block(j, carry):
            buf = (buf0 + j) % 2

            @pl.when(j + 1 < nblk)
            def _next_block():
                copies(b, j + 1, 1 - buf, start)

            @pl.when((j + 1 == nblk) & (b + 1 < S) & (ln_ref[nxt] > 0))
            def _next_slot():
                copies(nxt, 0, 1 - buf, start)

            wait_block(b, j, buf)
            row = base + j * bk + jax.lax.broadcasted_iota(jnp.int32,
                                                           (bk, 1), 0)
            live_row = row < ln if window is None \
                else (row < ln) & (row >= lo)
            col = base + j * bk + jax.lax.broadcasted_iota(
                jnp.int32, (acc_ref.shape[-2], bk), 1)
            live_col = col < ln if window is None \
                else (col < ln) & (col >= lo)

            def attend(q, k, v, m, l, acc, *fresh):
                """One head's block: (G, hd) rows against (bk, hd) K and V."""
                v = jnp.where(live_row, v, jnp.zeros_like(v))   # dead rows
                if fresh:
                    fk, fv = fresh
                    k = jnp.where(row == ln - 1, fk, k)
                    v = jnp.where(row == ln - 1, fv, v)
                s_ = jax.lax.dot_general(
                    q, k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                s_ = jnp.where(live_col, s_, -jnp.inf)  # ragged tail mask
                m_new = jnp.maximum(m, jnp.max(s_, axis=-1, keepdims=True))
                alpha = jnp.exp(m - m_new)
                pexp = jnp.exp(s_ - m_new)
                l_new = l * alpha + jnp.sum(pexp, axis=-1, keepdims=True)
                acc_new = acc * alpha + jax.lax.dot_general(
                    pexp.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                return m_new, l_new, acc_new

            # a group's heads are a batch dimension of the two matmuls
            m_ref[...], l_ref[...], acc_ref[...] = \
                (attend if nh is None else jax.vmap(attend))(
                    q, kbuf[buf], vbuf[buf], m_ref[...], l_ref[...],
                    acc_ref[...], *fresh)
            return carry

        jax.lax.fori_loop(0, nblk, block, 0)
        l = l_ref[...]
        lsafe = jnp.where(l == 0.0, 1.0, l)            # length-0 slot
        emit(b, acc_ref[...] / lsafe)
        return (buf0 + nblk) % 2

    jax.lax.fori_loop(0, S, slot, jnp.int32(0))


def _tile_rows(dtype_bytes: int) -> int:
    """Rows of one (8 x 32-bit) sublane tile of this dtype."""
    return 8 * max(4 // dtype_bytes, 1)


def _whole_tiles(rows: int, dtype_bytes: int) -> bool:
    """``rows`` rows of this dtype are whole sublane tiles."""
    return rows % _tile_rows(dtype_bytes) == 0


def _walk_scratch(ppb: int, ps: int, hd: int, G: int, dtype,
                  nh: int | None = None):
    """The walk's VMEM: K and V double buffers, their DMA semaphores, and
    the online-softmax m / l / acc of one (slot, head) — each with ``nh``
    heads in front for a walk of a group."""
    heads = () if nh is None else (nh,)
    return [pltpu.VMEM((2, *heads, ppb * ps, hd), dtype),
            pltpu.VMEM((2, *heads, ppb * ps, hd), dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((*heads, G, 1), jnp.float32),
            pltpu.VMEM((*heads, G, 1), jnp.float32),
            pltpu.VMEM((*heads, G, hd), jnp.float32)]


def _paged_decode_kernel(bt_ref, ln_ref, q_ref, kp_ref, vp_ref, o_ref,
                         kbuf, vbuf, sem, m_ref, l_ref, acc_ref, *,
                         scale: float, ps: int, npg: int, ppb: int,
                         window: int | None = None):
    """A group of ``nh`` KV heads of every request: q block (B, nh, G, hd)
    where G = n_heads // kv_heads grouped rows of the single decode
    position."""
    def emit(b, out):
        o_ref[b] = out.astype(o_ref.dtype)

    _walk_live_pages(pl.program_id(0) * q_ref.shape[1], bt_ref, ln_ref,
                     kp_ref, vp_ref, kbuf, vbuf, sem, m_ref, l_ref, acc_ref,
                     S=q_ref.shape[0], npg=npg, ps=ps, ppb=ppb, scale=scale,
                     q_of=lambda b: q_ref[b], emit=emit, window=window)


def pallas_paged_decode_attention(q, k_pages, v_pages, block_tables, lengths,
                                  scale=None, window=None):
    if _gspmd_mesh.get() is not None:
        # plan: heads are embarrassingly parallel — q by head, the pool by
        # kv-head, no reduction; each shard pages its own heads' K/V
        heads = (None, "tp", None, None)
        return _under_plan(
            lambda ax, *t: _paged_decode_call(*t, scale=scale, window=window),
            (heads, _POOL, _POOL, _REP, _REP), heads,
            q, k_pages, v_pages, block_tables, lengths)
    return _paged_decode_call(q, k_pages, v_pages, block_tables, lengths,
                              scale=scale, window=window)


def _paged_decode_call(q, k_pages, v_pages, block_tables, lengths, scale=None,
                       window=None):
    B, H, T, hd = q.shape
    if T != 1:
        # the kernel's single ragged mask (col < length) is only the causal
        # mask when every grouped row sits at the SAME position — direct
        # callers must not rely on the claim-time checker to reject T > 1
        raise ValueError(
            f"pallas_paged_decode_attention is decode-only (T == 1); got "
            f"T={T} — prefill chunks take the nn.paged_decode_attention "
            f"decomposition, which masks per row")
    KV, P, ps, _ = k_pages.shape
    npg = block_tables.shape[1]
    G = (H // KV) * T                                  # grouped decode rows
    scale_v = scale if scale is not None else 1.0 / math.sqrt(hd)
    item = q.dtype.itemsize
    # a head's share of the kernel's VMEM besides the staging: its query
    # and output blocks (double-buffered, G padded to whole sublane tiles)
    # and its f32 m / l / acc
    Gp = -(-G // _tile_rows(item)) * _tile_rows(item)
    ppb, nh = decode_pages_per_block(
        ps, hd, item, npg, kv_heads=KV,
        head_bytes=4 * B * Gp * hd * item + Gp * (hd + 256) * 4)
    # recorded at dispatch, which is trace time (see pallas_sdpa_bwd)
    _observe.event("kernel_path", op="nn.paged_decode_attention",
                   rung=(f"walk_{nh}h" if window is None
                         else f"ring_walk_{npg}p_{nh}h"),
                   T=npg * ps if window is None else window, hd=hd,
                   staged_bytes=4 * nh * ppb * ps * hd * item,
                   heads_per_copy=nh, pages_per_block=ppb)
    q4 = q.reshape(B, KV, G, hd)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                         # block_tables, lengths
        grid=(KV // nh,),
        in_specs=[
            pl.BlockSpec((B, nh, G, hd), lambda h, bt, ln: (0, h, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),         # k pages, in HBM
            pl.BlockSpec(memory_space=pl.ANY),         # v pages
        ],
        out_specs=pl.BlockSpec((B, nh, G, hd), lambda h, bt, ln: (0, h, 0, 0)),
        scratch_shapes=_walk_scratch(ppb, ps, hd, G, k_pages.dtype, nh),
    )
    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, scale=scale_v, ps=ps,
                          npg=npg, ppb=ppb, window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        interpret=_interpret(), **_grid_params(planned_vmem=True),
    )(block_tables.astype(jnp.int32).reshape(-1), lengths.astype(jnp.int32),
      q4, k_pages, v_pages)
    return out.reshape(B, H, T, hd)


def _paged_decode_checker(q, k_pages, v_pages, block_tables, lengths,
                          scale=None, window=None):
    if not _enabled():
        return False
    if q.ndim != 4 or k_pages.ndim != 4 or v_pages.ndim != 4:
        return False
    B, H, T, hd = q.shape
    KV, P, ps, hd2 = k_pages.shape
    if T != 1:
        return False  # ragged DECODE kernel; prefill chunks decompose
    if hd2 != hd or tuple(v_pages.shape) != tuple(k_pages.shape):
        return False
    if H % KV != 0:
        return False
    # f32 accumulation: reject f64 (x64 mode) rather than silently narrow;
    # store dtype must match q (the kernel emits q.dtype)
    if q.dtype != k_pages.dtype or v_pages.dtype != k_pages.dtype:
        return False
    if not q.dtype.is_float or q.dtype.bytes > 4:
        return False
    if (block_tables.ndim != 2 or block_tables.shape[0] != B
            or lengths.ndim != 1 or lengths.shape[0] != B):
        return False
    if not block_tables.dtype.is_int or not lengths.dtype.is_int:
        return False
    if _interpret():
        return True
    # real-TPU tiling: lane-aligned head dim, and pages of whole sublane
    # tiles (a page is one DMA into a row slice of the walk's buffer). The
    # claim stays cost-model gated either way.
    return hd % 128 == 0 and _whole_tiles(ps, q.dtype.bytes)


# ---------------------------------------------------------------------------
# banded flash forward (serving prefill chunks): a chunk's rows against keys
# gathered in position order, for both cache kinds. Positions ride as
# scalar-prefetch operands, so one program serves every chunk: row i sits at
# q_pos0 + i, key j at k_pos0 + j, and key b is visible to row a iff
# 0 <= b <= a and (window) b > a - W. Grid (kv head, group row, key block):
# the key blocks wholly outside the band skip their compute, and their index
# is clamped into the band, so a revisited block costs no DMA either.
# ---------------------------------------------------------------------------

def _band_blocks(qp0, kp0, *, Tq: int, bk: int, nkb: int, window):
    """First and last key block the chunk's band touches."""
    lo_pos = 0 if window is None else jnp.maximum(qp0 - window + 1, 0)
    j_lo = jnp.clip((lo_pos - kp0) // bk, 0, nkb - 1)
    j_hi = jnp.clip((qp0 + Tq - 1 - kp0) // bk, 0, nkb - 1)
    return j_lo, j_hi


def _banded_kernel(qp_ref, kp_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref,
                   l_ref, *, scale: float, bk: int, nkb: int, window):
    j = pl.program_id(2)
    qp0, kp0 = qp_ref[0], kp_ref[0]
    Tq = q_ref.shape[1]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)

    j_lo, j_hi = _band_blocks(qp0, kp0, Tq=Tq, bk=bk, nkb=nkb, window=window)

    @pl.when((j >= j_lo) & (j <= j_hi))
    def _compute():
        q, k, v = q_ref[0], k_ref[0], v_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        a = qp0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        b = kp0 + j * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        ok = (b >= 0) & (b <= a)
        if window is not None:
            ok = ok & (b > a - window)
        s = jnp.where(ok, s, -jnp.inf)
        m = m_ref[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        # a row the block leaves wholly masked keeps m at -inf: exp(-inf -
        # (-inf)) is NaN, so shift by 0 there (its p and alpha are 0 anyway)
        m_use = jnp.where(m_new == -jnp.inf, 0.0, m_new)
        alpha = jnp.exp(m - m_use)
        p = jnp.exp(s - m_use)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == nkb - 1)
    def _finalize():
        l = l_ref[...]
        o_ref[0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
                    ).astype(o_ref.dtype)


def _banded_key_block(Lk: int) -> int | None:
    return next((b for b in (512, 256, 128) if Lk % b == 0), None)


def pallas_banded_attention(q, k, v, q_pos0, k_pos0, window=None, scale=None):
    H, Tq, hd = q.shape
    KV, Lk, _ = k.shape
    n_rep = H // KV
    scale_v = scale if scale is not None else 1.0 / math.sqrt(hd)
    bk = _banded_key_block(Lk) or Lk
    nkb = Lk // bk
    # recorded at dispatch, which is trace time (see pallas_sdpa_bwd)
    _observe.event("kernel_path", op="nn.banded_attention",
                   rung=f"{'band' if window else 'causal'}_{nkb}x{bk}", T=Tq,
                   hd=hd, staged_bytes=(2 * Tq + 4 * bk) * hd * q.dtype.itemsize
                   + Tq * (hd + 2) * 4)

    def kmap(h, r, j, qp, kp):
        j_lo, j_hi = _band_blocks(qp[0], kp[0], Tq=Tq, bk=bk, nkb=nkb,
                                  window=window)
        return (h, jnp.clip(j, j_lo, j_hi), 0)

    qmap = lambda h, r, j, qp, kp: (h * n_rep + r, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(KV, n_rep, nkb),
        in_specs=[pl.BlockSpec((1, Tq, hd), qmap),
                  pl.BlockSpec((1, bk, hd), kmap),
                  pl.BlockSpec((1, bk, hd), kmap)],
        out_specs=pl.BlockSpec((1, Tq, hd), qmap),
        scratch_shapes=[pltpu.VMEM((Tq, hd), jnp.float32),
                        pltpu.VMEM((Tq, 1), jnp.float32),
                        pltpu.VMEM((Tq, 1), jnp.float32)])
    return pl.pallas_call(
        functools.partial(_banded_kernel, scale=scale_v, bk=bk, nkb=nkb,
                          window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((H, Tq, hd), q.dtype),
        interpret=_interpret(),
    )(jnp.asarray(q_pos0, jnp.int32).reshape(1),
      jnp.asarray(k_pos0, jnp.int32).reshape(1), q, k, v)


def _banded_checker(q, k, v, q_pos0, k_pos0, window=None, scale=None):
    if not _enabled():
        return False
    if q.ndim != 3 or k.ndim != 3 or tuple(k.shape) != tuple(v.shape):
        return False
    if q.dtype != k.dtype or v.dtype != k.dtype:
        return False
    if not q.dtype.is_float or q.dtype.bytes > 4:
        return False
    if q.shape[0] % k.shape[0] or q.shape[2] != k.shape[2]:
        return False
    if _interpret():
        return True
    return (q.shape[2] % 128 == 0 and _whole_tiles(q.shape[1], q.dtype.bytes)
            and _banded_key_block(k.shape[1]) is not None)


# ---------------------------------------------------------------------------
# the experts an expert layer holds (serving: decode steps and prefill
# chunks alike): sort the assignments by expert, ONE grouped matmul kernel
# over the ragged groups, unsort. Every held expert's group is padded to
# whole row tiles, so a tile belongs to one expert; the tile -> expert map
# and the count of live tiles ride as scalar-prefetch operands. An expert no
# row hit has no tile and costs nothing; an expert's three matrices stream
# once a row tile (at decode every group fits one tile: once a step). A
# dead tile (past the live count) skips its compute and pins every index to
# the last live tile's, so it moves no bytes. Dropless: the buffer holds
# every assignment whatever the skew.
# ---------------------------------------------------------------------------

def _moe_tiles(N: int, D: int, F: int, itemsize: int) -> tuple[int, int]:
    """Row tile and feed-forward block of the grouped kernel: the rows of a
    decode step in one tile (every group then fits one), 256-row tiles for
    a chunk (where a tile's GEMMs take as long as its weights' stream), and
    the largest feed-forward block whose staging stays inside the VMEM the
    kernel is compiled with."""
    from thunder_tpu.core.cost_model import VMEM_LIMIT_BYTES

    tm = N if N <= 256 else 256
    for bf in (512, 256, 128):
        if F % bf:
            continue
        rows = tm * D * (4 * itemsize + 4)      # x, out (double) + f32 acc
        tiles = 2 * 3 * bf * D * itemsize       # gate/up/down, double
        if rows + tiles <= int(0.8 * VMEM_LIMIT_BYTES):
            return tm, bf
    return tm, min(F, 128)


def _moe_kernel(te_ref, nl_ref, x_ref, w_ref, wg_ref, wu_ref, wd_ref, o_ref,
                acc_ref, *, act: str, nf: int, cast):
    t, f = pl.program_id(0), pl.program_id(1)

    @pl.when(t < nl_ref[0])
    def _live():
        @pl.when(f == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        x = x_ref[...]
        g = jax.lax.dot_general(x, wg_ref[0], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        ga = _ACT_IMPLS[act](g).astype(cast)
        u = jax.lax.dot_general(x, wu_ref[0], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32).astype(cast)
        acc_ref[...] += jax.lax.dot_general(
            ga * u, wd_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

        @pl.when(f == nf - 1)
        def _finalize():
            o_ref[...] = (acc_ref[...] * w_ref[...]).astype(o_ref.dtype)


def moe_group_layout(expert_ids, E: int, tm: int):
    """Where each assignment goes in the sorted, tile-padded buffer.

    ``expert_ids`` (N, K) -> ``dest`` (N*K,) buffer row of every assignment
    (``n_tiles * tm``, past the buffer, for one whose expert is not held),
    ``tile_expert`` (n_tiles,), ``n_live`` (1,) and the static ``n_tiles``."""
    A = expert_ids.size
    n_tiles = A // tm + E
    flat = expert_ids.reshape(A)
    held = (flat >= 0) & (flat < E)
    key = jnp.where(held, flat, E).astype(jnp.int32)
    order = jnp.argsort(key, stable=True)
    skey = key[order]
    counts = jnp.sum(key[:, None] == jnp.arange(E, dtype=jnp.int32)[None, :],
                     axis=0, dtype=jnp.int32)                     # (E,)
    tiles = (counts + tm - 1) // tm
    tile_end = jnp.cumsum(tiles)
    n_live = tile_end[-1]
    grp_start = jnp.cumsum(counts) - counts
    e_of = jnp.minimum(skey, E - 1)
    dest_sorted = jnp.where(
        skey < E,
        (tile_end - tiles)[e_of] * tm + jnp.arange(A, dtype=jnp.int32)
        - grp_start[e_of], n_tiles * tm)
    dest = jnp.zeros(A, jnp.int32).at[order].set(dest_sorted)
    last = jnp.maximum(n_live - 1, 0)
    tile_expert = jnp.minimum(jnp.searchsorted(
        tile_end, jnp.minimum(jnp.arange(n_tiles, dtype=jnp.int32), last),
        side="right"), E - 1).astype(jnp.int32)
    return dest, tile_expert, n_live.reshape(1).astype(jnp.int32), n_tiles


def pallas_moe_experts(x, w_gate, w_up, w_down, expert_ids, expert_weights,
                       act: str = "silu"):
    N, D = x.shape
    E, F, _ = w_gate.shape
    K = expert_ids.shape[1]
    tm, bf = _moe_tiles(N, D, F, x.dtype.itemsize)
    nf = F // bf
    _observe.event("kernel_path", op="nn.moe_experts",
                   rung=f"grouped_{tm}x{bf}", T=N, hd=D,
                   staged_bytes=tm * D * (4 * x.dtype.itemsize + 4)
                   + 6 * bf * D * x.dtype.itemsize)
    dest, tile_expert, n_live, n_tiles = moe_group_layout(expert_ids, E, tm)
    R = n_tiles * tm
    src = jnp.zeros(R, jnp.int32).at[dest].set(
        jnp.arange(N * K, dtype=jnp.int32) // K, mode="drop")
    w_sorted = jnp.zeros((R, 1), jnp.float32).at[dest, 0].set(
        expert_weights.reshape(N * K).astype(jnp.float32), mode="drop")
    xs = x[src]                                                    # (R, D)

    live_t = lambda t, nl: jnp.maximum(jnp.minimum(t, nl[0] - 1), 0)
    live_f = lambda t, f, nl: jnp.where(t < nl[0], f, nf - 1)
    rows = lambda t, f, te, nl: (live_t(t, nl), 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(n_tiles, nf),
        in_specs=[
            pl.BlockSpec((tm, D), rows),
            pl.BlockSpec((tm, 1), rows),
            pl.BlockSpec((1, bf, D),
                         lambda t, f, te, nl: (te[t], live_f(t, f, nl), 0)),
            pl.BlockSpec((1, bf, D),
                         lambda t, f, te, nl: (te[t], live_f(t, f, nl), 0)),
            pl.BlockSpec((1, D, bf),
                         lambda t, f, te, nl: (te[t], 0, live_f(t, f, nl))),
        ],
        out_specs=pl.BlockSpec((tm, D), rows),
        scratch_shapes=[pltpu.VMEM((tm, D), jnp.float32)])
    ys = pl.pallas_call(
        functools.partial(_moe_kernel, act=act, nf=nf, cast=x.dtype),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R, D), x.dtype),
        interpret=_interpret(), **_grid_params(planned_vmem=True),
    )(tile_expert, n_live, xs, w_sorted, w_gate, w_up, w_down)
    # unsort: every row sums what its held assignments produced (a row of a
    # dead tile was never written: select, never multiply)
    picked = ys[jnp.minimum(dest, R - 1)].reshape(N, K, D)
    held = (dest < R).reshape(N, K, 1)
    out = jnp.sum(jnp.where(held, picked.astype(jnp.float32), 0.0), axis=1)
    return out.astype(x.dtype)


def _moe_experts_checker(x, w_gate, w_up, w_down, expert_ids, expert_weights,
                         act: str = "silu"):
    if not _enabled() or act not in _ACT_IMPLS:
        return False
    if x.ndim != 2 or w_gate.ndim != 3 or expert_ids.ndim != 2:
        return False
    if not x.dtype.is_float or x.dtype.bytes > 4:
        return False
    if any(w.dtype != x.dtype for w in (w_gate, w_up, w_down)):
        return False
    if not expert_ids.dtype.is_int:
        return False
    if _interpret():
        return True
    N, D = x.shape
    F = w_gate.shape[1]
    tm, _ = _moe_tiles(int(N), int(D), int(F), x.dtype.bytes)
    return (D % 128 == 0 and F % 128 == 0 and N % tm == 0
            and _whole_tiles(tm, x.dtype.bytes))


# ---------------------------------------------------------------------------
# the delta rule with per-channel decay (nn.kda_chunk / nn.kda_decode): a
# float32 state (dk x dv) a head, the serving engine's state kind.
#
# Decode: grid (slot, head group); a step holds ``hg`` heads of one slot's
# state in VMEM (as many as the VMEM holds: _kda_heads_per_step), reads it
# once and writes it once into the same buffer (the state is aliased input
# -> output, and the pool the engine donates is updated in place). A loop
# walks the step's heads in groups of 8 (one sublane tile of the vectors);
# the vectors arrive as rows (8, dk), one transpose a group makes the
# columns the decay and the rank-one update scale the state's rows by, and
# every product is a VPU multiply with a sublane sum.
#
# Prefill: grid (head, inner chunk), the chunks in order with the state in
# a VMEM scratch. A step builds the chunk's pair matrices a row t at a time
# from exponents <= 0 (G_t - G_s, s <= t), solves (I + A) by forward
# substitution in the same sweep, then the WY form: four MXU products carry
# the state to the chunk's end.
# ---------------------------------------------------------------------------

_HIGHEST = jax.lax.Precision.HIGHEST


def _mm(a, b, ca: int = 1, cb: int = 0):
    """a . b contracting a's dim ``ca`` with b's dim ``cb``, float32."""
    return jax.lax.dot_general(a, b, (((ca,), (cb,)), ((), ())),
                               precision=_HIGHEST,
                               preferred_element_type=jnp.float32)


def _is_f32(t) -> bool:
    return t.dtype.is_float and t.dtype.bytes == 4


def _kda_decode_staging(hg: int, dk: int, dv: int) -> int:
    """VMEM a decode grid step of ``hg`` heads stages: every block twice
    (the pipeline's two buffers), the float32 state in and out, the four
    dk-vectors and v in, o out."""
    return 2 * 4 * hg * (2 * dk * dv + 4 * dk + 2 * dv)


def _kda_heads_per_step(H: int, dk: int, dv: int) -> int:
    """Heads of one slot a decode grid step holds: the most that divide
    ``H`` and fill whole sublane groups (or are ``H``) whose staging fits
    the VMEM the kernel is compiled with. A step's arithmetic and
    bookkeeping hide under its copies only when the step is large: on a
    v5e at 128 x 128 heads, 8 a step stream the state at 69% of HBM's
    bandwidth, 32 or 64 at 80%, what copying it through reads."""
    from thunder_tpu.core.cost_model import VMEM_LIMIT_BYTES

    legal = [n for n in range(H, 0, -1)
             if H % n == 0 and (n % 8 == 0 or n == H)]
    return next((n for n in legal
                 if _kda_decode_staging(n, dk, dv) <= VMEM_LIMIT_BYTES),
                legal[-1])


def _kda_decode_kernel(up_ref, q_ref, k_ref, kb_ref, a_ref, v_ref, s_ref,
                       o_ref, so_ref, *, hg: int):
    live = up_ref[pl.program_id(0)] != 0
    u = 8 if hg % 8 == 0 else hg                           # heads unrolled

    def group(i, carry):
        r = pl.ds(pl.multiple_of(i * u, u), u)
        qT, kT = q_ref[0, r].T, k_ref[0, r].T              # (dk, u)
        kbT, aT = kb_ref[0, r].T, a_ref[0, r].T
        v = v_ref[0, r]                                    # (u, dv)
        o = []
        for j in range(u):
            S = s_ref[0, i * u + j]                        # (dk, dv)
            Sd = S * aT[:, j:j + 1]
            kv = jnp.sum(Sd * kT[:, j:j + 1], axis=0, keepdims=True)
            S1 = jnp.where(live, Sd + kbT[:, j:j + 1] * (v[j:j + 1] - kv), S)
            so_ref[0, i * u + j] = S1
            o.append(jnp.sum(S1 * qT[:, j:j + 1], axis=0, keepdims=True))
        o_ref[0, r] = jnp.concatenate(o)
        return carry

    jax.lax.fori_loop(0, hg // u, group, 0)


def pallas_kda_decode(q, k, v, g, beta, state, update):
    Sl, H, dk = q.shape
    dv = v.shape[2]
    hg = _kda_heads_per_step(H, dk, dv)
    _observe.event("kernel_path", op="nn.kda_decode", rung=f"heads_{hg}",
                   heads_per_step=hg, chunk=1,
                   state_dtype=str(jnp.dtype(state.dtype)),
                   T=Sl, hd=dk, staged_bytes=_kda_decode_staging(hg, dk, dv))
    f32 = jnp.float32
    q, k, v = (a.astype(f32) for a in (q, k, v))
    kb = k * beta.astype(f32)[:, :, None]
    a = jnp.exp(g.astype(f32))
    vec = lambda d: pl.BlockSpec((1, hg, d), lambda b, h, up: (b, h, 0))
    st = pl.BlockSpec((1, hg, dk, dv), lambda b, h, up: (b, h, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(Sl, H // hg),
        in_specs=[vec(dk), vec(dk), vec(dk), vec(dk), vec(dv), st],
        out_specs=[vec(dv), st])
    o, s1 = pl.pallas_call(
        functools.partial(_kda_decode_kernel, hg=hg),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((Sl, H, dv), f32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operand 6 (the scalar-prefetched mask counts): the state
        input_output_aliases={6: 1},
        interpret=_interpret(),
        **_grid_params("parallel", "parallel", planned_vmem=True),
    )(update.astype(jnp.int32), q, k, kb, a, v, state)
    return o, s1


def _kda_decode_checker(q, k, v, g, beta, state, update):
    if not _enabled():
        return False
    if q.ndim != 3 or state.ndim != 4 or not _is_f32(state):
        return False
    if not update.dtype.is_int:
        return False
    if _interpret():
        return True
    from thunder_tpu.core.cost_model import VMEM_LIMIT_BYTES

    Sl, H, dk = q.shape
    dv = v.shape[2]
    hg = _kda_heads_per_step(H, dk, dv)
    return (dk % 128 == 0 and dv % 128 == 0
            and _kda_decode_staging(hg, dk, dv) <= VMEM_LIMIT_BYTES)


def _kda_chunk_kernel(q_ref, k_ref, kb_ref, vb_ref, g_ref, s0_ref, o_ref,
                      s_ref, st, *, C: int, nc: int):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        st[...] = s0_ref[0]

    q, k, kb, g = q_ref[0], k_ref[0], kb_ref[0], g_ref[0]   # (C, dk)
    rows = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    col = jax.lax.broadcasted_iota(jnp.int32, (C, 1), 0)
    G = _mm((rows >= cols).astype(jnp.float32), g)         # cumsum in chunk
    # row t of A (s < t) and of P (s <= t) as columns over s; row t of
    # (I + A)^-1 from the rows above it
    Tm = (rows == cols).astype(jnp.float32)
    PT = jnp.zeros((C, C), jnp.float32)
    for t in range(C):
        ks = k * jnp.exp(jnp.minimum(G[t:t + 1] - G, 0.0))   # (C_s, dk)
        a_t = jnp.where(col < t, jnp.sum(ks * kb[t:t + 1], axis=1,
                                         keepdims=True), 0.0)
        p_t = jnp.where(col <= t, jnp.sum(ks * q[t:t + 1], axis=1,
                                          keepdims=True), 0.0)
        if t:
            row = Tm[t:t + 1] - jnp.sum(a_t * Tm, axis=0, keepdims=True)
            Tm = jnp.where(rows == t, row, Tm)
        PT = jnp.where(cols == t, p_t, PT)
    eG = jnp.exp(G)
    ones = jnp.ones((C, 1), jnp.float32)
    last_col = _mm(g, ones, 0, 0)                          # (dk, 1): G_C
    last = G[C - 1:C]                                      # (1, dk)
    S = st[...]
    U = _mm(Tm, vb_ref[0]) - _mm(_mm(Tm, kb * eG), S)
    o_ref[0] = _mm(q * eG, S) + _mm(PT, U, 0, 0)
    S = jnp.exp(last_col) * S + _mm(k * jnp.exp(last - G), U, 0, 0)
    st[...] = S

    @pl.when(c == nc - 1)
    def _done():
        s_ref[0] = S


def pallas_kda_chunk(q, k, v, g, beta, state, n_valid, chunk=64):
    H, T, dk = q.shape
    dv = v.shape[2]
    C = min(int(chunk), T)
    Tp = -(-T // C) * C
    nc = Tp // C
    _observe.event("kernel_path", op="nn.kda_chunk", rung=f"wy_{C}",
                   heads_per_step=1, chunk=C,
                   state_dtype=str(jnp.dtype(state.dtype)),
                   T=T, hd=dk, staged_bytes=4 * (4 * C * dk + C * dv
                                                 + 2 * dk * dv))
    f32 = jnp.float32
    pad = lambda a: jnp.pad(a.astype(f32), ((0, 0), (0, Tp - T))
                            + ((0, 0),) * (a.ndim - 2))
    q, k, v, g, beta = map(pad, (q, k, v, g, beta))
    valid = (jnp.arange(Tp) < n_valid)[None, :]
    g = jnp.where(valid[..., None], g, 0.0)
    beta = jnp.where(valid, beta, 0.0)[..., None]
    kb, vb = k * beta, v * beta
    blk = lambda d: pl.BlockSpec((1, C, d), lambda h, c: (h, c, 0))
    st = pl.BlockSpec((1, dk, dv), lambda h, c: (h, 0, 0))
    o, s1 = pl.pallas_call(
        functools.partial(_kda_chunk_kernel, C=C, nc=nc),
        grid=(H, nc),
        in_specs=[blk(dk), blk(dk), blk(dk), blk(dv), blk(dk), st],
        out_specs=[blk(dv), st],
        out_shape=[jax.ShapeDtypeStruct((H, Tp, dv), f32),
                   jax.ShapeDtypeStruct((H, dk, dv), f32)],
        scratch_shapes=[pltpu.VMEM((dk, dv), f32)],
        interpret=_interpret(),
        **_grid_params("parallel", "arbitrary"),
    )(q, k, kb, vb, g, state.astype(f32))
    return o[:, :T], s1


def _kda_chunk_checker(q, k, v, g, beta, state, n_valid, chunk=64):
    if not _enabled():
        return False
    if q.ndim != 3 or state.ndim != 3 or not _is_f32(state):
        return False
    if _interpret():
        return True
    H, T, dk = q.shape
    C = min(int(chunk), T)
    return dk % 128 == 0 and v.shape[2] % 128 == 0 and C % 8 == 0


# ---------------------------------------------------------------------------
# whole-decode-layer megakernel (serving T==1): ONE launch per transformer
# layer per decoded token, claimed from the nn.decode_layer composite the
# block planner's chaining stage builds (nn.attn_subblock alone gets the
# same kernel minus the MLP phases — the quarantine fallback's middle rung).
#
# The grid is ONE flattened sequential dimension whose steps encode three
# phases; index maps decode the phase from the step index and pin every
# operand not used by the current phase to a constant block (revisiting the
# same block index means Mosaic skips the redundant DMA). Its length,
# H + 3*KV (+ F / bf), does not depend on the context window:
#
#   phase QKV  (H + 2*KV steps, one head each): at step 0 the whole slot
#     batch's rows are normalized into VMEM scratch; each step streams one
#     head's weight tile, runs the (S, D) x (D, hd) projection, applies the
#     rope half-rotation in-register, and parks the roped rows in scratch
#     (k/v rows are also emitted as outputs for the page-pool append).
#   phase ATTN (KV steps, one KV head each, whatever the block-table
#     window): the page walk of the paged decode attention above
#     (_walk_live_pages) over every slot — the pools stay in HBM, each
#     request's LIVE pages are copied a block at a time into a double
#     buffer, online-softmax (m, l, acc) runs over the whole (G, block)
#     score tile, a dead page costs nothing. THIS token's row is patched
#     from the fresh-row scratch (jnp.where on the row iota), so the kernel
#     never re-reads its own append from HBM. Each slot's finalized head
#     group lands in an (S, G*hd) scratch row; at the end of the head's walk
#     the whole slot batch is projected through the head group's wo slice
#     in ONE (S, G*hd) x (G*hd, D) matmul and accumulated onto the residual
#     rows — wo streams through VMEM once a layer, and the out-projection
#     rides the attention phase, no separate pass. (Until PR 26 this phase
#     was S * KV * npg grid steps of one page each, and a step of this
#     grid cost 0.6 us with nothing in it: 161 of a 182 ms decode step at
#     32 slots x 8 KV heads x 128 pages.)
#   phase MLP  (F / bf steps, decode_layer only): the pallas_mlp_subblock
#     recipe at row-block = the whole slot batch — second norm from the
#     residual accumulator at the first step, gate/up/down tiles streamed,
#     final step stores h2 + mlp.
#
# The one HBM write the kernel does NOT absorb is the page-pool append
# itself: the fresh K/V rows leave as (KV, S, hd) outputs and a plain jax
# scatter places them (same replace semantics as the decomposition's
# prims.scatter) — identical traffic to the unfused path, fused into the
# same XLA program, and the attention phase never waits on it thanks to the
# VMEM patch.
# ---------------------------------------------------------------------------


def _decode_qkv_phase(i, h_ref, wn1_ref, wq_ref, wk_ref, wv_ref, cos_ref,
                      sin_ref, kr_ref, vr_ref, xn_ref, q_ref, kf_ref, vf_ref,
                      hacc_ref, *, H: int, KV: int, hd: int, eps: float,
                      cast, init_h: bool):
    """Phase QKV step: norm-once init, then one head's projection + rope."""
    @pl.when(i == 0)
    def _init():
        h = h_ref[...]
        h32 = h.astype(jnp.float32)
        ms = jnp.mean(h32 * h32, axis=-1, keepdims=True)
        xn_ref[...] = ((h32 * jax.lax.rsqrt(ms + eps)).astype(cast)
                       * wn1_ref[...]).astype(xn_ref.dtype)
        hacc_ref[...] = h32 if init_h else jnp.zeros_like(hacc_ref)

    xn = xn_ref[...]
    c = cos_ref[...]
    s = sin_ref[...]

    def rope(t):
        # half-rotation as ONE lane rotate: the wrapper hands in cos as
        # [c, c] and sin as [-s, s] (full head width), so
        # [t1*c - t2*s, t2*c + t1*s] == t*cos + roll(t, hd/2)*sin — no
        # 64-lane slices or lane concatenation for Mosaic to relayout. The
        # rotate itself runs on 32-bit data (Mosaic has no packed rotate);
        # the round trip is exact, the arithmetic stays in the row dtype
        rot = pltpu.roll(t.astype(jnp.float32), hd // 2, 1).astype(t.dtype)
        return t * c + rot * s

    @pl.when(i < H)
    def _q():
        t = jax.lax.dot_general(xn, wq_ref[...], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32).astype(cast)
        q_ref[jnp.clip(i, 0, H - 1)] = rope(t).astype(q_ref.dtype)

    @pl.when((i >= H) & (i < H + KV))
    def _k():
        t = jax.lax.dot_general(xn, wk_ref[...], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32).astype(cast)
        rk = rope(t)
        kf_ref[jnp.clip(i - H, 0, KV - 1)] = rk.astype(kf_ref.dtype)
        kr_ref[...] = rk[None].astype(kr_ref.dtype)

    @pl.when((i >= H + KV) & (i < H + 2 * KV))
    def _v():
        t = jax.lax.dot_general(xn, wv_ref[...], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32).astype(cast)
        vf_ref[jnp.clip(i - H - KV, 0, KV - 1)] = t.astype(vf_ref.dtype)
        vr_ref[...] = t[None].astype(vr_ref.dtype)


def _decode_attn_phase(i, off, wo_ref, kp_ref, vp_ref, bt_ref, ln_ref, q_ref,
                       kf_ref, vf_ref, hacc_ref, att_ref, walk, *, S: int,
                       KV: int, G: int, hd: int, ps: int, npg: int, ppb: int,
                       scale: float, cast):
    """Phase ATTN step: one KV head's walk over every slot's live pages,
    then the head group's out-projection for the whole slot batch."""
    @pl.when((i >= off) & (i < off + KV))
    def _head():
        kvh = jnp.clip(i - off, 0, KV - 1)

        def q_of(b):
            return q_ref[pl.ds(kvh * G, G), pl.ds(b, 1), :] \
                .reshape(G, hd).astype(cast)

        def fresh_of(b):
            # THIS token's rows (position length-1) from the fresh-row
            # scratch: the HBM page still holds the pre-append contents
            return tuple(r[pl.ds(kvh, 1), pl.ds(b, 1), :]
                         .reshape(1, hd).astype(cast)
                         for r in (kf_ref, vf_ref))

        def emit(b, out):
            att_ref[pl.ds(b, 1), :] = out.astype(cast).astype(jnp.float32) \
                .reshape(1, G * hd)

        _walk_live_pages(kvh, bt_ref, ln_ref, kp_ref, vp_ref, *walk, S=S,
                         npg=npg, ps=ps, ppb=ppb, scale=scale, q_of=q_of,
                         emit=emit, fresh_of=fresh_of)
        hacc_ref[...] += jax.lax.dot_general(
            att_ref[...].astype(cast), wo_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)


def _decode_mlp_phase(i, off, nf, wn2_ref, wg_ref, wu_ref, wd_ref, o_ref,
                      hacc_ref, x2_ref, macc_ref, *, eps: float, act: str,
                      cast):
    """Phase MLP step: the mlp_subblock recipe at row-block = whole batch."""
    f = i - off

    @pl.when(f == 0)
    def _init():
        h2 = hacc_ref[...]
        ms = jnp.mean(h2 * h2, axis=-1, keepdims=True)
        x2_ref[...] = ((h2 * jax.lax.rsqrt(ms + eps)).astype(cast)
                       * wn2_ref[...]).astype(x2_ref.dtype)
        macc_ref[...] = jnp.zeros_like(macc_ref)

    @pl.when(f >= 0)
    def _body():
        n = x2_ref[...]
        gpre = jax.lax.dot_general(n, wg_ref[...], (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)
        ga = _ACT_IMPLS[act](gpre).astype(cast)
        u = jax.lax.dot_general(n, wu_ref[...], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32).astype(cast)
        macc_ref[...] += jax.lax.dot_general(ga * u, wd_ref[...],
                                             (((1,), (1,)), ((), ())),
                                             preferred_element_type=jnp.float32)

    @pl.when(f == nf - 1)
    def _store():
        o_ref[...] = (hacc_ref[...] + macc_ref[...]).astype(o_ref.dtype)


def _decode_layer_kernel(bt_ref, ln_ref, h_ref, wn1_ref, wq_ref, wk_ref,
                         wv_ref, wo_ref, cos_ref, sin_ref, kp_ref, vp_ref,
                         wn2_ref, wg_ref, wu_ref, wd_ref,
                         o_ref, kr_ref, vr_ref,
                         xn_ref, q_ref, kf_ref, vf_ref, hacc_ref, att_ref,
                         kbuf, vbuf, sem, m_ref, l_ref, acc_ref,
                         x2_ref, macc_ref, *,
                         H, KV, G, hd, ps, npg, ppb, nf, eps, scale, act, cast):
    i = pl.program_id(0)
    OA = H + 2 * KV
    _decode_qkv_phase(i, h_ref, wn1_ref, wq_ref, wk_ref, wv_ref, cos_ref,
                      sin_ref, kr_ref, vr_ref, xn_ref, q_ref, kf_ref, vf_ref,
                      hacc_ref, H=H, KV=KV, hd=hd, eps=eps, cast=cast,
                      init_h=True)
    _decode_attn_phase(i, OA, wo_ref, kp_ref, vp_ref, bt_ref, ln_ref, q_ref,
                       kf_ref, vf_ref, hacc_ref, att_ref,
                       (kbuf, vbuf, sem, m_ref, l_ref, acc_ref),
                       S=h_ref.shape[0], KV=KV, G=G, hd=hd, ps=ps, npg=npg,
                       ppb=ppb, scale=scale, cast=cast)

    @pl.when(i >= OA + KV)
    def _mlp():
        _decode_mlp_phase(i, OA + KV, nf, wn2_ref, wg_ref, wu_ref, wd_ref,
                          o_ref, hacc_ref, x2_ref, macc_ref, eps=eps, act=act,
                          cast=cast)


def _attn_subblock_kernel(bt_ref, ln_ref, h_ref, wn1_ref, wq_ref, wk_ref,
                          wv_ref, wo_ref, cos_ref, sin_ref, kp_ref, vp_ref,
                          o_ref, kr_ref, vr_ref,
                          xn_ref, q_ref, kf_ref, vf_ref, hacc_ref, att_ref,
                          kbuf, vbuf, sem, m_ref, l_ref, acc_ref, *,
                          H, KV, G, hd, ps, npg, ppb, eps, scale, cast):
    i = pl.program_id(0)
    OA = H + 2 * KV
    _decode_qkv_phase(i, h_ref, wn1_ref, wq_ref, wk_ref, wv_ref, cos_ref,
                      sin_ref, kr_ref, vr_ref, xn_ref, q_ref, kf_ref, vf_ref,
                      hacc_ref, H=H, KV=KV, hd=hd, eps=eps, cast=cast,
                      init_h=False)
    _decode_attn_phase(i, OA, wo_ref, kp_ref, vp_ref, bt_ref, ln_ref, q_ref,
                       kf_ref, vf_ref, hacc_ref, att_ref,
                       (kbuf, vbuf, sem, m_ref, l_ref, acc_ref),
                       S=h_ref.shape[0], KV=KV, G=G, hd=hd, ps=ps, npg=npg,
                       ppb=ppb, scale=scale, cast=cast)

    @pl.when(i == pl.num_programs(0) - 1)
    def _store():
        o_ref[...] = hacc_ref[...].astype(o_ref.dtype)  # pre-residual proj


def _decode_call(h, w_norm, wq, wk, wv, wo, cos, sin, k_pages, v_pages,
                 block_tables, lengths, write_pos, mlp=None, act="silu",
                 eps=1e-5, scale=None):
    """Shared wrapper: build the flattened phase grid, run the megakernel,
    and append the fresh K/V rows to the pools with the decomposition's
    replace-semantics scatter. ``mlp=(w_norm2, w_gate, w_up, w_down)``
    selects the full decode-layer kernel; None the attention sub-block."""
    S, T, D = h.shape
    KV, P, ps, hd = k_pages.shape
    H = wq.shape[0] // hd
    G = H // KV
    npg = block_tables.shape[1]
    scale_v = scale if scale is not None else 1.0 / math.sqrt(hd)
    cast = h.dtype
    h2 = h.reshape(S, D)
    # full-head-width rope tables for the kernel's one-roll half-rotation:
    # cos -> [c, c], sin -> [-s, s] (lane-dense (S, hd) blocks)
    cos2 = cos.reshape(S, hd // 2)
    sin2 = sin.reshape(S, hd // 2)
    cos2 = jnp.concatenate([cos2, cos2], axis=-1)
    sin2 = jnp.concatenate([-sin2, sin2], axis=-1)
    OA = H + 2 * KV
    # pages of each pool a block of the walk stages: the gate's own number
    ppb = decode_subblock_pages_per_block(
        S, D, H, KV, hd, ps, 0 if mlp is None else mlp[1].shape[0],
        cast.itemsize, npg)

    in_specs = [
        pl.BlockSpec((S, D), lambda i, bt, ln: (0, 0)),            # h
        pl.BlockSpec((D,), lambda i, bt, ln: (0,)),                # wn1
        pl.BlockSpec((hd, D), lambda i, bt, ln: (jnp.clip(i, 0, H - 1), 0)),
        pl.BlockSpec((hd, D),
                     lambda i, bt, ln: (jnp.clip(i - H, 0, KV - 1), 0)),
        pl.BlockSpec((hd, D),
                     lambda i, bt, ln: (jnp.clip(i - H - KV, 0, KV - 1), 0)),
        pl.BlockSpec((D, G * hd),                                  # wo
                     lambda i, bt, ln: (0, jnp.clip(i - OA, 0, KV - 1))),
        pl.BlockSpec((S, hd), lambda i, bt, ln: (0, 0)),           # [c, c]
        pl.BlockSpec((S, hd), lambda i, bt, ln: (0, 0)),           # [-s, s]
        pl.BlockSpec(memory_space=pl.ANY),                         # k pages
        pl.BlockSpec(memory_space=pl.ANY),                         # v pages
    ]
    operands = [h2, w_norm, wq, wk, wv, wo, cos2, sin2, k_pages, v_pages]
    scratch = [
        pltpu.VMEM((S, D), cast),          # normed rows
        # per-head row stashes are read back ONE ROW at a dynamic slot
        # index: kept 32-bit (values already rounded to the row dtype) so
        # the single-row slice never splits a packed sub-32-bit sublane
        pltpu.VMEM((H, S, hd), jnp.float32),   # roped q
        pltpu.VMEM((KV, S, hd), jnp.float32),  # fresh k rows
        pltpu.VMEM((KV, S, hd), jnp.float32),  # fresh v rows
        pltpu.VMEM((S, D), jnp.float32),   # residual accumulator
        pltpu.VMEM((S, G * hd), jnp.float32),  # one head group's attention
        #                                        rows, written a slot a time
        *_walk_scratch(ppb, ps, hd, G, k_pages.dtype),
    ]
    if mlp is not None:
        wn2, wg, wu, wd = mlp
        F = wg.shape[0]
        bf = _pick_block(F, _SUBBLOCK_FF_BUDGET)
        nf = F // bf
        OM = OA + KV
        in_specs += [
            pl.BlockSpec((D,), lambda i, bt, ln: (0,)),            # wn2
            pl.BlockSpec((bf, D),
                         lambda i, bt, ln: (jnp.clip(i - OM, 0, nf - 1), 0)),
            pl.BlockSpec((bf, D),
                         lambda i, bt, ln: (jnp.clip(i - OM, 0, nf - 1), 0)),
            pl.BlockSpec((D, bf),
                         lambda i, bt, ln: (0, jnp.clip(i - OM, 0, nf - 1))),
        ]
        operands += [wn2, wg, wu, wd]
        scratch += [pltpu.VMEM((S, D), cast),          # second norm rows
                    pltpu.VMEM((S, D), jnp.float32)]   # mlp accumulator
        kern = functools.partial(_decode_layer_kernel, H=H, KV=KV, G=G,
                                 hd=hd, ps=ps, npg=npg, ppb=ppb, nf=nf,
                                 eps=eps, scale=scale_v, act=act, cast=cast)
        n_total = OM + nf
    else:
        kern = functools.partial(_attn_subblock_kernel, H=H, KV=KV, G=G,
                                 hd=hd, ps=ps, npg=npg, ppb=ppb, eps=eps,
                                 scale=scale_v, cast=cast)
        n_total = OA + KV

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                         # block_tables, lengths
        grid=(n_total,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((S, D), lambda i, bt, ln: (0, 0)),
            pl.BlockSpec((1, S, hd),
                         lambda i, bt, ln: (jnp.clip(i - H, 0, KV - 1), 0, 0)),
            pl.BlockSpec((1, S, hd),
                         lambda i, bt, ln: (jnp.clip(i - H - KV, 0, KV - 1),
                                            0, 0)),
        ],
        scratch_shapes=scratch,
    )
    out, k_rows, v_rows = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((S, D), cast),
                   jax.ShapeDtypeStruct((KV, S, hd), cast),
                   jax.ShapeDtypeStruct((KV, S, hd), cast)],
        interpret=_interpret(), **_grid_params(planned_vmem=True),
    )(block_tables.astype(jnp.int32).reshape(-1), lengths.astype(jnp.int32),
      *operands)
    # the page-pool append stays a plain replace-semantics scatter in the
    # same XLA program (identical traffic to the decomposition's
    # prims.scatter; duplicate idle-slot positions all hit the reserved
    # scratch page, any write wins)
    wp = write_pos.astype(jnp.int32)
    kp = k_pages.reshape(KV, P * ps, hd).at[:, wp].set(k_rows)
    vp = v_pages.reshape(KV, P * ps, hd).at[:, wp].set(v_rows)
    return (out.reshape(S, T, D), kp.reshape(KV, P, ps, hd),
            vp.reshape(KV, P, ps, hd))


def pallas_attn_subblock(h, w_norm, wq, wk, wv, wo, cos, sin, k_pages,
                         v_pages, block_tables, lengths, write_pos,
                         eps=1e-5, scale=None):
    args = (h, w_norm, wq, wk, wv, wo, cos, sin, k_pages, v_pages,
            block_tables, lengths, write_pos)
    if _gspmd_mesh.get() is None:
        return _decode_call(*args, mlp=None, eps=eps, scale=scale)

    # plan: q/k/v column-parallel BY HEAD, the pool by kv-head, the
    # out-projection row-parallel — each shard attends its own heads over
    # its own pool slice and emits a PARTIAL (pre-residual) projection;
    # one all-reduce, and the pool never leaves its shard
    def shard(ax, *local):
        out, kp, vp = _decode_call(*local, mlp=None, eps=eps, scale=scale)
        return jax.lax.psum(out, ax), kp, vp

    return _under_plan(
        shard,
        (_REP, _REP, _COL, _COL, _COL, _ROW, _REP, _REP, _POOL, _POOL,
         _REP, _REP, _REP),
        [_REP, _POOL, _POOL], *args)


def pallas_decode_layer(h, attn_norm, wq, wk, wv, wo, cos, sin, k_pages,
                        v_pages, block_tables, lengths, write_pos, mlp_norm,
                        w_gate, w_up, w_down, act="silu", eps=1e-5,
                        scale=None):
    return _decode_call(h, attn_norm, wq, wk, wv, wo, cos, sin, k_pages,
                        v_pages, block_tables, lengths, write_pos,
                        mlp=(mlp_norm, w_gate, w_up, w_down), act=act,
                        eps=eps, scale=scale)


def _attn_subblock_checker(h, w_norm, wq, wk, wv, wo, cos, sin, k_pages,
                           v_pages, block_tables, lengths, write_pos,
                           eps=1e-5, scale=None):
    if not _enabled():
        return False
    if h.ndim != 3 or int(h.shape[1]) != 1:
        return False                       # decode-only: one row per slot
    if k_pages.ndim != 4 or tuple(v_pages.shape) != tuple(k_pages.shape):
        return False
    KV, P, ps, hd = (int(d) for d in k_pages.shape)
    if hd % 2:
        return False
    S, D = int(h.shape[0]), int(h.shape[-1])
    if w_norm is None or getattr(w_norm, "ndim", 0) != 1 \
            or int(w_norm.shape[0]) != D:
        return False
    if wq.ndim != 2 or int(wq.shape[1]) != D or int(wq.shape[0]) % hd:
        return False
    H = int(wq.shape[0]) // hd
    if H % KV:
        return False
    if tuple(wk.shape) != (KV * hd, D) or tuple(wv.shape) != (KV * hd, D):
        return False
    if tuple(wo.shape) != (D, H * hd):
        return False
    if tuple(cos.shape) != (S, 1, 1, hd // 2) \
            or tuple(sin.shape) != (S, 1, 1, hd // 2):
        return False
    # f32 norm/softmax/GEMM accumulation: reject f64 (x64 mode) rather than
    # silently narrow; weights, tables and pools must share the row dtype
    # (the kernel writes its fresh rows straight into the pools)
    if not h.dtype.is_float or h.dtype.bytes > 4:
        return False
    if any(w.dtype != h.dtype
           for w in (w_norm, wq, wk, wv, wo, cos, sin, k_pages, v_pages)):
        return False
    if (block_tables.ndim != 2 or int(block_tables.shape[0]) != S
            or lengths.ndim != 1 or int(lengths.shape[0]) != S
            or write_pos.ndim != 1 or int(write_pos.shape[0]) != S):
        return False
    if not (block_tables.dtype.is_int and lengths.dtype.is_int
            and write_pos.dtype.is_int):
        return False
    if _interpret():
        return True
    from thunder_tpu.core.cost_model import (
        VMEM_BUDGET_BYTES,
        decode_subblock_vmem_bytes,
    )

    # under the tensor-parallel plan each shard's kernel sees heads / tp
    tp = _plan_shards()
    if H % tp or KV % tp:
        return False
    return (hd % 128 == 0 and _whole_tiles(ps, h.dtype.bytes)
            and D % 128 == 0 and S % 8 == 0
            and decode_subblock_vmem_bytes(S, D, H // tp, KV // tp, hd, ps,
                                           0, h.dtype.bytes,
                                           int(block_tables.shape[1]))
            <= VMEM_BUDGET_BYTES)


def _decode_layer_checker(h, attn_norm, wq, wk, wv, wo, cos, sin, k_pages,
                          v_pages, block_tables, lengths, write_pos,
                          mlp_norm, w_gate, w_up, w_down, act="silu",
                          eps=1e-5, scale=None):
    if act not in _ACT_IMPLS:
        return False
    if not _attn_subblock_checker(h, attn_norm, wq, wk, wv, wo, cos, sin,
                                  k_pages, v_pages, block_tables, lengths,
                                  write_pos, eps, scale):
        return False
    D = int(h.shape[-1])
    if mlp_norm is None or getattr(mlp_norm, "ndim", 0) != 1 \
            or int(mlp_norm.shape[0]) != D:
        return False
    if w_gate.ndim != 2 or int(w_gate.shape[1]) != D \
            or tuple(w_up.shape) != tuple(w_gate.shape):
        return False
    F = int(w_gate.shape[0])
    if tuple(w_down.shape) != (D, F):
        return False
    if any(w.dtype != h.dtype for w in (mlp_norm, w_gate, w_up, w_down)):
        return False
    if _interpret():
        return True
    from thunder_tpu.core.cost_model import (
        VMEM_BUDGET_BYTES,
        decode_subblock_vmem_bytes,
    )

    KV, _, ps, hd = (int(d) for d in k_pages.shape)
    H = int(wq.shape[0]) // hd
    S = int(h.shape[0])
    return (F % 128 == 0
            and decode_subblock_vmem_bytes(S, D, H, KV, hd, ps, F,
                                           h.dtype.bytes,
                                           int(block_tables.shape[1]))
            <= VMEM_BUDGET_BYTES)


# ---------------------------------------------------------------------------
# fused multi-tensor AdamW (the apex-multi_tensor_apply / torch-"foreach"
# analog, claimed from the optim.fused_adamw composite built by
# core.fusion_passes.optimizer_fusion_pass). One elementwise kernel body,
# two ways of feeding it a dtype bucket:
#
# - every tile-aligned matrix of the bucket is updated IN PLACE in its own
#   HBM layout — p/m/v alias their outputs, the grid walks (rows, lanes)
#   blocks of the tensor as it lies, nothing is copied. These are all of a
#   transformer's weight bytes.
# - what is left (norm vectors, biases, odd shapes — kilobytes) is flattened
#   into one zero-padded (rows, 128) slab per operand stream and takes ONE
#   launch.
#
# The first form exists because of the chip: packing EVERY tensor into
# slabs, as this kernel first did, stages a copy of each of the seven
# streams — XLA cannot fuse a concatenate into a Mosaic custom call, and a
# flat (n/128, 128) view of a (rows, cols) matrix is a relayout under TPU
# tiling, not a bitcast. AOT-compiled for a v5e that was 5.0 GiB of
# temporaries for ONE 7B-geometry layer's 202 M parameters (2.6x the
# operands); the 2-layer bench bucket could not fit 16 GB at all.
# ---------------------------------------------------------------------------

# slab geometry (lane width + row-block) is owned by ops/optim.py::
# slab_geometry — ONE source of truth shared with the slab-persistent
# optimizer state, so the kernel tiles can never drift from the persistent
# layout (that identity is what the bit-identity tests pin)
from thunder_tpu.ops.optim import SLAB_LANE as _ADAMW_LANE  # noqa: E402

# f32 bytes of one operand block of the in-place form: 7 streams x 2
# pipeline buffers of at most this stay under Mosaic's default scoped VMEM
_ADAMW_BLOCK_BYTES = 1 << 20
_ADAMW_SUBLANES = 16    # bf16 packs 16 rows per sublane tile (f32: 8)


def _fused_adamw_kernel(g_ref, p_ref, m_ref, v_ref, bc1_ref, bc2_ref,
                        pn_ref, mn_ref, vn_ref, *, lr: float, beta1: float,
                        beta2: float, eps: float, weight_decay: float):
    """Elementwise AdamW on one tile; the op order mirrors the
    ``optim.adamw_step`` decomposition exactly (f32 arithmetic, store
    rounded to each stream's dtype). Exact op order bounds fused-vs-unfused
    divergence at final-bit ULPs (XLA contracts mul+add to FMA differently
    per compilation mode — bit-identity across modes is not well-defined;
    the 4-ULP parity suite in tests/test_pallas.py pins the bound)."""
    g = g_ref[...].astype(jnp.float32)
    m = m_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    p = p_ref[...].astype(jnp.float32)
    m_new = m * beta1 + g * (1.0 - beta1)
    v_new = v * beta2 + (g * g) * (1.0 - beta2)
    m_hat = m_new / bc1_ref[0, 0]
    v_hat = v_new / bc2_ref[0, 0]
    upd = m_hat / (jnp.sqrt(v_hat) + eps)
    if weight_decay:
        upd = upd + p * weight_decay
    pn_ref[...] = (p - upd * lr).astype(pn_ref.dtype)
    mn_ref[...] = m_new.astype(mn_ref.dtype)
    vn_ref[...] = v_new.astype(vn_ref.dtype)


def _slab_pack(ts, sizes, rows_pad):
    """Flatten+concat a tensor list into a zero-tail-padded (rows, 128) slab."""
    total = sum(sizes)
    n_pad = rows_pad * _ADAMW_LANE
    flat = [jnp.ravel(t) for t in ts]
    cat = flat[0] if len(flat) == 1 else jnp.concatenate(flat)
    if n_pad != total:
        cat = jnp.concatenate([cat, jnp.zeros((n_pad - total,), cat.dtype)])
    return cat.reshape(rows_pad, _ADAMW_LANE)


def _slab_unpack(slab, like, sizes):
    flat = slab.reshape(-1)
    outs, off = [], 0
    for t, s in zip(like, sizes):
        outs.append(flat[off:off + s].reshape(t.shape))
        off += s
    return tuple(outs)


def _adamw_call(g2, p2, m2, v2, bc1, bc2, *, block, m_dtype, v_dtype,
                **hyper):
    """The shared kernel call over four same-shaped 2-D operands, ``block``
    a (rows, lanes) tile dividing them — used by the in-place form, the
    pack-per-step slab and the slab-persistent ``optim.fused_adamw_slab``
    claim, so all three run the IDENTICAL kernel body (that is what makes
    their parameter updates bit-identical). Each of p/m/v aliases its output
    when the stored dtype is unchanged: under a donating jit the update is
    in place, and a staged slab is reused instead of doubled."""
    rows, cols = p2.shape
    bn, bc = block
    tile = pl.BlockSpec((bn, bc), lambda i, j: (i, j))
    scalar_spec = pl.BlockSpec((1, 1), lambda i, j: (0, 0))
    out_dtypes = (p2.dtype, m_dtype, v_dtype)
    aliases = {1 + k: k for k, (a, dt) in enumerate(zip((p2, m2, v2), out_dtypes))
               if a.dtype == dt}
    return pl.pallas_call(
        functools.partial(_fused_adamw_kernel, **hyper),
        grid=(rows // bn, cols // bc),
        in_specs=[tile, tile, tile, tile, scalar_spec, scalar_spec],
        out_specs=[tile, tile, tile],
        out_shape=[jax.ShapeDtypeStruct((rows, cols), dt) for dt in out_dtypes],
        input_output_aliases=aliases,
        interpret=_interpret(),
        **_grid_params("parallel", "parallel"),
    )(g2, p2, m2, v2,
      jnp.asarray(bc1, jnp.float32).reshape(1, 1),
      jnp.asarray(bc2, jnp.float32).reshape(1, 1))


def _adamw_inplace_view(shape) -> tuple | None:
    """``((rows, cols), (block_rows, block_cols))`` when a tensor of this
    shape can be updated in place in its own layout, else ``None`` (it rides
    the packed slab). Needs a lane-aligned last dim and sublane-aligned
    rows; leading dims collapse into rows only where that is layout-free
    (the second-to-last dim already fills whole sublane tiles)."""
    if len(shape) < 2:
        return None
    cols = int(shape[-1])
    rows = int(math.prod(shape[:-1]))
    if cols % _ADAMW_LANE or rows % _ADAMW_SUBLANES:
        return None
    if len(shape) > 2 and int(shape[-2]) % _ADAMW_SUBLANES:
        return None
    budget = _ADAMW_BLOCK_BYTES // 4                    # f32 elements
    bc = cols
    if _ADAMW_SUBLANES * cols > budget:                 # very wide: block lanes
        bc = max(c for c in range(_ADAMW_LANE, cols + 1, _ADAMW_LANE)
                 if cols % c == 0 and _ADAMW_SUBLANES * c <= budget)
    bn = max(r for r in range(_ADAMW_SUBLANES, rows + 1, _ADAMW_SUBLANES)
             if rows % r == 0 and r * bc <= max(budget, _ADAMW_SUBLANES * bc))
    return (rows, cols), (bn, bc)


def pallas_fused_adamw(params, grads, ms, vs, bc1, bc2, *, lr: float = 1e-3,
                       beta1: float = 0.9, beta2: float = 0.999,
                       eps: float = 1e-8, weight_decay: float = 0.0,
                       state_dtype=None, v_dtype=None):
    """The whole dtype bucket: aligned matrices in place, one launch each;
    the rest through one packed slab. Zero-padding the slab tail is benign:
    padded lanes compute 0/(sqrt(0)+eps) = 0 (no NaNs) and are sliced off
    on unpack."""
    from thunder_tpu.ops.optim import slab_geometry

    hyper = dict(lr=lr, beta1=beta1, beta2=beta2, eps=eps,
                 weight_decay=weight_decay)
    m_dtype = state_dtype.jax if state_dtype is not None else ms[0].dtype
    vv_dtype = v_dtype.jax if v_dtype is not None else vs[0].dtype
    n = len(params)
    pn, mn, vn = [None] * n, [None] * n, [None] * n
    packed = []
    for i, p in enumerate(params):
        view = _adamw_inplace_view(p.shape)
        if view is None:
            packed.append(i)
            continue
        flat, block = view
        out = _adamw_call(grads[i].reshape(flat), p.reshape(flat),
                          ms[i].reshape(flat), vs[i].reshape(flat), bc1, bc2,
                          block=block, m_dtype=m_dtype, v_dtype=vv_dtype,
                          **hyper)
        pn[i], mn[i], vn[i] = (o.reshape(p.shape) for o in out)
    if packed:
        sub = lambda ts: [ts[i] for i in packed]
        sizes = [int(math.prod(params[i].shape)) for i in packed]  # () -> 1
        rows_pad, bn = slab_geometry(sum(sizes))
        outs = _adamw_call(
            _slab_pack(sub(grads), sizes, rows_pad),
            _slab_pack(sub(params), sizes, rows_pad),
            _slab_pack(sub(ms), sizes, rows_pad),
            _slab_pack(sub(vs), sizes, rows_pad),
            bc1, bc2, block=(bn, _ADAMW_LANE), m_dtype=m_dtype,
            v_dtype=vv_dtype, **hyper)
        for dst, slab in zip((pn, mn, vn), outs):
            for i, t in zip(packed, _slab_unpack(slab, sub(params), sizes)):
                dst[i] = t
    return tuple(pn), tuple(mn), tuple(vn)


def pallas_fused_adamw_slab(params, grads, m_slab, v_slab, bc1, bc2, *,
                            sizes, lr: float = 1e-3, beta1: float = 0.9,
                            beta2: float = 0.999, eps: float = 1e-8,
                            weight_decay: float = 0.0):
    """Slab-persistent claim: m/v arrive AS the persistent (rows, 128)
    slabs and leave the same way — no pack/unpack of the state streams
    exists on this path; p/g are still packed, and the p update unpacked,
    per step (so at 7B widths it stages two full copies of the weights —
    see the section header; ``AdamW(slab_persistent=True)`` is opt-in)."""
    from thunder_tpu.ops.optim import slab_geometry

    sizes = [int(s) for s in sizes]
    rows_pad, bn = slab_geometry(sum(sizes))
    pn, mn, vn = _adamw_call(
        _slab_pack(grads, sizes, rows_pad), _slab_pack(params, sizes, rows_pad),
        m_slab, v_slab, bc1, bc2, block=(bn, _ADAMW_LANE),
        m_dtype=m_slab.dtype, v_dtype=v_slab.dtype,
        lr=lr, beta1=beta1, beta2=beta2, eps=eps, weight_decay=weight_decay)
    return _slab_unpack(pn, params, sizes), mn, vn


def _fused_adamw_checker(params, grads, ms, vs, bc1, bc2, **hyper):
    if not _enabled():
        return False
    params, grads, ms, vs = tuple(params), tuple(grads), tuple(ms), tuple(vs)
    if not params or any(len(g) != len(params) for g in (grads, ms, vs)):
        return False
    for group in (params, grads, ms, vs):
        d0 = group[0].dtype
        if any(t.dtype != d0 for t in group):
            return False  # the fusion pass buckets by dtype; mixed = bug
        # arithmetic is f32: claiming an f64 bucket (x64 mode) would
        # silently narrow — reject, keep the decomposition
        if not d0.is_float or d0.bytes > 4:
            return False
    # configured m/v storage dtypes (checkpoint re-coercion) must be float
    # and representable by the f32 kernel too
    for dt in (hyper.get("state_dtype"), hyper.get("v_dtype")):
        if dt is not None and (not dt.is_float or dt.bytes > 4):
            return False
    return True


def _fused_adamw_slab_checker(params, grads, m_slab, v_slab, bc1, bc2, *,
                              sizes, **hyper):
    if not _enabled():
        return False
    from thunder_tpu.ops.optim import SLAB_LANE, slab_geometry

    params, grads = tuple(params), tuple(grads)
    sizes = tuple(int(s) for s in sizes)
    if not params or len(grads) != len(params) or len(sizes) != len(params):
        return False
    for group in (params, grads):
        d0 = group[0].dtype
        if any(t.dtype != d0 for t in group) or not d0.is_float or d0.bytes > 4:
            return False
    for slab in (m_slab, v_slab):
        if not slab.dtype.is_float or slab.dtype.bytes > 4 or slab.ndim != 2:
            return False
    rows_pad, _ = slab_geometry(sum(sizes))
    return (tuple(m_slab.shape) == (rows_pad, SLAB_LANE)
            and tuple(v_slab.shape) == (rows_pad, SLAB_LANE))


def _pallas_claim_profitable(bsym):
    """Cost-model claim gate (``ImplInfo.profitable``): on real TPU a
    memory-bound claim with a tiny working set loses to leaving the op
    inside an XLA fusion region (kernel launch + pipeline fill dominate);
    in interpret mode cost ratios are meaningless, so always claim — the
    CPU test suite exercises kernels that way."""
    if _interpret():
        return True
    from thunder_tpu.core.compile_data import get_compile_option

    if not get_compile_option(
            "fusion_cost_model",
            "gate memory-bound Pallas claims on the roofline cost model "
            "(claims moving under ~1 MiB stay inside XLA fusion regions)", True):
        return True
    from thunder_tpu.core.cost_model import claim_worthwhile

    return claim_worthwhile(bsym)


# ---------------------------------------------------------------------------
# registration: claim the nn composite symbols
# ---------------------------------------------------------------------------

# pallas_call impls are jax-traceable: the XLA fusion pass may absorb
# claimed kernels INTO its jit regions (see XLAFusionExecutor.can_absorb)
ex.fusible_into_regions = True

_sdpa_sym = get_op("nn.sdpa_fwd")
_sdpa_bwd_sym = get_op("nn.sdpa_bwd")
_ce_sym = get_op("nn.ce_fwd")
_rms_sym = get_op("nn.rms_norm")

sdpa_fwd_op = ex.register_operator("sdpa_fwd", meta=_sdpa_sym.meta, fn=pallas_sdpa_fwd)
sdpa_bwd_op = ex.register_operator("sdpa_bwd", meta=_sdpa_bwd_sym.meta, fn=pallas_sdpa_bwd)
ce_fwd_op = ex.register_operator("ce_fwd", meta=_ce_sym.meta, fn=pallas_ce_fwd)
rms_norm_op = ex.register_operator("rms_norm", meta=_rms_sym.meta, fn=pallas_rms_norm)

ex.register_implementation("nn.sdpa_fwd", sdpa_fwd_op, checker=_sdpa_checker)
ex.register_implementation("nn.sdpa_bwd", sdpa_bwd_op, checker=_sdpa_bwd_checker)
ex.register_implementation("nn.ce_fwd", ce_fwd_op, checker=_ce_checker,
                           profitable=_pallas_claim_profitable)
ex.register_implementation("nn.rms_norm", rms_norm_op, checker=_rms_checker,
                           profitable=_pallas_claim_profitable)

_fused_adamw_sym = get_op("optim.fused_adamw")
fused_adamw_op = ex.register_operator(
    "fused_adamw", meta=_fused_adamw_sym.meta, fn=pallas_fused_adamw)
# no `profitable` hook: the optimizer fusion pass only BUILDS the
# composite when cost_model.fused_adamw_profitable already accepted the
# bucket, so a second claim-time gate would just re-ask the same question
ex.register_implementation("optim.fused_adamw", fused_adamw_op,
                           checker=_fused_adamw_checker)

# slab-persistent variant: emitted directly by AdamW(slab_persistent=True)
# with the bucket layout already decided (same reasoning: no second gate)
_fused_adamw_slab_sym = get_op("optim.fused_adamw_slab")
fused_adamw_slab_op = ex.register_operator(
    "fused_adamw_slab", meta=_fused_adamw_slab_sym.meta,
    fn=pallas_fused_adamw_slab)
ex.register_implementation("optim.fused_adamw_slab", fused_adamw_slab_op,
                           checker=_fused_adamw_slab_checker)

# block-planner megakernel: the whole MLP sub-block forward (claimed from
# the composite the planner emits; no `profitable` hook — the planner's
# cost model already decided)
_mlp_sub_sym = get_op("nn.mlp_subblock")
mlp_subblock_op = ex.register_operator(
    "mlp_subblock", meta=_mlp_sub_sym.meta, fn=pallas_mlp_subblock)
ex.register_implementation("nn.mlp_subblock", mlp_subblock_op,
                           checker=_mlp_subblock_checker)

_rms_res_sym = get_op("nn.rms_norm_residual")
_linear_act_sym = get_op("nn.linear_act")
rms_norm_residual_op = ex.register_operator(
    "rms_norm_residual", meta=_rms_res_sym.meta, fn=pallas_rms_norm_residual)
linear_act_op = ex.register_operator(
    "linear_act", meta=_linear_act_sym.meta, fn=pallas_linear_act)
ex.register_implementation("nn.rms_norm_residual", rms_norm_residual_op,
                           checker=_rms_res_checker,
                           profitable=_pallas_claim_profitable)
ex.register_implementation("nn.linear_act", linear_act_op,
                           checker=_linear_act_checker,
                           profitable=_pallas_claim_profitable)

# serving: ragged paged decode attention (claimed from the composite the
# serving runner emits; prefill chunks fail the T==1 checker and take
# the XLA decomposition). Cost-model gated like the other memory-bound
# claims — a tiny pool gather can stay inside the XLA region.
_paged_sym = get_op("nn.paged_decode_attention")
paged_decode_op = ex.register_operator(
    "paged_decode_attention", meta=_paged_sym.meta,
    fn=pallas_paged_decode_attention)
ex.register_implementation("nn.paged_decode_attention", paged_decode_op,
                           checker=_paged_decode_checker,
                           profitable=_pallas_claim_profitable)

# serving: the whole-decode-layer megakernel family (claimed from the
# composites the block planner's attention walk + chaining stage build;
# no `profitable` hook — the planner's decode cost model is the gate).
# Layered quarantine fallback: pallas.decode_layer -> the two sub-block
# kernels -> the fully per-op XLA chain.
_attn_sub_sym = get_op("nn.attn_subblock")
_decode_layer_sym = get_op("nn.decode_layer")
attn_subblock_op = ex.register_operator(
    "attn_subblock", meta=_attn_sub_sym.meta, fn=pallas_attn_subblock)
decode_layer_op = ex.register_operator(
    "decode_layer", meta=_decode_layer_sym.meta, fn=pallas_decode_layer)
ex.register_implementation("nn.attn_subblock", attn_subblock_op,
                           checker=_attn_subblock_checker)
ex.register_implementation("nn.decode_layer", decode_layer_op,
                           checker=_decode_layer_checker)

# serving prefill: the chunk's banded flash forward, and the grouped expert
# matmul both serving programs use. No `profitable` hook: the decomposition
# of either materialises what the kernel exists to avoid (the whole score
# matrix; every held expert over every row).
_banded_sym = get_op("nn.banded_attention")
banded_attention_op = ex.register_operator(
    "banded_attention", meta=_banded_sym.meta, fn=pallas_banded_attention)
ex.register_implementation("nn.banded_attention", banded_attention_op,
                           checker=_banded_checker)
_moe_sym = get_op("nn.moe_experts")
moe_experts_op = ex.register_operator(
    "moe_experts", meta=_moe_sym.meta, fn=pallas_moe_experts)
ex.register_implementation("nn.moe_experts", moe_experts_op,
                           checker=_moe_experts_checker)

# serving, a state kind: the delta rule's decode step (the state aliased in
# place) and its prefill chunk. No `profitable` hook: the decomposition of
# either moves the state more than once (and the chunk's builds every
# pair's decay at once).
_kda_decode_sym = get_op("nn.kda_decode")
kda_decode_op = ex.register_operator(
    "kda_decode", meta=_kda_decode_sym.meta, fn=pallas_kda_decode)
ex.register_implementation("nn.kda_decode", kda_decode_op,
                           checker=_kda_decode_checker)
_kda_chunk_sym = get_op("nn.kda_chunk")
kda_chunk_op = ex.register_operator(
    "kda_chunk", meta=_kda_chunk_sym.meta, fn=pallas_kda_chunk)
ex.register_implementation("nn.kda_chunk", kda_chunk_op,
                           checker=_kda_chunk_checker)

# inference-path SDPA (no lse output needed)
def pallas_sdpa(q, k, v, attn_mask=None, dropout_p=0.0, is_causal=False, scale=None):
    return pallas_sdpa_fwd(q, k, v, is_causal, scale)[0]

def _sdpa_full_checker(q, k, v, attn_mask=None, dropout_p=0.0, is_causal=False, scale=None):
    return attn_mask is None and not dropout_p and _sdpa_checker(q, k, v, is_causal, scale)

sdpa_op = ex.register_operator(
    "sdpa", meta=get_op("nn.scaled_dot_product_attention").meta, fn=pallas_sdpa)
ex.register_implementation("nn.scaled_dot_product_attention", sdpa_op,
                           checker=_sdpa_full_checker)
