"""Distributed collective prims.

Reference parity: ``thunder/distributed/prims.py`` — collectives are traced
as *async prims returning FutureTensorProxy* consumed by an explicit ``wait``
(:62-171 there), the IR design that makes comm/compute overlap visible and
reorderable. TPU lowering: each collective maps to the ``jax.lax`` collective
on a named mesh axis inside ``shard_map``; ``wait`` lowers to identity and
XLA's async-collective scheduler performs the actual overlap (SURVEY §5
"Distributed communication backend"). No process groups, no NCCL, no
bucketing — XLA's combiners replace ``GradBuckets``.

VJP rules for ``synchronize`` implement the DP/FSDP grad flows
(reference ``distributed/prims.py:376-419``).
"""

from __future__ import annotations

from enum import Enum, auto

import jax

from thunder_tpu.core import dtypes
from thunder_tpu.core.baseutils import check
from thunder_tpu.core.proxies import DistParallelType, FutureTensorProxy, TensorProxy
from thunder_tpu.core.prims import OpTags, make_prim
from thunder_tpu.core.transforms import register_vjp


class DistPrimIDs(Enum):
    ALL_GATHER = auto()
    ALL_REDUCE = auto()
    REDUCE_SCATTER = auto()
    BROADCAST = auto()
    PPERMUTE = auto()
    ALL_TO_ALL = auto()
    WAIT = auto()
    SYNCHRONIZE = auto()
    REGATHER = auto()
    SYNCHRONIZE_TP_OUTPUT = auto()
    SYNCHRONIZE_TP_INPUT = auto()
    AXIS_INDEX = auto()
    BUCKETED_ALL_GATHER = auto()
    BUCKETED_REDUCE_SCATTER = auto()
    BUCKET_UNPACK_GATHER = auto()
    BUCKET_UNPACK_SCATTER = auto()


# ---------------------------------------------------------------------------
# pinned lowering switch
# ---------------------------------------------------------------------------

# NORTHSTAR r5 measured XLA rewriting zero-2's reduce-scatters into
# all-reduces on the v5p AOT path (per-chip comm 2.2x the trace-level bytes).
# The pinned lowering feeds each sharded collective through
# ``jax.lax.optimization_barrier`` — the same pin ``regather`` uses against
# CSE — so the collective the trace scheduled is the collective XLA emits.
# Default ON; ``pin_collectives(False)`` is the A/B escape hatch for an
# on-chip measurement nobody has made yet (ROADMAP S5). The census's
# ``reduce-scatter-rewritten`` finding verifies the pin per compile.
_PIN_STATE = {"enabled": True}


def pin_collectives(enabled: bool | None = None) -> bool:
    """Get (no arg) or set the pinned-collective-lowering switch; returns the
    previous value when setting."""
    prev = _PIN_STATE["enabled"]
    if enabled is not None:
        _PIN_STATE["enabled"] = bool(enabled)
    return prev


def _pin(a):
    if _PIN_STATE["enabled"]:
        return jax.lax.optimization_barrier(a)
    return a


# ---------------------------------------------------------------------------
# metas: async collectives return futures
# ---------------------------------------------------------------------------

def _all_gather_meta(a: TensorProxy, axis: str, dim: int, size: int) -> FutureTensorProxy:
    shape = list(a.shape)
    shape[dim] = shape[dim] * size
    return FutureTensorProxy(a, shape=shape)


all_gather = make_prim(DistPrimIDs.ALL_GATHER, "all_gather", _all_gather_meta,
                       tags=(OpTags.COLLECTIVE_OP,))


def _all_reduce_meta(a: TensorProxy, axis: str, op: str = "sum") -> FutureTensorProxy:
    return FutureTensorProxy(a)


all_reduce = make_prim(DistPrimIDs.ALL_REDUCE, "all_reduce", _all_reduce_meta,
                       tags=(OpTags.COLLECTIVE_OP,))


def _reduce_scatter_meta(a: TensorProxy, axis: str, dim: int, size: int) -> FutureTensorProxy:
    shape = list(a.shape)
    check(shape[dim] % size == 0, lambda: f"reduce_scatter: dim {dim} ({shape[dim]}) not divisible by {size}")
    shape[dim] //= size
    return FutureTensorProxy(a, shape=shape)


reduce_scatter = make_prim(DistPrimIDs.REDUCE_SCATTER, "reduce_scatter", _reduce_scatter_meta,
                           tags=(OpTags.COLLECTIVE_OP,))


def _broadcast_meta(a: TensorProxy, axis: str, src_index: int = 0) -> FutureTensorProxy:
    return FutureTensorProxy(a)


broadcast = make_prim(DistPrimIDs.BROADCAST, "broadcast", _broadcast_meta,
                      tags=(OpTags.COLLECTIVE_OP,))


def _ppermute_meta(a: TensorProxy, axis: str, perm: tuple) -> FutureTensorProxy:
    return FutureTensorProxy(a)


ppermute = make_prim(DistPrimIDs.PPERMUTE, "ppermute", _ppermute_meta,
                     tags=(OpTags.COLLECTIVE_OP,))


def _all_to_all_meta(a: TensorProxy, axis: str, split_dim: int, concat_dim: int, size: int) -> FutureTensorProxy:
    shape = list(a.shape)
    check(shape[split_dim] % size == 0, "all_to_all: split dim not divisible by axis size")
    shape[split_dim] //= size
    shape[concat_dim] *= size
    return FutureTensorProxy(a, shape=shape)


all_to_all = make_prim(DistPrimIDs.ALL_TO_ALL, "all_to_all", _all_to_all_meta,
                       tags=(OpTags.COLLECTIVE_OP,))


# bucketed collectives: the overlap-scheduling pass coalesces sub-threshold
# same-(dtype, mesh-axis) collectives into ONE fused issue/wait pair
# (distributed/comm_reorder.bucket_collectives). Layout contracts:
#   bucketed_all_gather(axis, size, *shards) -> future[(size, sum numel_i)]
#     — each member arrives raveled and concatenated; row d holds device d's
#       members back to back.
#   bucketed_reduce_scatter(axis, size, *grads) -> future[(sum numel_i/size,)]
#     — each member reshaped (size, -1) and concatenated on dim 1; the
#       scatter leaves this device's shards back to back.
# ``bucket_unpack_gather/scatter`` slice one member back out (static offset).

def _bucketed_all_gather_meta(axis: str, size: int, *shards) -> FutureTensorProxy:
    total = 0
    for s in shards:
        n = 1
        for d in s.shape:
            n *= int(d)
        total += n
    return FutureTensorProxy(shards[0], shape=(size, total))


bucketed_all_gather = make_prim(DistPrimIDs.BUCKETED_ALL_GATHER, "bucketed_all_gather",
                                _bucketed_all_gather_meta, tags=(OpTags.COLLECTIVE_OP,))


def _bucketed_reduce_scatter_meta(axis: str, size: int, *grads) -> FutureTensorProxy:
    total = 0
    for g in grads:
        check(g.shape[0] % size == 0,
              lambda: f"bucketed_reduce_scatter: dim 0 ({g.shape[0]}) not divisible by {size}")
        n = 1
        for d in g.shape:
            n *= int(d)
        total += n // size
    return FutureTensorProxy(grads[0], shape=(total,))


bucketed_reduce_scatter = make_prim(DistPrimIDs.BUCKETED_REDUCE_SCATTER,
                                    "bucketed_reduce_scatter",
                                    _bucketed_reduce_scatter_meta,
                                    tags=(OpTags.COLLECTIVE_OP,))


def _bucket_unpack_gather_meta(buf: TensorProxy, offset: int, shape: tuple) -> TensorProxy:
    return TensorProxy(shape=tuple(shape), dtype=buf.dtype, device=buf.device)


bucket_unpack_gather = make_prim(DistPrimIDs.BUCKET_UNPACK_GATHER, "bucket_unpack_gather",
                                 _bucket_unpack_gather_meta)

bucket_unpack_scatter = make_prim(DistPrimIDs.BUCKET_UNPACK_SCATTER, "bucket_unpack_scatter",
                                  _bucket_unpack_gather_meta)


def _wait_meta(f: FutureTensorProxy) -> TensorProxy:
    return TensorProxy(shape=f.shape, dtype=f.dtype, device=f.device)


wait = make_prim(DistPrimIDs.WAIT, "wait", _wait_meta)


def _axis_index_meta(axis: str) -> TensorProxy:
    from thunder_tpu.core.devices import default_device

    return TensorProxy(shape=(), dtype=dtypes.int32, device=default_device())


axis_index = make_prim(DistPrimIDs.AXIS_INDEX, "axis_index", _axis_index_meta,
                       tags=(OpTags.COLLECTIVE_OP,))


# synchronize: the polymorphic param-sync op (reference prims.py:376-419).
def _synchronize_meta(a: TensorProxy, axis: str, parallel_type: DistParallelType, size: int,
                      token: TensorProxy | None = None) -> TensorProxy:
    if parallel_type is DistParallelType.FULLY_SHARDED:
        shape = (a.shape[0] * size,) + a.shape[1:]
        return TensorProxy(shape=shape, dtype=a.dtype, device=a.device)
    if parallel_type in (DistParallelType.REPLICATED, DistParallelType.EXPERT_SHARDED,
                         DistParallelType.PIPELINE_REPLICATED):
        return TensorProxy(shape=a.shape, dtype=a.dtype, device=a.device)
    raise NotImplementedError(f"synchronize for {parallel_type}")


synchronize = make_prim(DistPrimIDs.SYNCHRONIZE, "synchronize", _synchronize_meta,
                        tags=(OpTags.COLLECTIVE_OP,))

# regather: a backward-pass re-issue of a FULLY_SHARDED synchronize (FSDP
# ZeRO-3, reference rematerialization.py:394 rematerialize_all_gather). A
# distinct prim so neither trace-level CSE nor XLA CSE folds it back into the
# forward gather (its lowering starts with an optimization barrier).
regather = make_prim(DistPrimIDs.REGATHER, "regather", _synchronize_meta,
                     tags=(OpTags.COLLECTIVE_OP,))


def _sync_tp_output_meta(a: TensorProxy, axis: str, size: int) -> TensorProxy:
    """Row-parallel linear output: partial sums -> all_reduce."""
    return TensorProxy(shape=a.shape, dtype=a.dtype, device=a.device)


synchronize_tp_output = make_prim(DistPrimIDs.SYNCHRONIZE_TP_OUTPUT, "synchronize_tp_output",
                                  _sync_tp_output_meta, tags=(OpTags.COLLECTIVE_OP,))


def _sync_tp_input_meta(a: TensorProxy, axis: str, size: int) -> TensorProxy:
    """Column-parallel linear input: identity fwd, all_reduce bwd."""
    return TensorProxy(shape=a.shape, dtype=a.dtype, device=a.device)


synchronize_tp_input = make_prim(DistPrimIDs.SYNCHRONIZE_TP_INPUT, "synchronize_tp_input",
                                 _sync_tp_input_meta, tags=(OpTags.COLLECTIVE_OP,))


# ---------------------------------------------------------------------------
# eager (jax.lax) implementations — valid inside shard_map
# ---------------------------------------------------------------------------

import functools  # noqa: E402

from thunder_tpu.executors.eagerjax import impl  # noqa: E402


def _collective_faults(fn):
    """Host the ``collective`` fault-injection domain on each comm lowering.
    The lowerings run while the sharded program is traced, so an injected
    collective fault surfaces at compile/dispatch of the distributed step —
    the point where a real hung/failed collective would take the job down."""

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        from thunder_tpu.runtime import faults as _faults

        _faults.maybe_fail("collective", site=fn.__name__.strip("_"))
        return fn(*args, **kwargs)

    return wrapper


@impl(DistPrimIDs.ALL_GATHER)
@_collective_faults
def _all_gather_impl(a, axis, dim, size):
    # pinned: the barrier keeps the gather where the trace scheduled it
    # (XLA CSE/motion would otherwise re-plan the issue point the overlap
    # pass chose — the same failure mode regather pins against)
    return jax.lax.all_gather(_pin(a), axis, axis=dim, tiled=True)


@impl(DistPrimIDs.ALL_REDUCE)
@_collective_faults
def _all_reduce_impl(a, axis, op="sum"):
    if op == "sum":
        return jax.lax.psum(a, axis)
    if op == "max":
        return jax.lax.pmax(a, axis)
    if op == "min":
        return jax.lax.pmin(a, axis)
    if op == "mean":
        return jax.lax.pmean(a, axis)
    raise ValueError(f"unknown reduce op {op}")


@impl(DistPrimIDs.REDUCE_SCATTER)
@_collective_faults
def _reduce_scatter_impl(a, axis, dim, size):
    # pinned against the NORTHSTAR r5 pessimization: on the v5p AOT path XLA
    # rewrote these grad reduce-scatters into all-reduces (~2x the bytes per
    # grad reduction). The barrier blocks the pattern rewrite/motion across
    # the operand, so the psum_scatter survives as an HLO reduce-scatter —
    # verified per compile by the census's ``reduce-scatter-rewritten``
    # finding staying quiet.
    return jax.lax.psum_scatter(_pin(a), axis, scatter_dimension=dim, tiled=True)


@impl(DistPrimIDs.BUCKETED_ALL_GATHER)
@_collective_faults
def _bucketed_all_gather_impl(axis, size, *shards):
    cat = jax.numpy.concatenate([jax.numpy.ravel(s) for s in shards])
    return jax.lax.all_gather(_pin(cat), axis, axis=0, tiled=False)


@impl(DistPrimIDs.BUCKETED_REDUCE_SCATTER)
@_collective_faults
def _bucketed_reduce_scatter_impl(axis, size, *grads):
    cat = jax.numpy.concatenate(
        [jax.numpy.reshape(g, (size, -1)) for g in grads], axis=1)
    return jax.lax.psum_scatter(_pin(cat), axis, scatter_dimension=0, tiled=False)


@impl(DistPrimIDs.BUCKET_UNPACK_GATHER)
def _bucket_unpack_gather_impl(buf, offset, shape):
    # buf: (n_dev, total_local); the member occupies a contiguous run of each
    # row; stacking the rows on dim 0 reproduces the tiled all_gather layout
    n = buf.shape[0]
    numel = 1
    for d in shape:
        numel *= int(d)
    seg = buf[:, offset:offset + numel // n]
    return jax.numpy.reshape(seg, tuple(shape))


@impl(DistPrimIDs.BUCKET_UNPACK_SCATTER)
def _bucket_unpack_scatter_impl(buf, offset, shape):
    numel = 1
    for d in shape:
        numel *= int(d)
    return jax.numpy.reshape(buf[offset:offset + numel], tuple(shape))


@impl(DistPrimIDs.BROADCAST)
@_collective_faults
def _broadcast_impl(a, axis, src_index=0):
    # true broadcast: every rank receives src_index's value. Lowered as a
    # masked psum — zero everywhere except src, then sum across the axis —
    # which XLA turns into a one-to-all on ICI. (The round-1 identity impl
    # was only correct for already-replicated operands.)
    idx = jax.lax.axis_index(axis)
    contrib = jax.numpy.where(idx == src_index, a, jax.numpy.zeros_like(a))
    return jax.lax.psum(contrib, axis)


@impl(DistPrimIDs.PPERMUTE)
@_collective_faults
def _ppermute_impl(a, axis, perm):
    return jax.lax.ppermute(a, axis, perm=list(perm))


@impl(DistPrimIDs.ALL_TO_ALL)
@_collective_faults
def _all_to_all_impl(a, axis, split_dim, concat_dim, size):
    return jax.lax.all_to_all(a, axis, split_axis=split_dim, concat_axis=concat_dim, tiled=True)


@impl(DistPrimIDs.WAIT)
def _wait_impl(f):
    return f


@impl(DistPrimIDs.AXIS_INDEX)
def _axis_index_impl(axis):
    return jax.lax.axis_index(axis)


@impl(DistPrimIDs.SYNCHRONIZE)
@_collective_faults
def _synchronize_impl(a, axis, parallel_type, size, token=None):
    if parallel_type is DistParallelType.FULLY_SHARDED:
        return jax.lax.all_gather(a, axis, axis=0, tiled=True)
    return a


@impl(DistPrimIDs.REGATHER)
@_collective_faults
def _regather_impl(a, axis, parallel_type, size, token=None):
    # the barrier prevents XLA CSE from merging this with the forward
    # all_gather (which would revert ZeRO-3 to ZeRO-2); chaining ``token``
    # (an operand of the first backward consumer) through the same barrier
    # adds a data dependency that stops the scheduler from hoisting every
    # regather to program start — the gather runs just before its use
    if token is not None:
        a = jax.lax.optimization_barrier((a, token))[0]
    else:
        a = jax.lax.optimization_barrier(a)
    if parallel_type is DistParallelType.FULLY_SHARDED:
        return jax.lax.all_gather(a, axis, axis=0, tiled=True)
    return a


@impl(DistPrimIDs.SYNCHRONIZE_TP_OUTPUT)
@_collective_faults
def _sync_tp_output_impl(a, axis, size):
    return jax.lax.psum(a, axis)


@impl(DistPrimIDs.SYNCHRONIZE_TP_INPUT)
@_collective_faults
def _sync_tp_input_impl(a, axis, size):
    return a


# ---------------------------------------------------------------------------
# VJP rules: the DP/FSDP/TP gradient comm flows
# ---------------------------------------------------------------------------

@register_vjp(DistPrimIDs.SYNCHRONIZE)
def _synchronize_vjp(a, axis, parallel_type, size):
    out = synchronize(a, axis, parallel_type, size)

    def pullback(g):
        from thunder_tpu import ops

        if parallel_type is DistParallelType.FULLY_SHARDED:
            # ZeRO grad flow: reduce-scatter the global grad back to shards,
            # averaged across the data-parallel axis
            gs = wait(reduce_scatter(g, axis, 0, size))
            return [(a, ops.true_divide(gs, float(size)))]
        if parallel_type is DistParallelType.EXPERT_SHARDED:
            # expert grads are already complete on the owning rank (cotangents
            # arrive via the backward all_to_all); only the data-parallel
            # mean scaling is needed — no collective
            return [(a, ops.true_divide(g, float(size)))]
        if parallel_type is DistParallelType.PIPELINE_REPLICATED:
            # pipeline stages each hold the TRUE partial grad (nonzero only on
            # the stage that computes with the param: embed on stage 0, head on
            # the last stage); the sum — not the mean — is the full grad
            return [(a, wait(all_reduce(g, axis, "sum")))]
        # DDP: grads averaged across replicas
        gr = wait(all_reduce(g, axis, "sum"))
        return [(a, ops.true_divide(gr, float(size)))]

    return out, pullback


@register_vjp(DistPrimIDs.SYNCHRONIZE_TP_OUTPUT)
def _sync_tp_output_vjp(a, axis, size):
    out = synchronize_tp_output(a, axis, size)

    def pullback(g):
        return [(a, g)]  # psum fwd -> identity bwd (g already replicated)

    return out, pullback


@register_vjp(DistPrimIDs.SYNCHRONIZE_TP_INPUT)
def _sync_tp_input_vjp(a, axis, size):
    out = synchronize_tp_input(a, axis, size)

    def pullback(g):
        return [(a, wait(all_reduce(g, axis, "sum")))]

    return out, pullback


@register_vjp(DistPrimIDs.ALL_GATHER)
def _all_gather_vjp(a, axis, dim, size):
    out = all_gather(a, axis, dim, size)

    def pullback(g):
        return [(a, wait(reduce_scatter(g, axis, dim, size)))]

    return out, pullback


@register_vjp(DistPrimIDs.ALL_REDUCE)
def _all_reduce_vjp(a, axis, op="sum"):
    check(op == "sum", "only sum all_reduce is differentiable")
    out = all_reduce(a, axis, op)

    def pullback(g):
        return [(a, g)]

    return out, pullback


@register_vjp(DistPrimIDs.REDUCE_SCATTER)
def _reduce_scatter_vjp(a, axis, dim, size):
    out = reduce_scatter(a, axis, dim, size)

    def pullback(g):
        return [(a, wait(all_gather(g, axis, dim, size)))]

    return out, pullback


@register_vjp(DistPrimIDs.PPERMUTE)
def _ppermute_vjp(a, axis, perm):
    out = ppermute(a, axis, perm)
    inv = [(d, s) for (s, d) in perm]

    def pullback(g):
        return [(a, wait(ppermute(g, axis, tuple(inv))))]

    return out, pullback


@register_vjp(DistPrimIDs.ALL_TO_ALL)
def _all_to_all_vjp(a, axis, split_dim, concat_dim, size):
    out = all_to_all(a, axis, split_dim, concat_dim, size)

    def pullback(g):
        return [(a, wait(all_to_all(g, axis, concat_dim, split_dim, size)))]

    return out, pullback


@register_vjp(DistPrimIDs.WAIT)
def _wait_vjp(f):
    out = wait(f)

    def pullback(g):
        return [(f, g)]

    return out, pullback
