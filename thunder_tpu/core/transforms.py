"""Program transforms: trace evaluation/replay and autograd (VJP).

The VJP engine mirrors the reference's design (``thunder/core/transforms.py``:
``augmented_forward_pass`` :3233, ``backward_pass`` :3264,
``forward_and_backward_from_trace`` :3587) but with a closure-based rule
registry: each differentiable prim registers a rule that computes its primal
output *and returns a pullback*; both directions are recorded as ordinary
trace operations, so the result of differentiation is itself a printable,
transformable trace. Composites without a registered rule are differentiated
through their decomposition. Executors can override grads per-op by
registering a rule for the op's id (the reference's ``register_augmented_forward``
/ grad_transform mechanism).

Two consumption modes:
- ``inline_value_and_grad(fn)``: usable *inside* a traced function — inlines
  fwd+bwd into the current trace (whole-train-step compilation, the TPU-first
  default; improves on the reference, which never compiles the optimizer —
  SURVEY §3.5).
- ``forward_and_backward_from_trace(trc)``: splits into an augmented forward
  trace returning (outputs, saved_for_backward) and a backward trace — the
  torch-autograd-style split used by the module API.
"""

from __future__ import annotations

import math
from numbers import Number
from typing import Any, Callable, Sequence

from thunder_tpu.core import dtypes, prims
from thunder_tpu.core.baseutils import check
from thunder_tpu.core.prims import PrimIDs
from thunder_tpu.core.proxies import NumberProxy, Proxy, TensorProxy, Variable
from thunder_tpu.core.pytree import tree_flatten, tree_map, tree_unflatten
from thunder_tpu.core.symbol import BoundSymbol
from thunder_tpu.core.trace import TraceCtx, from_trace, get_tracectx, tracectx
from thunder_tpu.core.utils import free_vars

# ---------------------------------------------------------------------------
# trace evaluation (replay)
# ---------------------------------------------------------------------------

# Substitution listeners: trace-time contexts that key state off proxy
# IDENTITY (e.g. fp8 delayed-scaling slots keyed by the weight proxy) register
# a callback here; every replay engine that renames proxies (eval_trace
# composite emission, sub-trace input mirroring, value_and_grad env binding,
# checkpoint recompute pinning) reports orig -> replacement pairs so such
# state follows the logical value across passes instead of multiplying.
_subst_listeners: list = []


def notify_substitution(orig, new) -> None:
    if not _subst_listeners or orig is new:
        return
    for cb in _subst_listeners:
        cb(orig, new)


def _env_map(env: dict, x):
    if isinstance(x, Proxy):
        v = Variable(x)
        return env[v] if v in env else x
    if isinstance(x, tuple):
        return tuple(_env_map(env, i) for i in x)
    if isinstance(x, list):
        return [_env_map(env, i) for i in x]
    if isinstance(x, dict):
        return {k: _env_map(env, v) for k, v in x.items()}
    return x


def _bind_outputs(env: dict, old_out, new_out):
    old_flat, _ = tree_flatten(old_out)
    new_flat, _ = tree_flatten(new_out)
    for o, n in zip(old_flat, new_flat):
        if isinstance(o, Proxy):
            env[Variable(o)] = n


def eval_trace(trc: TraceCtx, *args):
    """Replay a trace's operations under the current trace context (or
    eagerly, if the symbols resolve). Returns the trace's output."""
    env: dict = {}
    check(len(args) == len(trc.args), lambda: f"eval_trace: expected {len(trc.args)} args, got {len(args)}")
    for p, a in zip(trc.args, args):
        env[Variable(p)] = a
        notify_substitution(p, a)
    result = None
    for bsym in trc.bound_symbols:
        if bsym.sym.id is PrimIDs.PYTHON_RETURN:
            result = _env_map(env, bsym.args[0]) if bsym.args else None
            break
        if bsym.sym.id in (PrimIDs.COMMENT, PrimIDs.PYTHON_DEL):
            continue
        if bsym.sym.meta is None:  # impl-only symbol: re-emit verbatim
            cur = get_tracectx()
            if cur is not None:
                cur.add_bound_symbol(bsym.from_bsym())
            for o in bsym.flat_proxy_outs():
                env.setdefault(Variable(o), o)
            continue
        out = bsym.sym(*_env_map(env, bsym.args), **_env_map(env, bsym.kwargs))
        _bind_outputs(env, bsym.output, out)
    return result


# ---------------------------------------------------------------------------
# VJP rule registry
# ---------------------------------------------------------------------------

_vjp_rules: dict[Any, Callable] = {}

# prims that are legitimately non-differentiable (grads stop here)
_NONDIFF = {
    PrimIDs.EQ, PrimIDs.NE, PrimIDs.GE, PrimIDs.GT, PrimIDs.LE, PrimIDs.LT,
    PrimIDs.BITWISE_AND, PrimIDs.BITWISE_OR, PrimIDs.BITWISE_XOR, PrimIDs.BITWISE_NOT,
    PrimIDs.LOGICAL_NOT, PrimIDs.SIGN, PrimIDs.SIGNBIT, PrimIDs.FLOOR, PrimIDs.CEIL,
    PrimIDs.ROUND, PrimIDs.TRUNC, PrimIDs.ISNAN, PrimIDs.ISINF, PrimIDs.ISFINITE,
    PrimIDs.ARGMAX, PrimIDs.ARGMIN, PrimIDs.ARGSORT, PrimIDs.IOTA, PrimIDs.FULL,
    PrimIDs.RNG_KEY, PrimIDs.RNG_SPLIT, PrimIDs.UNIFORM, PrimIDs.NORMAL,
    PrimIDs.RANDOM_BITS, PrimIDs.ITEM, PrimIDs.SHIFT_LEFT, PrimIDs.SHIFT_RIGHT,
    PrimIDs.FMOD, PrimIDs.REMAINDER, PrimIDs.FLOOR_DIV, PrimIDs.COPYSIGN,
    PrimIDs.CHECK_TENSOR_SHAPE_AND_METADATA, PrimIDs.CHECK_NUMBER_TYPE_AND_VALUE,
    PrimIDs.CHECK_STRING_VALUE, PrimIDs.CHECK_LITERAL_LIKE, PrimIDs.UNPACK_TRIVIAL,
    PrimIDs.PYTHON_PRINT, PrimIDs.COMMENT, PrimIDs.SINK, PrimIDs.DEVICE_PUT,
    PrimIDs.SHARDING_CONSTRAINT, PrimIDs.SORT,
    PrimIDs.NEXTAFTER,
}


def register_vjp(op_id):
    def deco(rule):
        _vjp_rules[op_id] = rule
        return rule

    return deco


def has_vjp_rule(op_id) -> bool:
    return op_id in _vjp_rules


def _is_float_tensor(x) -> bool:
    return isinstance(x, TensorProxy) and x.dtype.is_inexact


# ---------------------------------------------------------------------------
# augmented forward + backward passes
# ---------------------------------------------------------------------------

class PullbackRecord:
    __slots__ = ("out", "pullback")

    def __init__(self, out, pullback):
        self.out = out
        self.pullback = pullback


def augmented_forward(bsyms: Sequence[BoundSymbol], env: dict) -> list[PullbackRecord]:
    """Replay ``bsyms`` under the current trace, collecting pullbacks.

    ``env`` maps the original trace's proxies (by Variable) to replayed
    values; it is updated in place.
    """
    records: list[PullbackRecord] = []
    for bsym in bsyms:
        sym_id = bsym.sym.id
        if sym_id in (PrimIDs.PYTHON_RETURN, PrimIDs.COMMENT, PrimIDs.PYTHON_DEL):
            continue
        if bsym.sym.meta is None:  # impl-only symbol (const_tensor): re-emit
            cur = get_tracectx()
            if cur is not None:
                cur.add_bound_symbol(bsym.from_bsym())
            for o in bsym.flat_proxy_outs():
                env.setdefault(Variable(o), o)
            continue
        margs = _env_map(env, bsym.args)
        mkwargs = _env_map(env, bsym.kwargs)
        rule = _vjp_rules.get(sym_id)
        res = rule(*margs, **mkwargs) if rule is not None else None
        if res is NotImplemented:  # rule declined (unsupported arg combo)
            res = None
        if res is not None:
            out, pullback = res
            records.append(PullbackRecord(out, pullback))
            _bind_outputs(env, bsym.output, out)
        elif bsym.subsymbols:
            records.extend(augmented_forward(bsym.subsymbols, env))
            # composite outputs are produced by subsymbols; map directly
            out_flat, _ = tree_flatten(bsym.output)
            for o in out_flat:
                if isinstance(o, Proxy) and Variable(o) not in env:
                    env[Variable(o)] = o  # produced literally by subsymbol replay
        else:
            # pass-through composite (e.g. eval-mode dropout, p=0 dropout):
            # every output proxy aliases an input proxy and there is no
            # decomposition to recurse into. Grads flow through the shared
            # Variable; just bind the mapped values. (ADVICE r1: subsymbol-less
            # alias bsyms must not raise.)
            arg_vars = {Variable(a) for a in bsym.flat_proxy_args()}
            out_proxies = bsym.flat_proxy_outs()
            if out_proxies and all(Variable(o) in arg_vars for o in out_proxies):
                _bind_outputs(env, bsym.output, _env_map(env, bsym.output))
                continue
            if sym_id not in _NONDIFF and any(_is_float_tensor(o) for o in out_proxies) \
                    and any(_is_float_tensor(a) for a in bsym.flat_proxy_args()):
                raise NotImplementedError(f"no VJP rule for prim {bsym.sym.name} (id={sym_id})")
            out = bsym.sym(*margs, **mkwargs)
            _bind_outputs(env, bsym.output, out)
    return records


def backward_pass(records: list[PullbackRecord], grads: dict[Variable, Any]) -> dict[Variable, Any]:
    """Walk pullbacks in reverse, accumulating cotangents keyed by Variable."""
    from thunder_tpu import ops

    from thunder_tpu.core.proxies import FutureTensorProxy

    def put_grad(p, g):
        if g is None or not isinstance(p, (TensorProxy, FutureTensorProxy)):
            return
        if not p.dtype.is_inexact:
            return
        # grads carry the primal's dtype (torch convention): implicit type
        # promotion inside mixed-dtype prims (bf16 × f32) must round-trip,
        # or every bf16 param would get an f32 grad
        if isinstance(g, TensorProxy) and g.dtype != p.dtype:
            g = ops.convert_element_type(g, p.dtype)
        v = Variable(p)
        if v in grads:
            grads[v] = ops.add(grads[v], g)
        else:
            grads[v] = g

    for rec in reversed(records):
        out_flat = [o for o in tree_flatten(rec.out)[0] if isinstance(o, Proxy)]
        gs = [grads.get(Variable(o)) for o in out_flat]
        if all(g is None for g in gs):
            continue
        g_arg = gs[0] if len(gs) == 1 else tuple(gs)
        pairs = rec.pullback(g_arg)
        if pairs is None:
            continue
        for p, g in pairs:
            put_grad(p, g)
    return grads


# ---------------------------------------------------------------------------
# user-facing transforms
# ---------------------------------------------------------------------------

def _trace_subfn(fn, args, kwargs) -> tuple[TraceCtx, list, Any]:
    """Trace ``fn`` in a detached TraceCtx with fresh input proxies mirroring
    the (possibly proxy) arguments. Returns (trace, input_proxies, out)."""
    from thunder_tpu.core.proxies import proxy_for

    inner = TraceCtx("subfn")
    outer = get_tracectx()
    if outer is not None:
        # share the name registry so replayed proxies don't collide
        inner._names = outer._names
        inner._counters = outer._counters
    from thunder_tpu.core.proxies import DistParallelType

    with tracectx(inner):
        flat, treedef = tree_flatten((args, kwargs))
        proxies = []   # input proxies of the inner trace
        passed = []    # values the traced fn actually receives
        for leaf in flat:
            if isinstance(leaf, TensorProxy):
                p = TensorProxy(shape=leaf.shape, dtype=leaf.dtype, device=leaf.device,
                                distparallel_type=leaf.distparallel_type)
                for attr in ("dist_axis", "dist_size", "dist_replica_axis", "dist_replica_size",
                             "dist_shard_axis", "dist_shard_size"):
                    if hasattr(leaf, attr):
                        setattr(p, attr, getattr(leaf, attr))
                proxies.append(p)
                notify_substitution(leaf, p)
                # distributed param sync INSIDE the grad scope: FSDP params are
                # all-gathered here and their VJP reduce-scatters the grads
                # (reference: synchronize in fwd, prims.py:376-419)
                if (p.distparallel_type in (DistParallelType.FULLY_SHARDED,
                                            DistParallelType.REPLICATED,
                                            DistParallelType.EXPERT_SHARDED,
                                            DistParallelType.PIPELINE_REPLICATED)
                        and getattr(p, "dist_axis", None) is not None):
                    from thunder_tpu.distributed import prims as dist_prims

                    # HSDP: a REPLICATED synchronize over the replica axis
                    # APPLIED TO THE SHARD (inside the gather) — identity
                    # forward, grad all-reduce-mean backward. Order matters
                    # for bandwidth, not math (both VJPs are linear): inside,
                    # the replica all-reduce (the cross-pod/DCN hop) moves
                    # shard-sized grads; outside it would move gathered-size.
                    synced = p
                    if getattr(p, "dist_replica_axis", None) is not None:
                        synced = dist_prims.synchronize(
                            synced, p.dist_replica_axis, DistParallelType.REPLICATED,
                            p.dist_replica_size)
                    synced = dist_prims.synchronize(synced, p.dist_axis,
                                                    p.distparallel_type, p.dist_size)
                    passed.append(synced)
                elif (p.distparallel_type in (DistParallelType.COLUMN_WISE,
                                              DistParallelType.ROW_WISE)
                      and (getattr(p, "dist_replica_axis", None) is not None
                           or getattr(p, "dist_shard_axis", None) is not None)):
                    from thunder_tpu.distributed import prims as dist_prims

                    synced = p
                    if getattr(p, "dist_shard_axis", None) is not None:
                        # FSDP×TP: all-gather the dim-0 fsdp shard of the tp
                        # slice; the VJP reduce-scatters + means the grads
                        # over the fsdp (data) axis
                        synced = dist_prims.synchronize(
                            synced, p.dist_shard_axis, DistParallelType.FULLY_SHARDED,
                            p.dist_shard_size)
                    if getattr(p, "dist_replica_axis", None) is not None:
                        # TP×DP: identity forward, dp-mean of shard grads back
                        synced = dist_prims.synchronize(
                            synced, p.dist_replica_axis, DistParallelType.REPLICATED,
                            p.dist_replica_size)
                    # the sync must not strip the TP mark ops.linear keys its
                    # boundary collectives on
                    synced.distparallel_type = p.distparallel_type
                    synced.dist_axis = p.dist_axis
                    synced.dist_size = p.dist_size
                    passed.append(synced)
                else:
                    passed.append(p)
            elif isinstance(leaf, Proxy):
                proxies.append(leaf)
                passed.append(leaf)
            else:
                proxies.append(leaf)
                passed.append(leaf)
        pargs, pkwargs = tree_unflatten(treedef, passed)
        out = fn(*pargs, **pkwargs)
        prims.python_return(out)
    inner.output = out
    inner.args = [p for p in proxies if isinstance(p, Proxy)]
    return inner, [p for p in proxies if isinstance(p, Proxy)], out


def promote_free_vars(inner: TraceCtx, inner_inputs) -> list:
    """Promote closure-captured outer proxies of a sub-trace to explicit
    inputs (appended to ``inner.args``), so dataflow analyses (DCE,
    saved-set, replay) see them. Returns the promoted proxies in order —
    callers pass them as extra symbol args."""
    from thunder_tpu.core.utils import free_vars

    input_set = {Variable(p) for p in inner_inputs}
    frees = [v.proxy for v in free_vars(inner.bound_symbols) if v not in input_set]
    inner.args = list(inner_inputs) + frees
    return frees


def inline_value_and_grad(fn, argnums=0, has_aux: bool = False):
    """Differentiate ``fn`` inline in the current trace (or under jit).

    Returns a callable: (args) -> (value, grads) where grads matches the
    structure of args[argnums]. The loss must be a scalar float tensor.
    """
    argnums_t = (argnums,) if isinstance(argnums, int) else tuple(argnums)

    def transformed(*args, **kwargs):
        from thunder_tpu import ops

        check(get_tracectx() is not None,
              "inline_value_and_grad must run under tracing (wrap with thunder_tpu.jit)")
        inner, inner_inputs, _ = _trace_subfn(fn, args, kwargs)
        # env: inner input proxies -> actual outer values (same flatten order)
        flat_actual, _ = tree_flatten((args, kwargs))
        env: dict = {}
        j = 0
        for leaf in flat_actual:
            if isinstance(leaf, Proxy):
                env[Variable(inner_inputs[j])] = leaf
                notify_substitution(inner_inputs[j], leaf)
                j += 1
        check(j == len(inner_inputs), "inline_value_and_grad: argument flattening mismatch")
        records = augmented_forward(inner.bound_symbols, env)
        out = _env_map(env, inner.output)
        if has_aux:
            check(isinstance(out, tuple) and len(out) == 2, "has_aux=True requires fn to return (loss, aux)")
            loss, aux = out
        else:
            loss = out
        check(isinstance(loss, TensorProxy) and loss.numel == 1 and loss.dtype.is_inexact,
              lambda: f"grad requires a scalar float loss, got {loss}")
        grads: dict[Variable, Any] = {Variable(loss): ops.ones_like(loss)}
        # boundary marker: trace passes that distinguish forward from backward
        # (e.g. FSDP ZeRO-3 rematerialize_all_gather) key off this comment
        prims.comment("backward pass begins")
        backward_pass(records, grads)
        prims.comment("backward pass ends")

        def grad_of(x):
            if isinstance(x, TensorProxy):
                g = grads.get(Variable(x))
                return g if g is not None else ops.zeros_like(x)
            return None

        grad_results = tuple(tree_map(grad_of, args[i]) for i in argnums_t)
        gout = grad_results[0] if isinstance(argnums, int) else grad_results
        return ((loss, aux), gout) if has_aux else (loss, gout)

    return transformed


def forward_and_backward_from_trace(trc: TraceCtx) -> tuple[TraceCtx, TraceCtx, list]:
    """Split a computation trace into an augmented forward trace returning
    ``(outputs, saved_for_backward)`` and a backward trace
    ``(saved_for_backward..., cotangents...) -> grads_of_inputs``."""
    from thunder_tpu import ops

    fwd = from_trace(trc)
    fwd.fn_name = "augmented_forward"
    env: dict = {Variable(p): p for p in trc.args}
    with tracectx(fwd):
        records = augmented_forward(trc.bound_symbols, env)
        out = _env_map(env, trc.output)

    out_flat = [o for o in tree_flatten(out)[0] if isinstance(o, TensorProxy) and o.dtype.is_inexact]

    # backward trace: replay pullbacks with fresh cotangent inputs
    bwd = TraceCtx("backward")
    bwd._names = set(fwd._names)
    bwd._counters = dict(fwd._counters)
    with tracectx(bwd):
        cotangents = [TensorProxy(f"ct{i}", shape=o.shape, dtype=o.dtype, device=o.device)
                      for i, o in enumerate(out_flat)]
        grads: dict[Variable, Any] = {}
        for o, ct in zip(out_flat, cotangents):
            v = Variable(o)
            # the same proxy may appear in several output slots (return h, h):
            # cotangents accumulate, they don't overwrite
            grads[v] = ops.add(grads[v], ct) if v in grads else ct
        backward_pass(records, grads)
        input_grads = tuple(
            grads.get(Variable(p)) if isinstance(p, TensorProxy) else None for p in trc.args
        )
        prims.python_return(input_grads)
    bwd.output = input_grads

    # saved-for-backward = free variables of the backward trace minus cotangents
    ct_names = {c.name for c in cotangents}
    saved = [v.proxy for v in free_vars(bwd.bound_symbols) if v.proxy.name not in ct_names]
    bwd.args = list(saved) + list(cotangents)

    with tracectx(fwd):
        prims.python_return((out, tuple(saved)))
    fwd.output = (out, tuple(saved))
    fwd.set_provenance("Augmented forward pass")
    bwd.set_provenance("Backward pass")
    return fwd, bwd, saved


# ---------------------------------------------------------------------------
# VJP rules for prims
# ---------------------------------------------------------------------------

def _pairs(*pairs):
    return [(p, g) for p, g in pairs if isinstance(p, TensorProxy)]


def _unary(prim, dfn):
    """dfn(g, a, out) -> grad_a"""

    def rule(a):
        out = prim(a)

        def pullback(g):
            return _pairs((a, dfn(g, a, out)))

        return out, pullback

    return rule


def _register_unary(pid, prim, dfn):
    _vjp_rules[pid] = _unary(prim, dfn)


def _O():
    from thunder_tpu import ops

    return ops


_register_unary(PrimIDs.NEG, prims.neg, lambda g, a, o: _O().neg(g))
_register_unary(PrimIDs.ABS, prims.abs, lambda g, a, o: _O().mul(g, _O().sign(a)))
_register_unary(PrimIDs.EXP, prims.exp, lambda g, a, o: _O().mul(g, o))
_register_unary(PrimIDs.EXP2, prims.exp2, lambda g, a, o: _O().mul(_O().mul(g, o), math.log(2.0)))
_register_unary(PrimIDs.EXPM1, prims.expm1, lambda g, a, o: _O().mul(g, _O().add(o, 1.0)))
_register_unary(PrimIDs.LOG, prims.log, lambda g, a, o: _O().true_divide(g, a))
_register_unary(PrimIDs.LOG1P, prims.log1p, lambda g, a, o: _O().true_divide(g, _O().add(a, 1.0)))
_register_unary(PrimIDs.LOG2, prims.log2, lambda g, a, o: _O().true_divide(g, _O().mul(a, math.log(2.0))))
_register_unary(PrimIDs.LOG10, prims.log10, lambda g, a, o: _O().true_divide(g, _O().mul(a, math.log(10.0))))
_register_unary(PrimIDs.SQRT, prims.sqrt, lambda g, a, o: _O().true_divide(g, _O().mul(2.0, o)))
_register_unary(PrimIDs.RSQRT, prims.rsqrt,
                lambda g, a, o: _O().mul(_O().mul(-0.5, g), _O().mul(o, _O().mul(o, o))))
_register_unary(PrimIDs.SIN, prims.sin, lambda g, a, o: _O().mul(g, _O().cos(a)))
_register_unary(PrimIDs.COS, prims.cos, lambda g, a, o: _O().neg(_O().mul(g, _O().sin(a))))
_register_unary(PrimIDs.TAN, prims.tan, lambda g, a, o: _O().mul(g, _O().add(1.0, _O().mul(o, o))))
_register_unary(PrimIDs.TANH, prims.tanh, lambda g, a, o: _O().mul(g, _O().sub(1.0, _O().mul(o, o))))
_register_unary(PrimIDs.SINH, prims.sinh, lambda g, a, o: _O().mul(g, _O().cosh(a)))
_register_unary(PrimIDs.COSH, prims.cosh, lambda g, a, o: _O().mul(g, _O().sinh(a)))
_register_unary(PrimIDs.ASIN, prims.asin,
                lambda g, a, o: _O().true_divide(g, _O().sqrt(_O().sub(1.0, _O().mul(a, a)))))
_register_unary(PrimIDs.ACOS, prims.acos,
                lambda g, a, o: _O().neg(_O().true_divide(g, _O().sqrt(_O().sub(1.0, _O().mul(a, a))))))
_register_unary(PrimIDs.ATAN, prims.atan,
                lambda g, a, o: _O().true_divide(g, _O().add(1.0, _O().mul(a, a))))
_register_unary(PrimIDs.ASINH, prims.asinh,
                lambda g, a, o: _O().true_divide(g, _O().sqrt(_O().add(_O().mul(a, a), 1.0))))
_register_unary(PrimIDs.ACOSH, prims.acosh,
                lambda g, a, o: _O().true_divide(g, _O().sqrt(_O().sub(_O().mul(a, a), 1.0))))
_register_unary(PrimIDs.ATANH, prims.atanh,
                lambda g, a, o: _O().true_divide(g, _O().sub(1.0, _O().mul(a, a))))
_register_unary(PrimIDs.ERF, prims.erf,
                lambda g, a, o: _O().mul(g, _O().mul(2.0 / math.sqrt(math.pi),
                                                     _O().exp(_O().neg(_O().mul(a, a))))))
_register_unary(PrimIDs.ERFC, prims.erfc,
                lambda g, a, o: _O().neg(_O().mul(g, _O().mul(2.0 / math.sqrt(math.pi),
                                                              _O().exp(_O().neg(_O().mul(a, a)))))))
_register_unary(PrimIDs.RECIPROCAL, prims.reciprocal,
                lambda g, a, o: _O().neg(_O().mul(g, _O().mul(o, o))))
# d/dx erfinv(x) = sqrt(pi)/2 * exp(erfinv(x)^2)
_register_unary(PrimIDs.ERFINV, prims.erfinv,
                lambda g, a, o: _O().mul(g, _O().mul(math.sqrt(math.pi) / 2.0,
                                                     _O().exp(_O().mul(o, o)))))
_register_unary(PrimIDs.DIGAMMA, prims.digamma,
                lambda g, a, o: _O().mul(g, prims.polygamma(a, 1)))
# d/dx ndtri(x) = sqrt(2*pi) * exp(ndtri(x)^2 / 2)
_register_unary(PrimIDs.NDTRI, prims.ndtri,
                lambda g, a, o: _O().mul(g, _O().mul(math.sqrt(2.0 * math.pi),
                                                     _O().exp(_O().mul(0.5, _O().mul(o, o))))))


_register_unary(PrimIDs.LGAMMA, prims.lgamma,
                lambda g, a, o: _O().mul(g, prims.digamma(a)))


@register_vjp(PrimIDs.DYNAMIC_SLICE)
def _dynamic_slice_vjp(a, start_indices, slice_sizes):
    out = prims.dynamic_slice(a, start_indices, slice_sizes)

    def pullback(g):
        from thunder_tpu import ops

        return _pairs((a, prims.dynamic_update_slice(ops.zeros_like(a), g, start_indices)))

    return out, pullback


@register_vjp(PrimIDs.DYNAMIC_UPDATE_SLICE)
def _dynamic_update_slice_vjp(a, update, start_indices):
    out = prims.dynamic_update_slice(a, update, start_indices)

    def pullback(g):
        from thunder_tpu import ops

        gu = prims.dynamic_slice(g, start_indices, tuple(update.shape))
        ga = prims.dynamic_update_slice(g, ops.zeros_like(update), start_indices)
        return _pairs((a, ga), (update, gu))

    return out, pullback


@register_vjp(PrimIDs.POLYGAMMA)
def _polygamma_vjp(a, n):
    out = prims.polygamma(a, n)

    def pullback(g):
        from thunder_tpu import ops

        return _pairs((a, ops.mul(g, prims.polygamma(a, n + 1))))

    return out, pullback


@register_vjp(PrimIDs.CUMSUM)
def _cumsum_vjp(a, dim):
    out = prims.cumsum(a, dim)

    def pullback(g):
        from thunder_tpu import ops

        return _pairs((a, ops.flip(ops.cumsum(ops.flip(g, dim), dim), dim)))

    return out, pullback


@register_vjp(PrimIDs.CUMPROD)
def _cumprod_vjp(a, dim):
    out = prims.cumprod(a, dim)

    def pullback(g):
        return _pairs((a, prims.cumprod_grad(g, a, dim)))

    return out, pullback


@register_vjp(PrimIDs.ADD)
def _add_vjp(a, b):
    out = prims.add(a, b)

    def pullback(g):
        return _pairs((a, g), (b, g))

    return out, pullback


@register_vjp(PrimIDs.SUB)
def _sub_vjp(a, b):
    out = prims.sub(a, b)

    def pullback(g):
        from thunder_tpu import ops

        return _pairs((a, g), (b, ops.neg(g)))

    return out, pullback


@register_vjp(PrimIDs.MUL)
def _mul_vjp(a, b):
    out = prims.mul(a, b)

    def pullback(g):
        from thunder_tpu import ops

        return _pairs((a, ops.mul(g, b)), (b, ops.mul(g, a)))

    return out, pullback


@register_vjp(PrimIDs.DIV)
def _div_vjp(a, b):
    out = prims.div(a, b)

    def pullback(g):
        from thunder_tpu import ops

        ga = ops.true_divide(g, b)
        gb = ops.neg(ops.true_divide(ops.mul(g, out), b))
        return _pairs((a, ga), (b, gb))

    return out, pullback


@register_vjp(PrimIDs.POW)
def _pow_vjp(a, b):
    out = prims.pow(a, b)

    def pullback(g):
        from thunder_tpu import ops

        ga = ops.mul(g, ops.mul(b, ops.pow(a, ops.sub(b, 1.0)))) if isinstance(a, TensorProxy) else None
        gb = None
        if isinstance(b, TensorProxy):
            if isinstance(a, TensorProxy):
                loga = ops.where(ops.gt(a, 0.0), ops.log(ops.maximum(a, 1e-45)), ops.zeros_like(a))
            else:
                loga = math.log(a) if a > 0 else 0.0
            gb = ops.mul(g, ops.mul(out, loga))
        return _pairs((a, ga), (b, gb))

    return out, pullback


@register_vjp(PrimIDs.MAXIMUM)
def _maximum_vjp(a, b):
    out = prims.maximum(a, b)

    def pullback(g):
        from thunder_tpu import ops

        mask = ops.ge(a, b) if isinstance(a, TensorProxy) else ops.le(b, a)
        maskf = ops.convert_element_type(mask, g.dtype)
        return _pairs((a, ops.mul(g, maskf)), (b, ops.mul(g, ops.sub(1.0, maskf))))

    return out, pullback


@register_vjp(PrimIDs.MINIMUM)
def _minimum_vjp(a, b):
    out = prims.minimum(a, b)

    def pullback(g):
        from thunder_tpu import ops

        mask = ops.le(a, b) if isinstance(a, TensorProxy) else ops.ge(b, a)
        maskf = ops.convert_element_type(mask, g.dtype)
        return _pairs((a, ops.mul(g, maskf)), (b, ops.mul(g, ops.sub(1.0, maskf))))

    return out, pullback


@register_vjp(PrimIDs.ATAN2)
def _atan2_vjp(a, b):
    out = prims.atan2(a, b)

    def pullback(g):
        from thunder_tpu import ops

        denom = ops.add(ops.mul(a, a), ops.mul(b, b))
        return _pairs((a, ops.true_divide(ops.mul(g, b), denom)),
                      (b, ops.neg(ops.true_divide(ops.mul(g, a), denom))))

    return out, pullback


@register_vjp(PrimIDs.ZETA)
def _zeta_vjp(a, b):
    # reference zeta_backward: only d/dy is implemented,
    # d/dy zeta(x, y) = -x * zeta(x + 1, y); d/dx has no closed form here.
    out = prims.zeta(a, b)

    def pullback(g):
        from thunder_tpu import ops

        gb = ops.mul(g, ops.mul(ops.neg(a), prims.zeta(ops.add(a, 1.0), b))) \
            if isinstance(b, TensorProxy) else None
        return _pairs((b, gb))

    return out, pullback


@register_vjp(PrimIDs.WHERE)
def _where_vjp(pred, a, b):
    out = prims.where(pred, a, b)

    def pullback(g):
        from thunder_tpu import ops

        ga = ops.where(pred, g, ops.zeros_like(g)) if isinstance(a, TensorProxy) else None
        gb = ops.where(pred, ops.zeros_like(g), g) if isinstance(b, TensorProxy) else None
        return _pairs((a, ga), (b, gb))

    return out, pullback


@register_vjp(PrimIDs.CONVERT_ELEMENT_TYPE)
def _convert_vjp(a, dtype):
    out = prims.convert_element_type(a, dtype)

    def pullback(g):
        from thunder_tpu import ops

        if isinstance(a, TensorProxy) and a.dtype.is_inexact:
            return _pairs((a, ops.convert_element_type(g, a.dtype)))
        return None

    return out, pullback


@register_vjp(PrimIDs.DETACH)
def _detach_vjp(a):
    out = prims.detach(a)
    return out, lambda g: None


@register_vjp(PrimIDs.BROADCAST_IN_DIM)
def _broadcast_in_dim_vjp(a, shape, broadcast_dimensions):
    out = prims.broadcast_in_dim(a, shape, broadcast_dimensions)
    bdims = tuple(broadcast_dimensions)

    def pullback(g):
        from thunder_tpu import ops

        reduce_dims = [d for d in range(len(shape)) if d not in bdims]
        for i, d in enumerate(bdims):
            if a.shape[i] == 1 and shape[d] != 1:
                reduce_dims.append(d)
        ga = g
        if reduce_dims:
            ga = prims.sum(g, tuple(sorted(reduce_dims)))
        ga = ops.reshape(ga, a.shape)
        return _pairs((a, ga))

    return out, pullback


@register_vjp(PrimIDs.RESHAPE)
def _reshape_vjp(a, shape):
    out = prims.reshape(a, shape)

    def pullback(g):
        from thunder_tpu import ops

        return _pairs((a, ops.reshape(g, a.shape)))

    return out, pullback


@register_vjp(PrimIDs.SQUEEZE)
def _squeeze_vjp(a, dims):
    out = prims.squeeze(a, dims)

    def pullback(g):
        from thunder_tpu import ops

        return _pairs((a, ops.reshape(g, a.shape)))

    return out, pullback


@register_vjp(PrimIDs.TRANSPOSE)
def _transpose_vjp(a, permutation):
    out = prims.transpose(a, permutation)
    perm = tuple(permutation)

    def pullback(g):
        inv = [0] * len(perm)
        for i, p in enumerate(perm):
            inv[p] = i
        return _pairs((a, prims.transpose(g, tuple(inv))))

    return out, pullback


@register_vjp(PrimIDs.SLICE)
def _slice_vjp(a, start_indices, end_indices, strides=None):
    out = prims.slice_prim(a, start_indices, end_indices, strides)
    st = tuple(strides) if strides is not None else (1,) * a.ndim

    def pullback(g):
        cfg = []
        for d, (s, stride) in enumerate(zip(start_indices, st)):
            osz = out.shape[d]
            covered = s + (osz - 1) * stride + 1 if osz > 0 else s
            cfg.append((s, a.shape[d] - covered, stride - 1))
        return _pairs((a, prims.pad(g, 0.0, tuple(cfg))))

    return out, pullback


@register_vjp(PrimIDs.PAD)
def _pad_vjp(a, padding_value, padding_config):
    out = prims.pad(a, padding_value, padding_config)

    def pullback(g):
        starts, ends, strides = [], [], []
        for (lo, hi, interior), s in zip(padding_config, a.shape):
            starts.append(lo)
            ends.append(lo + s + max(0, s - 1) * interior)
            strides.append(interior + 1)
        return _pairs((a, prims.slice_prim(g, starts, ends, strides)))

    return out, pullback


@register_vjp(PrimIDs.CAT)
def _cat_vjp(tensors, dim):
    out = prims.cat(tensors, dim)

    def pullback(g):
        pairs = []
        off = 0
        for t in tensors:
            starts = [0] * t.ndim
            ends = list(g.shape)
            starts[dim], ends[dim] = off, off + t.shape[dim]
            pairs.append((t, prims.slice_prim(g, starts, ends)))
            off += t.shape[dim]
        return _pairs(*pairs)

    return out, pullback


@register_vjp(PrimIDs.FLIP)
def _flip_vjp(a, dims):
    out = prims.flip(a, dims)

    def pullback(g):
        return _pairs((a, prims.flip(g, dims)))

    return out, pullback


@register_vjp(PrimIDs.SUM)
def _sum_vjp(a, dims):
    out = prims.sum(a, dims)
    dims_t = tuple(dims)

    def pullback(g):
        from thunder_tpu import ops

        keep_shape = tuple(1 if i in dims_t else s for i, s in enumerate(a.shape))
        return _pairs((a, ops.expand_to(ops.reshape(g, keep_shape), a.shape)))

    return out, pullback


@register_vjp(PrimIDs.PROD)
def _prod_vjp(a, dims):
    out = prims.prod(a, dims)
    dims_t = tuple(dims)

    def pullback(g):
        from thunder_tpu import ops

        keep_shape = tuple(1 if i in dims_t else s for i, s in enumerate(a.shape))
        gb = ops.expand_to(ops.reshape(g, keep_shape), a.shape)
        ob = ops.expand_to(ops.reshape(out, keep_shape), a.shape)
        return _pairs((a, ops.true_divide(ops.mul(gb, ob), a)))

    return out, pullback


def _minmax_reduction_vjp(prim):
    def rule(a, dims):
        out = prim(a, dims)
        dims_t = tuple(dims)

        def pullback(g):
            from thunder_tpu import ops

            keep_shape = tuple(1 if i in dims_t else s for i, s in enumerate(a.shape))
            ob = ops.expand_to(ops.reshape(out, keep_shape), a.shape)
            gb = ops.expand_to(ops.reshape(g, keep_shape), a.shape)
            mask = ops.convert_element_type(ops.eq(a, ob), g.dtype)
            counts = ops.expand_to(ops.reshape(prims.sum(mask, dims_t), keep_shape), a.shape)
            return _pairs((a, ops.true_divide(ops.mul(gb, mask), counts)))

        return out, pullback

    return rule


_vjp_rules[PrimIDs.AMAX] = _minmax_reduction_vjp(prims.amax)
_vjp_rules[PrimIDs.AMIN] = _minmax_reduction_vjp(prims.amin)


@register_vjp(PrimIDs.TAKE)
def _take_vjp(a, indices, dim):
    out = prims.take(a, indices, dim)

    def pullback(g):
        from thunder_tpu import ops

        n = indices.numel if isinstance(indices, TensorProxy) else 1
        g2 = ops.reshape(g, a.shape[:dim] + (n,) + a.shape[dim + 1:])
        idx_flat = ops.reshape(indices, (n,))
        zeros = ops.zeros_like(a)
        # row-wise scatter (1 index per slice). The per-element SCATTER_ADD
        # form lowers to an XLA scatter over flattened (row, col) index pairs
        # — orders of magnitude slower on TPU for embedding-style gradients.
        return _pairs((a, prims.index_add(zeros, idx_flat, g2, dim)))

    return out, pullback


@register_vjp(PrimIDs.TAKE_ALONG_AXIS)
def _take_along_axis_vjp(a, indices, dim):
    out = prims.take_along_axis(a, indices, dim)

    def pullback(g):
        from thunder_tpu import ops

        return _pairs((a, prims.scatter_add(ops.zeros_like(a), indices, g, dim)))

    return out, pullback


@register_vjp(PrimIDs.INDEX_ADD)
def _index_add_vjp(a, indices, value, dim):
    out = prims.index_add(a, indices, value, dim)

    def pullback(g):
        return _pairs((a, g), (value, prims.take(g, indices, dim)))

    return out, pullback


@register_vjp(PrimIDs.INDEX_PUT)
def _index_put_vjp(a, indices, values, accumulate):
    out = prims.index_put(a, indices, values, accumulate)

    def pullback(g):
        from thunder_tpu import ops
        from thunder_tpu.core import dtypes as _dt

        # General k-tensor advanced indexing over the k LEADING dims (jax
        # ``a.at[tuple].set`` semantics): linearize the jointly-broadcast
        # indices over the leading dims' row-major strides, then the grad
        # gather/zero-scatter reduce to the 1-D case on the flattened view.
        k = len(indices)
        lead = tuple(int(s) for s in a.shape[:k])
        tail = tuple(int(s) for s in a.shape[k:])
        L = 1
        for s in lead:
            L *= s
        bshape = ()
        for t in indices:
            bshape = ops.compute_broadcast_shape(
                bshape, tuple(getattr(t, "shape", ())))
        N = 1
        for s in bshape:
            N *= s
        linear = ops.linearize_indices(indices, list(lead), bshape)
        if isinstance(linear, TensorProxy):
            lin_flat = ops.reshape(linear, (N,))
        else:  # all-int indices
            lin_flat = ops.full((N,), int(linear), dtype=_dt.int32,
                                device=a.device)
        g_flat = ops.reshape(g, (L,) + tail) if k > 1 else g
        g_sel = prims.take(g_flat, lin_flat, 0)
        if accumulate:
            g_a = g
        else:
            # replace semantics: with duplicate indices only the winning
            # write affects the output — replay the scatter with writer ids
            # and zero the grads of overwritten rows
            ids = prims.iota(N, dtype=_dt.int32, device=a.device)
            writer = prims.index_put(
                ops.full((L,), -1, dtype=_dt.int32, device=a.device),
                (lin_flat,), ids, False)
            win = ops.eq(prims.take(writer, lin_flat, 0), ids)
            g_sel = ops.where(ops.reshape(win, (N,) + (1,) * (g_sel.ndim - 1)),
                              g_sel, ops.zeros_like(g_sel))
            g_a = prims.index_put(g_flat, (lin_flat,), ops.zeros_like(g_sel), False)
            g_a = ops.reshape(g_a, tuple(int(s) for s in a.shape)) if k > 1 else g_a
        g_sel = ops.reshape(g_sel, bshape + tail)
        if not isinstance(values, TensorProxy):
            return _pairs((a, g_a))
        # values may have broadcast against the indexed slice: sum-to-shape
        if tuple(g_sel.shape) != tuple(values.shape):
            extra = g_sel.ndim - values.ndim
            if extra:
                g_sel = ops.sum(g_sel, dim=tuple(range(extra)))
            reduce_dims = tuple(i for i, (gs, vs) in enumerate(
                zip(g_sel.shape, values.shape)) if gs != vs)
            if reduce_dims:
                g_sel = ops.sum(g_sel, dim=reduce_dims, keepdim=True)
        return _pairs((a, g_a), (values, g_sel))

    return out, pullback


@register_vjp(PrimIDs.SCATTER_ADD)
def _scatter_add_vjp(a, indices, value, dim):
    out = prims.scatter_add(a, indices, value, dim)

    def pullback(g):
        return _pairs((a, g), (value, prims.take_along_axis(g, indices, dim)))

    return out, pullback


@register_vjp(PrimIDs.SCATTER)
def _scatter_vjp(a, indices, value, dim):
    out = prims.scatter(a, indices, value, dim)

    def pullback(g):
        from thunder_tpu import ops

        # scattered-to positions take their grad from ``value``; ``a``'s grad
        # is g with those positions zeroed (replace semantics)
        zeros = ops.zeros_like(value)
        return _pairs((a, prims.scatter(g, indices, zeros, dim)),
                      (value, prims.take_along_axis(g, indices, dim)))

    return out, pullback


# ---------------------------------------------------------------------------
# forward-mode (jvp) and batching (vmap)
# ---------------------------------------------------------------------------

# prims linear in their single differentiable tensor argument (arg 0):
# tangent = op(t, <other args unchanged>)
_SINGLE_LINEAR_PRIMS = {
    PrimIDs.NEG, PrimIDs.BROADCAST_IN_DIM, PrimIDs.RESHAPE, PrimIDs.SQUEEZE,
    PrimIDs.TRANSPOSE, PrimIDs.SLICE, PrimIDs.FLIP, PrimIDs.SUM, PrimIDs.CUMSUM,
    PrimIDs.TAKE, PrimIDs.TAKE_ALONG_AXIS, PrimIDs.CONVERT_ELEMENT_TYPE,
    PrimIDs.DYNAMIC_SLICE,
}

# bilinear prims: tangent = op(t_a, b) + op(a, t_b)
_BILINEAR_PRIMS = {PrimIDs.DOT_GENERAL, PrimIDs.MUL}


def jvp_call(fn, primals: tuple, tangents: tuple):
    """Forward-mode derivative, usable under tracing. Elementwise prims reuse
    their VJP pullbacks (diagonal Jacobian ⇒ Jt == Jᵀt applied elementwise);
    linear/bilinear prims use structural rules
    (reference jvp: ``thunder/core/transforms.py:2175``)."""
    from thunder_tpu import ops
    from thunder_tpu.core.prims import OpTags

    check(get_tracectx() is not None, "jvp_call must run under tracing")
    inner, inner_inputs, _ = _trace_subfn(fn, primals, {})
    flat_p, _ = tree_flatten(primals)
    flat_t, _ = tree_flatten(tangents)
    env: dict = {}
    tan: dict[Variable, Any] = {}
    j = 0
    for p, t in zip(flat_p, flat_t):
        if isinstance(p, Proxy):
            env[Variable(inner_inputs[j])] = p
            notify_substitution(inner_inputs[j], p)
            if t is not None:
                # key tangents by the OUTER (mapped) proxies — replayed bsym
                # args are env-mapped before tangent lookup
                tan[Variable(p)] = t
            j += 1

    def tangent_of(x):
        return tan.get(Variable(x)) if isinstance(x, Proxy) else None

    def walk(bsyms):
        for bsym in bsyms:
            sym_id = bsym.sym.id
            if sym_id in (PrimIDs.PYTHON_RETURN, PrimIDs.COMMENT, PrimIDs.PYTHON_DEL):
                continue
            if bsym.sym.meta is None:  # const_tensor etc.
                cur = get_tracectx()
                if cur is not None:
                    cur.add_bound_symbol(bsym.from_bsym())
                for o in bsym.flat_proxy_outs():
                    env.setdefault(Variable(o), o)
                continue
            if not bsym.sym.is_prim and bsym.subsymbols:
                walk(bsym.subsymbols)
                out_flat, _ = tree_flatten(bsym.output)
                for o in out_flat:
                    if isinstance(o, Proxy) and Variable(o) not in env:
                        env[Variable(o)] = o
                continue

            margs = _env_map(env, bsym.args)
            mkwargs = _env_map(env, bsym.kwargs)
            flat_margs, adef = tree_flatten(margs)
            arg_tans = [tangent_of(a) for a in flat_margs]
            has_tan = any(t is not None for t in arg_tans)

            out = bsym.sym(*margs, **mkwargs)
            _bind_outputs(env, bsym.output, out)
            if not has_tan:
                continue

            def op_with(i, val):
                sub = list(flat_margs)
                sub[i] = val
                return bsym.sym(*tree_unflatten(adef, sub), **mkwargs)

            t_out = None
            if sym_id in _SINGLE_LINEAR_PRIMS:
                t_out = op_with(0, arg_tans[0]) if arg_tans[0] is not None else None
            elif sym_id is PrimIDs.PAD:
                # pad value is a constant: tangent pads with zero
                t_out = prims.pad(arg_tans[0], 0.0, bsym.args[2] if len(bsym.args) > 2
                                  else margs[2])
            elif sym_id is PrimIDs.ADD:
                terms = [t for t in arg_tans if t is not None]
                t_out = terms[0] if len(terms) == 1 else ops.add(*terms)
            elif sym_id is PrimIDs.SUB:
                ta, tb = arg_tans[0], arg_tans[1]
                if ta is not None and tb is not None:
                    t_out = ops.sub(ta, tb)
                elif ta is not None:
                    t_out = ta
                else:
                    t_out = ops.neg(tb)
            elif sym_id is PrimIDs.WHERE:
                pred, a, b = margs
                ta = arg_tans[1] if len(arg_tans) > 1 else None
                tb = arg_tans[2] if len(arg_tans) > 2 else None
                za = ta if ta is not None else ops.zeros_like(out)
                zb = tb if tb is not None else ops.zeros_like(out)
                t_out = prims.where(pred, za, zb)
            elif sym_id is PrimIDs.CAT:
                tensors = margs[0]
                tans = [tangent_of(t) for t in tensors]
                pieces = [tn if tn is not None else ops.zeros_like(t)
                          for t, tn in zip(tensors, tans)]
                t_out = prims.cat(pieces, margs[1])
            elif sym_id in _BILINEAR_PRIMS:
                for i, t in enumerate(arg_tans):
                    if t is None:
                        continue
                    term = op_with(i, t)
                    t_out = term if t_out is None else ops.add(t_out, term)
            elif sym_id is PrimIDs.DETACH:
                t_out = None  # stop_gradient kills tangents in forward mode too
            elif sym_id is PrimIDs.DYNAMIC_UPDATE_SLICE:
                # jointly linear in (operand, update); start indices constant
                a_, u_ = margs[0], margs[1]
                ta = arg_tans[0] if arg_tans[0] is not None else ops.zeros_like(a_)
                tu = arg_tans[1] if arg_tans[1] is not None else ops.zeros_like(u_)
                t_out = prims.dynamic_update_slice(ta, tu, margs[2])
            elif sym_id is PrimIDs.CUMPROD:
                t_out = prims.cumprod_tangent(flat_margs[0], arg_tans[0], margs[1])
            elif sym_id in (PrimIDs.SCATTER, PrimIDs.SCATTER_ADD, PrimIDs.INDEX_ADD):
                # jointly linear in (a, value); indices are constant
                a_, idx_, v_, dim_ = margs
                ta = arg_tans[0]
                tv = None
                for i, fa in enumerate(flat_margs):
                    if fa is v_:
                        tv = arg_tans[i]
                if tv is None and sym_id is not PrimIDs.SCATTER:
                    t_out = ta  # scatter-add of a zero value is the identity
                else:
                    ta = ta if ta is not None else ops.zeros_like(a_)
                    tv = tv if tv is not None else ops.zeros_like(v_)
                    t_out = bsym.sym(ta, idx_, tv, dim_)
            elif sym_id is PrimIDs.CONVOLUTION:
                a_, w_, b_ = margs[0], margs[1], margs[2]
                ta, tw = arg_tans[0], arg_tans[1]
                terms = []
                if ta is not None:
                    terms.append(prims.convolution(ta, w_, None, **mkwargs))
                if tw is not None:
                    terms.append(prims.convolution(a_, tw, None, **mkwargs))
                tb = None
                if b_ is not None:
                    for i, fa in enumerate(flat_margs):
                        if fa is b_:
                            tb = arg_tans[i]
                if tb is not None:
                    terms.append(ops.reshape(tb, (1, -1) + (1,) * (a_.ndim - 2)))
                t_out = terms[0]
                for term in terms[1:]:
                    t_out = ops.add(t_out, term)
                if tuple(t_out.shape) != tuple(out.shape):  # bias-only tangent
                    t_out = ops.add(t_out, ops.zeros_like(out))
            elif sym_id in _vjp_rules and OpTags.ELEMENTWISE_OP in bsym.sym.tags:
                res = _vjp_rules[sym_id](*margs, **mkwargs)
                if res is NotImplemented or res is None:
                    raise NotImplementedError(f"no jvp rule for {bsym.sym.name}")
                _, pullback = res
                for i, t in enumerate(arg_tans):
                    if t is None:
                        continue
                    pairs = pullback(t) or []
                    for p_, g_ in pairs:
                        if p_ is flat_margs[i]:
                            t_out = g_ if t_out is None else ops.add(t_out, g_)
            elif sym_id in _NONDIFF:
                t_out = None
            else:
                raise NotImplementedError(f"no jvp rule for prim {bsym.sym.name}")
            if t_out is not None:
                out_proxies = [x for x in tree_flatten(out)[0] if isinstance(x, Proxy)]
                if out_proxies:
                    tan[Variable(out_proxies[0])] = t_out

    walk(inner.bound_symbols)
    out = _env_map(env, inner.output)
    out_flat = [o for o in tree_flatten(out)[0] if isinstance(o, Proxy)]
    out_tans = tuple(tan.get(Variable(o)) for o in out_flat)
    return out, out_tans[0] if len(out_tans) == 1 else out_tans


def vmap_call(fn, in_axes=0):
    """Batching transform. Lowers to an opaque jax.vmap over the traced
    function's JAX interpretation — correct for all ops, but opaque to
    trace-level autograd (differentiate outside, or use per-sample ops).
    Reference: ``thunder/core/transforms.py:1902`` (also partial)."""
    import jax

    def wrapper(*args):
        from thunder_tpu.core.proxies import TensorProxy as TP
        from thunder_tpu.core.symbol import Symbol
        from thunder_tpu.executors.xla import run_bsyms

        check(get_tracectx() is not None, "vmap_call must run under tracing")
        axes = in_axes if isinstance(in_axes, (tuple, list)) else (in_axes,) * len(args)
        check(len(axes) == len(args), "in_axes length must match args")
        # trace fn at the unbatched rank
        unbatched = []
        for a, ax in zip(args, axes):
            if isinstance(a, TP) and ax is not None:
                shape = tuple(s for i, s in enumerate(a.shape) if i != ax)
                unbatched.append(TP(shape=shape, dtype=a.dtype, device=a.device))
            else:
                unbatched.append(a)
        inner, inner_inputs, _ = _trace_subfn(lambda *xs: fn(*xs), tuple(unbatched), {})
        input_names = [p.name for p in inner_inputs]
        out_spec = inner.output

        def jax_fn(*vals):
            env = dict(zip(input_names, vals))
            run_bsyms(inner.bound_symbols, env)

            def read(x):
                return env[x.name] if isinstance(x, Proxy) else x

            return tree_map(read, out_spec, is_leaf=lambda x: isinstance(x, Proxy))

        # jax_fn's positional args are exactly the proxy leaves of (args,)
        proxy_axes = tuple(ax for a, ax in zip(args, axes) if isinstance(a, TP))
        proxy_args = [a for a in args if isinstance(a, TP)]
        vmapped = jax.vmap(jax_fn, in_axes=proxy_axes)

        bdim = None
        for a, ax in zip(args, axes):
            if isinstance(a, TP) and ax is not None:
                bdim = a.shape[ax]
                break
        check(bdim is not None, "vmap requires at least one batched tensor arg")

        out_metas = tree_map(
            lambda o: TensorProxy(shape=(bdim,) + o.shape, dtype=o.dtype, device=o.device)
            if isinstance(o, TensorProxy) else o,
            out_spec, is_leaf=lambda x: isinstance(x, Proxy))

        trc = get_tracectx()
        idx = getattr(trc, "_vmap_counter", 0)
        trc._vmap_counter = idx + 1
        vsym = Symbol(f"vmap{idx}", None, id=f"vmap:{idx}", is_prim=True, python_impl=vmapped)
        trc.add_bound_symbol(vsym.bind(*proxy_args, output=out_metas))
        return out_metas

    return wrapper


@register_vjp(PrimIDs.EINSUM)
def _einsum_vjp(equation, *operands):
    out = prims.einsum(equation, *operands)
    eq = equation.replace(" ", "")
    check("->" in eq and "." not in eq,
          "einsum grad requires explicit '->' output and no ellipsis")
    lhs, rhs = eq.split("->")
    specs = lhs.split(",")

    def pullback(g):
        from thunder_tpu import ops

        pairs = []
        for i, op in enumerate(operands):
            if not isinstance(op, TensorProxy):
                continue
            other_specs = [specs[j] for j in range(len(specs)) if j != i]
            others = [operands[j] for j in range(len(specs)) if j != i]
            gi_eq = ",".join([rhs] + other_specs) + "->" + specs[i]
            gi = prims.einsum(gi_eq, g, *others)
            if gi.dtype is not op.dtype:
                gi = ops.convert_element_type(gi, op.dtype)
            pairs.append((op, gi))
        return pairs

    return out, pullback


@register_vjp(PrimIDs.TOPK)
def _topk_vjp(a, k, dim):
    values, indices = prims.topk(a, k, dim)

    def pullback(g):
        from thunder_tpu import ops

        g_vals = g[0] if isinstance(g, tuple) else g
        if g_vals is None:
            return None
        zeros = ops.zeros_like(a)
        return _pairs((a, prims.scatter_add(zeros, indices, g_vals, dim)))

    return (values, indices), pullback


@register_vjp(PrimIDs.CONVOLUTION)
def _convolution_vjp(a, w, bias, *, stride, padding, dilation, groups):
    out = prims.convolution(a, w, bias, stride=stride, padding=padding,
                            dilation=dilation, groups=groups)

    def pullback(g):
        from thunder_tpu import ops

        ga, gw = prims.convolution_backward(g, a, w, stride=stride, padding=padding,
                                            dilation=dilation, groups=groups)
        pairs = [(a, ga), (w, gw)]
        if bias is not None:
            # bias broadcasts over batch + spatial dims; its grad is the sum
            pairs.append((bias, ops.sum(g, dim=(0,) + tuple(range(2, g.ndim)))))
        return _pairs(*pairs)

    return out, pullback


@register_vjp(PrimIDs.DOT_GENERAL)
def _dot_general_vjp(a, b, *, contract_dims, batch_dims=((), ()), preferred_element_type=None):
    out = prims.dot_general(a, b, contract_dims=contract_dims, batch_dims=batch_dims,
                            preferred_element_type=preferred_element_type)
    (ac, bc), (ab, bb) = contract_dims, batch_dims
    ac, bc, ab, bb = tuple(ac), tuple(bc), tuple(ab), tuple(bb)
    a_free = [d for d in range(a.ndim) if d not in ac and d not in ab]
    b_free = [d for d in range(b.ndim) if d not in bc and d not in bb]
    nb = len(ab)

    def pullback(g):
        from thunder_tpu import ops

        # grad_a: contract g's b_free dims with b's free dims
        g_bfree_pos = tuple(range(nb + len(a_free), nb + len(a_free) + len(b_free)))
        ga_t = prims.dot_general(g, b, contract_dims=(g_bfree_pos, tuple(b_free)),
                                 batch_dims=(tuple(range(nb)), bb))
        # ga_t dims: [batch(ab order), a_free(asc), b_contract dims(asc) ~ paired a_contract]
        src = [0] * a.ndim
        for i, d in enumerate(ab):
            src[d] = i
        for j, d in enumerate(a_free):
            src[d] = nb + j
        sorted_bc = sorted(bc)
        for idx, bd in enumerate(sorted_bc):
            a_dim = ac[bc.index(bd)]
            src[a_dim] = nb + len(a_free) + idx
        ga = prims.transpose(ga_t, tuple(src)) if tuple(src) != tuple(range(a.ndim)) else ga_t
        if ga.dtype is not a.dtype:
            ga = ops.convert_element_type(ga, a.dtype)

        # grad_b: contract g's a_free dims with a's free dims
        g_afree_pos = tuple(range(nb, nb + len(a_free)))
        gb_t = prims.dot_general(g, a, contract_dims=(g_afree_pos, tuple(a_free)),
                                 batch_dims=(tuple(range(nb)), ab))
        # gb_t dims: [batch(bb order), b_free(asc), a_contract dims(asc) ~ paired b_contract]
        srcb = [0] * b.ndim
        for i, d in enumerate(bb):
            srcb[d] = i
        for j, d in enumerate(b_free):
            srcb[d] = nb + j
        sorted_ac = sorted(ac)
        for idx, ad in enumerate(sorted_ac):
            b_dim = bc[ac.index(ad)]
            srcb[b_dim] = nb + len(b_free) + idx
        gb = prims.transpose(gb_t, tuple(srcb)) if tuple(srcb) != tuple(range(b.ndim)) else gb_t
        if gb.dtype is not b.dtype:
            gb = ops.convert_element_type(gb, b.dtype)
        return _pairs((a, ga), (b, gb))

    return out, pullback


@register_vjp(PrimIDs.OPT_BARRIER)
def _opt_barrier_vjp(*args):
    out = prims.opt_barrier(*args)

    def pullback(g):
        gs = list(g) if isinstance(g, (tuple, list)) else [g]
        return [(a, ct) for a, ct in zip(args, gs)]  # identity: 1:1 with args

    return out, pullback
