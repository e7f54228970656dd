"""Trace → executable-trace passes: executor claiming, fusion, del insertion.

Reference parity: ``thunder/executors/passes.py`` (
``_transform_for_operator_executor_execution`` :34, ``transform_for_execution``
:136, ``del_last_used`` :290). The claim walk is the same design: each bound
symbol is offered to the executors in priority order; an executor can
substitute its own symbol (with a runtime callable) or rewrite via an
execution transform; unclaimed composites are decomposed into their
subsymbols and re-offered; unclaimed prims fall back to the eager JAX
executor. FusionExecutors then run their fusion passes in list order.
"""

from __future__ import annotations

from thunder_tpu.core.baseutils import check
from thunder_tpu.core.prims import OpTags, PrimIDs
from thunder_tpu.core.proxies import Proxy, Variable
from thunder_tpu.core.pytree import tree_flatten
from thunder_tpu.core.symbol import BoundSymbol, Symbol
from thunder_tpu.core.trace import TraceCtx, from_trace, tracectx
from thunder_tpu.core.transform_common import dce
from thunder_tpu.core.utils import consumed_vars, produced_vars
from thunder_tpu.executors import Executor, FusionExecutor
from thunder_tpu.observe import decisions as _decisions
from thunder_tpu.observe import registry as _observe
from thunder_tpu.runtime import quarantine as _quarantine


_PASSTHROUGH_IDS = (PrimIDs.PYTHON_RETURN, PrimIDs.COMMENT, PrimIDs.PYTHON_DEL,
                    PrimIDs.UNPACK_TRIVIAL)


def _run_execution_transform(transform, bsym: BoundSymbol, trc: TraceCtx) -> list[BoundSymbol]:
    tmp = TraceCtx("exec_transform")
    tmp._names = trc._names  # share the name registry: no collisions
    tmp._counters = trc._counters
    with tracectx(tmp):
        out = transform(*bsym.args, **bsym.kwargs)
    new_flat, _ = tree_flatten(out)
    old_flat, _ = tree_flatten(bsym.output)
    swap = {}
    for n, o in zip(new_flat, old_flat):
        if isinstance(n, Proxy) and isinstance(o, Proxy) and n.name != o.name:
            swap[Variable(n)] = o
    return [b.from_bsym_swap_proxies(swap) for b in tmp.bound_symbols]


def claim_bsym(bsym: BoundSymbol, executors, trc: TraceCtx) -> list[BoundSymbol]:
    if bsym.sym.id in _PASSTHROUGH_IDS or bsym.sym.executor is not None:
        return [bsym]
    log = _decisions.active()  # decision log: one flag read per bsym when off
    for ex in executors:
        if isinstance(ex, FusionExecutor):
            continue  # fusion executors run as whole-trace passes afterwards
        impl = ex.get_impl(bsym)
        if impl is None:
            continue
        # quarantine gate: a claim id that failed at compile/runtime (this
        # process or a previous one — the set persists next to the compile
        # cache) is never offered again; the op falls through to the XLA
        # lowering. ALWAYS recorded in the decision log so explain() answers
        # "why is this op no longer fused".
        claim_id = impl.symbol.id if impl.symbol is not None \
            else f"{ex.name}.{bsym.sym.name}"
        qreason = _quarantine.quarantine_reason(claim_id)
        if qreason is not None:
            # (runtime.fallbacks counts degradation EVENTS at the dispatch
            # layer; counting every per-compile rejection here would inflate
            # the metric with each unrelated recompile)
            if log:
                _decisions.record("claim", bsym.sym.name, ex.name, "rejected",
                                  f"quarantined: {qreason}")
            continue
        if not ex.can_execute(bsym):
            if log:
                _decisions.record("claim", bsym.sym.name, ex.name, "rejected",
                                  "checker refused (shape/dtype/tiling legality)")
            continue
        # cost-model gate: a legal claim may still lose to leaving the op
        # inside an XLA fusion region (memory-bound op, tiny working set).
        # A gate that RAISES is a bug in the cost model and propagates —
        # it must not read as "don't claim"
        if impl.profitable is not None and not impl.profitable(bsym):
            if log:
                from thunder_tpu.core import cost_model

                flops, nbytes = cost_model.bsym_cost(bsym)
                _decisions.record(
                    "claim", bsym.sym.name, ex.name, "rejected",
                    "cost model: claim loses to XLA region fusion",
                    cost={"flops": flops, "bytes": nbytes,
                          "min_claim_bytes": cost_model.MIN_CLAIM_BYTES})
            continue
        if not getattr(ex, "get_fuel", lambda *_: True)():
            if log:
                _decisions.record("claim", bsym.sym.name, ex.name, "rejected",
                                  "optimization fuel exhausted")
            continue
        if impl.execution_transform is not None:
            if log:
                _decisions.record("claim", bsym.sym.name, ex.name, "claimed",
                                  "via execution transform")
            return _run_execution_transform(impl.execution_transform, bsym, trc)
        if impl.symbol is not None:
            if log:
                _decisions.record("claim", bsym.sym.name, ex.name, "claimed")
            claimed = impl.symbol.bind(*bsym.args, output=bsym.output,
                                       subsymbols=bsym.subsymbols, **bsym.kwargs)
            claimed.header = bsym.header  # keep pass annotations (fusion markers)
            return [claimed]
    from thunder_tpu.executors.eagerjax import get_eager_impl

    if bsym.sym.is_prim:
        check(get_eager_impl(bsym.sym) is not None or bsym.sym.python_impl is not None,
              lambda: f"no executor can run prim {bsym.sym.name}")
        if log:
            _decisions.record("claim", bsym.sym.name, "eagerjax", "fallback",
                              "unclaimed prim runs on the eager JAX executor")
        return [bsym]
    if len(bsym.subsymbols) == 0:
        # identity composite (e.g. eval-mode dropout returns its input):
        # every output proxy is an input proxy, so nothing needs emitting —
        # downstream bsyms already reference the producing names
        arg_names = {p.name for p in bsym.flat_proxy_args()}
        outs = bsym.flat_proxy_outs()
        if outs and all(p.name in arg_names for p in outs):
            return []
    check(len(bsym.subsymbols) > 0, lambda: f"unclaimed symbol {bsym.sym.name} has no decomposition")
    if log:
        _decisions.record("claim", bsym.sym.name, None, "decomposed",
                          f"no executor claims the composite; re-offering its "
                          f"{len(bsym.subsymbols)} subsymbols")
    out: list[BoundSymbol] = []
    for sub in bsym.subsymbols:
        out.extend(claim_bsym(sub, executors, trc))
    return out


def transform_for_execution(trc: TraceCtx, executors) -> TraceCtx:
    """Fusion-prep passes + claim pass + fusion passes + DCE (reference
    ``passes.py:136``, extended with the Fusion 2.0 rewrites)."""
    from thunder_tpu.core.fusion_passes import (
        block_fusion_pass,
        epilogue_fusion_pass,
        horizontal_fusion_pass,
        optimizer_fusion_pass,
    )

    # run BEFORE claiming: horizontal merging works on unclaimed dot_generals,
    # and the block/epilogue/optimizer rewrites build composites for the
    # claim walk to offer. The block planner goes FIRST — it wants whole
    # sub-block chains, which horizontal merging (gate+up GEMMs share the
    # normed activation) and epilogue fusion (add→rms_norm) would otherwise
    # carve up. This is the planner's one entry: a train step's chain is
    # prim-level here (the anchor scan early-outs, its GEMMs are XLA's); an
    # inference trace's composite-level chains survive to this pass.
    with _observe.span("block_fusion"):
        trc = block_fusion_pass(trc, executors)
    with _observe.span("horizontal_fusion"):
        trc = horizontal_fusion_pass(trc)
    with _observe.span("epilogue_fusion"):
        trc = epilogue_fusion_pass(trc, executors)
    with _observe.span("optimizer_fusion"):
        trc = optimizer_fusion_pass(trc, executors)

    with _observe.span("claim"):
        ex_bsyms: list[BoundSymbol] = []
        for bsym in trc.bound_symbols:
            ex_bsyms.extend(claim_bsym(bsym, executors, trc))
        new = from_trace(trc)
        new.bound_symbols = ex_bsyms
        new.set_provenance("Executor claim pass")
    from thunder_tpu.core.compile_data import get_compile_option

    # Region annotation happens at CLAIM granularity — before the fusion
    # executors run — because that is the level the decision log speaks at
    # (one planned block / bucketed optimizer chain per claimed bsym). The
    # XLA fusion pass then absorbs the annotated impls into its jax.jit
    # regions, so the named_scope still reaches the lowered HLO metadata and
    # TPU profiler traces attribute time inside fused programs back to the
    # exact verdict. The annotated claim-level trace is kept on the returned
    # trace (``_region_trace``) so observe.profile can replay it region by
    # region on backends without a profiler.
    region_trc = None
    if get_compile_option(
            "region_annotations",
            "wrap each claimed executor callable in a jax.named_scope carrying "
            "its stable region name (executor:symbol#occurrence — the id the "
            "decision log, observe.profile and ProfileTransform share), so "
            "profiler traces attribute time back to compiler verdicts",
            True):
        with _observe.span("annotate_regions"):
            new = region_trc = annotate_regions(new)
    for ex in executors:
        if isinstance(ex, FusionExecutor):
            with _observe.span(f"fusion_pass:{ex.name}"):
                new = ex.fusion_pass(new)
    new = dce(new)
    new.set_provenance("Transform for execution")
    new._region_trace = region_trc
    return new


def annotate_regions(trc: TraceCtx) -> TraceCtx:
    """Thread the stable region names (``observe.profile.region_names_for``
    — the SAME ids the decision log joins on) through dispatch: each bound
    symbol carrying a ``python_impl`` (claimed executor ops, fusion-region
    callables) is rebound to a copy whose impl runs under
    ``jax.named_scope(region_name)``, so the region name lands in the
    lowered HLO op metadata and ``jax.profiler`` traces attribute device
    time back to the exact verdict that scheduled the region."""
    import jax

    from thunder_tpu.observe.profile import region_names_for

    names = region_names_for(trc)
    new = from_trace(trc)
    bsyms: list[BoundSymbol] = []
    for bsym, name in zip(trc.bound_symbols, names):
        if name is None or bsym.sym.python_impl is None:
            bsyms.append(bsym)
            continue
        inner = bsym.sym.python_impl

        def make_impl(_name, _inner):
            def annotated(*args, **kw):
                with jax.named_scope(_name):
                    return _inner(*args, **kw)

            return annotated

        sym = Symbol(bsym.sym.name, bsym.sym.meta, id=bsym.sym.id,
                     is_prim=bsym.sym.is_prim, executor=bsym.sym.executor,
                     python_impl=make_impl(name, inner), tags=bsym.sym.tags)
        bsyms.append(bsym.from_bsym(sym=sym))
    new.bound_symbols = bsyms
    new.set_provenance("Region annotations")
    return new


def del_last_used(trc: TraceCtx) -> TraceCtx:
    """Insert ``del`` statements after each proxy's last use so the eager
    path releases buffers promptly (reference ``passes.py:290``)."""
    from thunder_tpu.core import prims

    out_vars: set[Variable] = set()
    flat_out, _ = tree_flatten(trc.output)
    for o in flat_out:
        if isinstance(o, Proxy):
            out_vars.add(Variable(o))
    arg_vars = {Variable(a) for a in trc.args}

    # only names bound at top level of the generated function may be deleted
    visible: set[Variable] = set(arg_vars)
    for bsym in trc.bound_symbols:
        for p in bsym.flat_proxy_outs():
            visible.add(Variable(p))

    last_use: dict[Variable, int] = {}
    for i, bsym in enumerate(trc.bound_symbols):
        for v in consumed_vars(bsym):
            if v in visible:
                last_use[v] = i

    dels_at: dict[int, list[Proxy]] = {}
    for v, i in last_use.items():
        if v in out_vars or v in arg_vars:
            continue
        dels_at.setdefault(i, []).append(v.proxy)

    new = from_trace(trc)
    bsyms: list[BoundSymbol] = []
    for i, bsym in enumerate(trc.bound_symbols):
        bsyms.append(bsym)
        if i in dels_at and bsym.sym.id is not PrimIDs.PYTHON_RETURN:
            ps = sorted(dels_at[i], key=lambda p: p.name)
            bsyms.append(prims.python_del.bind(*ps, output=None))
    new.bound_symbols = bsyms
    new.set_provenance("Delete last used")
    return new
