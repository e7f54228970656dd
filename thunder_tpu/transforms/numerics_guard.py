"""NumericsGuardTransform: in-graph NaN/spike detection with in-graph skip.

A trace-level pass (``Transform.transform_traces_pre_prologue``) that turns
any compiled training step into a self-defending one:

1. **Health reductions, fused into the step.** Non-finite element counts
   over the gradients, the loss, and the new state, plus the global grad
   norm, are appended to the computation trace as ordinary prims — XLA
   fuses them into the step's existing regions, so detection costs one
   small *health word* fetch per step (layout:
   ``runtime.sentinel.IDX_*``), not a host round-trip per tensor.
2. **In-graph skip.** Every (old_state_input, new_state_output) leaf pair
   is rewired through ``where(healthy, new, old)``: a non-finite step
   commits **bit-identical** previous state — no recompile, no host
   involvement, the guarded step stays one XLA executable.
3. **Deterministic injection.** Two scalar *poison inputs* are threaded
   into the program (``0.0`` = healthy); the ``numerics:grads`` /
   ``numerics:loss`` fault domains of ``runtime.faults.FaultPlan`` feed
   NaN through them, so chaos tests corrupt values inside the real
   compiled graph on exact, schedulable steps.

Pairing contract: ``state_argnums`` name the positional args that carry
state (params, optimizer state, ...) and ``state_outputs`` the positions of
their updated values in the step's returned tuple — the default
``(0, 1) -> (1, 2)`` matches the canonical
``step(params, opt_state, *batch) -> (loss, new_params, new_opt_state)``.
Each arg subtree must mirror its output subtree leaf-for-leaf.

Gradients are auto-detected from the optimizer composites
(``optim.adamw_step`` / ``optim.fused_adamw`` /
``optim.fused_adamw_slab``); steps without them (inline
SGD, custom updates) can mark grads explicitly with
:func:`observe_grads`. With no grads found the guard still protects via
the loss and new-state counts (grad norm reports 0).

Cost note: the selects keep the OLD state live until the verdict, so XLA
cannot alias donated parameter buffers into the update — the rollback
guarantee costs up to one extra copy of the guarded state in peak memory
plus the select bandwidth (not measured on the chip: no benchmark cell
runs the guard). With ``donate_argnums`` set, a failing call still consumes its
input buffers, so in-process *bisection* cannot replay them — it
escalates ``PersistentNonFinite`` to the supervisor (checkpoint restore)
instead; jit without donation to enable in-process bisection.

Distributed steps: when the input proxies carry dist annotations the
non-finite totals and the grad norm are all-reduced over the mesh axes
before the verdict, so every shard takes the same branch of the select.

The host side — counting, the loss-EWMA spike detector, rewind/bisection
escalation — lives in ``thunder_tpu.runtime.sentinel``.
"""

from __future__ import annotations

from thunder_tpu.core import dtypes, prims
from thunder_tpu.core.baseutils import check
from thunder_tpu.core.prims import PrimIDs
from thunder_tpu.core.proxies import DistParallelType, Proxy, TensorProxy, Variable
from thunder_tpu.core.pytree import tree_flatten, tree_unflatten
from thunder_tpu.core.trace import TraceCtx, tracectx
from thunder_tpu.core.transform_common import Transform
from thunder_tpu.core.utils import consumed_vars
from thunder_tpu.ops import opsymbol
from thunder_tpu.runtime import faults as _faults
from thunder_tpu.runtime import sentinel as _sentinel


@opsymbol(id="sentinel.observe_grads")
def observe_grads(grads):
    """Identity marker: tag a pytree of gradients for the numerics guard.

    Steps whose gradients don't flow through the optimizer composites
    (inline SGD, custom updates) call ``grads = observe_grads(grads)``
    before consuming them; the guard reads the marker for its grad-health
    reductions and strips it. Without the guard the marker is dropped by
    the claim pass (identity composite) — zero cost."""
    return grads


def _is_float_tensor(p) -> bool:
    return isinstance(p, TensorProxy) and p.dtype.is_float


class NumericsGuardTransform(Transform):
    """See the module docstring. One instance guards one jitted function;
    its :class:`~thunder_tpu.runtime.sentinel.NumericsSentinel` accumulates
    that function's health history (skips, EWMA, escalation state)."""

    def __init__(self, *, state_argnums=(0, 1), state_outputs=(1, 2),
                 loss_output: int | None = 0, policy=None, sentinel=None,
                 inject: bool = True):
        self.state_argnums = tuple(state_argnums)
        self.state_outputs = tuple(state_outputs)
        self.loss_output = loss_output
        self.sentinel = sentinel or _sentinel.NumericsSentinel(policy=policy)
        self.inject = inject
        self._installed = False
        self._n_extra_inputs = 0
        self._has_pairs = False
        self._grads_found = False

    # -- trace pass ----------------------------------------------------------
    def transform_traces_pre_prologue(self, prologue_trc, computation_trc,
                                      epilogue_trc, **kwargs):
        trc = computation_trc
        in_proxies = getattr(trc, "input_proxies", None)
        in_treedef = getattr(trc, "input_treedef", None)
        check(in_proxies is not None and in_treedef is not None,
              "NumericsGuardTransform needs the traced input structure "
              "(trc.input_proxies/input_treedef) — attach it via thunder_tpu.jit")
        pargs, _pkwargs = tree_unflatten(in_treedef, list(in_proxies))

        # -- pair old-state inputs with new-state outputs ---------------------
        old_leaves: list = []
        for i in self.state_argnums:
            check(i < len(pargs), lambda: (
                f"NumericsGuardTransform: state_argnums includes {i} but the "
                f"step takes {len(pargs)} positional args"))
            flat, _ = tree_flatten(pargs[i])
            old_leaves.extend(flat)
        out = trc.output
        check(isinstance(out, (tuple, list)) and len(out) > max(
            (*self.state_outputs, self.loss_output or 0)), lambda: (
            "NumericsGuardTransform: the step must return a tuple with the "
            f"state_outputs positions {self.state_outputs} (got "
            f"{type(out).__name__} of length "
            f"{len(out) if isinstance(out, (tuple, list)) else 'n/a'})"))
        new_leaves: list = []
        for i in self.state_outputs:
            flat, _ = tree_flatten(out[i])
            new_leaves.extend(flat)
        check(len(old_leaves) == len(new_leaves), lambda: (
            f"NumericsGuardTransform: state args flatten to {len(old_leaves)} "
            f"leaves but state outputs to {len(new_leaves)} — state_argnums "
            f"{self.state_argnums} must mirror state_outputs {self.state_outputs}"))
        pairs: list[tuple[TensorProxy, TensorProxy]] = []
        for o, n in zip(old_leaves, new_leaves):
            if not (isinstance(o, TensorProxy) and isinstance(n, TensorProxy)):
                continue  # baked constants / scalars: nothing to select
            if o.name == n.name:
                continue  # passthrough leaf: old IS new, select is a no-op
            check(tuple(o.shape) == tuple(n.shape) and o.dtype == n.dtype,
                  lambda: (f"NumericsGuardTransform: state leaf mismatch — "
                           f"input {o.name} {o.dtype}{tuple(o.shape)} vs output "
                           f"{n.name} {n.dtype}{tuple(n.shape)}"))
            pairs.append((o, n))

        loss_p = out[self.loss_output] if self.loss_output is not None else None
        if not isinstance(loss_p, TensorProxy):
            loss_p = None

        # -- locate gradients (with their parameter proxies when known: the
        # param's dist annotation decides whether a grad leaf's sum-of-
        # squares is shard-local or replicated on a mesh) ---------------------
        grads: list[TensorProxy] = []
        grad_refs: list = []  # parallel: the param proxy, or None (markers)
        seen_g: set[Variable] = set()

        def _take(g, ref=None):
            if isinstance(g, TensorProxy) and Variable(g) not in seen_g:
                seen_g.add(Variable(g))
                grads.append(g)
                grad_refs.append(ref)

        marker_idxs: set[int] = set()
        marked: list[TensorProxy] = []
        for idx, b in enumerate(trc.bound_symbols):
            sid = str(b.sym.id)
            if sid == "sentinel.observe_grads":
                marker_idxs.add(idx)
                for p in b.flat_proxy_args():
                    if isinstance(p, TensorProxy):
                        marked.append(p)
        if marked:
            for p in marked:
                _take(p)
            # strip the identity markers (outputs == inputs, so downstream
            # references stay valid); in-place — the trace's scope stack
            # aliases this list
            trc.bound_symbols[:] = [b for i, b in enumerate(trc.bound_symbols)
                                    if i not in marker_idxs]
        else:
            for b in trc.bound_symbols:
                sid = str(b.sym.id)
                if sid == "optim.adamw_step":
                    _take(b.args[1], b.args[0])
                elif sid in ("optim.fused_adamw", "optim.fused_adamw_slab"):
                    # both multi-tensor forms carry (params, grads, ...) as
                    # their first two args — the slab variant differs only in
                    # how the MOMENTS are stored, not where the grads are
                    for p_ref, g in zip(b.args[0], b.args[1]):
                        _take(g, p_ref)

        # -- pop the return; everything below emits into the trace ------------
        check(trc.bound_symbols and trc.bound_symbols[-1].sym.id is PrimIDs.PYTHON_RETURN,
              "NumericsGuardTransform: computation trace has no return")
        trc.bound_symbols.pop()

        from thunder_tpu import ops

        f32 = dtypes.float32
        poison_g = poison_l = None
        if self.inject:
            with tracectx(trc):
                poison_g = TensorProxy("numerics_poison_grads", shape=(), dtype=f32)
                poison_l = TensorProxy("numerics_poison_loss", shape=(), dtype=f32)

        # poison the grads at their first consumer: g' = g + cast(poison)
        grad_swap: dict[Variable, Proxy] = {}
        if self.inject and grads:
            gvars = {Variable(g) for g in grads}
            insert_at = len(trc.bound_symbols)
            for i, b in enumerate(trc.bound_symbols):
                if any(v in gvars for v in consumed_vars(b)):
                    insert_at = i
                    break
            tmp = TraceCtx("numerics_poison")
            tmp._names = trc._names
            tmp._counters = trc._counters
            poisoned: list[TensorProxy] = []
            with tracectx(tmp):
                for g in grads:
                    if _is_float_tensor(g):
                        gp = ops.add(g, ops.convert_element_type(poison_g, g.dtype))
                        grad_swap[Variable(g)] = gp
                        poisoned.append(gp)
                    else:
                        poisoned.append(g)
            tail = [b.from_bsym_swap_proxies(grad_swap, skip_output=True)
                    for b in trc.bound_symbols[insert_at:]]
            # in-place — the trace's scope stack aliases this list
            trc.bound_symbols[:] = (trc.bound_symbols[:insert_at]
                                    + tmp.bound_symbols + tail)
            grads = poisoned

        loss_swap: dict[Variable, Proxy] = {}
        select_swap: dict[Variable, Proxy] = {}
        with tracectx(trc):
            def count_nonfinite(t):
                nf = ops.logical_not(ops.isfinite(t))
                return ops.sum(ops.convert_element_type(nf, f32))

            zero = ops.full((), 0.0, dtype=f32)
            loss_checked = loss_p
            if loss_p is not None and self.inject:
                loss_checked = ops.add(
                    loss_p, ops.convert_element_type(poison_l, loss_p.dtype))
                loss_swap[Variable(loss_p)] = loss_checked
            # distributed step: the verdict (and the norm) must agree across
            # shards, or one shard would skip while another commits
            axes = sorted({
                getattr(p, "dist_axis") for p in in_proxies
                if isinstance(p, TensorProxy)
                and p.distparallel_type is not DistParallelType.NONE
                and getattr(p, "dist_axis", None) is not None})
            from thunder_tpu.optim import sharded_axis_of

            nf_grads = zero
            # grad norm splits by the owning param's annotation (the SAME
            # rule as optim.clip_grad_norm, via the shared sharded_axis_of):
            # a sharded leaf's sumsq is psum'd over exactly ITS mesh axis;
            # replicated leaves are identical on every rank and sum locally
            # (psum would inflate the norm by up to sqrt(world_size)).
            # Unpaired grads (observe_grads markers) can't be routed by
            # annotation — they join an unattributed bucket reduced over
            # every axis: conservative for FSDP (grads arrive
            # reduce-scattered), over-counting for replicated markers.
            normsq_local = zero
            normsq_axis: dict[str, object] = {}   # axis -> sharded sumsq
            normsq_unattr = zero
            for g, ref in zip(grads, grad_refs):
                if not _is_float_tensor(g):
                    continue
                nf_grads = ops.add(nf_grads, count_nonfinite(g))
                gf = ops.convert_element_type(g, f32)
                ss = ops.sum(ops.mul(gf, gf))
                if not axes:
                    normsq_local = ops.add(normsq_local, ss)
                elif ref is None:
                    normsq_unattr = ops.add(normsq_unattr, ss)
                else:
                    ax = sharded_axis_of(ref)
                    if ax is None:
                        normsq_local = ops.add(normsq_local, ss)
                    else:
                        normsq_axis[ax] = ss if ax not in normsq_axis \
                            else ops.add(normsq_axis[ax], ss)
            nf_loss = (count_nonfinite(loss_checked)
                       if _is_float_tensor(loss_checked) else zero)
            nf_state = zero
            for _o, n in pairs:
                if _is_float_tensor(n):
                    nf_state = ops.add(nf_state, count_nonfinite(n))
            normsq = normsq_local
            if axes:
                # ONE packed all-reduce per mesh axis covers the verdict
                # counts (reduced over EVERY axis so the whole mesh agrees;
                # counts over replicated quantities come back ×world_size,
                # which leaves the zero/non-zero verdict exact), the
                # unattributed norm bucket, and — on its own axis only —
                # that axis's sharded sumsq
                from thunder_tpu.distributed import prims as dist_prims

                packed = ops.stack([nf_grads, nf_loss, nf_state,
                                    normsq_unattr], 0)
                for ax in axes:
                    packed = dist_prims.wait(dist_prims.all_reduce(packed, ax, "sum"))
                    if ax in normsq_axis:
                        normsq = ops.add(normsq, dist_prims.wait(
                            dist_prims.all_reduce(normsq_axis[ax], ax, "sum")))
                nf_grads = ops.getitem(packed, 0)
                nf_loss = ops.getitem(packed, 1)
                nf_state = ops.getitem(packed, 2)
                normsq = ops.add(normsq, ops.getitem(packed, 3))
            total = ops.add(ops.add(nf_grads, nf_loss), nf_state)
            healthy = ops.lt(total, 0.5)
            grad_norm = ops.sqrt(normsq)
            for o, n in pairs:
                select_swap[Variable(n)] = ops.where(healthy, n, o)
            loss_f = (ops.convert_element_type(loss_checked, f32)
                      if _is_float_tensor(loss_checked) else zero)
            health_word = ops.stack([nf_grads, nf_loss, nf_state, grad_norm,
                                     loss_f], 0)

            # rebuild the output: selected state, poisoned loss/grads where
            # they are returned, health word appended
            flat_out, out_tdef = tree_flatten(trc.output)
            swapped = []
            for x in flat_out:
                if isinstance(x, Proxy):
                    v = Variable(x)
                    for m in (select_swap, loss_swap, grad_swap):
                        if v in m:
                            x = m[v]
                            break
                swapped.append(x)
            core = tree_unflatten(out_tdef, swapped)
            new_output = (core, health_word)
            prims.python_return(new_output)
        trc.output = new_output
        if self.inject:
            trc.args = list(trc.args) + [poison_g, poison_l]
            self._n_extra_inputs = 2
        self._installed = True
        self._has_pairs = bool(pairs)
        self._grads_found = bool(grads)
        return prologue_trc, trc, epilogue_trc

    # -- driver hooks --------------------------------------------------------
    def extra_input_avals(self):
        """Avals of the poison inputs this transform appended to the trace
        signature (the driver extends ``entry.input_avals`` with them)."""
        import jax
        import numpy as np

        return [jax.ShapeDtypeStruct((), np.float32)] * self._n_extra_inputs

    def wrap_run_fn(self, tfn, entry, inner):
        """Per-entry runtime wrapper: feed the poison inputs, peel the
        health word (the ONE host fetch per step), drive the sentinel."""
        if not self._installed:
            return inner
        import numpy as np

        from thunder_tpu.observe import decisions as _decisions

        sent = self.sentinel
        n_extra = self._n_extra_inputs
        has_pairs = self._has_pairs
        fn_name = tfn.fn_name
        # hold THIS entry's decision log (wrap_run_fn runs inside its
        # compile, so the live sink IS this compile's log — the list object
        # that becomes CompileStats.last_decisions and is never mutated
        # afterwards). A replay bundle must carry the failing entry's
        # decisions, not whichever entry compiled most recently.
        entry_decisions = _decisions.current_log()

        def guarded(*inps):
            step = sent.steps + 1  # the step this call will become
            if n_extra:
                pg = np.float32("nan") if _faults.should_corrupt(
                    "numerics:grads", step=step, site=fn_name) else np.float32(0.0)
                pl = np.float32("nan") if _faults.should_corrupt(
                    "numerics:loss", step=step, site=fn_name) else np.float32(0.0)
                inps = (*inps, pg, pl)
            out = inner(*inps)
            core, health = out
            sent._replay_source = (fn_name, entry, inps, entry_decisions)
            try:
                sent.ingest(health, has_state_select=has_pairs)
            except _sentinel.SilentNumericsFault as e:
                e.transform = self
                e.entry = entry
                raise
            finally:
                sent._replay_source = None
            return core

        guarded.__wrapped__ = inner
        return guarded
