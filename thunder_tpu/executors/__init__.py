"""Executor framework: prioritized, extensible op claiming + region fusion.

The best idea in the reference (``thunder/extend/__init__.py:56-281``) kept
here: every operation in a trace can be *claimed* by an executor — an
``OperatorExecutor`` substitutes a single bound symbol with an
executor-specific symbol carrying a concrete runtime callable (e.g. a Pallas
flash-attention kernel claiming ``nn.scaled_dot_product_attention``), and a
``FusionExecutor`` groups whole regions into one fused callable (the XLA
executor jax.jit's regions). Executors are consulted in priority order;
the eager-JAX executor is the always-on fallback.
"""

from __future__ import annotations

from typing import Any, Callable, Sequence

from thunder_tpu.core.baseutils import check
from thunder_tpu.core.symbol import BoundSymbol, Symbol


class ImplInfo:
    """How an executor implements one symbol id.

    ``checker`` answers *can* this executor run the bsym (shape/dtype/tiling
    legality); ``profitable`` answers *should* it (cost-model gate: a legal
    claim may still lose to leaving the op inside an XLA fusion region).
    Both default to yes."""

    __slots__ = ("symbol", "checker", "execution_transform", "grad_transform", "profitable")

    def __init__(self, symbol: Symbol | None = None, checker: Callable | None = None,
                 execution_transform: Callable | None = None, grad_transform: Callable | None = None,
                 profitable: Callable | None = None):
        self.symbol = symbol
        self.checker = checker
        self.execution_transform = execution_transform
        self.grad_transform = grad_transform
        self.profitable = profitable


class Executor:
    # executors that opt in allow the XLA fusion pass to ABSORB their claimed
    # bound symbols into jit regions (the claimed python_impl must be
    # jax-traceable, e.g. a pallas_call): elementwise producers/consumers
    # then fuse around the custom kernel inside one XLA program instead of
    # the claim splitting the region at both kernel boundaries
    fusible_into_regions = False

    def __init__(self, name: str, version: str = "0.1"):
        self.name = name
        self.version = version
        self.implmap: dict[Any, ImplInfo] = {}

    def can_execute(self, bsym: BoundSymbol) -> bool:
        impl = self.implmap.get(bsym.sym.id)
        if impl is None:
            return False
        # a checker that RAISES is a bug, not a "no": it propagates, so a
        # kernel never silently stops claiming
        if impl.checker is not None:
            return bool(impl.checker(*bsym.args, **bsym.kwargs))
        return True

    def get_impl(self, bsym: BoundSymbol) -> ImplInfo | None:
        return self.implmap.get(bsym.sym.id)

    def __repr__(self):
        return f"<Executor {self.name}>"


class OperatorExecutor(Executor):
    """Executor providing per-op runtime callables (reference
    ``thunder/extend/__init__.py:197-279``)."""

    def register_operator(self, name: str, *, meta: Callable | None = None, fn: Callable,
                          like: Symbol | None = None, tags=None) -> Symbol:
        if meta is None and like is not None:
            meta = like.meta
        # every claimed kernel impl runs under the fault-domain guard: it
        # hosts the `kernel:<executor>.<op>` injection domain and attributes
        # failures to the claim id (KernelExecutionError), which is what lets
        # the dispatch layer quarantine exactly this kernel and recompile
        # with the XLA fallback instead of killing the job
        from thunder_tpu.runtime.faults import kernel_guard

        sym_id = f"{self.name}.{name}"
        sym = Symbol(name, meta, id=sym_id, is_prim=True, executor=self,
                     python_impl=kernel_guard(sym_id, fn),
                     tags=tags or (like.tags if like is not None else None))
        return sym

    def register_implementation(self, id_or_sym, op: Symbol | None = None, *,
                                checker: Callable | None = None,
                                execution_transform: Callable | None = None,
                                grad_transform: Callable | None = None,
                                profitable: Callable | None = None) -> None:
        sym_id = id_or_sym.id if isinstance(id_or_sym, Symbol) else id_or_sym
        self.implmap[sym_id] = ImplInfo(symbol=op, checker=checker,
                                        execution_transform=execution_transform,
                                        grad_transform=grad_transform,
                                        profitable=profitable)


class FusionExecutor(Executor):
    """Executor that fuses whole regions of the trace; with optimization-fuel
    debugging as in the reference (``thunder/extend/__init__.py:143-162``)."""

    def __init__(self, name: str, version: str = "0.1"):
        super().__init__(name, version)
        import os

        fuel = os.environ.get(f"{name.upper()}_OPTIMIZATION_FUEL")
        self._fuel = int(fuel) if fuel else None

    def get_fuel(self, amount: int = 1) -> bool:
        if self._fuel is None:
            return True
        if self._fuel < amount:
            return False
        self._fuel -= amount
        return True

    def fusion_pass(self, trace):
        raise NotImplementedError

    def can_fuse(self, bsym: BoundSymbol) -> bool:
        raise NotImplementedError


def single_op_executor(executor_name: str, op_name: str, fn: Callable, *,
                       meta: Callable | None = None, like: Symbol | None = None,
                       checker: Callable | None = None,
                       grad_transform: Callable | None = None,
                       register: bool = True) -> OperatorExecutor:
    """Create an OperatorExecutor claiming exactly one operation — the
    smallest possible custom-kernel integration (reference
    ``thunder/extend/__init__.py:282``).

    ``fn`` is the runtime callable; ``like`` (an existing Symbol, e.g. an op
    from ``thunder_tpu.ops``) supplies the meta and the claimed id.
    """
    ex = OperatorExecutor(executor_name)
    sym = ex.register_operator(op_name, meta=meta, like=like, fn=fn)
    target = like.id if like is not None else op_name
    ex.register_implementation(target, sym, checker=checker, grad_transform=grad_transform)
    if register:
        register_executor(ex)
    return ex


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

_executor_map: dict[str, Executor] = {}
_default_executors: list[Executor] = []
_always_executors: list[Executor] = []


def register_executor(ex: Executor, *, default: bool = False, always: bool = False, index: int | None = None):
    _executor_map[ex.name] = ex
    if default and ex not in _default_executors:
        _default_executors.insert(index if index is not None else len(_default_executors), ex)
    if always and ex not in _always_executors:
        _always_executors.append(ex)
    return ex


def get_executor(name: str) -> Executor | None:
    _ensure_builtin_executors()
    return _executor_map.get(name)

def get_all_executors() -> tuple[Executor, ...]:
    _ensure_builtin_executors()
    return tuple(_executor_map.values())


def get_default_executors() -> tuple[Executor, ...]:
    _ensure_builtin_executors()
    return tuple(_default_executors)


def get_always_executors() -> tuple[Executor, ...]:
    _ensure_builtin_executors()
    return tuple(_always_executors)


def resolve_executors(executors: Sequence | None) -> tuple[Executor, ...]:
    if executors is None:
        return get_default_executors()
    out = []
    for e in executors:
        if isinstance(e, Executor):
            out.append(e)
        elif isinstance(e, str):
            ex = get_executor(e)
            check(ex is not None, lambda: f"unknown executor {e!r}; known: {list(_executor_map)}")
            out.append(ex)
        else:
            raise TypeError(f"cannot resolve executor from {e!r}")
    for a in get_always_executors():
        if a not in out:
            out.append(a)
    return tuple(out)


_builtins_loaded = False


def _ensure_builtin_executors():
    """Import built-in executors (registers them). Deferred to avoid import cycles."""
    global _builtins_loaded
    if _builtins_loaded:
        return
    _builtins_loaded = True
    from thunder_tpu.executors import eagerjax, pallasex, xla  # noqa: F401
