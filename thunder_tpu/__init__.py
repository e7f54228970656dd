"""thunder_tpu: a TPU-native deep-learning trace compiler.

``thunder_tpu.jit(fn)`` acquires the user's program as a printable,
multi-stage trace over a small primitive set; trace transforms provide
autograd (``value_and_grad`` inlined for whole-train-step compilation),
distributed parallelism, and optimization passes; a prioritized executor
system dispatches operations — an eager ``jax.numpy`` fallback, an XLA
fusion executor, and Pallas kernel executors.

Capability parity with lightning-thunder's driver
(``thunder/__init__.py:262`` jit, ``CompileData/CompileStats``
``thunder/common.py:57,181``, cache ``CacheEntry`` ``thunder/__init__.py:242``,
introspection ``last_traces`` ``:859-944``) — re-architected TPU-first:
constant-values caching keyed on input metadata, functional RNG, no
bytecode interpreter (JAX-style duck tracing).
"""

from __future__ import annotations

import os as _os
import time
from numbers import Number
from typing import Any, Callable, Sequence

import numpy as _np

from thunder_tpu.core import dtypes, devices, prims
from thunder_tpu.core.baseutils import check
from thunder_tpu.core.proxies import NumberProxy, Proxy, StringProxy, TensorProxy
from thunder_tpu.core.pytree import tree_flatten, tree_unflatten
from thunder_tpu.core.trace import TraceCtx, TraceResults, get_tracectx, tracectx
from thunder_tpu.core.transform_common import Transform, cse, dce
from thunder_tpu.core.transforms import (
    forward_and_backward_from_trace,
    inline_value_and_grad,
    jvp_call,
    vmap_call,
)
# load the checkpoint-IO SUBMODULE first: the import system sets the package's
# ``checkpoint`` attribute to the module exactly once (at first load), so
# importing it eagerly here — before the function binding below — means a later
# ``from thunder_tpu.checkpoint import save_checkpoint`` elsewhere can never
# shadow ``tt.checkpoint`` (the activation-checkpoint function) back to a module
import thunder_tpu.checkpoint as checkpoint_io  # noqa: F401
from thunder_tpu.core.rematerialization import (
    checkpoint,
    rematerialize_forward_and_backward,
)
from thunder_tpu import observe  # noqa: F401  (thunder_tpu.observe.*)
from thunder_tpu.observe import registry as _observe
from thunder_tpu import runtime as runtime  # noqa: F401  (fault-domain runtime)
from thunder_tpu.runtime import faults as _faults
from thunder_tpu.runtime import quarantine as _quarantine
from thunder_tpu.runtime import sentinel as _sentinel
from thunder_tpu.runtime.faults import KernelExecutionError

__version__ = "0.1.0"

_CACHE_OPTIONS = ("constant values", "symbolic values", "no caching")


# ---------------------------------------------------------------------------
# rng state (host-side; threaded functionally through compiled programs)
# ---------------------------------------------------------------------------

_rng_state: dict[str, Any] = {"key": None}


# the one in-checkout default for JAX's persistent compilation cache
# (gitignored). The path is part of the cache key, so it never moves.
_DEFAULT_COMPILATION_CACHE = _os.path.join(
    _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))), ".jax_cache")


def enable_compilation_cache(directory: str | None = None, *,
                             min_compile_secs: float = 1.0) -> str:
    """Place JAX's persistent compilation cache and return the directory in
    use — the ONE helper every entry point (chip_smoke.py,
    benchmark/run.py, tests/conftest.py, ``ElasticTrainer``) shares.

    ``JAX_COMPILATION_CACHE_DIR`` set in the environment: JAX's own handling
    owns the cache — nothing is configured here, whatever ``directory``
    says (the cache is placed from OUTSIDE the program). Not set:
    ``directory``, or one fixed ``.jax_cache`` at the checkout root.

    Only XLA executables live there. The kernel-quarantine set and the
    cost-model calibration overlay change WHAT is compiled, so they follow
    their own settings (``THUNDER_TPU_QUARANTINE_DIR`` /
    ``THUNDER_TPU_CALIBRATION_DIR`` or an explicit ``configure()``) and
    never ride in a cache directory a driver reuses across commits."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as _cc

    env = _os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    directory = str(directory) if directory is not None \
        else _DEFAULT_COMPILATION_CACHE
    if jax.config.jax_compilation_cache_dir != directory:
        jax.config.update("jax_compilation_cache_dir", directory)
        # jax binds its cache object to the directory at first use and then
        # ignores updates; reset so a changed directory takes effect even
        # after earlier compiles in this process
        _cc.reset_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      float(min_compile_secs))
    return directory


def manual_seed(seed: int) -> None:
    import jax

    _rng_state["key"] = jax.random.PRNGKey(seed)


def _next_rng_key():
    import jax

    if _rng_state["key"] is None:
        manual_seed(0)
    _rng_state["key"], sub = jax.random.split(_rng_state["key"])
    return sub


# ---------------------------------------------------------------------------
# compile data / stats
# ---------------------------------------------------------------------------

class CompileStats:
    def __init__(self):
        self.cache_hits = 0
        self.cache_misses = 0
        self.last_traces: list[TraceCtx] = []
        self.last_prologue_traces: list[TraceCtx] = []
        self.last_interpreted_ns = 0
        self.last_transform_ns = 0
        self.last_entry = None  # most recently compiled CacheEntry (for last_hlo)
        # observe subsystem: per-compile decision log (executor claims /
        # rejections, fusion accept/reject with cost-model inputs) and
        # per-pass walltimes (ms) — always collected, see thunder_tpu.observe
        self.last_decisions: list[dict] = []
        self.last_pass_times: dict[str, float] = {}
        # measured-time observatory: the last observe.profile.profile_window
        # result ({"profile": StepProfile, "ledger": [...], "summary": {...}})
        # — model-vs-measured residuals joined to last_decisions by region id
        self.last_profile = None
        self.fn_name = "fn"  # set by the owning ThunderTPUFunction
        # census knobs for this function's compiles (observe.census.ensure
        # reads them): the serving runner stashes its decode layer count +
        # launch budget here so the decode-launch-growth finding regenerates
        # on every census evaluation, not only at bind time
        self.census_context: dict = {}

    @property
    def last_census(self):
        """The executable census of the most recently compiled entry
        (``thunder_tpu.observe.census``): HLO collective instructions with
        ring-model recv bytes and async fractions (denominators included),
        kernel-launch / fusion-region counts, XLA cost/memory analysis, and
        the pessimization sentinel's findings. Lazy — the first access pays
        one memoized AOT compile of the entry (jax exposes no handle to the
        executable the run path built); never raises (census errors are
        counted and surfaced, not thrown). ``None`` before any compile."""
        from thunder_tpu.observe import census as _census

        return _census.ensure(self, fn_name=self.fn_name)

    @property
    def last_interpreted_ms(self) -> float:
        return self.last_interpreted_ns / 1e6

    @property
    def last_transform_ms(self) -> float:
        return self.last_transform_ns / 1e6

    def summary(self) -> str:
        """Human-readable compile-time breakdown of the last compilation.
        Pass times render hierarchically (sub-passes key as ``parent/child``
        in ``last_pass_times``): siblings at one level sum to their parent,
        so no line double-counts another."""
        lines = [
            f"cache: {self.cache_misses} miss(es), {self.cache_hits} hit(s)",
            f"tracing (interpretation): {self.last_interpreted_ms:.2f} ms",
            f"transforms + dispatch: {self.last_transform_ms:.2f} ms",
        ]

        def render(prefix: str, depth: int):
            level = {k: v for k, v in self.last_pass_times.items()
                     if k.startswith(prefix) and "/" not in k[len(prefix):]}
            for name, ms in sorted(level.items(), key=lambda kv: -kv[1]):
                lines.append(f"  {'  ' * depth}{name[len(prefix):]}: {ms:.2f} ms")
                render(name + "/", depth + 1)

        render("", 0)
        if self.last_decisions:
            lines.append(f"decisions recorded: {len(self.last_decisions)} "
                         f"(see thunder_tpu.observe.explain)")
        return "\n".join(lines)

    def __repr__(self):
        return f"<CompileStats\n{self.summary()}\n>"


class CacheEntry:
    __slots__ = ("computation_fn", "run_fn", "tensor_indices", "uses_rng", "traces",
                 "prologue_trace", "prologue_fn", "out_spec", "arg_of_flat",
                 "input_avals", "jit_obj", "is_sharded", "_examine_compiled",
                 "_examine_lowered", "census", "n_dev")

    def __init__(self, computation_fn, tensor_indices, uses_rng, traces, prologue_trace,
                 prologue_fn, out_spec):
        self.computation_fn = computation_fn
        self.run_fn = computation_fn  # may be wrapped (jit / shard_map) in finalize
        self.tensor_indices = tensor_indices
        self.uses_rng = uses_rng
        self.traces = traces
        self.prologue_trace = prologue_trace
        self.prologue_fn = prologue_fn
        self.out_spec = out_spec
        self.arg_of_flat: dict[int, int] | None = None  # flat index -> positional argnum
        self.input_avals = None  # jax.ShapeDtypeStructs of run_fn's inputs
        self.jit_obj = None      # the jax.jit object (lowerable), when one exists
        self.is_sharded = False  # True for shard_map-wrapped (distributed) entries
        # introspection caches: the ONE AOT lowering/executable every
        # consumer (census, last_hlo, examine.xla_memory/xla_cost) shares —
        # the no-recompile discipline lives in observe.census
        self._examine_lowered = None
        self._examine_compiled = None
        self.census = None       # memoized executable census (observe.census)
        self.n_dev = 1           # mesh size (distributed finalize overrides)


def _is_arraylike(x) -> bool:
    import jax

    return isinstance(x, (jax.Array, _np.ndarray)) or (
        hasattr(x, "shape") and hasattr(x, "dtype") and not isinstance(x, Proxy)
    )


def _leaf_key(leaf):
    if _is_arraylike(leaf):
        # the dtype OBJECT (numpy dtype / jax dtype) hashes and compares by
        # value; str(dtype) cost ~2x the whole key build on the decode hot
        # path (measured r4: 0.5 ms/call probing a 35-leaf tree)
        return ("T", tuple(leaf.shape), leaf.dtype)
    if isinstance(leaf, bool):
        return ("B", leaf)
    if isinstance(leaf, Number):
        return ("N", type(leaf).__name__, leaf)
    if isinstance(leaf, str):
        return ("S", leaf)
    if leaf is None:
        return ("Z",)
    return ("O", type(leaf).__name__)


_KEY_PARTS = ("treedef", "frontend specialization", "quarantine epoch",
              "bisection suppression", "fault plan")


def _miss_reason(key, cache: dict) -> str:
    """Why a call missed the guard cache: which component of its key differs
    from the NEAREST entry the cache holds (the one that agrees with it in
    most components). Runs on a miss only, with a compile to follow."""
    if key is None:
        return "no caching"
    if not cache:
        return "first"

    def differences(held):
        diffs = [part for part, a, b in zip(_KEY_PARTS, key, held) if a != b]
        mine, theirs = key[-1], held[-1]
        if "treedef" in diffs or len(mine) != len(theirs):
            return diffs or ["treedef"]     # leaves of two trees don't pair
        for i, (a, b) in enumerate(zip(mine, theirs)):
            if a == b:
                continue
            if a[0] == b[0] == "T":
                what = f"shape {b[1]} -> {a[1]}" if a[1] != b[1] \
                    else f"dtype {b[2]} -> {a[2]}"
            else:
                what = "value" if a[0] == b[0] else f"kind {b[0]} -> {a[0]}"
            diffs.append(f"leaf {i} {what}")
        return diffs

    nearest = min((differences(held) for held in cache), key=len)
    return nearest[0] if len(nearest) == 1 \
        else f"{nearest[0]} (+{len(nearest) - 1} more)"


class ThunderTPUFunction:
    """The compiled-function wrapper returned by ``thunder_tpu.jit``."""

    def __init__(self, fn: Callable, *, executors=None, cache: str = "constant values",
                 transforms: Sequence[Transform] = (), enable_cse: bool = True,
                 insert_dels: bool = True, sharp_edges: str = "allow",
                 fn_name: str | None = None, seq_buckets: Sequence[int] | None = None,
                 seq_argnums: Sequence[int] | None = None, seq_dim: int = -1,
                 **compile_options):
        from thunder_tpu.executors import resolve_executors

        check(cache in _CACHE_OPTIONS, lambda: f"unknown cache option {cache!r}")
        check(sharp_edges in ("allow", "warn", "error"),
              lambda: f"unknown sharp_edges option {sharp_edges!r}")
        self.sharp_edges = sharp_edges
        self.fn = fn
        self.executors = resolve_executors(executors)
        self.cache_option = cache
        self.transforms = list(transforms)
        self.enable_cse = enable_cse
        self.insert_dels = insert_dels
        self.fn_name = fn_name or getattr(fn, "__name__", "fn")
        self._span_args = {"fn": self.fn_name}  # shared by every jit_* span
        self._cache: dict = {}
        self._stats = CompileStats()
        self._stats.fn_name = self.fn_name
        # Frontends may stash call-varying specialization context here (the
        # torch dialect's input-alias pattern: which args share a storage —
        # reference guards aliases via the prologue, thunder/__init__.py:
        # 357-375). It joins the cache key, so a call with aliased views
        # never hits an entry compiled for distinct tensors (and vice versa:
        # distinct tensors never re-trace an aliased specialization).
        # THREAD-LOCAL: each calling thread carries its own value, so
        # concurrent calls to one jitted fn never serialize or clobber each
        # other's specialization (advisor r4: the old shared field forced
        # callers to hold a lock across the whole execution).
        import threading as _threading

        self._call_tls = _threading.local()
        self.compile_options = dict(compile_options)
        self._compile_ctx = None  # last CompileContext (option usage report)
        self.__name__ = f"thunder_tpu.jit({self.fn_name})"
        # shape-polymorphic caching via bucketing (reference SYMBOLIC_VALUES
        # over shapes, thunder/core/proxies.py:624-1136 + options.py:95 —
        # on TPU the idiomatic answer is a fixed ladder of compiled lengths)
        self.seq_buckets = None
        self.seq_argnums = tuple(seq_argnums) if seq_argnums is not None else None
        self.seq_dim = seq_dim
        self._accepts_seq_len = False
        if seq_buckets is not None:
            from thunder_tpu.data import LengthBucketer

            self.seq_buckets = LengthBucketer(seq_buckets)
            import inspect

            # explicit `seq_len` parameter only — a VAR_KEYWORD catch-all
            # would misfire on forwarding wrappers (e.g. the torch-dialect
            # traced(*args, **kwargs) shim) and crash fns that don't take it
            try:
                self._accepts_seq_len = "seq_len" in inspect.signature(fn).parameters
            except (TypeError, ValueError):
                self._accepts_seq_len = False

    def _leaf_cache_key(self, leaf):
        # symbolic values: non-bool numbers become runtime inputs guarded by
        # type only (reference SYMBOLIC_VALUES, thunder/core/options.py:95) —
        # tensor SHAPES stay static: XLA compiles static programs, so shape
        # polymorphism on TPU is handled by data-pipeline bucketing
        # (thunder_tpu.data.LengthBucketer: pad to a small fixed ladder of
        # lengths, bounding compilations to the bucket count)
        if (self.cache_option == "symbolic values" and isinstance(leaf, Number)
                and not isinstance(leaf, bool)):
            return ("N", type(leaf).__name__)
        return _leaf_key(leaf)

    # -- bucketing ----------------------------------------------------------
    def _pad_to_bucket(self, args, kwargs):
        """Pad designated tensor leaves along ``seq_dim`` to the bucket ladder
        so distinct sequence lengths hit at most ``len(buckets)`` compiled
        programs. The TRUE length is passed to ``fn`` as a 0-d int32 array
        kwarg ``seq_len`` (when the signature accepts it) — a runtime tensor
        input, so masking sees the real length while the compiled shape stays
        the bucket's. Outputs keep the PADDED length: callers index them with
        the true length (or a mask), not ``[:, -1]``."""
        import jax.numpy as jnp
        import jax.tree_util as _jtu

        flat_paths, treedef = _jtu.tree_flatten_with_path((args, kwargs))
        flat = [leaf for _, leaf in flat_paths]
        designated = []
        for i, (path, leaf) in enumerate(flat_paths):
            if not _is_arraylike(leaf) or not getattr(leaf, "ndim", 0):
                continue
            if self.seq_argnums is not None:
                # path[0] selects args(0)/kwargs(1); path[1] the positional idx
                if len(path) < 2 or getattr(path[0], "idx", None) != 0:
                    continue
                if getattr(path[1], "idx", None) not in self.seq_argnums:
                    continue
            designated.append(i)
        check(designated, lambda: "seq_buckets is set but no tensor args were found")
        lengths = {int(flat[i].shape[self.seq_dim]) for i in designated}
        check(len(lengths) == 1, lambda: (
            f"seq_buckets: designated tensor args disagree on the sequence "
            f"dimension size ({sorted(lengths)}); pass seq_argnums to select "
            f"which positional args carry the sequence axis"))
        L = lengths.pop()
        Lb = self.seq_buckets.bucket_for(L)
        if Lb != L:
            new_flat = list(flat)
            for i in designated:
                leaf = flat[i]
                d = self.seq_dim % leaf.ndim
                widths = [(0, 0)] * leaf.ndim
                widths[d] = (0, Lb - L)
                new_flat[i] = jnp.pad(jnp.asarray(leaf), widths)
            args, kwargs = tree_unflatten(treedef, new_flat)
        if self._accepts_seq_len and "seq_len" not in kwargs:
            kwargs = dict(kwargs)
            kwargs["seq_len"] = _np.asarray(L, _np.int32)
        return args, kwargs

    # -- call ---------------------------------------------------------------
    def _entry_for(self, args, kwargs):
        """Single cache-lookup/compile path shared by __call__ and the
        compile-only entry point. Returns (entry, flat_inputs)."""
        # the guard (pad, flatten, a key per leaf, lookup) as a span of its
        # own on a hit; a miss leaves the compile spans in its place
        with _observe.span("jit_guard", "step", self._span_args,
                           record_pass_time=False, ring=False) as guard:
            if self.seq_buckets is not None:
                args, kwargs = self._pad_to_bucket(args, kwargs)
            flat, treedef = tree_flatten((args, kwargs))
            # the quarantine epoch joins the key (entries compiled before a
            # kernel was quarantined embed that kernel and must never hit
            # again), as does the context's bisection-suppression set (a
            # probe entry only serves calls under that same probe
            # configuration), and — only for plans with trace-time
            # numerics:kernel specs — the active FaultPlan's identity (that
            # corruption is baked into the executable, and must never serve
            # after the plan is cleared; grads/loss poison rides runtime
            # inputs, so ordinary plans and the production no-plan path add
            # nothing to the key)
            plan = _faults.active_plan()
            plan_key = id(plan) if plan is not None and plan.affects_compile() \
                else None
            key = (treedef, self._extra_cache_key, _quarantine.epoch(),
                   _quarantine.suppression_key(), plan_key,
                   tuple(self._leaf_cache_key(l) for l in flat)) \
                if self.cache_option != "no caching" else None
            entry = self._cache.get(key) if key is not None else None
            if entry is None:
                guard.cancel()
        if entry is None:
            self._stats.cache_misses += 1
            _observe.inc("cache.misses")
            _observe.event("cache_miss", fn=self.fn_name,
                           reason=_miss_reason(key, self._cache))
            entry = self._compile(flat, treedef, args, kwargs)
            if key is not None:
                self._cache[key] = entry
        else:
            self._stats.cache_hits += 1
            _observe.inc("cache.hits")
        return entry, flat

    def compile(self, *args, **kwargs) -> "CacheEntry":
        """Compile for these inputs WITHOUT executing (tooling entry point:
        ``examine`` and AOT-style inspection). Uses the same cache keying as
        ``__call__``, so a later call with the same shapes hits the entry."""
        entry, _ = self._entry_for(args, kwargs)
        return entry

    def __call__(self, *args, **kwargs):
        # one call as a span (registry on): the guard and the dispatch
        # (``step:<fn>``) nest under it, and it under the caller's span
        with _observe.span("jit_call", "step", self._span_args,
                           record_pass_time=False, ring=False):
            entry, flat = self._entry_for(args, kwargs)
            inps = [flat[i] for i in entry.tensor_indices]
            if entry.uses_rng:
                inps.append(_next_rng_key())
            return self._run_contained(entry.run_fn, inps, args, kwargs)

    def _run_contained(self, run_fn, inps, args, kwargs):
        """Run a compiled entry with the two containment paths: inside
        ``runtime.quarantine.containment()`` (the supervisors' opt-in) a
        claimed-kernel crash quarantines and recompiles — anywhere else it
        raises; a sentinel silent-fault escalation bisects. Shared by
        ``__call__`` and the ``bind()`` fast path so the dispatch can never
        drift between them."""
        try:
            return run_fn(*inps)
        except KernelExecutionError as err:
            if not _quarantine.containment_enabled():
                raise
            return self._quarantine_and_rerun(err, args, kwargs)
        except _sentinel.SilentNumericsFault as err:
            return self._bisect_and_rerun(err, args, kwargs)

    def _quarantine_and_rerun(self, err: KernelExecutionError, args, kwargs):
        """Graceful degradation: a claimed kernel died at compile or at
        runtime — quarantine that claim id, recompile the trace with the
        claim disabled (the op falls back to the XLA executor), and re-run.
        Loops in case a second claimed kernel fails on the recompiled
        program; a claim id seen twice means quarantining it didn't remove
        it from the program, so the error is real and propagates."""
        seen: set[str] = set()
        while True:
            if err.claim_id in seen:
                raise err
            seen.add(err.claim_id)
            _quarantine.get_quarantine().add(
                err.claim_id, reason=repr(err.__cause__ or err), phase=err.phase)
            _observe.inc("runtime.fallbacks")
            _observe.event("kernel_fallback", fn=self.fn_name, claim=err.claim_id,
                           phase=err.phase)
            # every cached entry may embed the quarantined kernel; the epoch
            # in the cache key already forces misses — drop the dead entries
            self._cache.clear()
            entry, flat = self._entry_for(args, kwargs)
            inps = [flat[i] for i in entry.tensor_indices]
            if entry.uses_rng:
                inps.append(_next_rng_key())
            try:
                return entry.run_fn(*inps)
            except KernelExecutionError as e2:
                err = e2
            except _sentinel.SilentNumericsFault as snf:
                # the crash was contained but another kernel is SILENTLY
                # corrupt: hand over to the bisection path (same symmetry as
                # __call__'s own dispatch between the two containments)
                return self._bisect_and_rerun(snf, args, kwargs)

    def _bisect_and_rerun(self, err, args, kwargs):
        """Silent-fault containment: the numerics sentinel saw repeated
        non-finite output at this trace point. Bisect the claimed custom
        kernels — recompile with candidate groups disabled
        (``runtime.quarantine.suppress``) and re-run on the same inputs —
        to attribute the corruption; the offender joins the PERSISTED
        quarantine (same path as crashing kernels) and the step re-runs on
        the XLA fallback. Unattributable corruption (still non-finite with
        every custom kernel disabled) re-raises as PersistentNonFinite for
        the supervisor's rewind/restart ladder."""
        guard = err.transform
        if guard is None:  # raised outside a guard wrapper: nothing to bisect
            raise err
        if not _sentinel.inputs_alive((args, kwargs)):
            # donate_argnums consumed the call's buffers in the failing
            # execution: probes cannot re-run these inputs. Escalate to the
            # supervisor ladder (rewind/restart from a checkpoint) instead
            # of crashing every probe on deleted arrays.
            raise _sentinel.PersistentNonFinite(
                f"persistent non-finite output of {self.fn_name}: the step's "
                f"inputs were donated (donate_argnums), so in-process "
                f"bisection cannot replay them — recover via the supervisor "
                f"(checkpoint restore + replay), or jit without donation to "
                f"enable bisection") from err
        sent = guard.sentinel
        seen: set[str] = set()
        # pin the RNG stream: every probe must run the SAME program on the
        # SAME inputs (probes differing only in the disabled set), and the
        # containment path must not advance the training stream — the final
        # re-run draws exactly the key a plain retry of this step would have
        rng_key0 = _rng_state["key"]
        while True:
            entry = err.entry if err.entry is not None else self._stats.last_entry
            exec_trc = entry.traces[-1] if entry is not None and entry.traces else None
            candidates = [] if exec_trc is None else \
                [c for c in _sentinel.claimed_kernel_ids(exec_trc) if c not in seen]
            if candidates:  # an empty set probes nothing: not a bisection run
                _observe.inc("runtime.bisections")
                _observe.event("bisection_started", fn=self.fn_name,
                               candidates=len(candidates))

            def probe(disabled):
                _rng_state["key"] = rng_key0
                with _quarantine.suppress(disabled):
                    self._cache.clear()
                    with sent.probing():
                        self(*args, **kwargs)
                return sent.last_verdict is not None and sent.last_verdict.healthy

            try:
                offenders = _sentinel.attribute_offenders(candidates, probe)
            finally:
                # a probe that raises (an active FaultPlan firing on a probe
                # recompile, an XLA error) must still unpin the RNG stream
                # and drop the probe-configuration entries
                self._cache.clear()
                _rng_state["key"] = rng_key0
            if not offenders:
                _observe.event("bisection_unattributed", fn=self.fn_name)
                raise _sentinel.PersistentNonFinite(
                    f"persistent non-finite output of {self.fn_name} could not "
                    f"be attributed to a claimed kernel "
                    f"({len(candidates)} candidates probed)") from err
            for offender in offenders:
                seen.add(offender)
                _quarantine.get_quarantine().add(
                    offender, phase="numerics",
                    reason=f"silent numerics fault attributed by bisection ({err})")
                _observe.inc("runtime.fallbacks")
                _observe.event("bisection_attributed", fn=self.fn_name,
                               claim=offender)
            sent.reset_episode()  # containment done: the re-run starts clean
            try:
                return self(*args, **kwargs)
            except _sentinel.SilentNumericsFault as e2:
                err = e2  # a second corrupt kernel: bisect the rest

    def bind(self, *args, **kwargs):
        """Compile for these inputs and return a ZERO-GUARD callable bound
        to that one cache entry — the serving fast path. A decode loop
        calling the jitted fn thousands of times per second pays the guard
        cache (flatten + per-leaf keys) on every call (~0.15 ms, measured
        r5 — ~4% of a 2-layer decode step); the bound callable skips it.
        The caller owns revalidation: invoking it with a different pytree
        structure, shapes, or dtypes than the binding inputs is undefined
        (reference analog: the reference hands back a compiled
        ``CompiledFunction`` the same way, thunder/__init__.py jit).

        Containment still applies: a claimed-kernel crash or a sentinel
        silent-fault escalation re-enters the driver's quarantine/bisection
        path with the call's own arguments — but the containment recompiles
        under a NEW cache entry, so after it fires the caller should
        re-``bind`` (the stale bound entry would re-contain every call)."""
        check(self.seq_buckets is None,
              "bind() does not compose with seq_buckets: the bound callable "
              "skips the guard path that pads inputs to the bucket. For "
              "ragged-length serving use thunder_tpu.serving.ServingEngine "
              "— its scheduler owns the bucketing (LengthBucketer prefill "
              "chunks) and binds a fixed-shape decode step. Otherwise call "
              "the jitted function directly, or bind a fn without buckets")
        entry, _ = self._entry_for(args, kwargs)
        tensor_indices = entry.tensor_indices
        uses_rng = entry.uses_rng
        run_fn = entry.run_fn

        def bound(*a, **k):
            fl, _ = tree_flatten((a, k))
            inps = [fl[i] for i in tensor_indices]
            if uses_rng:
                inps.append(_next_rng_key())
            return self._run_contained(run_fn, inps, a, k)

        bound.entry = entry
        return bound

    # -- compilation --------------------------------------------------------
    def _trace(self, flat, treedef) -> tuple[TraceCtx, list[int]]:
        trc = TraceCtx("computation")
        tensor_indices: list[int] = []
        with tracectx(trc):
            proxies = []
            symbolic_numbers = self.cache_option == "symbolic values"
            for i, leaf in enumerate(flat):
                if _is_arraylike(leaf):
                    p = self._make_input_proxy(i, leaf)
                    proxies.append(p)
                    tensor_indices.append(i)
                elif (symbolic_numbers and isinstance(leaf, Number)
                      and not isinstance(leaf, bool)):
                    p = NumberProxy(leaf)  # value is a runtime input, not baked
                    proxies.append(p)
                    tensor_indices.append(i)
                else:
                    proxies.append(leaf)  # constant-values caching: baked + guarded
            pargs, pkwargs = tree_unflatten(treedef, proxies)
            result = self.fn(*pargs, **pkwargs)
            prims.python_return(result)
        trc.args = [proxies[i] for i in tensor_indices]
        trc.output = result
        if getattr(trc, "rng_input_proxy", None) is not None:
            trc.args.append(trc.rng_input_proxy)
        # the full (proxy-for-every-leaf) input structure, for transforms
        # that need to map positional args to their proxies (the numerics
        # guard pairs state args with state outputs through this)
        trc.input_proxies = list(proxies)
        trc.input_treedef = treedef
        trc.set_provenance("Tracing (duck-typed interpretation)")
        return trc, tensor_indices

    def _build_prologue(self, flat, tensor_indices) -> TraceCtx:
        pro = TraceCtx("prologue")
        with tracectx(pro):
            pro_proxies = []
            returns = []
            for i, leaf in enumerate(flat):
                if _is_arraylike(leaf):
                    p = TensorProxy(f"arg{i}", shape=leaf.shape, dtype=dtypes.to_dtype(leaf.dtype))
                    prims.check_tensor_shape_and_metadata(p, tuple(p.shape), p.dtype, str(p.device))
                    returns.append(p)
                elif isinstance(leaf, Number):
                    p = NumberProxy(leaf, f"arg{i}")
                    if self.cache_option == "symbolic values" and not isinstance(leaf, bool):
                        prims.check_number_type(p, type(leaf).__name__)
                        returns.append(p)
                    else:
                        prims.check_number_type_and_value(p, leaf)
                elif isinstance(leaf, str):
                    p = StringProxy(leaf, f"arg{i}")
                    prims.check_string_value(p, leaf)
                else:
                    p = NumberProxy(0, f"arg{i}", python_type=type(leaf))
                    prims.check_literal_like(p, leaf)
                pro_proxies.append(p)
            prims.python_return(tuple(returns))
        pro.args = pro_proxies
        pro.output = tuple(returns)
        pro.set_provenance("Prologue (input guards)")
        return pro

    def _compile(self, flat, treedef, args, kwargs) -> CacheEntry:
        from thunder_tpu.core.compile_data import CompileContext, compile_context

        self._compile_ctx = CompileContext(self.compile_options)
        with compile_context(self._compile_ctx):
            return self._compile_inner(flat, treedef, args, kwargs)

    def _compile_inner(self, flat, treedef, args, kwargs) -> CacheEntry:
        from thunder_tpu.observe import decisions as _decisions

        _faults.maybe_fail("compile", site=self.fn_name)
        # collect locally, install into stats only on success: a failed
        # recompile must not leave explain()/summary() mixing this compile's
        # partial decisions/pass-times with the previous compile's traces
        pass_times: dict[str, float] = {}
        with _observe.collect_pass_times(pass_times), \
                _decisions.collect() as decision_log, \
                _observe.span("compile", args={"fn": self.fn_name},
                              record_pass_time=False):
            entry = self._compile_instrumented(flat, treedef, args, kwargs)
        self._stats.last_pass_times = pass_times
        self._stats.last_decisions = decision_log
        _observe.inc("compile.count")
        _observe.set_gauge("compile.interpreted_ms", self._stats.last_interpreted_ms)
        _observe.set_gauge("compile.transform_ms", self._stats.last_transform_ms)
        return entry

    def _compile_instrumented(self, flat, treedef, args, kwargs) -> CacheEntry:
        from thunder_tpu.executors.passes import del_last_used, transform_for_execution
        from thunder_tpu.observe import runtime as _obs_runtime

        t0 = time.perf_counter_ns()
        with _observe.span("trace"):
            trc, tensor_indices = self._trace(flat, treedef)
        self._stats.last_interpreted_ns = time.perf_counter_ns() - t0
        if trc.sharp_edges and self.sharp_edges != "allow":
            msg = "sharp edges detected during tracing (reference SHARP_EDGES_OPTIONS):\n  " \
                  + "\n  ".join(trc.sharp_edges)
            if self.sharp_edges == "error":
                raise RuntimeError(msg)
            import warnings

            warnings.warn(msg, stacklevel=3)
        traces = [trc]

        t1 = time.perf_counter_ns()
        with _observe.span("prologue"):
            prologue = self._build_prologue(flat, tensor_indices)
            for tr in self.transforms:
                _, trc, _ = tr.transform_traces_pre_prologue(prologue, trc, None)

        with _observe.span("dce+cse"):
            trc = dce(trc)
            traces.append(trc)
            if self.enable_cse:
                trc = cse(trc)
                trc = dce(trc)
                traces.append(trc)

        with _observe.span("transform_for_execution"):
            exec_trc = transform_for_execution(trc, self.executors)
        # the claim-level region-annotated trace (observe.profile replays it
        # per region on backends without a profiler) rides in entry.traces so
        # it survives the post-optimization transforms below, which rebuild
        # the execution trace and would drop the attribute
        region_trc = getattr(exec_trc, "_region_trace", None)
        if region_trc is not None:
            traces.append(region_trc)
        for tr in self.transforms:
            exec_trc = tr.transform_trace_post_optimization(exec_trc)
        if self.insert_dels:
            with _observe.span("del_last_used"):
                exec_trc = del_last_used(exec_trc)
        traces.append(exec_trc)
        self._stats.last_transform_ns = time.perf_counter_ns() - t1

        from thunder_tpu.core.compile_data import get_compile_option

        execution_file = get_compile_option(
            "execution_file",
            "dump the final generated program to this file — or, if the file "
            "already exists (user-edited), execute its contents instead "
            "(reference set_execution_callback_file: hand-patch generated code)",
            None)
        with _observe.span("codegen"):
            computation_fn = exec_trc.python_callable(execution_file=execution_file)
            prologue_fn = prologue.python_callable()
        # sanity-run the prologue guards once on the compiling inputs
        prologue_fn(*flat)

        uses_rng = getattr(traces[0], "rng_input_proxy", None) is not None
        entry = CacheEntry(computation_fn, tensor_indices, uses_rng, traces, prologue,
                           prologue_fn, None)
        # map flat leaf positions to top-level positional args (donation support)
        import jax.tree_util as _jtu

        flat_with_paths, _ = _jtu.tree_flatten_with_path((args, kwargs))
        entry.arg_of_flat = {}
        for i, (path, _leaf) in enumerate(flat_with_paths):
            if len(path) >= 2 and getattr(path[0], "idx", None) == 0:
                entry.arg_of_flat[i] = getattr(path[1], "idx", None)
        import jax as _jax

        def _leaf_aval(leaf):
            # GSPMD inputs: a leaf committed to a NamedSharding over >1 device
            # must carry that sharding into the aval, or census lowering
            # (`jit_obj.lower(*input_avals)`) would compile an unsharded
            # program and miss every collective the real step executes
            aval = _jax.ShapeDtypeStruct(
                tuple(leaf.shape), dtypes.to_dtype(leaf.dtype).jax)
            sh = getattr(leaf, "sharding", None)
            if (isinstance(sh, _jax.sharding.NamedSharding)
                    and sh.mesh.size > 1 and getattr(leaf, "_committed", True)):
                aval = _jax.ShapeDtypeStruct(aval.shape, aval.dtype, sharding=sh)
            return aval

        if all(hasattr(flat[i], "shape") for i in tensor_indices):
            entry.input_avals = [_leaf_aval(flat[i]) for i in tensor_indices]
            if uses_rng:
                entry.input_avals.append(_jax.ShapeDtypeStruct((2,), _np.uint32))
            # transforms may thread extra runtime inputs into the trace
            # signature (the numerics guard's poison scalars)
            for tr in self.transforms:
                extra = getattr(tr, "extra_input_avals", None)
                if extra is not None:
                    entry.input_avals.extend(extra())
        # else (symbolic-values caching: number inputs): no avals — last_hlo
        # reports accordingly
        with _observe.span("finalize"):
            self._finalize_entry(entry, flat, exec_trc)
        # runtime step metrics: one disabled-check per call when observe is
        # off, walltime/span/memory-estimate recording when on
        entry.run_fn = _obs_runtime.instrument_entry(entry, self.fn_name)
        # transform runtime wrappers (outermost): the numerics guard feeds
        # its poison inputs and peels the health word here. REVERSED so the
        # first transform's wrapper ends up outermost — wrappers append
        # their extra inputs outermost-first, which must match the order
        # the transforms appended their proxies to the trace signature
        # (and extra_input_avals / the distributed in_specs extension)
        for tr in reversed(self.transforms):
            hook = getattr(tr, "wrap_run_fn", None)
            if hook is not None:
                entry.run_fn = hook(self, entry, entry.run_fn)
        self._stats.last_traces = traces
        self._stats.last_prologue_traces = [prologue]
        self._stats.last_entry = entry
        return entry

    # -- subclass hooks (distributed wrappers override these) ---------------
    def _make_input_proxy(self, i: int, leaf) -> TensorProxy:
        return TensorProxy(shape=leaf.shape, dtype=dtypes.to_dtype(leaf.dtype))

    def _finalize_entry(self, entry: CacheEntry, flat, exec_trc) -> None:
        """Whole-program compilation: the generated trace callable is pure JAX
        ops, so one ``jax.jit`` over it gives XLA whole-program fusion and a
        persistent executable — the TPU answer to the reference's CUDA-graphs
        executor (``thunder/executors/cudagraphex.py:133``: capture once,
        replay with stable buffers). Region fusions inline into the outer jit.

        ``donate_argnums=(i, ...)`` (a jit compile option, matching jax.jit's
        parameter): tensor leaves under those positional args are donated so
        XLA reuses their buffers for outputs — in-place optimizer updates.
        """
        if self.cache_option == "symbolic values":
            # number inputs are Python scalars guarded by type; an outer jit
            # would re-trace per value, defeating symbolic caching — keep the
            # per-region execution path
            return
        from thunder_tpu.core.compile_data import get_compile_option

        if not get_compile_option(
                "whole_program_jit",
                "compile the entire execution trace as one XLA program "
                "(persistent executable; CUDA-graphs analog)", True):
            return
        # host-sync ops (item etc.) need concrete values — they cannot live
        # under an outer jit; keep the per-region path (regions stay compiled,
        # sync ops run eagerly between them)
        from thunder_tpu.core.prims import OpTags as _OpTags

        for b in exec_trc.bound_symbols:
            if _OpTags.DEVICE_SYNC_OP in b.sym.tags:
                return
        import jax

        donate_args = tuple(get_compile_option(
            "donate_argnums",
            "positional args whose tensor leaves are donated to XLA "
            "(buffer reuse for outputs; pass params/optimizer-state argnums)",
            ()) or ())
        donate = ()
        if donate_args and entry.arg_of_flat is not None:
            donate = tuple(
                j for j, fi in enumerate(entry.tensor_indices)
                if entry.arg_of_flat.get(fi) in donate_args)
        # GSPMD: when any input is committed to a multi-device mesh the jit
        # compiles one SPMD program over it — record the device count so the
        # census ring model and budget gates divide by the right n
        gspmd_mesh = None
        for leaf in flat:
            sh = getattr(leaf, "sharding", None)
            if (isinstance(sh, jax.sharding.NamedSharding)
                    and sh.mesh.size > getattr(entry, "n_dev", 1)):
                entry.n_dev = sh.mesh.size
                gspmd_mesh = sh.mesh
        program = entry.computation_fn
        if gspmd_mesh is not None:
            # Mosaic kernels cannot be auto-partitioned: claimed Pallas
            # impls must know, WHENEVER this program is traced (first run,
            # census lowering), which mesh it compiles over, so they wrap
            # themselves in a shard_map with their partitioning plan
            from thunder_tpu.executors.pallasex import gspmd_mesh as _scope

            def program(*inps, _fn=entry.computation_fn, _mesh=gspmd_mesh):
                with _scope(_mesh):
                    return _fn(*inps)

            # same jit name -> same HLO module name -> same persistent-cache
            # key as the unwrapped program
            program.__name__ = entry.computation_fn.__name__

        entry.run_fn = jax.jit(program, donate_argnums=donate)
        entry.jit_obj = entry.run_fn

    @property
    def _extra_cache_key(self):
        return getattr(self._call_tls, "extra_cache_key", None)

    @_extra_cache_key.setter
    def _extra_cache_key(self, value):
        self._call_tls.extra_cache_key = value

    # -- introspection ------------------------------------------------------
    @property
    def cache_hits(self):
        return self._stats.cache_hits

    @property
    def cache_misses(self):
        return self._stats.cache_misses


def jit(fn: Callable | None = None, *, executors=None, cache: str = "constant values",
        transforms: Sequence[Transform] = (), enable_cse: bool = True,
        insert_dels: bool = True, sharp_edges: str = "allow",
        seq_buckets: Sequence[int] | None = None,
        seq_argnums: Sequence[int] | None = None, seq_dim: int = -1,
        **compile_options) -> ThunderTPUFunction:
    """Compile ``fn``: trace → transform → dispatch to executors.

    ``seq_buckets=(256, 512, ...)`` enables shape-polymorphic caching by
    bucketing: on each call, tensor args (all of them, or those selected by
    ``seq_argnums``) are zero-padded along ``seq_dim`` to the next ladder
    length, bounding compilations to the ladder size; the true length is
    passed as a 0-d ``seq_len`` tensor when ``fn`` accepts it, so masking
    stays exact (the TPU answer to the reference's symbolic-shape caching,
    ``thunder/core/proxies.py:624-1136``, ``thunder/core/options.py:95``).
    Outputs keep the PADDED length — index them with the true length or a
    mask (``logits[:, -1]`` would read a pad position).

    Free-form ``**compile_options`` are queried lazily by passes/executors via
    ``thunder_tpu.core.compile_data.get_compile_option``; see
    ``last_compile_options`` for the used/unused report.

    Reference: ``thunder.jit`` (``thunder/__init__.py:262``).
    """
    shape_opts = dict(seq_buckets=seq_buckets, seq_argnums=seq_argnums, seq_dim=seq_dim)
    if fn is None:
        def deco(f):
            return jit(f, executors=executors, cache=cache, transforms=transforms,
                       enable_cse=enable_cse, insert_dels=insert_dels,
                       sharp_edges=sharp_edges, **shape_opts, **compile_options)

        return deco
    import sys

    _torch = sys.modules.get("torch")
    if _torch is not None and isinstance(fn, _torch.nn.Module):
        from thunder_tpu.torch import jit as torch_jit

        return torch_jit(fn, executors=executors, cache=cache, transforms=transforms,
                         enable_cse=enable_cse, insert_dels=insert_dels,
                         sharp_edges=sharp_edges, **shape_opts, **compile_options)
    return ThunderTPUFunction(fn, executors=executors, cache=cache, transforms=transforms,
                              enable_cse=enable_cse, insert_dels=insert_dels,
                              sharp_edges=sharp_edges, **shape_opts, **compile_options)


# ---------------------------------------------------------------------------
# autograd entry points
# ---------------------------------------------------------------------------

def value_and_grad(fn: Callable, argnums=0, has_aux: bool = False) -> Callable:
    """Trace-level VJP of ``fn``; usable inside a jitted function (inlines
    forward+backward into the enclosing trace)."""
    return inline_value_and_grad(fn, argnums=argnums, has_aux=has_aux)


def grad(fn: Callable, argnums=0) -> Callable:
    vag = inline_value_and_grad(fn, argnums=argnums)

    def grad_fn(*args, **kwargs):
        _, g = vag(*args, **kwargs)
        return g

    return grad_fn


def jvp(fn: Callable) -> Callable:
    """Forward-mode derivative: jvp(fn)(primals, tangents) -> (out, out_tangent).
    Usable inside a jitted function (reference ``transforms.py:2175``)."""

    def jvp_fn(primals, tangents):
        return jvp_call(fn, tuple(primals), tuple(tangents))

    return jvp_fn


def _vmap_impl(fn: Callable, in_axes=0) -> Callable:
    """Trace-level vmap (per-prim batching rules, composable with grad and
    executor claiming — reference ``thunder/core/transforms.py:1902``), with
    automatic fallback to the opaque jax.vmap lowering for ops without rules."""

    def wrapper(*args):
        from thunder_tpu.core.batching import NoBatchRule, inline_vmap
        from thunder_tpu.core.trace import get_tracectx

        trc = get_tracectx()
        mark = len(trc.bound_symbols) if trc is not None else 0
        try:
            return inline_vmap(fn, in_axes)(*args)
        except NoBatchRule:
            if trc is not None:  # roll back partially-emitted batched ops
                del trc.bound_symbols[mark:]
            return vmap_call(fn, in_axes=in_axes)(*args)

    return wrapper


def vmap(fn: Callable, in_axes=0) -> Callable:
    """Batching transform (reference ``transforms.py:1902``): trace-level
    per-prim batching rules — the output is ordinary trace IR, so it composes
    with ``tt.grad`` and executor claiming (a vmapped SDPA is still claimed
    by Pallas). Ops without a rule fall back per-call to the opaque jax.vmap
    lowering."""
    return _vmap_impl(fn, in_axes=in_axes)


# ---------------------------------------------------------------------------
# introspection (reference thunder/__init__.py:859-944)
# ---------------------------------------------------------------------------

def _as_tfn(x) -> ThunderTPUFunction:
    check(isinstance(x, ThunderTPUFunction), "expected a thunder_tpu.jit-compiled function")
    return x


def last_traces(jfn) -> list[TraceCtx]:
    return _as_tfn(jfn)._stats.last_traces


def last_execution_trace(jfn) -> TraceCtx:
    return _as_tfn(jfn)._stats.last_traces[-1]


def last_prologue_traces(jfn) -> list[TraceCtx]:
    return _as_tfn(jfn)._stats.last_prologue_traces


def cache_hits(jfn) -> int:
    return _as_tfn(jfn)._stats.cache_hits


def cache_misses(jfn) -> int:
    return _as_tfn(jfn)._stats.cache_misses


def compile_stats(jfn) -> CompileStats:
    return _as_tfn(jfn)._stats


def last_hlo(jfn, *, optimized: bool = False) -> str:
    """StableHLO (or XLA-optimized HLO with ``optimized=True``) of the most
    recently compiled entry — the per-stage dump SURVEY §7 calls out as the
    multi-host debugging essential (the trace prints Python; this is what XLA
    actually receives/produces).

    Both stages are memoized per entry through ``observe.census``'s shared
    accessors: ``optimized=True`` used to pay a FULL second XLA compile via
    ``lowered.compile()`` on every call — now the first caller (here, the
    census, or ``examine.xla_memory/xla_cost``) builds the one AOT
    executable and everyone after reuses it."""
    from thunder_tpu.observe import census as _census

    entry = _as_tfn(jfn)._stats.last_entry
    check(entry is not None, "no compilation has run yet")
    check(entry.input_avals is not None,
          "entry has no recorded input shapes (symbolic-values caching)")
    check(entry.jit_obj is not None,
          "entry is not whole-program-jitted (device-sync ops in the trace or "
          "whole_program_jit=False); no HLO available")
    if optimized:
        return _census.compiled_for_entry(entry).as_text()
    return _census.lowered_for_entry(entry).as_text()


def hlo_census(jfn) -> dict | None:
    """The per-compile executable census of ``jfn``'s most recent entry —
    ``CompileStats.last_census`` as a function (see
    ``thunder_tpu.observe.census`` for the dict shape and the
    pessimization-sentinel findings it carries)."""
    return _as_tfn(jfn)._stats.last_census


def last_jaxpr(jfn):
    """Closed jaxpr of the most recently compiled entry's computation.
    Single-program entries only — a distributed entry's computation runs
    per-shard inside shard_map (its collectives are unbound outside it);
    use ``last_hlo`` there."""
    import jax

    entry = _as_tfn(jfn)._stats.last_entry
    check(entry is not None, "no compilation has run yet")
    check(entry.input_avals is not None,
          "entry has no recorded input shapes (symbolic-values caching)")
    check(not getattr(entry, "is_sharded", False),
          "distributed entries run per-shard inside shard_map — the jaxpr of "
          "the local computation is not well-formed standalone; use last_hlo")
    return jax.make_jaxpr(entry.computation_fn)(*entry.input_avals)


def last_compile_options(jfn) -> str:
    """Report which compile options the last compilation queried (with their
    self-registered descriptions) and which passed options were never used
    (reference ``thunder/__init__.py:980-1015``)."""
    from thunder_tpu.core.compile_data import used_and_unused_options

    ctx = _as_tfn(jfn)._compile_ctx
    if ctx is None:
        return "no compilation has run yet"
    queried, unused = used_and_unused_options(ctx)
    lines = ["queried compile options:"]
    for name, desc in sorted(queried.items()):
        mark = "set" if name in ctx.options else "default"
        lines.append(f"  {name} [{mark}]: {desc}")
    if unused:
        lines.append("passed but never queried (possibly misspelled):")
        for name in sorted(unused):
            lines.append(f"  {name}")
    return "\n".join(lines)


# re-exports
from thunder_tpu import ops  # noqa: E402,F401
from thunder_tpu.ops import autocast  # noqa: E402,F401
from thunder_tpu.executors import (  # noqa: E402,F401
    get_all_executors,
    get_default_executors,
    get_executor,
)
from thunder_tpu import serving  # noqa: E402,F401  (thunder_tpu.serving.*)
