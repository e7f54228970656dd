"""Kernel quarantine: remember which claimed kernels are broken, compile
around them.

When a claimed custom kernel (a Pallas claim) fails at compile or at
runtime, the dispatch layer calls :func:`get_quarantine().add(claim_id)` and
recompiles; the claim pass (``executors/passes.py``) consults
:func:`quarantine_reason` before offering a bound symbol to an executor, so
the quarantined claim is rejected with a ``"quarantined: ..."`` decision
record (visible in ``observe.explain()``) and the op falls through to the
XLA executor's lowering — graceful degradation instead of a dead job.

Containment is OPT-IN (:func:`containment`): by default a claimed kernel
that raises while being traced, compiled or run is an ERROR — a bring-up, a
benchmark or a test that asserts a kernel is present must see the
exception, not a program that quietly served through the XLA decomposition.
The supervisors (``serving.EngineSupervisor``, ``elastic.ElasticTrainer``)
run their steps inside ``containment()``; measurement scripts finish with
:func:`assert_clean`.

Persistence: :func:`configure` points the quarantine at a directory (an
explicit call, or ``THUNDER_TPU_QUARANTINE_DIR`` from the environment); the
set is written as JSON there, so a restarted process skips the known-bad
kernel *before* paying a doomed compile. It never rides in the compile-cache
directory by default: a set that changes WHAT is compiled must not travel
with a cache a driver reuses across commits.

Every mutation bumps a process-wide *epoch* that joins the dispatch cache
key, so entries compiled before a quarantine event can never serve after it.
"""

from __future__ import annotations

import json
import os
import threading
import time
from contextlib import contextmanager
from contextvars import ContextVar

from thunder_tpu.observe import registry as _observe

_FILENAME = "kernel_quarantine.json"

_epoch = 0
_epoch_lock = threading.Lock()


def _bump_epoch() -> None:
    global _epoch
    with _epoch_lock:
        _epoch += 1


def epoch() -> int:
    """Monotonic counter of quarantine mutations; part of the dispatch
    cache key (a stale entry embedding a quarantined kernel never hits)."""
    return _epoch


class KernelQuarantine:
    """The quarantine set: claim id -> {reason, phase, time, count}."""

    def __init__(self, path: str | None = None):
        self._lock = threading.Lock()
        self._kernels: dict[str, dict] = {}
        self._path: str | None = None
        if path is not None:
            self.attach(path)

    # -- persistence --------------------------------------------------------
    def attach(self, path: str) -> None:
        """Bind to ``path`` (a JSON file): merge whatever a previous process
        quarantined there, then persist the union."""
        path = os.path.abspath(path)
        with self._lock:
            self._path = path
            disk = self._load(path)
            for k, rec in disk.items():
                self._kernels.setdefault(k, rec)
            self._persist()
        _bump_epoch()
        _observe.set_gauge("runtime.quarantined_kernels", len(self._kernels))

    @staticmethod
    def _load(path: str) -> dict:
        try:
            with open(path) as f:
                data = json.load(f)
            kernels = data.get("kernels", {})
            return kernels if isinstance(kernels, dict) else {}
        except Exception:
            return {}  # missing or torn file: start empty, rewrite on add

    def _persist(self) -> None:
        if self._path is None:
            return
        tmp = self._path + ".tmp"
        os.makedirs(os.path.dirname(self._path) or ".", exist_ok=True)
        with open(tmp, "w") as f:
            json.dump({"version": 1, "kernels": self._kernels}, f, indent=2)
        os.replace(tmp, self._path)

    # -- mutation -----------------------------------------------------------
    def add(self, claim_id: str, *, reason: str = "", phase: str = "runtime") -> None:
        with self._lock:
            rec = self._kernels.get(claim_id)
            if rec is None:
                self._kernels[claim_id] = {"reason": reason, "phase": phase,
                                           "time": time.time(), "count": 1}
            else:
                rec["count"] = rec.get("count", 0) + 1
                rec["reason"] = reason or rec.get("reason", "")
            self._persist()
            n = len(self._kernels)
        _bump_epoch()
        _observe.set_gauge("runtime.quarantined_kernels", n)
        _observe.event("kernel_quarantined", claim=claim_id, reason=reason,
                       phase=phase)

    def remove(self, claim_id: str) -> None:
        with self._lock:
            self._kernels.pop(claim_id, None)
            self._persist()
            n = len(self._kernels)
        _bump_epoch()
        _observe.set_gauge("runtime.quarantined_kernels", n)

    def clear(self) -> None:
        with self._lock:
            self._kernels.clear()
            self._persist()
        _bump_epoch()
        _observe.set_gauge("runtime.quarantined_kernels", 0)

    # -- queries ------------------------------------------------------------
    def reason(self, claim_id: str) -> str | None:
        rec = self._kernels.get(claim_id)
        if rec is None:
            return None
        return rec.get("reason") or f"quarantined at {rec.get('phase', '?')} time"

    def ids(self) -> tuple[str, ...]:
        return tuple(self._kernels)

    def __contains__(self, claim_id: str) -> bool:
        return claim_id in self._kernels

    def __len__(self) -> int:
        return len(self._kernels)

    @property
    def path(self) -> str | None:
        return self._path


# ---------------------------------------------------------------------------
# the process-wide quarantine
# ---------------------------------------------------------------------------

_active = KernelQuarantine()


def get_quarantine() -> KernelQuarantine:
    return _active


def configure(directory: str) -> KernelQuarantine:
    """Persist the quarantine set under ``directory``: loads claim ids a
    previous process recorded there."""
    _active.attach(os.path.join(str(directory), _FILENAME))
    return _active


def reset(path: str | None = None) -> KernelQuarantine:
    """Replace the process quarantine with a fresh instance (test harness:
    simulates a process restart; pass ``path`` to re-read a persisted set)."""
    global _active
    _active = KernelQuarantine(path)
    _bump_epoch()
    _observe.set_gauge("runtime.quarantined_kernels", len(_active))
    return _active


def is_quarantined(claim_id: str) -> bool:
    return claim_id in _active


def assert_clean() -> None:
    """Raise unless no kernel is quarantined and no fallback ran — the
    last line of a bring-up or a measurement: whatever they timed or
    checked was the program the planner chose, not a degraded one."""
    fallbacks = _observe.get_registry().counters.get("runtime.fallbacks", 0)
    if len(_active) or fallbacks:
        raise RuntimeError(
            f"kernel fallback on a measured path: runtime.fallbacks="
            f"{fallbacks}, quarantined="
            f"{ {k: _active.reason(k) for k in _active.ids()} }")


# containment (quarantine -> recompile on the XLA decomposition -> re-run) is
# recovery for SUPERVISED production; everywhere else a failing kernel is an
# error. A ContextVar like the suppression set below: the opt-in is visible
# only to the supervisor's own call chain.
_containment: ContextVar[bool] = ContextVar("kernel_fault_containment",
                                            default=False)


def containment_enabled() -> bool:
    return _containment.get()


@contextmanager
def containment():
    """Within this block a claimed kernel that fails is quarantined and the
    step re-runs on the XLA decomposition instead of raising."""
    tok = _containment.set(True)
    try:
        yield
    finally:
        _containment.reset(tok)


# temporary (non-persisted) claim disables: the numerics bisection recompiles
# with candidate kernel groups disabled to attribute a silent fault — these
# suppressions gate the claim pass exactly like a quarantine entry but never
# touch the persisted set. A ContextVar (not a module global): suppression
# is visible only to the bisection's own call chain — a concurrent compile
# on another thread never sees an unrelated probe's disables, and two
# concurrent bisections cannot clobber each other's suppression sets. The
# stored dict is treated as immutable (each suppress() installs a fresh
# copy). Cache correctness comes from :func:`suppression_key` joining the
# dispatch cache key — NOT from bumping the global epoch, which would
# permanently invalidate every other jitted function's cached entries on
# each probe enter/exit.
# the ContextVar holds (reasons_dict, precomputed_frozenset) so the hot
# dispatch path reads the cache-key component without allocating
_EMPTY_SUPPRESSION: tuple = ({}, frozenset())
_suppressed: ContextVar[tuple] = ContextVar("quarantine_suppressed",
                                            default=_EMPTY_SUPPRESSION)


def suppression_key() -> frozenset:
    """The context's active suppression set — part of the dispatch cache key
    (an entry compiled under one probe configuration only serves calls made
    under that same configuration). Precomputed at suppress() time: this is
    on the per-call dispatch path."""
    return _suppressed.get()[1]


@contextmanager
def suppress(claim_ids, reason: str = "bisection probe"):
    """Temporarily treat ``claim_ids`` as quarantined (scoped to this context,
    never persisted). Nests: inner suppressions stack on top of outer ones."""
    merged = dict(_suppressed.get()[0])
    for c in claim_ids:
        merged[c] = reason
    tok = _suppressed.set((merged, frozenset(merged)))
    try:
        yield
    finally:
        _suppressed.reset(tok)


def quarantine_reason(claim_id: str) -> str | None:
    r = _suppressed.get()[0].get(claim_id)
    if r is not None:
        return r
    return _active.reason(claim_id)


if os.environ.get("THUNDER_TPU_QUARANTINE_DIR"):
    configure(os.environ["THUNDER_TPU_QUARANTINE_DIR"])
