"""Typed serving errors: the request-SLO and engine-lifecycle vocabulary.

Every failure a caller can act on gets its own type — catching broad
``RuntimeError`` around ``submit()``/``drain()`` cannot distinguish "your
request was load-shed, resubmit later" from "the engine is wedged, page
somebody". The hierarchy:

- :class:`ServingError` — base for everything below.
- :class:`AdmissionRejected` — the request never became (or stopped being)
  resident for capacity/lifecycle reasons: admissions stopped by a drain,
  the bounded admission queue shed it under priority pressure, or a
  graceful-drain wall-clock bound evicted it.
- :class:`InfeasibleRequest` — the request could NEVER run on this engine
  (context window or total page pool too small); raised at ``submit()``
  time so an impossible request fails fast instead of queueing forever and
  wedging ``drain()``. Subclasses ``ValueError`` too: infeasibility is a
  caller bug, and pre-SLO code that caught ``ValueError`` keeps working.
- :class:`DeadlineExceeded` — the request's SLO deadline passed before it
  completed (shed from the queue, evicted mid-flight, or drained past the
  bound).
- :class:`EngineFault` — the engine's device state is unrecoverable in
  place (a failing dispatch consumed the donated page pools): a blind
  retry would crash on deleted buffers, so the engine escalates this to
  its supervisor, whose restart (pool rebuild + re-prefill of every
  in-flight request) is the only recovery rung.
- :class:`EngineStallError` — a ``drain()`` step made no progress (nothing
  admitted, prefilled, decoded, or shed) while requests remain; names the
  stuck requests instead of burning ``max_steps`` silently.
- :class:`RestartBudgetExceeded` — the supervisor's sliding-window restart
  budget ran out; the engine is failing faster than restarts can honestly
  mask, so the failure escalates to the caller. The health plane
  (:mod:`thunder_tpu.serving.health`) reads the same budget: a refused
  restart is what flips an engine's health to its terminal ``DEAD`` state,
  and each masked ``EngineFault`` restart reads as a ``DEGRADED`` episode.
- :class:`ShardingGeometryError` — the paged-pool geometry cannot be
  sharded over the requested mesh (kv-head count not divisible by the
  mesh axis size); raised at pool-construction time so a bad split fails
  typed instead of as an opaque XLA partitioner error. Subclasses
  ``ValueError`` too: it is a configuration bug.

Every per-engine error above (admission, deadline, fault, restart budget)
also carries ``engine_id`` so a fleet-level caller — the router, a
postmortem bundle — can attribute the failure to the engine that raised
it without string-parsing the message. ``engine_id`` is ``None`` when the
rejection happened above any single engine (e.g. the router's own
fleet-edge admission queue).

:class:`RestartState` is not an error: it is the typed record of what a
post-crash rebuild must reproduce — pool geometry, dtype, AND the mesh /
sharding plan — carried on :class:`EngineFault` so the supervisor's
restart is sharding-identical, not just shape-identical.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any


@dataclass(frozen=True)
class RestartState:
    """Everything a rebuild-after-crash needs to recreate the KV pool
    exactly: geometry + dtype (shape identity) and the tensor-parallel
    mesh (sharding identity — ``None`` for single-device engines)."""

    geometry: Any
    dtype: Any
    mesh: Any = None

    def describe(self) -> dict:
        # one geometry a cache kind; a one-kind engine carries it bare
        kinds = self.geometry if isinstance(self.geometry, tuple) \
            else (self.geometry,)
        d = {"n_layers": sum(g.n_layers for g in kinds),
             "kv_heads": kinds[0].kv_heads,
             "num_pages": kinds[0].num_pages,
             "tp_degree": 1, "mesh_shape": [1]}
        if len(kinds) > 1:
            d["num_pages_by_kind"] = [g.num_pages for g in kinds]
        if self.mesh is not None:
            md = self.mesh.describe()
            d["tp_degree"] = int(md["tp"])
            d["mesh_shape"] = list(md["mesh_shape"])
        return d


class ServingError(RuntimeError):
    """Base class for typed serving-engine errors."""


class AdmissionRejected(ServingError):
    """The engine refused (or revoked) admission for capacity/lifecycle
    reasons — draining, a full bounded queue, or priority shedding."""

    def __init__(self, message: str, *, request_id: int | None = None,
                 engine_id: str | None = None):
        super().__init__(message)
        self.request_id = request_id
        self.engine_id = engine_id


class InfeasibleRequest(AdmissionRejected, ValueError):
    """The request can never run on this engine (context window or total
    KV page pool too small) — raised at ``submit()`` so it fails fast."""


class DeadlineExceeded(ServingError):
    """The request's SLO deadline passed before completion."""

    def __init__(self, message: str, *, request_id: int | None = None,
                 deadline_s: float | None = None,
                 engine_id: str | None = None):
        super().__init__(message)
        self.request_id = request_id
        self.deadline_s = deadline_s
        self.engine_id = engine_id


class EngineFault(ServingError):
    """Device state lost mid-dispatch (donated page pools consumed by a
    failing step): per-step retry is impossible; only a supervised engine
    restart — pool rebuild plus re-prefill of in-flight requests — can
    recover. Carries the dispatch ``domain`` that escalated."""

    def __init__(self, message: str, *, domain: str = "",
                 restart_state: RestartState | None = None,
                 engine_id: str | None = None):
        super().__init__(message)
        self.domain = domain
        self.restart_state = restart_state
        self.engine_id = engine_id


class EngineStallError(ServingError):
    """``drain()`` detected a step with no progress while requests remain.
    ``stuck`` holds ``(request_id, state)`` pairs for triage."""

    def __init__(self, message: str, *, stuck: list | None = None):
        super().__init__(message)
        self.stuck = list(stuck or [])


class RestartBudgetExceeded(ServingError):
    """The supervisor's sliding-window restart budget is exhausted."""

    def __init__(self, message: str, *, in_window: int = 0,
                 max_restarts: int = 0, engine_id: str | None = None):
        super().__init__(message)
        self.in_window = in_window
        self.max_restarts = max_restarts
        self.engine_id = engine_id


class ShardingGeometryError(ServingError, ValueError):
    """The paged-pool geometry cannot be split over the mesh: the kv-head
    count must be divisible by the tensor-parallel axis size."""

    def __init__(self, message: str, *, kv_heads: int = 0, tp: int = 0):
        super().__init__(message)
        self.kv_heads = kv_heads
        self.tp = tp
