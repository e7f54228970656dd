"""Supervised serving-engine lifecycle: crash recovery, graceful drain,
and a stall watchdog.

The paper's core discipline — every fast path gets an always-available
fallback rung — extended one level up: the *engine itself* is the fast
path here, and the fallback rung is a supervised restart. PR 7/8 gave
training this story (fault domains, retry budgets, quarantine, the
numerics sentinel); :class:`EngineSupervisor` is the serving counterpart:

- **Crash recovery.** When a dispatch fault consumes the donated page
  pools mid-execution, the engine's retry classifier escalates FATAL and
  the scheduler raises :class:`~thunder_tpu.serving.errors.EngineFault`.
  The supervisor rebuilds the pools and the decode binding
  (:meth:`ServingEngine.rebuild_after_fault`) and re-admits every
  in-flight request by re-prefilling prompt + generated tokens — PR 10's
  recompute-on-resume discipline generalized from *preemption* to *crash*
  recovery, so surviving outputs stay token-identical to a fault-free run.
- **Restart budget.** Each restart charges a
  :class:`~thunder_tpu.runtime.retry.RestartBudget` sliding window; an
  engine failing faster than restarts can honestly mask escalates
  :class:`~thunder_tpu.serving.errors.RestartBudgetExceeded` to the
  caller instead of flapping forever.
- **Graceful drain/shutdown.** :meth:`drain` stops admissions (later
  ``submit()`` raises ``AdmissionRejected``), finishes residents under an
  optional wall-clock bound (expiry sheds the rest with
  ``DeadlineExceeded``), and records the whole episode in the
  ``serving.drain_ms`` histogram.
- **Stall watchdog.** With ``heartbeat_path=`` set, every :meth:`step`
  publishes a heartbeat and an :class:`~thunder_tpu.elastic.Watchdog`
  thread escalates when it goes stale — a dispatch hung inside the device
  never raises, but its heartbeat age climbs
  (``runtime.heartbeat_age_s``) and ``on_stall`` fires instead of the
  engine hanging forever unobserved.
- **statusz.** With ``statusz_dir=`` set, :meth:`step` also writes an
  atomic per-engine JSON status snapshot (same tmp+rename discipline as
  the heartbeat, throttled to ``statusz_interval_s``): engine vitals plus
  the health verdict when a :class:`~thunder_tpu.serving.health
  .FleetObservatory` attached one. A directory of these files IS the
  fleet's cross-process view (``FleetObservatory.aggregate_statusz``).

>>> sup = EngineSupervisor(engine, max_restarts=3, restart_window_s=600.0)
>>> req = sup.submit(prompt, max_new_tokens=32, deadline_s=30.0)
>>> sup.drain(deadline_s=120.0)   # stop admissions, finish residents
>>> sup.shutdown()
"""

from __future__ import annotations

import json
import os
import time
from typing import Callable

from thunder_tpu.observe import registry as _observe
from thunder_tpu.runtime import quarantine as _quarantine
from thunder_tpu.runtime import retry as _retry
from thunder_tpu.serving.errors import (
    EngineFault,
    EngineStallError,
    RestartBudgetExceeded,
)
from thunder_tpu.serving.scheduler import Request, ServingEngine


class EngineSupervisor:
    """Wraps a :class:`ServingEngine` with the restart/drain/watchdog
    lifecycle. All request traffic should flow through the supervisor
    (``submit``/``step``/``drain``) so faults recover transparently.

    With ``postmortem_dir=`` set, every typed serving failure —
    ``EngineFault`` (even when the restart rung recovers it),
    ``EngineStallError``, ``RestartBudgetExceeded``, and an SLO-attainment
    collapse below ``slo_floor`` — dumps a **postmortem bundle**: the
    always-on flight-recorder ring (the request-lifecycle black box, alive
    even with the registry disabled), the decode program's decision log, a
    registry snapshot, the engine/cache state summary
    (:meth:`ServingEngine.describe_state`, including the
    ``assert_quiescent`` findings and block-table occupancy), the restart
    budget's ``describe()``, and the Perfetto serving timeline
    (``timeline.json`` — built from the flight ring, loadable at
    chrome://tracing). The PR 8 replay-bundle discipline, generalized from
    numerics to serving."""

    def __init__(self, engine: ServingEngine, *,
                 restart_budget: _retry.RestartBudget | None = None,
                 max_restarts: int = 3, restart_window_s: float = 600.0,
                 heartbeat_path: str | None = None,
                 stall_timeout_s: float = 30.0,
                 on_stall: Callable[[float], None] | None = None,
                 postmortem_dir: str | None = None,
                 slo_floor: float | None = None, min_slo_samples: int = 8,
                 statusz_dir: str | None = None,
                 statusz_interval_s: float = 1.0):
        self.engine = engine
        # all supervisor emissions carry the supervised engine's label —
        # fleet aggregation keys on it
        self._obs = engine.obs
        self.budget = restart_budget or _retry.RestartBudget(
            max_restarts=max_restarts, window_s=restart_window_s)
        self.restarts = 0
        self.on_stall = on_stall
        self.postmortem_dir = postmortem_dir
        # attached by FleetObservatory.add(); stays None when unsupervised
        # by a fleet plane (statusz payloads then carry health: None)
        self.health = None
        self.statusz = None
        if statusz_dir is not None:
            from thunder_tpu.observe import statusz as _statusz

            self.statusz = _statusz.StatusWriter(
                statusz_dir, engine.engine_id,
                interval_s=statusz_interval_s)
        self.slo_floor = slo_floor
        self.min_slo_samples = int(min_slo_samples)
        self._slo_collapsed = False     # latched: one bundle per collapse
        # (attained, total, engine reset generation) at last (re)arm — the
        # generation detects reset_slo_window() even when the counters have
        # regrown past the base by the next check (totals alone can't).
        # Armed from the engine's CURRENT counters: attaching to a warm
        # engine must not judge pre-supervisor history
        self._slo_base = (engine._slo_attained, engine._slo_total,
                          engine._slo_resets)
        self.heartbeat = None
        self.watchdog = None
        if heartbeat_path is not None:
            from thunder_tpu.elastic import Heartbeat, Watchdog

            self.heartbeat = Heartbeat(heartbeat_path)
            self.watchdog = Watchdog(heartbeat_path, stall_timeout_s,
                                     escalate=self._escalate_stall).start()

    # -- request traffic ----------------------------------------------------
    def submit(self, prompt, max_new_tokens: int, **kwargs) -> Request:
        """Delegates to the engine (draining engines raise
        ``AdmissionRejected`` there — one admission gate, not two)."""
        return self.engine.submit(prompt, max_new_tokens, **kwargs)

    def step(self) -> bool:
        """One supervised engine iteration: publish the heartbeat, run the
        engine step, and turn an ``EngineFault`` into a budget-charged
        restart instead of a crash. Returns whether progress was made
        (a restart counts — recovery IS progress)."""
        if self.heartbeat is not None:
            self.heartbeat.beat(self.engine._step_count)
        if self.statusz is not None:
            self.statusz.maybe_write(self.status_payload())
        try:
            # supervised production opts in to kernel-fault containment: a
            # claimed kernel that dies is quarantined and the step re-runs
            # on its XLA decomposition (unsupervised, it raises)
            with _quarantine.containment():
                worked = self.engine.step()
        except EngineFault as e:
            # black box FIRST, while the engine still shows the crashed
            # state (consumed pools, stranded residents) — then recover
            self.dump_postmortem(e)
            self._restart(e)
            return True
        self._check_slo()
        return worked

    def status_payload(self) -> dict:
        """The /statusz snapshot body: cheap per-step engine vitals (no
        ``describe_state`` — that audits quiescence; this is a heartbeat
        with content). Health state rides along when a fleet plane
        attached an :class:`~thunder_tpu.serving.health.EngineHealth`."""
        eng = self.engine
        return {
            "step": eng._step_count,
            "admitting": eng.admitting,
            "queue_depth": len(eng.queue),
            "max_queue": eng.max_queue,
            "active_requests": eng.active_requests,
            "pages_free": eng.cache.pages_free,
            "pages_total": eng.cache.pages_total,
            "completed": len(eng.completed),
            "shed": len(eng.shed),
            "slo_attained": eng._slo_attained,
            "slo_total": eng._slo_total,
            "decode_rebinds": eng.decode_rebinds,
            "restarts": self.restarts,
            "budget": self.budget.describe(),
            "health": (self.health.state if self.health is not None else None),
        }

    def drain(self, *, deadline_s: float | None = None,
              max_steps: int = 1_000_000) -> list[Request]:
        """Graceful drain: stop admissions, then run residents and queued
        requests to completion under ``deadline_s`` (wall clock). On bound
        expiry the remainder is shed with ``DeadlineExceeded``; a
        no-progress step raises ``EngineStallError`` (same contract as
        ``ServingEngine.drain``, but each step here is supervised, so an
        engine fault mid-drain restarts and keeps draining). Records the
        episode in ``serving.drain_ms`` and returns the completed list."""
        eng = self.engine
        eng.stop_admissions()
        t0 = time.perf_counter()
        t0_us = _observe._now_us()
        try:
            for _ in range(max_steps):
                if eng.idle:
                    break
                if deadline_s is not None and \
                        time.perf_counter() - t0 > deadline_s:
                    victims = eng.shed_outstanding(
                        f"drain wall-clock bound ({deadline_s}s) expired")
                    self._obs.event("serving_drain_bound_expired",
                                   shed=[r.request_id for r in victims])
                    break
                if not self.step():
                    raise eng._stall_error("no-progress step during drain")
            else:
                if not eng.idle:
                    raise eng._stall_error(
                        f"no completion in {max_steps} drain steps")
        except EngineStallError as e:
            self.dump_postmortem(e)     # a stall IS the black-box case
            raise
        finally:
            self._obs.observe_value("serving.drain_ms",
                                   (time.perf_counter() - t0) * 1e3)
            # the drain episode on the scheduler track, next to its steps
            self._obs.record_span("drain", "serving:sched", t0_us,
                                 _observe._now_us() - t0_us,
                                 {"completed": len(eng.completed),
                                  "shed": len(eng.shed)})
        return eng.completed

    def shutdown(self, *, deadline_s: float | None = None) -> list[Request]:
        """Drain (bounded when ``deadline_s`` is given), then stop the
        watchdog thread. Terminal: the engine stays non-admitting."""
        try:
            return self.drain(deadline_s=deadline_s)
        finally:
            self.close()

    def close(self) -> None:
        """Stop the watchdog thread (idempotent). Does not drain. Flushes
        a final statusz snapshot so the terminal state is on disk."""
        if self.statusz is not None:
            try:
                self.statusz.write(self.status_payload())
            except Exception:
                pass
        if self.watchdog is not None:
            self.watchdog.stop()

    # -- recovery internals -------------------------------------------------
    def _escalate_stall(self, age_s: float) -> None:
        self._obs.event("serving_engine_stalled", age_s=age_s,
                       step=self.engine._step_count)
        # a hung engine is the paradigm black-box case: dump the ring
        # before the operator kills the process and it's gone (the
        # watchdog escalates once per stall episode, so this is one
        # bundle per stall, not one per poll)
        self.dump_postmortem(
            RuntimeError(f"engine stalled: heartbeat {age_s:.1f}s old at "
                         f"step {self.engine._step_count}"), tag="stall")
        if self.on_stall is not None:
            self.on_stall(age_s)

    def _check_slo(self) -> None:
        """SLO-attainment collapse detector: when the on-time ratio over
        terminal requests SINCE THE LAST (RE)ARM falls below ``slo_floor``
        (with at least ``min_slo_samples`` terminals in that window), the
        black box dumps once — silent degradation is the failure mode a
        flight recorder exists for. Latched until :meth:`rearm_slo` (one
        bundle per collapse, not one per step); the windowing means a
        rearm after mitigation starts a FRESH measurement instead of
        re-judging the historical misses that caused the first dump."""
        if self.slo_floor is None or self._slo_collapsed:
            return
        eng = self.engine
        base_a, base_t, base_gen = self._slo_base
        if eng._slo_resets != base_gen:  # engine's window was reset under us
            self._slo_base = (0, 0, eng._slo_resets)
            base_a, base_t = 0, 0
        total = eng._slo_total - base_t
        # the max() also guards min_slo_samples=0 ("judge immediately")
        # against a 0/0 before the first terminal request
        if total < max(self.min_slo_samples, 1):
            return
        ratio = (eng._slo_attained - base_a) / total
        if ratio < self.slo_floor:
            self._slo_collapsed = True
            self._obs.event("serving_slo_collapse", attainment=round(ratio, 4),
                           floor=self.slo_floor, samples=total)
            self.dump_postmortem(
                RuntimeError(f"SLO attainment collapsed: {ratio:.3f} < floor "
                             f"{self.slo_floor:g} over {total} "
                             f"terminal requests"), tag="slo_collapse")

    def rearm_slo(self) -> None:
        """Un-latch the SLO-collapse detector after mitigation and start a
        fresh measurement window (past misses are not re-judged)."""
        self._slo_collapsed = False
        eng = self.engine
        self._slo_base = (eng._slo_attained, eng._slo_total, eng._slo_resets)

    def dump_postmortem(self, cause: BaseException | str,
                        tag: str | None = None) -> str | None:
        """Write the black-box bundle for ``cause`` under
        ``postmortem_dir`` (no-op returning ``None`` when unset). Never
        raises — a postmortem failure must not break the recovery path it
        documents; partial bundles record their errors in the manifest."""
        if self.postmortem_dir is None:
            return None
        from thunder_tpu.observe import exporters as _exporters
        from thunder_tpu.observe import flight as _flight

        label = tag or (type(cause).__name__
                        if isinstance(cause, BaseException) else "incident")
        try:
            base = os.path.join(
                self.postmortem_dir,
                f"postmortem-step{self.engine._step_count:06d}-{label}")
            path, i = base, 1
            while os.path.exists(path):
                path = f"{base}.{i}"
                i += 1
            os.makedirs(path)
        except Exception:
            return None
        errors: list[str] = []

        def part(fname: str, build) -> None:
            try:
                obj = build()
                with open(os.path.join(path, fname), "w") as f:
                    json.dump(_exporters._jsonable(obj), f, default=str)
            except Exception as e:  # partial bundle beats no bundle
                errors.append(f"{fname}: {e!r}")

        try:
            n_flight = _flight.dump_jsonl(os.path.join(path, "flight.jsonl"))
        except Exception as e:
            n_flight = 0
            errors.append(f"flight.jsonl: {e!r}")
        part("engine.json", self.engine.describe_state)
        part("registry.json", _observe.snapshot)
        part("timeline.json", _exporters.flight_trace_dict)

        def decisions():
            import thunder_tpu as tt

            return tt.compile_stats(self.engine.runner.decode_jit) \
                .last_decisions
        part("decisions.json", decisions)
        part("MANIFEST.json", lambda: {
            "engine_id": self.engine.engine_id,
            "cause": repr(cause),
            "cause_type": (type(cause).__name__
                           if isinstance(cause, BaseException) else "str"),
            "created_s": time.time(),
            "step": self.engine._step_count,
            "restarts": self.restarts,
            "health": (self.health.state if self.health is not None
                       else None),
            "budget": self.budget.describe(),
            "flight_records": n_flight,
            "registry_enabled": _observe.is_enabled(),
            "errors": errors,
            "files": ["flight.jsonl", "engine.json", "registry.json",
                      "timeline.json", "decisions.json"],
        })
        self._obs.inc("serving.postmortems")
        self._obs.event("serving_postmortem", path=path, cause=repr(cause))
        return path

    def _restart(self, cause: BaseException) -> None:
        """The engine-level fallback rung: charge the sliding-window
        budget, rebuild pools + binding, re-admit in-flight requests."""
        if not self.budget.record():
            self._obs.event("serving_restart_budget_exhausted",
                           cause=repr(cause), budget=self.budget.describe())
            err = RestartBudgetExceeded(
                f"engine restart budget exhausted "
                f"({self.budget.describe()}); last fault: {cause!r}",
                in_window=self.budget.in_window,
                max_restarts=self.budget.max_restarts,
                engine_id=self.engine.engine_id)
            self.dump_postmortem(err)
            raise err from cause
        t0 = time.perf_counter()
        # hand the fault's typed restart state back to the engine: the
        # rebuild must reproduce the EXACT pool spec the crashed dispatch
        # ran against — geometry, dtype, and the tensor-parallel mesh —
        # not just shapes re-derived from geometry
        recovered = self.engine.rebuild_after_fault(
            getattr(cause, "restart_state", None))
        self.restarts += 1
        self._obs.inc("serving.engine_restarts")
        self._obs.event("serving_engine_restart", cause=repr(cause),
                       recovered=[r.request_id for r in recovered],
                       restart_ms=(time.perf_counter() - t0) * 1e3,
                       budget=self.budget.describe())
