"""Elastic training: checkpoint-restart supervision + failure detection.

NEW capability — the reference has **no** elastic runtime, rank-failure
handling, or fault injection (SURVEY §5 "Failure detection / elastic
recovery: Absent"). TPU-native approach: JAX SPMD jobs cannot mask a lost
chip inside a step, so elasticity = frequent cheap sharded checkpoints +
supervised restart — this module provides both halves, built on the
``thunder_tpu.runtime`` fault-domain subsystem:

- ``CheckpointManager``: rotating step checkpoints (orbax-backed via
  ``thunder_tpu.checkpoint``; each process writes its owned shards), commit
  markers + atomic latest-pointer (a crash between the data write and the
  LATEST flip leaves a *torn* step dir: it never counts toward retention,
  is swept at writer startup, and a torn/unreadable LATEST falls back to
  the newest committed marker), restore-onto-any-mesh.
- ``ElasticTrainer``: runs the compiled step under supervision — failures
  are classified (``runtime.retry``: retryable / fatal / degradable),
  recovered with jittered exponential backoff under a sliding-window
  restart budget, SIGTERM preemption commits a checkpoint and exits
  cleanly, and a warm restart reuses the persistent compile cache
  (``compile_cache_dir`` → ``enable_compilation_cache``) so replay costs
  seconds, not a fresh NORTHSTAR-scale compile.
- ``Heartbeat`` / ``check_stalled`` / ``Watchdog``: liveness file +
  in-process watchdog thread for hangs that never raise (a stuck
  collective); a heartbeat that is *never written* reads as stalled after
  a grace period — a trainer that dies before its first beat is flagged.
- ``FaultInjector``: the legacy step-level injector (kept for
  compatibility); new chaos tests use ``runtime.faults.FaultPlan`` which
  reaches every layer (compile, dispatch, kernels, collectives,
  checkpoint IO) — see ``thunder_tpu/runtime/faults.py``.
"""

from __future__ import annotations

import json
import os
import shutil
import signal
import threading
import time
from typing import Any, Callable

from thunder_tpu.checkpoint import (load_checkpoint, save_checkpoint,
                                    wait_for_checkpoints)
from thunder_tpu.observe import registry as _observe
from thunder_tpu.runtime import quarantine as _quarantine
from thunder_tpu.runtime import retry as _retry
from thunder_tpu.runtime import sentinel as _sentinel
from thunder_tpu.runtime.faults import FaultPlan
from thunder_tpu.runtime.retry import RestartBudget, RetryPolicy
from thunder_tpu.runtime.sentinel import NumericsPolicy


class CheckpointManager:
    """Rotating step checkpoints under ``root/step_N`` with a per-dir commit
    marker and a ``LATEST`` pointer written only after a successful save
    (atomic rename).

    Commit protocol: data lands in ``step_N``, then ``step_N/.committed``
    is written, then ``LATEST`` flips (atomic replace). A crash anywhere
    before the marker leaves a torn dir that (a) never counts toward the
    ``keep`` retention window, (b) is swept when the next *writer* starts
    (first ``save`` / supervisor startup — see :meth:`sweep_uncommitted`),
    and (c) can never be selected by ``latest_step`` — which also falls
    back to the newest committed marker when ``LATEST`` itself is missing
    or torn. ``_gc`` deletes only *committed* dirs beyond ``keep`` and
    never the dir ``LATEST`` references.

    ``asynchronous=True``: saves overlap training with a depth-1 pipeline —
    requesting save N first JOINS save N-1 and flips LATEST to it, then
    kicks off N in the background. LATEST therefore always names a
    fully-committed checkpoint; call :meth:`finalize` (ElasticTrainer does)
    before exiting so the last save commits too."""

    COMMIT_MARKER = ".committed"

    def __init__(self, root: str, keep: int = 3, asynchronous: bool = False):
        self.root = os.path.abspath(root)
        self.keep = keep
        self.asynchronous = asynchronous
        self._pending: int | None = None
        self._swept = False
        os.makedirs(self.root, exist_ok=True)

    def _step_dir(self, step: int) -> str:
        return os.path.join(self.root, f"step_{step}")

    def _step_dirs(self) -> list[int]:
        return sorted(
            int(d.split("_", 1)[1]) for d in os.listdir(self.root)
            if d.startswith("step_") and d.split("_", 1)[1].isdigit())

    def _is_committed(self, step: int) -> bool:
        return os.path.exists(os.path.join(self._step_dir(step), self.COMMIT_MARKER))

    def _committed_steps(self) -> list[int]:
        return [s for s in self._step_dirs() if self._is_committed(s)]

    def _latest_from_pointer(self) -> int | None:
        try:
            with open(os.path.join(self.root, "LATEST")) as f:
                return int(json.load(f)["step"])
        except Exception:
            return None  # missing or torn: caller falls back to markers

    def sweep_uncommitted(self) -> None:
        """Writer-startup sweep: a step dir without a commit marker is a
        torn write from a crashed process — remove it so it can never
        shadow a committed checkpoint or distort retention. The dir
        ``LATEST`` references is always kept (pre-marker-era checkpoints
        commit via the pointer alone).

        Deliberately NOT run from ``__init__``: a manager constructed only
        to *read* (``latest_step``/``restore_latest`` from a monitoring
        process) must never delete another writer's in-flight save, which
        is indistinguishable from a torn dir until its marker lands. The
        first :meth:`save` runs it (this process is then the root's
        writer, and its own saves haven't started), as does
        ``ElasticTrainer.run`` at supervisor startup.

        Only unmarked dirs ABOVE the committed latest are removed: a crash
        tears the save in flight, which is always the newest step; dirs at
        or below LATEST may be pre-marker-era committed checkpoints (valid
        rollback points), so they are never touched."""
        self._swept = True
        latest = self.latest_step()
        if latest is None:
            return  # no committed anchor: never delete blindly
        for s in self._step_dirs():
            if s <= latest or s == self._pending or self._is_committed(s):
                continue
            shutil.rmtree(self._step_dir(s), ignore_errors=True)

    def _write_latest(self, step: int) -> None:
        # marker FIRST: if we crash between the two writes, the fallback
        # scan in latest_step still finds this fully-written checkpoint
        d = self._step_dir(step)
        if not os.path.isdir(d):
            return  # the dir vanished (external cleanup): LATEST must not
            # be flipped to a checkpoint that no longer exists
        with open(os.path.join(d, self.COMMIT_MARKER), "w") as f:
            json.dump({"step": step, "time": time.time()}, f)
        tmp = os.path.join(self.root, ".LATEST.tmp")
        with open(tmp, "w") as f:
            json.dump({"step": step, "time": time.time()}, f)
        os.replace(tmp, os.path.join(self.root, "LATEST"))

    def _commit_pending(self) -> None:
        if self._pending is None:
            return
        # join only OUR pending save — other managers' in-flight saves are
        # their business (per-path checkpointers, no shared singleton)
        wait_for_checkpoints(self._step_dir(self._pending))
        self._write_latest(self._pending)
        self._pending = None
        self._gc()

    def finalize(self) -> None:
        """Join and commit any in-flight asynchronous save."""
        self._commit_pending()

    def save(self, step: int, state: Any) -> None:
        if not self._swept:
            self.sweep_uncommitted()  # first write: this manager owns the root
        d = self._step_dir(step)
        if self.asynchronous:
            # join the in-flight save BEFORE any delete: re-saving the
            # pending step must not rmtree a directory being written
            self._commit_pending()
            if os.path.exists(d):
                shutil.rmtree(d)
            was_async = save_checkpoint(d, state, asynchronous=True)
            if not was_async:
                # sync fallback (no orbax): the data is already on disk —
                # deferring LATEST would leave a committed checkpoint
                # unreferenced across a crash for no benefit (advisor r3)
                self._write_latest(step)
                self._gc()
                return
            self._pending = step
            return
        if os.path.exists(d):
            shutil.rmtree(d)
        save_checkpoint(d, state)
        self._write_latest(step)
        self._gc()

    def latest_step(self) -> int | None:
        step = self._latest_from_pointer()
        if step is not None and os.path.isdir(self._step_dir(step)):
            return step
        # LATEST missing/torn (crash mid-flip): newest committed marker wins
        committed = self._committed_steps()
        return committed[-1] if committed else None

    def restore_latest(self, template: Any | None = None) -> tuple[int, Any] | None:
        self._commit_pending()
        step = self.latest_step()
        if step is None:
            return None
        return step, load_checkpoint(self._step_dir(step), template)

    def _gc(self) -> None:
        # retention counts COMMITTED checkpoints only: torn dirs (crash
        # between save and the LATEST flip) must neither occupy keep slots
        # nor push the LATEST-committed checkpoint out of the window — and
        # the dir LATEST references is never deleted, whatever `keep` says
        latest = self._latest_from_pointer()
        committed = self._committed_steps()
        for s in committed[:-self.keep]:
            if s == latest:
                continue
            shutil.rmtree(self._step_dir(s), ignore_errors=True)


class Heartbeat:
    """Liveness file for external watchdogs: ``beat(step)`` each step;
    ``check_stalled`` (anywhere) reports if the trainer stopped making
    progress — the detector for hangs that never raise."""

    def __init__(self, path: str):
        self.path = os.path.abspath(path)

    def beat(self, step: int) -> None:
        tmp = self.path + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"step": step, "time": time.time()}, f)
        os.replace(tmp, self.path)


# first time each heartbeat path was observed missing/unreadable: the
# anchor for the missing-heartbeat grace period (a trainer that dies
# before its first beat must eventually read as stalled)
_first_missing: dict[str, float] = {}


def check_stalled(heartbeat_path: str, timeout_s: float, *,
                  grace_s: float | None = None, _now: float | None = None) -> bool:
    """True when the trainer behind ``heartbeat_path`` stopped progressing.

    A present heartbeat is stalled when older than ``timeout_s``. A missing
    or unreadable heartbeat is stalled once it has *stayed* missing for
    ``grace_s`` (default: ``timeout_s``) since this checker first looked —
    previously a never-written beat read as healthy forever, so a trainer
    that died before its first step was never flagged."""
    now = time.time() if _now is None else _now
    path = os.path.abspath(heartbeat_path)
    try:
        with open(path) as f:
            last = json.load(f)["time"]
    except Exception:
        first = _first_missing.setdefault(path, now)
        grace = timeout_s if grace_s is None else grace_s
        return (now - first) > grace
    _first_missing.pop(path, None)
    return (now - last) > timeout_s


class Watchdog:
    """In-process heartbeat watchdog thread with escalation.

    Polls the heartbeat file, exports its age as the
    ``runtime.heartbeat_age_s`` gauge, and calls ``escalate(age_s)`` once
    per stall episode (a fresh beat re-arms it). A heartbeat never written
    at all escalates after ``grace_s`` (default ``timeout_s``) — the
    in-process form of the :func:`check_stalled` fix."""

    def __init__(self, heartbeat_path: str, timeout_s: float, *,
                 poll_s: float | None = None, grace_s: float | None = None,
                 escalate: Callable[[float], None] | None = None):
        self.path = os.path.abspath(heartbeat_path)
        self.timeout_s = timeout_s
        self.grace_s = timeout_s if grace_s is None else grace_s
        self.poll_s = poll_s if poll_s is not None else max(timeout_s / 4.0, 0.01)
        self.escalate = escalate or (lambda age_s: None)
        self.stalled = False
        self.escalations = 0
        self._stop = threading.Event()
        self._started_at: float | None = None
        self._missing_since: float | None = None
        self._thread: threading.Thread | None = None

    def _beat_age(self) -> float | None:
        try:
            with open(self.path) as f:
                return max(time.time() - json.load(f)["time"], 0.0)
        except Exception:
            return None

    def _check_once(self) -> None:
        age = self._beat_age()
        if age is not None:
            self._missing_since = None
            _observe.set_gauge("runtime.heartbeat_age_s", age)
            stalled = age > self.timeout_s
        else:
            # grace anchored at when the beat FIRST went missing (a beat
            # that disappears after an hour of health must get the full
            # grace window, not escalate instantly)
            now = time.monotonic()
            if self._missing_since is None:
                self._missing_since = now
            waited = now - self._missing_since
            _observe.set_gauge("runtime.heartbeat_age_s", waited)
            stalled = waited > self.grace_s
            age = waited
        if stalled and not self.stalled:
            self.stalled = True
            self.escalations += 1
            _observe.inc("runtime.watchdog_escalations")
            _observe.event("watchdog_stalled", heartbeat=self.path, age_s=age)
            self.escalate(age)
        elif not stalled:
            self.stalled = False  # fresh beat re-arms escalation

    def _run(self) -> None:
        while not self._stop.wait(self.poll_s):
            self._check_once()

    def start(self) -> "Watchdog":
        self._started_at = time.monotonic()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="thunder-tpu-watchdog")
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5.0)


class FaultInjector:
    """Legacy step-level injector, now a thin facade over
    ``runtime.faults.FaultPlan`` — ONE injection surface for the whole
    stack. The old constructor signature (``fail_at`` / ``exc`` /
    ``repeat``) keeps working; under the hood it builds a ``step``-domain
    :class:`~thunder_tpu.runtime.faults.FaultSpec` (``repeat=True`` maps to
    ``transient=False``), so schedules, metrics (``runtime.faults_injected``)
    and events flow through the same machinery as every other domain. New
    code should pass ``fault_plan=`` to :class:`ElasticTrainer` directly."""

    def __init__(self, fail_at: set[int] | None = None, exc=RuntimeError,
                 repeat: bool = False):
        from thunder_tpu.runtime.faults import FaultSpec

        self.fail_at = set(fail_at or ())
        self.exc = exc
        self.repeat = repeat  # True = permanent fault (fires on every replay)
        self._spec = FaultSpec("step", at_steps=self.fail_at,
                               transient=not repeat, exc=exc) \
            if self.fail_at else None
        self.plan = FaultPlan([self._spec] if self._spec is not None else [])

    @property
    def fired(self) -> set[int]:
        """Steps at which this injector has fired (legacy inspection API)."""
        return set(self._spec._fired_steps) if self._spec is not None else set()

    def maybe_fail(self, step: int) -> None:
        self.plan.maybe_fail("step", step=step)


class ElasticTrainer:
    """Supervised training loop with checkpoint-restart recovery.

    ``step_fn(state, batch) -> state`` (state is any pytree; put the loss in
    it if you want it logged). ``data_fn(step) -> batch`` must be
    deterministic in ``step`` so replay after restore is exact.

    Supervision policy:

    - failures are classified via ``runtime.retry.classify`` — ``fatal``
      exceptions (KeyboardInterrupt, programming errors) propagate
      immediately; everything else restores the last checkpoint and
      replays,
    - restarts draw from a **sliding-window budget**: at most
      ``max_restarts`` restarts per ``restart_window_s`` seconds
      (``None`` = lifetime, the legacy behavior),
    - consecutive failures back off with ``retry_policy`` (jittered
      exponential; ``None`` = restart immediately),
    - SIGTERM (TPU preemption notice) sets a flag; after the in-flight step
      completes the trainer commits a checkpoint, emits ``preempted``, and
      returns cleanly — a fresh process resumes from that exact step,
    - ``watchdog_timeout_s`` starts an in-process :class:`Watchdog` on the
      heartbeat (escalates through ``on_event("stalled", ...)``),
    - ``compile_cache_dir`` enables the persistent compile cache (and the
      kernel-quarantine set next to it) so the post-restart replay recompiles
      from disk in seconds,
    - ``numerics_policy`` arms the numerical-integrity response ladder: it
      is installed process-wide for the duration of ``run()`` so any
      ``NumericsGuardTransform``-guarded step jitted without an explicit
      policy follows it. Non-finite steps are skipped *in-graph* by the
      guard (``runtime.skipped_steps``); a ``LossSpike`` raised by the
      sentinel is classified retryable and handled as a **rewind** — the
      trainer restores the last committed checkpoint and replays the data
      order exactly (``runtime.rewinds``, ``on_event("rewind", ...)``);
      persistent non-finite output triggers the sentinel's kernel bisection
      inside the jit driver before anything reaches this loop.
    """

    RETRYABLE = (RuntimeError, OSError)  # legacy alias; classification has
    # moved to thunder_tpu.runtime.retry.classify

    def __init__(self, step_fn: Callable, ckpt: CheckpointManager, *,
                 save_every: int = 100, max_restarts: int = 3,
                 restart_window_s: float | None = None,
                 retry_policy: RetryPolicy | None = None,
                 heartbeat: Heartbeat | None = None,
                 watchdog_timeout_s: float | None = None,
                 fault_injector: FaultInjector | None = None,
                 fault_plan: FaultPlan | None = None,
                 numerics_policy: NumericsPolicy | None = None,
                 numerics_sentinels=(),
                 compile_cache_dir: str | None = None,
                 handle_preemption: bool = True,
                 preempt_signals=(signal.SIGTERM,),
                 on_event: Callable[[str, dict], None] | None = None,
                 sleep_fn: Callable[[float], None] = time.sleep,
                 clock: Callable[[], float] = time.monotonic):
        if watchdog_timeout_s is not None and heartbeat is None:
            raise ValueError("watchdog_timeout_s requires heartbeat= (the "
                             "watchdog watches the heartbeat file)")
        self.step_fn = step_fn
        self.ckpt = ckpt
        self.save_every = save_every
        self.max_restarts = max_restarts
        self.restart_window_s = restart_window_s
        self.retry_policy = retry_policy
        self.heartbeat = heartbeat
        self.watchdog_timeout_s = watchdog_timeout_s
        self.fault_injector = fault_injector
        self.fault_plan = fault_plan
        self.numerics_policy = numerics_policy
        # sentinels whose guarded steps this trainer replays (e.g.
        # [guard.sentinel]); when given, restart refold-suppression is
        # delivered to exactly these instead of the process-wide broadcast
        # (several independent trainers/guards in one process: a broadcast
        # would freeze the EWMAs of guards this trainer never replays)
        self.numerics_sentinels = tuple(numerics_sentinels)
        self.compile_cache_dir = compile_cache_dir
        self.handle_preemption = handle_preemption
        self.preempt_signals = tuple(preempt_signals)
        self.on_event = on_event or (lambda kind, info: None)
        self.sleep_fn = sleep_fn
        self.clock = clock
        self.restarts = 0
        self.backoffs: list[float] = []  # delays actually slept (inspection)
        self._budget = RestartBudget(max_restarts, restart_window_s, clock=clock)
        self._preempted = False

    def request_preemption(self) -> None:
        """Ask the trainer to checkpoint and exit after the current step
        (what the SIGTERM handler calls; usable directly from tests or a
        cluster-notice poller thread)."""
        self._preempted = True

    # -- run ----------------------------------------------------------------
    def run(self, state: Any, data_fn: Callable[[int], Any], n_steps: int) -> Any:
        if self.compile_cache_dir is not None:
            # warm restart: executables come from disk, so the post-crash
            # replay compiles in seconds — and this supervisor ASKS for the
            # kernel-quarantine set to persist beside them, so the restarted
            # process skips a known-bad kernel before a doomed compile
            import thunder_tpu as tt

            tt.enable_compilation_cache(self.compile_cache_dir)
            _quarantine.configure(self.compile_cache_dir)
        installed: dict[int, Any] = {}
        if self.handle_preemption:
            def _on_signal(signum, frame):
                self._preempted = True
                self.on_event("preempt_signal", {"signum": signum})
                _observe.event("preempt_signal", signum=signum)

            for sig in self.preempt_signals:
                try:
                    installed[sig] = signal.signal(sig, _on_signal)
                except ValueError:  # not the main thread: rely on
                    pass            # request_preemption()
        if hasattr(self.ckpt, "sweep_uncommitted"):
            # supervisor startup: this process is the root's writer — torn
            # dirs from the previous incarnation's crash are removed now
            self.ckpt.sweep_uncommitted()
        watchdog = None
        if self.watchdog_timeout_s is not None and self.heartbeat is not None:
            watchdog = Watchdog(
                self.heartbeat.path, self.watchdog_timeout_s,
                escalate=lambda age: self.on_event("stalled", {"age_s": age}),
            ).start()
        prev_policy = None
        if self.numerics_policy is not None:
            # process-installed for the supervision scope: guards jitted
            # without an explicit policy follow the trainer's ladder
            prev_policy = _sentinel.install_policy(self.numerics_policy)
        try:
            return self._run_supervised(state, data_fn, n_steps)
        finally:
            if self.numerics_policy is not None:
                _sentinel.install_policy(prev_policy)
            if watchdog is not None:
                watchdog.stop()
            for sig, old in installed.items():
                signal.signal(sig, old)

    def _run_supervised(self, state, data_fn, n_steps):
        # resume from the latest checkpoint if one exists (process restart)
        restored = self.ckpt.restore_latest(template=state)
        start = 0
        if restored is not None:
            start, state = restored
            self.on_event("resume", {"step": start})
        # recovery baseline: a failure BEFORE the first periodic save finds
        # no checkpoint — replaying on top of already-advanced state would
        # double-apply steps, so restart-from-scratch resets to this state
        initial_state = state
        step = start
        consecutive_failures = 0
        while step < n_steps:
            if self._preempted:
                # the in-flight step has completed: commit and exit cleanly
                self.ckpt.save(step, state)
                if hasattr(self.ckpt, "finalize"):
                    self.ckpt.finalize()
                self.on_event("preempted", {"step": step})
                _observe.event("preempted", step=step)
                return state
            try:
                if self.fault_plan is not None:
                    self.fault_plan.maybe_fail("step", step=step)
                if self.fault_injector is not None:
                    self.fault_injector.maybe_fail(step)
                # supervised production opts in to kernel-fault containment
                # (quarantine + recompile on the XLA decomposition)
                with _quarantine.containment():
                    state = self.step_fn(state, data_fn(step))
                step += 1
                consecutive_failures = 0
                if self.heartbeat is not None:
                    self.heartbeat.beat(step)
                if step % self.save_every == 0 or step == n_steps:
                    self.ckpt.save(step, state)
                if step == n_steps and hasattr(self.ckpt, "finalize"):
                    self.ckpt.finalize()
            except BaseException as e:
                if _retry.classify(e) == _retry.FATAL:
                    raise
                t_fail = time.perf_counter()
                failed_step = step
                self.restarts += 1
                consecutive_failures += 1
                self.on_event("failure", {"step": step, "error": repr(e),
                                          "restart": self.restarts})
                _observe.inc("runtime.restarts")
                if not self._budget.record():
                    self.on_event("restart_budget_exhausted",
                                  {"in_window": self._budget.in_window,
                                   "window_s": self.restart_window_s})
                    raise
                if self.retry_policy is not None:
                    delay = self.retry_policy.delay_s(consecutive_failures)
                    if delay > 0:
                        self.backoffs.append(delay)
                        self.on_event("backoff", {"delay_s": delay,
                                                  "attempt": consecutive_failures})
                        _observe.inc("runtime.retries")
                        _observe.observe_value("runtime.backoff_ms", delay * 1e3)
                        self.sleep_fn(delay)
                restored = self.ckpt.restore_latest(template=state)
                if restored is None:
                    step = start
                    state = initial_state
                    self.on_event("restart_from_scratch", {"step": step})
                else:
                    step, state = restored
                    self.on_event("restart", {"step": step})
                if isinstance(e, _sentinel.LossSpike):
                    # numerics ladder rung 2: the sentinel judged a finite
                    # loss anomalous and the restore above just happened —
                    # only NOW is this a rewind (not before the budget gate:
                    # an exhausted budget re-raises without ever restoring).
                    # The deterministic data_fn makes the replay order exact;
                    # tell the sentinel how many already-folded steps are
                    # about to replay so it re-judges without re-folding.
                    _observe.inc("runtime.rewinds")
                    _observe.event("sentinel_rewind", step=failed_step,
                                   loss=e.loss, z=e.z)
                    self.on_event("rewind", {"step": failed_step,
                                             "loss": e.loss, "z": e.z})
                    if getattr(e, "sentinel", None) is not None:
                        e.sentinel.notify_rewind(failed_step - step)
                elif self.numerics_policy is not None:
                    # an armed trainer's ORDINARY restart also replays
                    # already-folded steps — suppress those refolds too, or
                    # every crash recovery deflates the EWMA variance (no
                    # exception-carried sentinel here: deliver to the
                    # explicitly-owned sentinels, else broadcast)
                    if self.numerics_sentinels:
                        for s in self.numerics_sentinels:
                            s.notify_rewind(failed_step - step)
                    else:
                        _sentinel.notify_rewind_all(failed_step - step)
                # time-to-recover: failure -> state restored, replay ready
                _observe.observe_value("runtime.recovery_ms",
                                       (time.perf_counter() - t_fail) * 1e3)
        return state
