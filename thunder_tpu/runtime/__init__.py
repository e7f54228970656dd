"""thunder_tpu.runtime: the fault-domain runtime.

Production hardening for the compile/dispatch stack (ROADMAP item 5,
SURVEY §5 "Failure detection / elastic recovery: Absent" in the reference):

- ``faults``: layered fault injection — a :class:`FaultPlan` names injection
  *domains* (``compile``, ``dispatch``, ``kernel:<claim>``, ``collective``,
  ``checkpoint_io``, ``step``) with deterministic schedules (step sets,
  every-N, seeded probability) and transient-vs-permanent semantics. Hook
  points are threaded through ``_compile_inner``, the ``CacheEntry.run_fn``
  wrapper, every ``register_operator`` claim impl (the Pallas kernels), the
  distributed collective lowerings, and ``checkpoint.save_checkpoint``.
- ``retry``: per-domain retry/timeout/backoff policies — jittered
  exponential backoff, deadline budgets, a sliding-window
  :class:`RestartBudget`, and an exception classifier
  (retryable / fatal / degradable).
- ``quarantine``: a claimed kernel that fails at compile or at runtime is
  an ERROR by default; inside ``quarantine.containment()`` (the supervisors'
  opt-in) the dispatch layer instead quarantines that claim id, recompiles
  the trace with the claim disabled (the op falls back to the XLA executor),
  and persists the quarantine set where ``configure()`` /
  ``THUNDER_TPU_QUARANTINE_DIR`` points so restarts skip the known-bad
  kernel. Every fallback lands in ``CompileStats.last_decisions`` (visible
  in ``observe.explain()``) and the ``runtime.fallbacks`` counter.

- ``sentinel``: the numerical-integrity side of the fault taxonomy — silent
  data faults (NaN/Inf grads, loss spikes, numerically corrupt claimed
  kernels) detected by in-graph health reductions
  (``thunder_tpu.transforms.NumericsGuardTransform``), skipped in-graph
  with bit-identical state, and escalated through a response ladder:
  skip-and-count → EWMA loss-spike rewind → automated bisection that
  attributes the corruption to one claimed kernel and feeds it into the
  persisted quarantine.

The supervisor side (SIGTERM-aware checkpoint-and-exit, restart backoff,
heartbeat watchdog, ``numerics_policy=`` rewind wiring) lives in
``thunder_tpu.elastic`` on top of these.
"""

from __future__ import annotations

from thunder_tpu.runtime import faults, quarantine, retry, sentinel  # noqa: F401
from thunder_tpu.runtime.faults import (  # noqa: F401
    FaultPlan,
    FaultSpec,
    InjectedFault,
    KernelExecutionError,
)
from thunder_tpu.runtime.retry import RestartBudget, RetryPolicy  # noqa: F401
from thunder_tpu.runtime.sentinel import (  # noqa: F401
    LossSpike,
    NumericsAnomaly,
    NumericsPolicy,
    NumericsSentinel,
    PersistentNonFinite,
    SilentNumericsFault,
)
