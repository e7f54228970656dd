"""thunder_tpu.observe: unified tracing/metrics/explain for the compiler
and runtime.

The paper's promise is that every *trace* is inspectable; this subsystem
makes the compiler's *decisions* inspectable too:

- a process-wide metric registry (counters/gauges/histograms/spans) with
  near-zero cost when disabled (``registry.py``),
- compile-pipeline spans and a per-op decision log (every executor
  claim/rejection, every fusion accept/reject with its cost-model inputs)
  threaded through ``_compile_inner``, ``executors/passes.py``,
  ``core/fusion_passes.py``, and ``core/rematerialization.py``,
- a ``step:<fn>`` span per dispatch via a wrapper on
  ``CacheEntry.run_fn`` (``runtime.py``),
- an ALWAYS-ON bounded flight recorder — events, gauge moves, and span
  edges land in a fixed-size ring even when the registry is disabled, so
  a serving fault leaves a black box to read back (``flight.py``),
- a per-compile EXECUTABLE CENSUS (``census.py``): what XLA actually
  scheduled — collective instructions with ring-model recv bytes and
  async fractions, launch/fusion counts, cost/memory analysis — plus a
  pessimization sentinel diffing the HLO against the trace's expectation
  (typed findings, ``compile.*``/``hlo.*`` gauges, budget gates),
- exporters: JSONL, Chrome/Perfetto trace (with serving request/scheduler
  tracks and counter tracks), Prometheus text (``exporters.py``),
- the MEASURED-TIME observatory (``profile.py``): stable per-region names
  (``executor:symbol#occurrence``) threaded through dispatch as
  ``jax.named_scope`` annotations, a profiled window of steps captured per
  region (profiler-trace ingestion on TPU, timed re-execution on CPU), and
  the model-vs-measured residual ledger joining measurements against the
  decision log's ``est_*_us`` predictions (``profile.*`` metrics + flight
  events),
- cost-model CALIBRATION (``calibrate.py``): per-platform least-squares
  fits of the efficiency/launch/bandwidth constants from accumulated
  ledger records, persisted as schema-versioned ``cost_calibration.json``
  next to the compile cache; applied through ``cost_model``'s overlay so
  every recalibrated verdict is a typed ``calibrated[...]`` decision
  (``calib.*`` metrics, ``CALIBRATION_BUDGETS.json`` drift gates),
- ``explain(jfn)`` — the human report: who executes each op, why fusions
  did or didn't fire, where compile time went, model-vs-measured
  residuals, and the per-request serving timeline (``explain.py``).

Quick start::

    from thunder_tpu import observe
    observe.enable()
    jfn = thunder_tpu.jit(fn); jfn(*args)
    print(observe.explain(jfn))
    observe.export_chrome_trace("/tmp/tt.json")   # open in chrome://tracing
"""

from __future__ import annotations

from thunder_tpu.observe import calibrate  # noqa: F401
from thunder_tpu.observe import census  # noqa: F401
from thunder_tpu.observe import decisions  # noqa: F401
from thunder_tpu.observe import flight  # noqa: F401
from thunder_tpu.observe import profile  # noqa: F401
from thunder_tpu.observe import statusz  # noqa: F401
from thunder_tpu.observe.exporters import (  # noqa: F401
    chrome_trace_dict,
    export_chrome_trace,
    export_jsonl,
    export_prometheus,
    flight_trace_dict,
)
from thunder_tpu.observe.explain import explain  # noqa: F401
from thunder_tpu.observe.registry import (  # noqa: F401
    Labeled,
    collect_pass_times,
    disable,
    engines_seen,
    event,
    get_registry,
    inc,
    is_enabled,
    labeled,
    observe_value,
    reset,
    set_gauge,
    snapshot,
    span,
)
from thunder_tpu.observe.profile import profile_window  # noqa: F401
from thunder_tpu.observe.registry import enable as _enable_registry
from thunder_tpu.observe.runtime import instrument_entry  # noqa: F401


def enable(*, clear: bool = False) -> None:
    """Enable instrumentation. ``clear=True`` resets prior metrics."""
    _enable_registry(clear=clear)
