"""Runtime step records: a lightweight wrapper over ``CacheEntry.run_fn``.

Every compiled entry's ``run_fn`` is wrapped once at compile time; per call
the wrapper costs one boolean check when the registry is disabled. When
enabled it opens a ``step:<fn>`` span around the dispatch (registry-only,
like the other hot-loop spans), so it also enters a
``jax.profiler.TraceAnnotation`` and a profiler trace shows it on its own
clock. JAX dispatch is asynchronous: the span is time-to-dispatch (plus any
synchronous work — prologue guards, host syncs). The FIRST call of an entry
triggers lazy XLA compilation inside ``run_fn``; its span carries
``first_call: True``. Its parent is the span open around the call:
``jit_call`` on the guarded path, the caller's own span (the serving
engine's ``decode_enqueue``) on the ``bind()`` path.

What is static per entry is recorded ONCE, when an entry is compiled with
the registry enabled, never per step: ``step.est_live_bytes`` (the
trace-liveness peak-memory estimate, ``examine.estimate_memory``) and
``step.collective_bytes`` (local collective payload of one step,
``examine.comm_report`` total in+out).
"""

from __future__ import annotations

from thunder_tpu.observe import registry as _registry


def _publish_static_estimates(exec_trc) -> None:
    from thunder_tpu.examine import comm_report, estimate_memory

    _registry.set_gauge("step.est_live_bytes",
                        estimate_memory(exec_trc)["peak_bytes"])
    comm = comm_report(exec_trc)
    _registry.set_gauge("step.collective_bytes",
                        comm["total_in_bytes"] + comm["total_out_bytes"])


def instrument_entry(entry, fn_name: str):
    """Wrap ``entry.run_fn``; returns the wrapped callable. Called once per
    entry, at compile time."""
    import itertools

    # the run_fn wrapper is the per-step chokepoint, so it also hosts the
    # `dispatch` fault-injection domain (one module-global None check per
    # call when no FaultPlan is installed)
    from thunder_tpu.runtime import faults as _faults

    inner = entry.run_fn
    if _registry.is_enabled() and entry.traces:
        _publish_static_estimates(entry.traces[-1])
    span_name = f"step:{fn_name}"
    call_counter = itertools.count(1)  # next() is atomic: concurrent callers
    # (serving threads) each draw a distinct number, so exactly one call is
    # classified as the compile-paying first call

    def run(*inps):
        _faults.maybe_fail("dispatch", site=fn_name)
        n_call = next(call_counter)
        if not _registry.is_enabled():
            return inner(*inps)
        # lazy XLA compile happens inside the first call
        with _registry.span(span_name, "step", {"first_call": n_call == 1},
                            record_pass_time=False, ring=False):
            return inner(*inps)

    run.__wrapped__ = inner
    return run
