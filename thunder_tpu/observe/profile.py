"""Measured-time observatory: per-region step profiling + the residual ledger.

The compiler's every fusion/claim verdict is produced by the hand-modeled
constants in ``core/cost_model.py``, and the decision log (PR 4) records what
those constants *predicted* per compile — this module measures what the
hardware actually *did* per region, and joins the two:

- **Region naming** (:func:`region_names_for`): ONE deterministic naming
  scheme — ``executor:symbol#occurrence`` — computed from the claim-level
  region-annotated trace (:func:`region_trace_for`), the granularity the
  decision log speaks at. Everything that talks about a region uses these
  names:
  the dispatch-time ``jax.named_scope`` annotations
  (``executors/passes.annotate_regions``), ``dev_utils.ProfileTransform``'s
  profiler annotations, the :class:`StepProfile` below, and the residual
  ledger's join against ``CompileStats.last_decisions``.
- **StepProfile capture** (:func:`capture`): a profiled window of steps.
  Two capture modes share one output shape: ``reexec`` re-executes the
  execution trace region by region with a ``block_until_ready`` clock
  around each (works on any backend, honest per-region device time on
  CPU/interpret); ``profiler`` runs the compiled step under
  ``jax.profiler.trace`` and ingests the dumped Chrome-trace events whose
  names carry the region annotations (the TPU path — per-region time from
  XLA's own timeline, no re-execution skew).
- **Residual ledger** (:func:`residual_ledger`): per-decision
  (predicted, measured, residual) records joining the profile against every
  decision carrying ``est_*_us`` cost-model estimates. No silent drops: a
  decision whose verdict kept the unfused form has no fused region to
  measure and lands as an explicit ``unattributed`` record. Accepted
  verdicts whose measured time exceeds their ``est_unfused_us`` are marked
  ``flipped`` — the measurement would have reversed the verdict.
- **Publication**: :func:`profile_window` is the one-call entry — capture,
  join, export ``profile.*`` gauges/histograms, and drop the ledger in the
  ALWAYS-ON flight ring (``profile_ledger`` + per-record
  ``profile_residual`` events), so ``observe.explain()``'s "model vs
  measured" section renders registry-off, the same black-box contract as
  the request timeline.

The ledger records are what ``observe.calibrate`` fits the cost-model
constants from (the per-platform overlay that closes ROADMAP item 5's loop).
"""

from __future__ import annotations

import json as _json
import os
import time
from typing import Any

from thunder_tpu.observe import registry as _observe

# ---------------------------------------------------------------------------
# region naming — the one owner of the scheme
# ---------------------------------------------------------------------------

# bound symbols that are codegen artifacts, not executed regions
_SKIP_SYM_NAMES = ("python_return", "comment", "python_del")


def _is_skip(bsym) -> bool:
    return bsym.sym.name in _SKIP_SYM_NAMES


def executor_name(bsym) -> str:
    """The executor that runs this bound symbol (``eagerjax`` for unclaimed
    prims) — same attribution ``observe.explain``'s executor section uses."""
    if bsym.sym.executor is not None:
        return bsym.sym.executor.name
    return "eagerjax"


def region_names_for(trc) -> list:
    """Stable per-region names for an execution trace, aligned 1:1 with
    ``trc.bound_symbols`` (``None`` for codegen artifacts like ``del`` and
    ``return``). Name shape: ``executor:symbol#occurrence`` — e.g.
    ``pallas:fused_adamw#0``, ``xla:fusion2#0``, ``eagerjax:add#3``.

    The occurrence counter makes names stable under insertion/removal of
    UNRELATED ops: the k-th ``pallas:mlp_subblock`` keeps its name as long
    as the mlp sub-blocks before it keep theirs. Everything keyed by region
    (profiler annotations, StepProfile, the residual ledger) uses THESE
    names — one owner, no ad-hoc variants."""
    counts: dict[str, int] = {}
    names: list = []
    for b in trc.bound_symbols:
        if _is_skip(b):
            names.append(None)
            continue
        base = f"{executor_name(b)}:{b.sym.name}"
        k = counts.get(base, 0)
        counts[base] = k + 1
        names.append(f"{base}#{k}")
    return names


# decision op -> the symbol name its ACCEPTED verdict materializes in the
# execution trace (tail of the op id: "optim.fused_adamw" -> "fused_adamw").
# Used to join est-carrying decisions to measured regions by occurrence order.
def _op_tail(op: str) -> str:
    return op.rsplit(".", 1)[-1]


# decisions that accepted a rewrite (the fused/bucketed region EXISTS in the
# exec trace and can be measured); everything else carrying est_*_us kept the
# unfused form and is explicitly unattributable to one region
_ACCEPTED_DECISIONS = ("bucketed", "planned", "chained", "merged", "rewritten",
                      "claimed")


def _has_estimates(d: dict) -> bool:
    cost = d.get("cost")
    return isinstance(cost, dict) and any(k.startswith("est_") and k.endswith("_us")
                                          for k in cost)


def attach_region_ids(exec_trc, decisions) -> int:
    """Join est-carrying decisions to execution-trace regions by occurrence
    order: the k-th accepted decision for op X maps to the k-th region whose
    symbol name is X's tail. Mutates each joined decision dict with a
    ``"region"`` key and returns the number attached. Decisions whose
    verdict kept the unfused form get no region (their est_unfused side is
    spread over many small regions) — the ledger marks them
    ``unattributed`` instead of dropping them."""
    names = region_names_for(exec_trc)
    by_sym: dict[str, list[str]] = {}
    for b, name in zip(exec_trc.bound_symbols, names):
        if name is not None:
            by_sym.setdefault(b.sym.name, []).append(name)
    taken: dict[str, int] = {}
    attached = 0
    for d in decisions:
        if not _has_estimates(d) or d.get("decision") not in _ACCEPTED_DECISIONS:
            continue
        tail = _op_tail(str(d.get("op", "")))
        pool = by_sym.get(tail)
        if not pool:
            continue
        k = taken.get(tail, 0)
        if k >= len(pool):
            continue
        taken[tail] = k + 1
        d["region"] = pool[k]
        attached += 1
    return attached


# ---------------------------------------------------------------------------
# StepProfile capture
# ---------------------------------------------------------------------------

class StepProfile:
    """Measured per-region durations over a profiled window of steps.

    ``regions`` maps region name -> ``{"mean_us", "total_us", "calls"}``
    (mean is per step). ``mode`` is ``"reexec"`` or ``"profiler"``;
    ``platform`` is the calibration platform the window ran on
    (``observe.calibrate.platform()``)."""

    def __init__(self, regions: dict, *, steps: int, mode: str, platform: str):
        self.regions = regions
        self.steps = steps
        self.mode = mode
        self.platform = platform

    def mean_us(self, region: str):
        rec = self.regions.get(region)
        return None if rec is None else rec["mean_us"]

    def total_us(self) -> float:
        return sum(r["total_us"] for r in self.regions.values())

    def to_dict(self) -> dict:
        return {"steps": self.steps, "mode": self.mode,
                "platform": self.platform, "regions": self.regions}

    def __repr__(self):
        return (f"<StepProfile {len(self.regions)} region(s), "
                f"{self.steps} step(s), mode={self.mode}, "
                f"platform={self.platform}>")


def _as_tfn(jfn):
    import thunder_tpu as tt

    return tt._as_tfn(jfn)


def region_trace_for(entry):
    """The trace region measurement speaks about: the claim-level
    region-annotated trace when the compile produced one (provenance
    "Region annotations" — one bound symbol per claimed kernel / eager prim,
    BEFORE the XLA fusion pass absorbs claimed kernels into its jax.jit
    regions), else the final execution trace. Decision verdicts are made at
    claim granularity, so this is the trace whose regions the ledger joins
    against and the reexec clock replays."""
    for t in reversed(entry.traces):
        if "Region annotations" in str(getattr(t, "provenance", "")):
            return t
    return entry.traces[-1]


def _entry_and_trace(jfn):
    tfn = _as_tfn(jfn)
    entry = tfn._stats.last_entry
    if entry is None or not entry.traces:
        raise RuntimeError(
            "profile.capture: no compiled entry — call or .compile() the "
            "function first (the profile replays the LAST compilation)")
    return tfn, entry, region_trace_for(entry)


def _flat_tensor_inputs(tfn, entry, args, kwargs):
    """The concrete tensors the execution trace's input proxies bind to, in
    trace-arg order — the same flatten+select the dispatch path performs."""
    from thunder_tpu.core.pytree import tree_flatten

    flat, _ = tree_flatten((tuple(args), dict(kwargs or {})))
    return [flat[i] for i in entry.tensor_indices]


def capture(jfn, args=(), kwargs=None, *, steps: int = 3, warmup: int = 1,
            mode: str = "auto") -> StepProfile:
    """Measure a profiled window of ``steps`` steps of ``jfn`` on ``args``,
    returning per-region durations keyed by :func:`region_names_for` names.

    ``mode="reexec"`` re-executes the execution trace region by region with
    a ``block_until_ready`` clock (any backend; the CPU/interpret fallback).
    ``mode="profiler"`` runs the compiled step under ``jax.profiler.trace``
    and ingests the dumped trace events by region annotation (the TPU path;
    requires the region ``named_scope`` annotations, on by default).
    ``mode="auto"`` picks ``profiler`` on TPU, ``reexec`` elsewhere.

    The capture never calls the donated ``run_fn`` in reexec mode — inputs
    are read, not consumed — so it is safe after a donating bench run as
    long as fresh (undonated) inputs are passed."""
    import jax

    from thunder_tpu.observe import calibrate as _calibrate

    tfn = _as_tfn(jfn)
    if tfn._stats.last_entry is None:
        tfn.compile(*args, **(kwargs or {}))
    if mode == "auto":
        mode = "profiler" if jax.default_backend() == "tpu" else "reexec"
    platform = _calibrate.platform()
    if mode == "reexec":
        regions = _capture_reexec(jfn, args, kwargs, steps=steps, warmup=warmup)
    elif mode == "profiler":
        regions = _capture_profiler(jfn, args, kwargs, steps=steps,
                                    warmup=warmup)
    else:
        raise ValueError(f"unknown capture mode {mode!r} "
                         "(expected 'auto', 'reexec' or 'profiler')")
    prof = StepProfile(regions, steps=steps, mode=mode, platform=platform)
    _observe.set_gauge("profile.regions_measured", len(regions))
    _observe.set_gauge("profile.window_steps", steps)
    _observe.event("profile_window", mode=mode, platform=platform,
                   steps=steps, regions=len(regions),
                   total_us=round(prof.total_us(), 3))
    return prof


def _capture_reexec(jfn, args, kwargs, *, steps: int, warmup: int) -> dict:
    """Per-region re-execution: interpret the execution trace bound symbol
    by bound symbol over concrete values (the same env-threading interpreter
    ``executors.xla.run_bsyms`` uses), timing each named region with a
    ``block_until_ready`` fence. Every bound symbol executes (dataflow must
    hold); only named regions are timed."""
    import jax

    from thunder_tpu.executors.xla import _bind, _subst

    tfn, entry, exec_trc = _entry_and_trace(jfn)
    tensors = _flat_tensor_inputs(tfn, entry, args, kwargs)
    trc_args = list(exec_trc.args)
    if len(trc_args) != len(tensors):
        raise RuntimeError(
            f"profile.capture(reexec): execution trace has {len(trc_args)} "
            f"input proxies but the call supplies {len(tensors)} tensor "
            f"leaves — was the entry compiled for these arguments?")
    base_env = {p.name: v for p, v in zip(trc_args, tensors)}
    rng_proxy = getattr(entry.traces[0], "rng_input_proxy", None)
    if rng_proxy is not None:
        import numpy as _np

        base_env[rng_proxy.name] = _np.zeros((2,), _np.uint32)

    names = region_names_for(exec_trc)
    bsyms = exec_trc.bound_symbols
    totals: dict[str, float] = {}
    calls: dict[str, int] = {}
    unmeasurable: set = set()
    for step in range(warmup + steps):
        env = dict(base_env)
        record = step >= warmup
        for b, name in zip(bsyms, names):
            if name is None:
                continue
            impl = b._resolve_impl()
            if impl is None:
                continue
            c_args = _subst(env, b.args)
            c_kwargs = _subst(env, b.kwargs)
            try:
                t0 = time.perf_counter_ns()
                out = impl(*c_args, **c_kwargs)
                jax.block_until_ready(out)
                dt_us = (time.perf_counter_ns() - t0) / 1e3
            except Exception:
                # regions that cannot run eagerly — collectives outside
                # their shard_map, shard-shaped reshapes fed full arrays —
                # yield proxy-shaped zeros so dataflow continues; their
                # regions stay UNMEASURED (their decisions land in the
                # ledger as explicit unattributed records, never as fake
                # timings)
                unmeasurable.add(name)
                out = _zeros_like_output(b.output)
            _bind(env, b.output, out)
            if record and name not in unmeasurable:
                totals[name] = totals.get(name, 0.0) + dt_us
                calls[name] = calls.get(name, 0) + 1
    if unmeasurable:
        _observe.set_gauge("profile.reexec_unmeasurable_regions",
                           len(unmeasurable))
    return {name: {"mean_us": round(totals[name] / steps, 3),
                   "total_us": round(totals[name], 3),
                   "calls": calls[name]}
            for name in totals if name not in unmeasurable}


def _zeros_like_output(output):
    """Proxy-shaped zero arrays matching a bound symbol's output structure —
    the dataflow stand-in for regions the reexec interpreter cannot run."""
    import jax.numpy as _jnp

    from thunder_tpu.core.proxies import TensorProxy

    def zero(p):
        if isinstance(p, TensorProxy):
            return _jnp.zeros(tuple(int(s) for s in p.shape), p.dtype.jax)
        if isinstance(p, (tuple, list)):
            return type(p)(zero(x) for x in p)
        return p

    return zero(output)


def _capture_profiler(jfn, args, kwargs, *, steps: int, warmup: int) -> dict:
    """Run the compiled step under ``jax.profiler.trace`` and ingest the
    dumped Chrome-trace events by region annotation. The window calls the
    real ``run_fn`` — donating functions must be profiled with inputs they
    may consume (or via the reexec mode)."""
    import tempfile

    import jax

    tfn, entry, exec_trc = _entry_and_trace(jfn)
    kwargs = kwargs or {}
    for _ in range(warmup):
        jax.block_until_ready(jfn(*args, **kwargs))
    logdir = tempfile.mkdtemp(prefix="thunder_tpu_profile_")
    with jax.profiler.trace(logdir):
        for _ in range(steps):
            jax.block_until_ready(jfn(*args, **kwargs))
    names = [n for n in region_names_for(exec_trc) if n is not None]
    totals = ingest_profiler_trace(logdir, names)
    return {name: {"mean_us": round(rec["total_us"] / steps, 3),
                   "total_us": round(rec["total_us"], 3),
                   "calls": rec["calls"]}
            for name, rec in totals.items()}


def ingest_profiler_trace(logdir: str, region_names) -> dict:
    """Parse the profiler dump under ``logdir`` (``*.trace.json[.gz]``,
    Chrome-trace format) and sum complete-event durations per region name.
    A trace event belongs to region R when its name IS R or carries R as a
    scope component (``.../R/...`` — how ``jax.named_scope`` annotations
    surface in XLA op names). Pure function of the files — unit-testable
    with a hand-built trace."""
    import gzip

    names = list(region_names)
    totals: dict[str, dict] = {}
    for root, _dirs, files in os.walk(logdir):
        for fn in files:
            path = os.path.join(root, fn)
            try:
                if fn.endswith(".trace.json.gz"):
                    with gzip.open(path, "rt") as f:
                        data = _json.load(f)
                elif fn.endswith(".trace.json"):
                    with open(path) as f:
                        data = _json.load(f)
                else:
                    continue
            except Exception:
                continue  # torn/partial dump: skip the file, keep the rest
            for ev in data.get("traceEvents", ()):
                if ev.get("ph") != "X":
                    continue
                nm = str(ev.get("name", ""))
                dur = float(ev.get("dur", 0.0))
                for r in names:
                    if nm == r or nm.startswith(r + "/") or f"/{r}/" in nm \
                            or nm.endswith("/" + r):
                        rec = totals.setdefault(r, {"total_us": 0.0, "calls": 0})
                        rec["total_us"] += dur
                        rec["calls"] += 1
                        break
    return totals


# ---------------------------------------------------------------------------
# residual ledger
# ---------------------------------------------------------------------------

# cost-dict component keys forwarded into ledger records — what
# observe.calibrate's per-family fits regress against
_FIT_COMPONENTS = ("stream_us", "flop_us", "boundary_us", "recv_bytes",
                   "total_bytes", "tensors", "members", "n_dev")


def residual_ledger(decisions, prof: StepProfile) -> list:
    """Join the decision log against a :class:`StepProfile`: one record per
    decision carrying ``est_*_us`` estimates, either ``measured`` (the
    accepted verdict's region was profiled) or ``unattributed`` (the
    verdict kept the unfused form, or the region was not in the window) —
    never silently dropped.

    Record shape::

        {"kind", "op", "decision", "region" | None,
         "status": "measured" | "unattributed",
         "predicted_us", "measured_us", "residual_us", "residual_pct",
         "flipped": bool,            # measurement would reverse the verdict
         "platform", ...fit components (stream_us/flop_us/...)}
    """
    ledger: list = []
    for d in decisions:
        if not _has_estimates(d):
            continue
        cost = d["cost"]
        rec: dict[str, Any] = {
            "kind": d.get("kind"), "op": d.get("op"),
            "decision": d.get("decision"), "region": d.get("region"),
            "platform": prof.platform,
            "predicted_us": cost.get("est_fused_us",
                                     cost.get("transfer_us")),
            "est_unfused_us": cost.get("est_unfused_us"),
            "measured_us": None, "residual_us": None, "residual_pct": None,
            "flipped": False, "status": "unattributed",
        }
        for k in _FIT_COMPONENTS:
            if k in cost:
                rec[k] = cost[k]
        region = d.get("region")
        measured = prof.mean_us(region) if region else None
        if measured is not None:
            pred = rec["predicted_us"]
            rec["status"] = "measured"
            rec["measured_us"] = measured
            if pred:
                rec["residual_us"] = round(measured - pred, 3)
                rec["residual_pct"] = round((measured - pred) / pred * 100.0, 2)
            unfused = rec["est_unfused_us"]
            # the flip test: an ACCEPTED fusion whose measured time exceeds
            # the modeled unfused time would have been rejected by a
            # measurement-informed verdict (and vice versa is unobservable
            # here — the rejected form has no fused region to measure)
            if unfused is not None and measured > unfused:
                rec["flipped"] = True
        ledger.append(rec)
    return ledger


def ledger_summary(ledger) -> dict:
    """Aggregate a ledger: coverage, residual percentiles, the worst region."""
    total = len(ledger)
    measured = [r for r in ledger if r["status"] == "measured"]
    pcts = sorted(abs(r["residual_pct"]) for r in measured
                  if r["residual_pct"] is not None)
    p50 = pcts[len(pcts) // 2] if pcts else None
    worst = None
    if measured:
        w = max(measured,
                key=lambda r: abs(r["residual_pct"] or 0.0))
        worst = {"region": w["region"], "op": w["op"],
                 "residual_pct": w["residual_pct"],
                 "predicted_us": w["predicted_us"],
                 "measured_us": w["measured_us"]}
    return {"decisions_with_estimates": total,
            "measured": len(measured),
            "unattributed": total - len(measured),
            "coverage": (len(measured) / total) if total else None,
            "ledger_coverage": 1.0 if total else None,  # every est decision
            # gets a record (measured or explicitly unattributed)
            "residual_p50_pct": p50,
            "flips": sum(1 for r in ledger if r["flipped"]),
            "worst_region": (worst or {}).get("region"),
            "worst": worst}


# monotonically increasing window id: ties each ledger's ring events
# together so explain() renders exactly the LATEST window
_window_seq = 0


def publish_ledger(ledger, prof: StepProfile) -> dict:
    """Export a ledger: ``profile.*`` gauges/histograms into the registry
    (when enabled) and — ALWAYS — a ``profile_ledger`` summary event plus
    per-record ``profile_residual`` events into the flight ring, so the
    explain() "model vs measured" section renders registry-off (the PR 13
    black-box contract). Returns the summary."""
    global _window_seq
    _window_seq += 1
    window = _window_seq
    summary = ledger_summary(ledger)
    _observe.set_gauge("profile.ledger_records", len(ledger))
    _observe.set_gauge("profile.measured_coverage",
                       summary["coverage"] or 0.0)
    if summary["residual_p50_pct"] is not None:
        _observe.set_gauge("profile.residual_p50_pct",
                           summary["residual_p50_pct"])
    _observe.set_gauge("profile.verdict_flips", summary["flips"])
    for rec in ledger:
        if rec["residual_pct"] is not None:
            _observe.observe_value("profile.residual_pct",
                                   abs(rec["residual_pct"]))
        # the ledger's decision kind rides as decision_kind: the event's own
        # "kind" slot is the event type (same convention as decision events)
        payload = {("decision_kind" if k == "kind" else k): v
                   for k, v in rec.items()}
        _observe.event("profile_residual", window=window, **payload)
    _observe.event("profile_ledger", window=window, mode=prof.mode,
                   platform=prof.platform, steps=prof.steps, **{
                       k: summary[k] for k in
                       ("decisions_with_estimates", "measured",
                        "unattributed", "residual_p50_pct", "flips",
                        "worst_region")})
    return summary


def profile_window(jfn, args=(), kwargs=None, *, steps: int = 3,
                   warmup: int = 1, mode: str = "auto") -> dict:
    """The one-call measured-time observatory entry: capture a profiled
    window of ``jfn`` on ``args``, join it against the last compile's
    decision log into the residual ledger, publish ``profile.*`` metrics +
    flight-ring events, and stash the result on ``compile_stats(jfn)``
    (``.last_profile``). Returns::

        {"profile": StepProfile, "ledger": [...], "summary": {...}}
    """
    tfn = _as_tfn(jfn)
    if tfn._stats.last_entry is None:
        tfn.compile(*args, **(kwargs or {}))
    tfn, entry, exec_trc = _entry_and_trace(jfn)
    prof = capture(jfn, args, kwargs, steps=steps, warmup=warmup, mode=mode)
    decisions = tfn._stats.last_decisions
    attach_region_ids(exec_trc, decisions)
    ledger = residual_ledger(decisions, prof)
    summary = publish_ledger(ledger, prof)
    result = {"profile": prof, "ledger": ledger, "summary": summary}
    tfn._stats.last_profile = result
    return result
