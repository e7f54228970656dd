"""Per-compile executable census + pessimization sentinel.

The NORTHSTAR evidence proved that XLA's own accounting of the compiled
executable is ground truth the trace cannot see — reduce-scatters silently
rewritten into all-reduces, async fractions far below what the trace-level
story implies. That measurement used to live only inside
``benchmarks/northstar.py`` (an offline bench). This module makes it a
per-compile observe surface:

- :func:`hlo_collectives` — the ONE shared parser (moved here from
  northstar; the bench imports it back): per-kind collective instruction
  counts, payload bytes, ring-model recv bytes per device, and async
  start/attribute pairing with denominators.
- :func:`trace_census` — the cheap trace-level half: claimed Pallas
  launches (the serving launch gauges are fed from here — one owner),
  whole-decode-layer fusions, XLA fusion regions, and the per-kind
  collective counts the TRACE expects (``examine.comm_report``).
- :func:`ensure` — lands the full census in ``CompileStats.last_census``:
  optimized-HLO collective census, HLO fusion/custom-call instruction
  counts, XLA ``cost_analysis`` flops and ``memory_analysis`` peak HBM.
  Lazy and memoized per entry: the FIRST access pays one AOT
  ``lower().compile()`` (jax gives no handle to the executable the run
  path compiled); every later access — census, ``last_hlo(optimized)``,
  ``examine.xla_memory/xla_cost`` — reuses that one executable via
  :func:`compiled_for_entry`. A census can NEVER fail or re-lower a
  compile: unexpected errors are caught, counted
  (``compile.census_errors``), and surfaced in the census dict.
- the **pessimization sentinel**: :func:`findings` diffs the trace-level
  expectation against the HLO reality and emits typed findings
  (:data:`PESSIMIZATION_KINDS`), recorded as decisions on the compile's
  log, exported as ``compile.*``/``hlo.*`` gauges, and dropped into the
  always-on flight ring as events.
- **regression gates**: :func:`check_budget` evaluates a census against a
  committed per-config budget (``CENSUS_BUDGETS.json``); tier-1 fails
  when a smoke-config compile drifts outside its bounds
  (``tests/test_census.py``).
"""

from __future__ import annotations

import re

from thunder_tpu.observe import registry as _registry

# ---------------------------------------------------------------------------
# the shared HLO collective parser (one owner; northstar imports this)
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
                "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8, "c64": 8, "c128": 16}

_COLLECTIVE_RE = None


def hlo_collectives(hlo: str, n_dev: int) -> dict:
    """Per-kind collective census from OPTIMIZED HLO text: instruction
    counts, output bytes, ring-model bytes RECEIVED per device per step,
    and the async fraction (VERDICT r4 #3: comm accounting must come from
    what XLA actually emits, with denominators, not substring counts).

    Ring cost model per instruction (bytes received by one device):
      all-gather      out_bytes * (n-1)/n
      reduce-scatter  out_bytes * (n-1)      (n-1 partial shards pass by)
      all-reduce      2 * out_bytes * (n-1)/n (reduce-scatter + all-gather)
      all-to-all      out_bytes * (n-1)/n
      collective-permute out_bytes
    """
    global _COLLECTIVE_RE
    if _COLLECTIVE_RE is None:
        _COLLECTIVE_RE = re.compile(
            r"=\s+((?:\()?[a-z0-9]+\[[0-9,]*\][^=]*?)\s"
            r"(all-reduce-start|all-reduce|all-gather-start|all-gather|"
            r"reduce-scatter-start|reduce-scatter|all-to-all-start|all-to-all|"
            r"collective-permute-start|collective-permute)\(")
    shape_re = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
    out: dict = {}
    for m in _COLLECTIVE_RE.finditer(hlo):
        shapes, op = shape_re.findall(m.group(1)), m.group(2)
        if not shapes:
            continue
        base = op.replace("-start", "")
        is_async = op.endswith("-start")

        def _nbytes(shape):
            dt, dims = shape
            elems = 1
            for d in dims.split(","):
                if d:
                    elems *= int(d)
            return elems * _DTYPE_BYTES.get(dt, 4)

        # async starts carry a tuple ((operands), (outputs), aux scalars):
        # pick the DESTINATION by semantics — all-gather's output is its
        # largest array, reduce-scatter's its smallest non-scalar, the rest
        # are shape-preserving
        sizes = sorted(_nbytes(s) for s in shapes)
        nonscalar = [b for b in sizes if b > 16] or sizes
        if base == "all-gather":
            nbytes = nonscalar[-1]
        elif base == "reduce-scatter":
            nbytes = nonscalar[0]
        else:
            nbytes = nonscalar[-1]
        e = out.setdefault(base, {"count": 0, "async_count": 0,
                                  "out_bytes": 0, "recv_bytes_per_dev": 0})
        e["count"] += 1
        if is_async:
            e["async_count"] += 1
        e["out_bytes"] += nbytes
        if base == "all-gather":
            recv = nbytes * (n_dev - 1) // n_dev
        elif base == "reduce-scatter":
            recv = nbytes * (n_dev - 1)
        elif base == "all-reduce":
            recv = 2 * nbytes * (n_dev - 1) // n_dev
        else:
            recv = nbytes * (n_dev - 1) // n_dev if base == "all-to-all" else nbytes
        e["recv_bytes_per_dev"] += recv
    # the TPU backend marks async scheduling two ways: explicit `-start`
    # instructions (counted above per instruction) and an
    # `async_collective_name="<op>-start"` backend-config attribute on
    # wrapped collectives — count the attribute form per kind too, and the
    # fraction uses whichever mechanism the backend chose
    for base in list(out):
        attr = hlo.count(f'async_collective_name="{base}-start')
        out[base]["async_attr_count"] = attr
        # the attribute can appear on both halves of a wrapped pair: clamp
        # to the instruction count so async_count/count stays a fraction
        out[base]["async_count"] = min(out[base]["count"],
                                       max(out[base]["async_count"], attr))
    total = sum(e["recv_bytes_per_dev"] for e in out.values())
    frac = {k: (min(1.0, e["async_count"] / e["count"]) if e["count"] else 0.0)
            for k, e in out.items()}
    return {"per_kind": out, "recv_bytes_per_device_total": total,
            "async_fraction": frac}


# ---------------------------------------------------------------------------
# pessimization vocabulary + thresholds
# ---------------------------------------------------------------------------

# The typed finding kinds the sentinel can emit. This dict IS the ops
# contract: every kind must be documented in NORTHSTAR.md's pessimization
# table (both directions enforced by tests/test_docs.py).
PESSIMIZATION_KINDS = {
    "reduce-scatter-rewritten": (
        "the trace emits reduce-scatters but the optimized HLO has none "
        "while all-reduces are present — XLA rewrote the cheap collective "
        "into one moving ~2x the bytes (the NORTHSTAR r5 catch)"),
    "sync-collective-fraction": (
        "the fraction of collective instructions scheduled async "
        "(start/done pairs or async_collective_name attributes) is below "
        "the configured floor — communication is not being overlapped"),
    "collective-count-inflation": (
        "the HLO carries substantially more collective instructions than "
        "the trace emitted — the compiler split or duplicated collectives "
        "instead of combining them"),
    "decode-launch-growth": (
        "a serving decode program dispatches more kernel launches per "
        "decoded layer per token than its budget — a megakernel fell back "
        "to its decomposition"),
}

# sentinel thresholds; configure() overrides process-wide. async_fraction_min
# defaults to 0.0 (disarmed) because the hermetic CPU mesh never schedules
# async collectives — TPU deployments arm it (NORTHSTAR r5 measured 14%
# async all-gathers; ROADMAP 3's overlap pass is judged against this gauge).
DEFAULT_THRESHOLDS = {
    "async_fraction_min": 0.0,
    "collective_inflation_factor": 2.0,
    "decode_launches_per_layer_max": None,
}

_thresholds = dict(DEFAULT_THRESHOLDS)


def configure(**overrides) -> dict:
    """Override sentinel thresholds process-wide; returns the active dict.
    Unknown keys raise (a typo'd threshold silently disarming the sentinel
    is exactly the failure mode this module exists to prevent)."""
    for k in overrides:
        if k not in DEFAULT_THRESHOLDS:
            raise KeyError(f"unknown census threshold {k!r}; "
                           f"known: {sorted(DEFAULT_THRESHOLDS)}")
    _thresholds.update(overrides)
    return dict(_thresholds)


def thresholds() -> dict:
    return dict(_thresholds)


# ---------------------------------------------------------------------------
# trace-level census (cheap — no XLA executable involved)
# ---------------------------------------------------------------------------

def trace_ring_recv_bytes(rep: dict, n_dev: int) -> int:
    """Trace-level recv-bytes-per-device expectation: the census ring model
    applied to what the TRACE says each collective moves
    (``examine.comm_report`` out_bytes per kind). This is the denominator of
    the ``recv_vs_trace_ratio_max`` budget gate — HLO recv bytes drifting
    above this expectation is exactly the NORTHSTAR r5 2.2x pessimization."""
    from thunder_tpu.core.cost_model import ring_recv_bytes

    total = 0
    for kind, e in (rep.get("collectives") or {}).items():
        total += ring_recv_bytes(kind, int(e.get("out_bytes", 0)), n_dev)
    return total


def trace_census(exec_trc, n_dev: int = 1) -> dict:
    """Launch/fusion shape of an execution trace plus the collective counts
    the TRACE expects. One owner for the claimed-launch walk: the serving
    runner's ``serving.decode_pallas_launches`` gauges are fed from here."""
    claims: dict[str, int] = {}     # claimed symbol id -> occurrences

    def walk(bsyms):
        for b in bsyms:
            ex = b.sym.executor
            if ex is not None and ex.name == "pallas":
                # one claimed kernel = one launch; its subsymbols are the
                # decomposition (never dispatched), don't recurse
                claims[b.sym.id] = claims.get(b.sym.id, 0) + 1
                continue
            # XLA regions ABSORB claimed pallas calls (Fusion 2.0); the
            # launches live one level down
            walk(b.subsymbols)

    walk(exec_trc.bound_symbols)
    launches = sum(claims.values())
    decode_layers = claims.get("pallas.decode_layer", 0)
    regions = sum(1 for b in exec_trc.bound_symbols
                  if str(b.sym.id).startswith("xla.fusion"))
    expected: dict[str, int] = {}
    total_expected = 0
    expected_recv = 0
    errors: list[str] = []
    try:
        from thunder_tpu import examine as _examine

        rep = _examine.comm_report(exec_trc)
        expected = {k: int(v["count"]) for k, v in rep["collectives"].items()}
        total_expected = sum(expected.values())
        expected_recv = trace_ring_recv_bytes(rep, n_dev)
    except Exception as e:
        # a zeroed expectation silently disarms the reduce-scatter-rewrite
        # and inflation sentinels — the failure must be surfaced and
        # counted (census['errors']), never swallowed
        errors.append(f"comm_report: {e!r}")
    return {"pallas_launches": launches, "decode_layer_fusions": decode_layers,
            "pallas_claims": claims, "xla_regions": regions, "expected_collectives": expected,
            "expected_collective_count": total_expected,
            "expected_recv_bytes_per_device": expected_recv, "errors": errors}


# ---------------------------------------------------------------------------
# memoized compiled-executable access (the no-recompile discipline)
# ---------------------------------------------------------------------------

def lowered_for_entry(entry):
    """The jax ``Lowered`` of an entry's whole-program jit, memoized on the
    entry — repeated ``last_hlo()`` calls must not re-trace."""
    low = getattr(entry, "_examine_lowered", None)
    if low is None:
        if entry.jit_obj is None or entry.input_avals is None:
            raise RuntimeError(
                "no whole-program-jitted entry to lower (device-sync ops, "
                "whole_program_jit=False, or symbolic-values caching)")
        low = entry.jit_obj.lower(*entry.input_avals)
        try:
            entry._examine_lowered = low
        except AttributeError:
            pass
    return low


def compiled_for_entry(entry):
    """The XLA-compiled executable of an entry, memoized on the entry.

    jax exposes no handle to the executable the run path compiled, so the
    FIRST caller (census, ``last_hlo(optimized=True)``, ``examine``) pays
    one AOT ``lower().compile()``; everyone after reuses this one object —
    a full model compile is seconds-to-minutes, so this accessor is the
    single place an introspection compile is allowed to happen."""
    compiled = getattr(entry, "_examine_compiled", None)
    if compiled is None:
        compiled = lowered_for_entry(entry).compile()
        try:
            entry._examine_compiled = compiled
        except AttributeError:
            pass
    return compiled


# ---------------------------------------------------------------------------
# executable census
# ---------------------------------------------------------------------------

def executable_census(compiled, *, n_dev: int) -> dict:
    """HLO-truth half of the census from an already-compiled executable:
    collective instructions (shared parser), fusion/custom-call instruction
    counts, ``cost_analysis`` flops, ``memory_analysis`` peak HBM. Each
    accessor is guarded independently — one backend not reporting cost
    analysis must not lose the collective story."""
    out: dict = {"collectives": None, "async": None, "hlo_fusions": 0,
                 "hlo_custom_calls": 0, "xla_flops": 0.0,
                 "hbm_bytes_accessed": 0.0, "memory": {}, "live_bytes": 0,
                 "errors": []}
    try:
        hlo = compiled.as_text()
        coll = hlo_collectives(hlo, n_dev)
        total = sum(e["count"] for e in coll["per_kind"].values())
        asyn = sum(e["async_count"] for e in coll["per_kind"].values())
        out["collectives"] = coll
        out["async"] = {"async": asyn, "count": total,
                        "fraction": (asyn / total) if total else 0.0}
        out["hlo_fusions"] = len(re.findall(r"\bfusion(?:\.\d+)?\(", hlo))
        out["hlo_custom_calls"] = hlo.count(" custom-call(")
    except Exception as e:
        out["errors"].append(f"hlo: {e!r}")
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        ca = dict(ca)
        out["xla_flops"] = float(ca.get("flops", 0.0))
        out["hbm_bytes_accessed"] = float(ca.get("bytes accessed", 0.0))
    except Exception as e:
        out["errors"].append(f"cost_analysis: {e!r}")
    try:
        ma = compiled.memory_analysis()
        mem = {k: int(getattr(ma, k, 0) or 0)
               for k in ("argument_size_in_bytes", "output_size_in_bytes",
                         "temp_size_in_bytes", "generated_code_size_in_bytes",
                         "alias_size_in_bytes")}
        # arguments and outputs alias (donated params/opt state) — live HBM
        # is args + temps + code (+ outputs - aliased), same model as the
        # northstar evidence pack
        out["memory"] = mem
        out["live_bytes"] = (
            mem["argument_size_in_bytes"] + mem["temp_size_in_bytes"]
            + mem["generated_code_size_in_bytes"]
            + max(0, mem["output_size_in_bytes"] - mem["alias_size_in_bytes"]))
    except Exception as e:
        out["errors"].append(f"memory_analysis: {e!r}")
    return out


# ---------------------------------------------------------------------------
# the sentinel
# ---------------------------------------------------------------------------

def findings(census: dict, th: dict | None = None) -> list[dict]:
    """Diff the trace-level expectation against the HLO reality; return
    typed findings (kinds from :data:`PESSIMIZATION_KINDS`). Pure function
    of the census dict — unit-testable on synthetic censuses."""
    th = {**_thresholds, **(th or {})}
    out: list[dict] = []
    coll = census.get("collectives")
    expected = census.get("expected_collectives") or {}
    per_kind = (coll or {}).get("per_kind", {})
    # trace reduce_scatter prims gone from the HLO while all-reduces remain.
    # Bucketed reduce-scatters (the overlap pass's fused pairs) lower to HLO
    # reduce-scatter too — they count toward the expectation so bucketing
    # cannot disarm this sentinel.
    rs_expected = (expected.get("reduce_scatter", 0)
                   + expected.get("bucketed_reduce_scatter", 0))
    if (coll is not None and rs_expected > 0
            and per_kind.get("reduce-scatter", {}).get("count", 0) == 0
            and per_kind.get("all-reduce", {}).get("count", 0) > 0):
        out.append({
            "kind": "reduce-scatter-rewritten",
            "detail": (f"trace expects {rs_expected} reduce-scatter(s); the "
                       f"optimized HLO has 0 and "
                       f"{per_kind['all-reduce']['count']} all-reduce(s) — "
                       f"~2x the bytes per grad reduction"),
            "data": {"expected_reduce_scatters": rs_expected,
                     "hlo_all_reduces": per_kind["all-reduce"]["count"]}})
    asyn = census.get("async")
    amin = float(th["async_fraction_min"])
    if asyn and asyn["count"] > 0 and asyn["fraction"] < amin:
        out.append({
            "kind": "sync-collective-fraction",
            "detail": (f"async fraction {asyn['async']}/{asyn['count']} "
                       f"({asyn['fraction']:.2f}) below the configured "
                       f"floor {amin:.2f}"),
            "data": {"async": asyn["async"], "count": asyn["count"],
                     "fraction": asyn["fraction"], "floor": amin}})
    n_expected = census.get("expected_collective_count", 0)
    factor = float(th["collective_inflation_factor"])
    if coll is not None and n_expected > 0:
        n_hlo = sum(e["count"] for e in per_kind.values())
        if n_hlo > factor * n_expected:
            out.append({
                "kind": "collective-count-inflation",
                "detail": (f"{n_hlo} HLO collective instructions vs "
                           f"{n_expected} expected by the trace "
                           f"(> {factor:g}x)"),
                "data": {"hlo_count": n_hlo, "expected_count": n_expected,
                         "factor": factor}})
    lmax = th["decode_launches_per_layer_max"]
    lpl = census.get("launches_per_layer")
    if lmax is not None and lpl is not None and lpl > lmax:
        out.append({
            "kind": "decode-launch-growth",
            "detail": (f"{lpl:g} launches per decode layer per token "
                       f"exceeds the budget of {lmax:g}"),
            "data": {"launches_per_layer": lpl, "budget": lmax}})
    return out


def launch_growth_finding(launches: int, n_layers: int,
                          budget_per_layer: float | None) -> dict | None:
    """The decode-launch-growth check for callers that know the program's
    layer count (the serving runner). Returns a finding dict or None."""
    if budget_per_layer is None or n_layers <= 0:
        return None
    return next(iter(findings(
        {"launches_per_layer": launches / n_layers},
        {"decode_launches_per_layer_max": budget_per_layer})), None)


def record_findings(fnd: list[dict], *, fn_name: str = "") -> None:
    """Export findings: one always-on flight event + registry counter per
    finding, and a decision record on the live per-compile log when one is
    active (post-compile callers sync into ``CompileStats.last_decisions``
    themselves — see :func:`ensure`)."""
    from thunder_tpu.observe import decisions as _decisions

    for f in fnd:
        _registry.event("pessimization", fn=fn_name, pessimization=f["kind"],
                        detail=f["detail"])
        _registry.inc("compile.pessimizations")
        _decisions.record("pessimization", f["kind"], None, "flagged",
                          reason=f["detail"], cost=f.get("data"))


# ---------------------------------------------------------------------------
# per-entry census assembly (lands in CompileStats.last_census)
# ---------------------------------------------------------------------------

def _collect(entry, *, fn_name: str) -> dict:
    census: dict = {"fn": fn_name, "n_dev": int(getattr(entry, "n_dev", 1) or 1),
                    "hlo_unavailable": None, "census_errors": 0,
                    "errors": [], "_flagged": [],
                    # executable-half keys are present (None/zero) even when
                    # the HLO is unavailable or the guarded compile failed,
                    # so census consumers never key-error on a partial census
                    "collectives": None, "async": None, "hlo_fusions": 0,
                    "hlo_custom_calls": 0, "xla_flops": 0.0,
                    "hbm_bytes_accessed": 0.0, "memory": {}, "live_bytes": 0}
    exec_trc = entry.traces[-1] if entry.traces else None
    if exec_trc is not None:
        try:
            tc = trace_census(exec_trc, n_dev=census["n_dev"])
            census["errors"] += tc.pop("errors", [])
            census.update(tc)
        except Exception as e:
            census["errors"].append(f"trace: {e!r}")
    if entry.jit_obj is None or entry.input_avals is None:
        census["hlo_unavailable"] = (
            "no whole-program executable (device-sync ops, "
            "whole_program_jit=False, or symbolic-values caching)")
        return census
    try:
        compiled = compiled_for_entry(entry)
    except Exception as e:
        census["errors"].append(f"compile: {e!r}")
        return census
    ec = executable_census(compiled, n_dev=census["n_dev"])
    # merge, don't clobber: a trace-half error recorded above must survive
    # the executable half's fresh errors list
    ec["errors"] = census["errors"] + ec["errors"]
    census.update(ec)
    return census


def _publish(census: dict) -> None:
    """Export the census on the observe surfaces: ``hlo.*``/``compile.*``
    gauges (Prometheus/JSONL exporters read them from the registry) and a
    flight-ring event (set_gauge/event are always-on toward the ring)."""
    coll = census.get("collectives")
    asyn = census.get("async") or {"async": 0, "count": 0, "fraction": 0.0}
    if coll is not None:
        _registry.set_gauge("hlo.collective_instructions", asyn["count"])
        _registry.set_gauge("hlo.collective_kinds", len(coll["per_kind"]))
        _registry.set_gauge("hlo.recv_bytes_per_device",
                            coll["recv_bytes_per_device_total"])
        _registry.set_gauge("hlo.async_collectives", asyn["async"])
        _registry.set_gauge("hlo.async_fraction", asyn["fraction"])
        _registry.set_gauge("hlo.fusion_instructions", census["hlo_fusions"])
        _registry.set_gauge("hlo.custom_calls", census["hlo_custom_calls"])
        _registry.set_gauge("hlo.xla_flops", census["xla_flops"])
        _registry.set_gauge("hlo.peak_hbm_bytes", census["live_bytes"])
    _registry.set_gauge("compile.pallas_launches",
                        census.get("pallas_launches", 0))
    _registry.set_gauge("compile.fusion_regions",
                        census.get("xla_regions", 0))
    _registry.inc("compile.census_runs")
    _registry.event("census", fn=census.get("fn", ""),
                    collective_instructions=asyn["count"],
                    async_fraction=asyn["fraction"],
                    recv_bytes_per_device=(coll or {}).get(
                        "recv_bytes_per_device_total", 0),
                    pallas_launches=census.get("pallas_launches", 0),
                    hlo_available=coll is not None)


def ensure(stats, *, fn_name: str = "", th: dict | None = None) -> dict | None:
    """Compute (once) and return the census of ``stats.last_entry``;
    re-evaluates sentinel findings on every call (thresholds may have
    moved) and syncs them into ``stats.last_decisions``. NEVER raises and
    never re-lowers: errors are counted (``compile.census_errors``) and
    surfaced in the census dict."""
    entry = getattr(stats, "last_entry", None)
    if entry is None:
        return None
    try:
        census = getattr(entry, "census", None)
        if census is None:
            census = _collect(entry, fn_name=fn_name)
            census["census_errors"] = len(census["errors"])
            if census["errors"]:
                _registry.inc("compile.census_errors", len(census["errors"]))
                _registry.event("census_error", fn=fn_name,
                                errors=list(census["errors"]))
            try:
                entry.census = census
            except AttributeError:
                pass
            _publish(census)
        # decode-program census context (the serving runner stashes its
        # layer count + launch budget on the stats): derive launches/layer
        # so the decode-launch-growth finding regenerates on every ensure,
        # not only at bind time
        ctx = getattr(stats, "census_context", None) or {}
        # tensor-parallel serving runners stamp their mesh descriptor into
        # the context: surface it on the census itself so postmortems and
        # bench metrics read mesh shape from the same record as collectives
        for key in ("mesh_shape", "tp_degree"):
            if ctx.get(key) is not None:
                census.setdefault(key, ctx[key])
        layers = ctx.get("decode_layers")
        if layers and census.get("launches_per_layer") is None:
            census["launches_per_layer"] = \
                census.get("pallas_launches", 0) / layers
        eff_th = dict(th or {})
        if ctx.get("decode_launches_per_layer_max") is not None:
            eff_th.setdefault("decode_launches_per_layer_max",
                              ctx["decode_launches_per_layer_max"])
        fnd = findings(census, eff_th)
        census["findings"] = fnd
        # only kinds not flagged on the PREVIOUS evaluation hit the flight
        # ring / counter — explain() re-ensures on every render and must
        # not replay events, but a kind that cleared and later re-fires
        # must be re-exported (so _flagged tracks the current set, it does
        # not grow forever)
        new = [f for f in fnd if f["kind"] not in census["_flagged"]]
        census["_flagged"] = [f["kind"] for f in fnd]
        record_findings(new, fn_name=census.get("fn", fn_name))
        recs = getattr(stats, "last_decisions", None)
        if isinstance(recs, list):
            recs[:] = [d for d in recs if d.get("kind") != "pessimization"]
            recs.extend({"kind": "pessimization", "op": f["kind"],
                         "executor": None, "decision": "flagged",
                         "reason": f["detail"], "cost": f.get("data")}
                        for f in fnd)
        return census
    except Exception as e:  # the census must never fail a compile path
        _registry.inc("compile.census_errors")
        _registry.event("census_error", fn=fn_name, errors=[repr(e)])
        return None


# ---------------------------------------------------------------------------
# regression gates (CENSUS_BUDGETS.json)
# ---------------------------------------------------------------------------

def check_budget(census: dict, budget: dict) -> list[str]:
    """Evaluate a census against one committed budget entry; returns the
    violation messages (empty = within budget). Understood keys:

    - ``require_kinds`` — collective kinds that must appear in the HLO
    - ``forbid_kinds`` — kinds that must NOT appear
    - ``min_counts`` / ``max_counts`` — per-kind instruction-count bounds
    - ``max_total_collectives`` — bound on total collective instructions
    - ``async_fraction_min`` / ``async_fraction_max`` — overall
      async-fraction bracket (both directions: a CPU-mesh smoke config
      drifting to nonzero async is as much a schedule change as a TPU
      config losing its overlap)
    - ``recv_bytes_per_device_min`` / ``recv_bytes_per_device_max`` —
      ring-model recv-byte bracket
    - ``recv_vs_trace_ratio_max`` — ceiling on HLO recv bytes as a multiple
      of the trace-level expectation
      (``census['expected_recv_bytes_per_device']``) — the per-compile gate
      on the NORTHSTAR r5 2.2x rewrite
    - ``max_launches_per_layer_per_token`` (+ ``layers``) — decode budget
    """
    v: list[str] = []
    coll = census.get("collectives")
    per_kind = (coll or {}).get("per_kind", {})
    for k in budget.get("require_kinds", ()):
        if per_kind.get(k, {}).get("count", 0) <= 0:
            v.append(f"required collective kind {k!r} absent from the HLO")
    for k in budget.get("forbid_kinds", ()):
        if per_kind.get(k, {}).get("count", 0) > 0:
            v.append(f"forbidden collective kind {k!r} present "
                     f"(x{per_kind[k]['count']})")
    for k, lo in (budget.get("min_counts") or {}).items():
        n = per_kind.get(k, {}).get("count", 0)
        if n < lo:
            v.append(f"{k}: {n} instruction(s) < budget min {lo}")
    for k, hi in (budget.get("max_counts") or {}).items():
        n = per_kind.get(k, {}).get("count", 0)
        if n > hi:
            v.append(f"{k}: {n} instruction(s) > budget max {hi}")
    total = sum(e["count"] for e in per_kind.values())
    hi = budget.get("max_total_collectives")
    if hi is not None and total > hi:
        v.append(f"total collective instructions {total} > budget {hi}")
    amin = budget.get("async_fraction_min")
    asyn = census.get("async")
    if amin is not None and asyn and asyn["count"] > 0 \
            and asyn["fraction"] < amin:
        v.append(f"async fraction {asyn['async']}/{asyn['count']} "
                 f"({asyn['fraction']:.2f}) < budget floor {amin}")
    amax = budget.get("async_fraction_max")
    if amax is not None and asyn and asyn["count"] > 0 \
            and asyn["fraction"] > amax:
        v.append(f"async fraction {asyn['async']}/{asyn['count']} "
                 f"({asyn['fraction']:.2f}) > budget ceiling {amax}")
    rmax = budget.get("recv_bytes_per_device_max")
    if rmax is not None and coll is not None \
            and coll["recv_bytes_per_device_total"] > rmax:
        v.append(f"recv bytes/device {coll['recv_bytes_per_device_total']} "
                 f"> budget {rmax}")
    rmin = budget.get("recv_bytes_per_device_min")
    if rmin is not None and coll is not None \
            and coll["recv_bytes_per_device_total"] < rmin:
        v.append(f"recv bytes/device {coll['recv_bytes_per_device_total']} "
                 f"< budget floor {rmin}")
    ratio = budget.get("recv_vs_trace_ratio_max")
    exp_recv = census.get("expected_recv_bytes_per_device", 0)
    if ratio is not None and coll is not None and exp_recv > 0:
        got = coll["recv_bytes_per_device_total"]
        if got > ratio * exp_recv:
            v.append(f"HLO recv bytes/device {got} > {ratio:g}x the "
                     f"trace-level expectation {exp_recv} "
                     f"(the reduce-scatter-rewrite signature)")
    lmax = budget.get("max_launches_per_layer_per_token")
    if lmax is not None:
        layers = max(1, int(budget.get("layers", 1)))
        lpl = census.get("pallas_launches", 0) / layers
        if lpl > lmax:
            v.append(f"{lpl:g} launches per decode layer per token "
                     f"> budget {lmax}")
    return v
