"""``observe.explain(jfn)``: the "why" report for a compiled function.

Answers, from the last compilation of a ``thunder_tpu.jit`` function:

- who executes each bound symbol of the execution trace (fusion regions
  list their members and anything they absorbed),
- why each fusion fired or didn't (the decision log with its cost-model
  inputs: token counts, widths, flops/bytes),
- why each executor claim was accepted or rejected (checker, cost model,
  fuel),
- where compile time went (per-pass walltimes),
- what XLA actually compiled — the per-compile executable census
  (``observe.census``): collective instructions with async fractions and
  ring-model recv bytes, launch/fusion counts, cost/memory analysis, any
  pessimization-sentinel findings, and the comm-reorder schedule report,
- what a step is estimated to cost (liveness peak bytes, collective bytes),
  and
- the serving request timeline — per-request queue/prefill/decode/TTFT
  breakdown and the sampled slot-occupancy histogram, read from the
  ALWAYS-ON flight ring (renders even with the registry disabled — the
  postmortem reading of this report).

Works without ``observe.enable()`` — the decision log and pass times are
collected per compile into ``CompileStats`` unconditionally (they are
negligible against tracing itself).
"""

from __future__ import annotations


def _executor_name(bsym) -> str:
    if bsym.sym.executor is not None:
        return bsym.sym.executor.name
    return "eagerjax"


def _fmt_cost(cost: dict | None) -> str:
    if not cost:
        return ""
    return " (" + ", ".join(f"{k}={v}" for k, v in cost.items()) + ")"


def _kernel_path_lines() -> list[str]:
    """Which rung of a kernel's dispatch ladder engaged, from the flight
    ring's ``kernel_path`` events (the kernels record them at dispatch, which
    is when JAX traces the program: after ``tt.jit``'s own compile has
    closed its decision log, so they are the process's, not one function's).
    Repeats collapse; empty when no laddered kernel was dispatched."""
    from collections import Counter

    from thunder_tpu.observe import flight as _flight

    # a kernel's own fields (T, hd, staged_bytes, the walk's heads_per_copy
    # and pages_per_block, ...) print as they were recorded
    meta = ("type", "kind", "ts_us", "labels", "op", "rung")
    seen = Counter((r["op"], r["rung"],
                    tuple((k, v) for k, v in r.items() if k not in meta))
                   for r in _flight.snapshot()
                   if r["type"] == "event" and r.get("kind") == "kernel_path")
    return [f"  kernel path: {op} -> {rung} ("
            + ", ".join(f"{k}={v}" for k, v in fields) + ")"
            + (f"  x{n}" if n > 1 else "")
            for (op, rung, fields), n in seen.items()]


_TIMELINE_MAX_REQUESTS = 16


def _request_timeline_lines() -> list[str]:
    """Per-request lifecycle breakdown from the flight ring: queue time,
    prefill time + chunk count, decode residency, TTFT, terminal state —
    plus the sampled slot-occupancy histogram. Empty when the ring holds
    no serving records."""
    from thunder_tpu.observe import flight as _flight

    recs = _flight.snapshot()
    phases: dict[int, dict[str, float]] = {}      # rid -> phase -> total ms
    chunks: dict[int, int] = {}
    info: dict[int, dict] = {}                    # rid -> lifecycle facts
    order: list[int] = []                         # by first appearance
    first_steps: list[int] = []     # iterations, last admission -> token

    def _req(rid: int) -> dict:
        if rid not in info:
            info[rid] = {}
            order.append(rid)
        return info[rid]

    for r in recs:
        if r["type"] == "span" and r.get("cat") == "serving:request":
            rid = int(r["args"].get("request", -1))
            if rid < 0:
                continue
            _req(rid)
            name = r["name"]
            if name == "prefill_chunk":
                chunks[rid] = chunks.get(rid, 0) + 1
            elif name in ("queued", "prefill", "decode"):
                ph = phases.setdefault(rid, {})
                ph[name] = ph.get(name, 0.0) + r["dur_us"] / 1e3
        elif r["type"] == "event":
            kind = r.get("kind", "")
            if not str(kind).startswith("serving_") or "request" not in r:
                continue
            d = _req(int(r["request"]))
            if kind == "serving_first_token":
                d["ttft_ms"] = r.get("ttft_ms")
                if r.get("steps") is not None:
                    first_steps.append(r["steps"])
            elif kind == "serving_complete":
                d["terminal"] = f"done ({r.get('generated', '?')} tokens)"
            elif kind == "serving_shed":
                d["terminal"] = f"shed ({r.get('reason', '?')})"
            elif kind == "serving_preempt":
                d["preemptions"] = d.get("preemptions", 0) + 1
    if not info:
        return []
    out: list[str] = []
    shown = order[-_TIMELINE_MAX_REQUESTS:]
    if len(order) > len(shown):
        out.append(f"  (... {len(order) - len(shown)} earlier request(s) "
                   f"aged out of this view)")
    for rid in shown:
        ph = phases.get(rid, {})
        d = info[rid]
        parts = [f"queued {ph.get('queued', 0.0):.1f} ms",
                 f"prefill {ph.get('prefill', 0.0):.1f} ms "
                 f"({chunks.get(rid, 0)} chunks)",
                 f"decode {ph.get('decode', 0.0):.1f} ms"]
        if d.get("ttft_ms") is not None:
            parts.append(f"ttft {d['ttft_ms']:.1f} ms")
        if d.get("preemptions"):
            parts.append(f"preempted x{d['preemptions']}")
        out.append(f"  req {rid}: " + ", ".join(parts)
                   + f" -> {d.get('terminal', 'in flight')}")
    # how often a prompt's replay row rode the decode step of the very
    # iteration that admitted it (``steps`` of ``serving_first_token``)
    if first_steps:
        same = sum(1 for n in first_steps if n == 1)
        out.append(f"  first token in its admission's own iteration: {same} "
                   f"of {len(first_steps)} "
                   f"({100.0 * same / len(first_steps):.1f}%)")
    # sampled slot-occupancy histogram (the engine's active_requests gauge
    # time series lives in the ring even when the registry is off)
    occ: dict[int, int] = {}
    for r in recs:
        if r["type"] == "gauge" and r.get("name") == "serving.active_requests":
            v = int(r["value"])
            occ[v] = occ.get(v, 0) + 1
    if occ:
        out.append("  slot occupancy (sampled): " + ", ".join(
            f"{k} x{occ[k]}" for k in sorted(occ)))
    # the share of the block-table window the decode attention walked
    # (``decode_dispatch`` carries both counts of every step)
    walks = [r["args"] for r in recs
             if r["type"] == "span" and r["name"] == "decode_dispatch"
             and r["args"].get("window_pages")]
    if walks:
        live = sum(a["live_pages"] for a in walks)
        window = sum(a["window_pages"] for a in walks)
        out.append(f"  decode walk: {live} live of {window} window pages "
                   f"({100.0 * live / window:.1f}%) over {len(walks)} steps")
    # an engine with a window cache kind: what each kind's walk covered and
    # what the ring gave back (``schedule`` carries the step's recycled pages)
    kinds = [a for a in walks if "live_pages_full" in a]
    if kinds:
        recycled = sum(r["args"].get("window_pages_recycled", 0) for r in recs
                       if r["type"] == "span" and r["name"] == "schedule")
        out.append(f"  cache kinds: full walked "
                   f"{sum(a['live_pages_full'] for a in kinds)} pages, window "
                   f"{sum(a['live_pages_window'] for a in kinds)} over "
                   f"{len(kinds)} steps; {recycled} window pages recycled")
    # an engine with a state kind: the rows whose recurrent state a decode
    # step read and wrote, and the prefill chunks that started a slot's state anew
    states = [a["state_rows"] for a in walks if "state_rows" in a]
    if states:
        resets = sum(1 for r in recs
                     if r["type"] == "span" and r["name"] == "prefill_chunk"
                     and r["args"].get("state_in") == 0)
        out.append(f"  cache kinds: state read for {sum(states)} rows over "
                   f"{len(states)} steps; {resets} slot states started "
                   f"from zero")
    routes = [r for r in recs
              if r["type"] == "event" and r.get("kind") == "moe_route"]
    if routes:
        n = len(routes)
        out.append(f"  expert routing: {sum(r['hit'] for r in routes) / n:.1f} "
                   f"held experts hit "
                   f"({sum(r.get('streamed', r['hit']) for r in routes) / n:.1f} "
                   f"streamed), "
                   f"{sum(r['local_picks'] for r in routes) / n:.1f} local "
                   f"picks, largest load {max(r['max_load'] for r in routes)} "
                   f"over {n} layer-steps")
    return out


_RESIDUAL_MAX_LINES = 16


def _model_vs_measured_lines() -> list[str]:
    """The measured-time observatory's residual ledger, read back from the
    ALWAYS-ON flight ring (``profile_ledger`` summary + ``profile_residual``
    records published by ``observe.profile.profile_window``) — renders with
    the registry disabled, the same black-box contract as the request
    timeline. Shows the LATEST profiled window: coverage, residual p50,
    then the worst-calibrated verdicts by |residual|, flagging any verdict
    the measurement would have FLIPPED, and the decisions no measurement
    attributed. Empty when no window was ever profiled."""
    from thunder_tpu.observe import flight as _flight

    recs = _flight.snapshot()
    summary = None
    for r in recs:
        if r["type"] == "event" and r.get("kind") == "profile_ledger":
            summary = r  # last one wins: the latest window
    if summary is None:
        return []
    window = summary.get("window")
    residuals = [r for r in recs
                 if r["type"] == "event" and r.get("kind") == "profile_residual"
                 and r.get("window") == window]
    out: list[str] = []
    out.append(f"  window {window}: {summary.get('steps', '?')} step(s), "
               f"mode={summary.get('mode', '?')}, "
               f"platform={summary.get('platform', '?')}")
    n_est = summary.get("decisions_with_estimates", 0)
    out.append(f"  coverage: {summary.get('measured', 0)}/{n_est} decision(s) "
               f"with est_*_us measured, "
               f"{summary.get('unattributed', 0)} unattributed")
    p50 = summary.get("residual_p50_pct")
    if p50 is not None:
        out.append(f"  |residual| p50: {p50:g}% of predicted")
    flips = summary.get("flips", 0)
    if flips:
        out.append(f"  VERDICT FLIPS: {flips} accepted fusion(s) measured "
                   f"slower than their modeled unfused alternative")
    measured = [r for r in residuals if r.get("status") == "measured"]
    measured.sort(key=lambda r: abs(r.get("residual_pct") or 0.0),
                  reverse=True)
    for r in measured[:_RESIDUAL_MAX_LINES]:
        flag = "  << FLIPPED" if r.get("flipped") else ""
        rp = r.get("residual_pct")
        out.append(
            f"  {r.get('region', '?')} [{r.get('decision_kind', '?')}:"
            f"{r.get('op', '?')} -> {r.get('decision', '?')}]: "
            f"predicted {r.get('predicted_us', '?')} µs, measured "
            f"{r.get('measured_us', '?')} µs"
            + (f" ({rp:+g}%)" if rp is not None else "") + flag)
    if len(measured) > _RESIDUAL_MAX_LINES:
        out.append(f"  (... {len(measured) - _RESIDUAL_MAX_LINES} more "
                   f"measured record(s))")
    unatt = [r for r in residuals if r.get("status") == "unattributed"]
    for r in unatt[:_RESIDUAL_MAX_LINES]:
        out.append(f"  unattributed: "
                   f"{r.get('decision_kind', '?')}:{r.get('op', '?')} "
                   f"-> {r.get('decision', '?')} (no fused region to "
                   f"measure — verdict kept the unfused form, or region "
                   f"outside the window)")
    if len(unatt) > _RESIDUAL_MAX_LINES:
        out.append(f"  (... {len(unatt) - _RESIDUAL_MAX_LINES} more "
                   f"unattributed record(s))")
    return out


_ROUTER_MAX_DECISIONS = 8


def _fleet_router_lines() -> list[str]:
    """The fleet router's placement story, read from the ALWAYS-ON flight
    ring (``serving_route_*`` events) — renders registry-off, so a
    postmortem can answer "why did this request land on that engine".
    Placement totals per engine/policy, failover migrations and
    drain-time rebalances by request, fleet-edge rejections, then the
    most recent decisions with the alternatives they rejected. Empty when
    no router ever ran."""
    from thunder_tpu.observe import flight as _flight

    recs = [r for r in _flight.snapshot()
            if r["type"] == "event"
            and str(r.get("kind", "")).startswith("serving_route_")]
    if not recs:
        return []
    out: list[str] = []
    decisions = [r for r in recs if r["kind"] == "serving_route_decision"]
    by_engine: dict[str, int] = {}
    by_policy: dict[str, int] = {}
    for r in decisions:
        by_engine[r.get("engine", "?")] = by_engine.get(
            r.get("engine", "?"), 0) + 1
        by_policy[r.get("policy", "?")] = by_policy.get(
            r.get("policy", "?"), 0) + 1
    if decisions:
        out.append(f"  decisions: {len(decisions)}  by engine: " + ", ".join(
            f"{e} x{by_engine[e]}" for e in sorted(by_engine))
            + "  by policy: " + ", ".join(
                f"{p} x{by_policy[p]}" for p in sorted(by_policy)))
    migrates = [r for r in recs if r["kind"] == "serving_route_migrate"]
    for r in migrates:
        out.append(f"  migrated: req {r.get('request', '?')} "
                   f"{r.get('from_engine', '?')} -> {r.get('engine', '?')} "
                   f"({r.get('generated', 0)} tokens generated, "
                   f"restart {r.get('restarts', '?')})")
    rebalances = [r for r in recs if r["kind"] == "serving_route_rebalance"]
    if rebalances:
        out.append("  rebalanced: " + ", ".join(
            f"req {r.get('request', '?')} {r.get('from_engine', '?')}"
            f"->{r.get('engine', '?')}" for r in rebalances))
    rejects = [r for r in recs if r["kind"] == "serving_route_reject"]
    if rejects:
        out.append(f"  fleet-edge rejections: {len(rejects)}")
    shown = decisions[-_ROUTER_MAX_DECISIONS:]
    if len(decisions) > len(shown):
        out.append(f"  (... {len(decisions) - len(shown)} earlier "
                   f"decision(s) aged out of this view)")
    for r in shown:
        alts = r.get("alternatives") or []
        rej = r.get("rejected") or {}
        parts = [f"req {r.get('request', '?')} -> {r.get('engine', '?')} "
                 f"[{r.get('policy', '?')}/{r.get('basis', '?')}]"]
        if alts:
            parts.append(f"over {', '.join(map(str, alts))}")
        if rej:
            parts.append("gated " + ", ".join(
                f"{e}:{why}" for e, why in sorted(rej.items())))
        out.append("  " + " ".join(parts))
    return out


def explain(jfn) -> str:
    """Return the textual report. The structured data behind it stays
    available on ``thunder_tpu.compile_stats(jfn)`` (``last_decisions``,
    ``last_pass_times``)."""
    import thunder_tpu as tt

    stats = tt.compile_stats(jfn)
    lines: list[str] = []
    name = getattr(jfn, "fn_name", getattr(jfn, "__name__", "fn"))
    lines.append(f"thunder_tpu.observe.explain: {name}")

    if not stats.last_traces:
        lines.append("  (no compilation has run yet — call or .compile() the "
                     "function first)")
        return "\n".join(lines)

    # -- compile summary (one renderer: CompileStats.summary) ---------------
    lines.append("")
    lines.append("== compile ==")
    lines.append(stats.summary())
    lines.extend(_kernel_path_lines())

    # -- executor assignment ------------------------------------------------
    exec_trc = stats.last_traces[-1]
    from thunder_tpu.core.prims import PrimIDs

    skip = (PrimIDs.PYTHON_RETURN, PrimIDs.COMMENT, PrimIDs.PYTHON_DEL)
    lines.append("")
    lines.append("== executors (execution trace) ==")
    for bsym in exec_trc.bound_symbols:
        if bsym.sym.id in skip:
            continue
        ex = _executor_name(bsym)
        entry = f"  {bsym.sym.name} [{ex}]"
        if bsym.subsymbols and bsym.sym.executor is not None:
            members = [s.sym.name for s in bsym.subsymbols]
            shown = ", ".join(members[:8]) + (", ..." if len(members) > 8 else "")
            entry += f" <- {len(members)} ops: {shown}"
        lines.append(entry)

    # -- decisions ----------------------------------------------------------
    decisions = stats.last_decisions
    fusion_dec = [d for d in decisions if d["kind"] == "fusion"]
    claim_dec = [d for d in decisions if d["kind"] == "claim"]
    block_dec = [d for d in decisions if d["kind"] == "block"]

    # block planner first: one line per candidate sub-block chain with its
    # verdict and the two numbers the objective compares (saved boundary
    # bytes vs the fused path's overheads)
    lines.append("")
    lines.append(f"== block planner ({len(block_dec)} candidate chains) ==")
    for d in block_dec:
        cost = d.get("cost") or {}
        chain = cost.get("chain", "?")
        detail = []
        if "saved_boundary_bytes" in cost:
            detail.append(f"saved_boundary_bytes={cost['saved_boundary_bytes']}")
        if "est_saved_us" in cost:
            detail.append(f"est_saved_us={cost['est_saved_us']}")
        if "vmem_bytes_per_step" in cost:
            detail.append(f"vmem_bytes_per_step={cost['vmem_bytes_per_step']}")
        suffix = f" ({', '.join(detail)})" if detail else ""
        # the planner plans three composite kinds (nn.mlp_subblock,
        # nn.attn_subblock, nn.decode_layer) — name the op per line
        lines.append(f"  {d.get('op', '?')} chain@{chain} -> {d['decision']}: "
                     f"{d.get('reason', '')}{suffix}")
    if not block_dec:
        lines.append("  (none — no sub-block chains found in this trace)")

    lines.append("")
    lines.append(f"== fusion decisions ({len(fusion_dec)}) ==")
    for d in fusion_dec:
        who = f" by {d['executor']}" if d.get("executor") else ""
        why = f": {d['reason']}" if d.get("reason") else ""
        lines.append(f"  {d['op']} -> {d['decision']}{who}{why}"
                     f"{_fmt_cost(d.get('cost'))}")
    if not fusion_dec:
        lines.append("  (none — no fusion opportunities in this trace)")

    lines.append("")
    lines.append(f"== claim decisions ({len(claim_dec)}) ==")
    # collapse repeats: the same (op, executor, decision, reason) may fire
    # hundreds of times in a deep trace
    seen: dict[tuple, int] = {}
    order: list[tuple] = []
    for d in claim_dec:
        key = (d["op"], d.get("executor"), d["decision"], d.get("reason", ""))
        if key not in seen:
            order.append(key)
        seen[key] = seen.get(key, 0) + 1
    for key in order:
        op, ex, decision, reason = key
        n = seen[key]
        who = f" by {ex}" if ex else ""
        why = f": {reason}" if reason else ""
        mult = f"  x{n}" if n > 1 else ""
        lines.append(f"  {op} -> {decision}{who}{why}{mult}")

    # -- compiled program (HLO census + pessimization sentinel) --------------
    # the executable's OWN accounting — what XLA actually scheduled, not
    # what the trace asked for. Lazy/memoized and guarded (observe.census):
    # rendering this section can never fail or re-lower a compile.
    lines.append("")
    lines.append("== compiled program (HLO census) ==")
    census = stats.last_census
    if census is None:
        lines.append("  (no compiled entry)")
    else:
        coll = census.get("collectives")
        if census.get("hlo_unavailable"):
            lines.append(f"  ({census['hlo_unavailable']})")
        elif coll is None:
            lines.append("  (executable analysis failed — see guarded "
                         "errors below)")
        else:
            asyn = census["async"]
            pk = coll["per_kind"]
            if pk:
                lines.append(
                    f"  collectives: {asyn['count']} instruction(s), "
                    f"{len(pk)} kind(s), "
                    f"{coll['recv_bytes_per_device_total'] / 1e6:.2f} MB "
                    f"recv/device (ring model, n_dev={census['n_dev']})")
                for k in sorted(pk):
                    e = pk[k]
                    lines.append(
                        f"    {k} x{e['count']} (async "
                        f"{e['async_count']}/{e['count']}), "
                        f"{e['recv_bytes_per_dev'] / 1e6:.2f} MB recv/dev")
                lines.append(f"  async fraction: "
                             f"{asyn['async']}/{asyn['count']} "
                             f"({asyn['fraction']:.2f})")
            else:
                lines.append("  collectives: none (single-device program)")
            lines.append(f"  hlo fusions: {census['hlo_fusions']}, "
                         f"custom calls: {census['hlo_custom_calls']}; "
                         f"trace: {census.get('pallas_launches', 0)} pallas "
                         f"launch(es), {census.get('xla_regions', 0)} xla "
                         f"region(s)")
            lines.append(f"  xla flops: {census['xla_flops']:.4g}, "
                         f"peak HBM (live): "
                         f"{census['live_bytes'] / 1e6:.2f} MB")
        if census.get("errors"):
            lines.append(f"  guarded census errors: {len(census['errors'])} "
                         f"(counted on compile.census_errors): "
                         + "; ".join(str(e) for e in census["errors"]))
        fnd = census.get("findings") or []
        if fnd:
            lines.append("  pessimizations:")
            for f in fnd:
                lines.append(f"    [{f['kind']}] {f['detail']}")
        else:
            lines.append("  pessimizations: none")

    # -- comm reorder (overlap-scheduling pass report) -----------------------
    comm_dec = [d for d in decisions if d["kind"] == "comm"]
    if comm_dec:
        lines.append("")
        lines.append("== comm reorder ==")
        for d in comm_dec:
            cost = d.get("cost") or {}
            if d["decision"] == "bailout":
                # a malformed trace must not skip scheduling invisibly
                lines.append(f"  BAILOUT: {d.get('reason', '')}")
            elif d["decision"] == "fallback":
                lines.append(f"  bucketing fallback: {d.get('reason', '')}")
            elif d["op"] == "comm_reorder":
                lines.append(f"  {d.get('reason', '')} "
                             f"({cost.get('issues', 0)} issue(s), "
                             f"{cost.get('waits', 0)} wait(s) total)")
                if "modeled_overlap_us" in cost:
                    lines.append(
                        f"  modeled overlap: {cost['modeled_overlap_us']:g} µs "
                        f"hidden; in-flight cap "
                        f"{cost.get('inflight_cap_bytes', 0) / 1e6:.0f} MB "
                        f"({cost.get('cap_deferrals', 0)} deferral(s), "
                        f"{cost.get('cap_forced', 0)} forced)")
            elif d["decision"] in ("decomposed", "pinned"):
                lines.append(f"  {d['op']}: {d.get('reason', '')}")
            elif d["op"] == "comm_bucketing":
                lines.append(f"  bucketing: {d.get('reason', '')}")
            elif d["decision"] in ("bucketed", "kept"):
                lines.append(f"  {d['op']} [{d['decision']}]: "
                             f"{d.get('reason', '')}")
            else:
                win = ""
                if "window_us" in cost:
                    win = (f", window {cost['window_us']:g} µs vs transfer "
                           f"{cost['transfer_us']:g} µs — "
                           f"{'covered' if cost.get('covered') else 'exposed'}")
                lines.append(
                    f"  {d['op']}: issue@{cost.get('issue_at', '?')} -> "
                    f"wait@{cost.get('wait_at', '?')} "
                    f"(distance {cost.get('distance', '?')}, "
                    f"was {cost.get('distance_before', '?')}{win})")

    # -- model vs measured (residual ledger) ---------------------------------
    # sourced from the ALWAYS-ON flight ring (profile_window publishes the
    # ledger there), so the section renders registry-off — the postmortem
    # answer to "were the cost model's verdicts right on this machine"
    residual = _model_vs_measured_lines()
    if residual:
        lines.append("")
        lines.append("== model vs measured (residual ledger) ==")
        lines.extend(residual)

    # -- numerics sentinel ---------------------------------------------------
    for tr in getattr(jfn, "transforms", ()):
        sent = getattr(tr, "sentinel", None)
        if sent is None or not hasattr(sent, "summary"):
            continue
        lines.append("")
        lines.append("== numerics sentinel ==")
        for ln in sent.summary().splitlines():
            lines.append(f"  {ln}")

    # -- serving ------------------------------------------------------------
    # rendered when the process has serving metrics (the engine's gauges /
    # histograms live in the process-wide registry, not per-compile state)
    from thunder_tpu.observe import registry as _registry

    if _registry.is_enabled():
        snap = _registry.snapshot()
        # SLO / supervision metrics get their own section: they describe the
        # engine LIFECYCLE (restarts, shedding, deadline health), not the
        # steady-state scheduler, and an operator triaging an incident reads
        # them first
        slo_keys = ("serving.engine_restarts", "serving.shed_requests",
                    "serving.deadline_misses", "serving.drain_ms",
                    "serving.slo_attainment")
        # the shared-prefix family reads as one unit: hit rate + parked
        # pages + COW copies + eviction pressure tell the whole
        # cache-effectiveness story at a glance
        prefix_keys = ("serving.prefix_hit_rate", "serving.cached_pages",
                       "serving.cow_copies", "serving.cache_evictions")
        def metric_line(k):
            # one renderer for both serving sections, gauge/counter/histogram
            if k in snap["gauges"]:
                return f"  {k}: {snap['gauges'][k]:g}"
            if k in snap["counters"]:
                return f"  {k}: {snap['counters'][k]:g} (counter)"
            h = snap["histograms"].get(k)
            if h and h["count"]:
                return (f"  {k}: n={h['count']} "
                        f"mean={h['sum'] / h['count']:.2f} "
                        f"min={h['min']:.2f} max={h['max']:.2f}")
            return None

        generic = sorted(
            k for src in ("gauges", "counters", "histograms")
            for k in snap[src]
            if k.startswith("serving.") and k not in slo_keys
            and k not in prefix_keys)
        generic_lines = [ln for k in generic if (ln := metric_line(k))]
        if generic_lines:
            lines.append("")
            lines.append("== serving ==")
            lines.extend(generic_lines)
        prefix_lines = [ln for k in prefix_keys if (ln := metric_line(k))]
        if prefix_lines:
            lines.append("")
            lines.append("== serving prefix cache ==")
            lines.extend(prefix_lines)
        slo_lines = [ln for k in slo_keys if (ln := metric_line(k))]
        if slo_lines:
            lines.append("")
            lines.append("== serving slo/supervision ==")
            lines.extend(slo_lines)

        # fleet section: when engines recorded LABELED series, break the
        # serving picture out per engine (the unlabeled sections above are
        # the process rollup — last-writer-wins for gauges — which is
        # exactly what a multi-engine process needs disambiguated)
        per_engine: dict[str, dict] = {}
        for fam in ("gauges", "counters"):
            for r in snap.get("labeled", {}).get(fam, []):
                eid = r["labels"].get("engine")
                if eid is not None and r["name"].startswith("serving."):
                    per_engine.setdefault(eid, {})[r["name"]] = r["value"]
        if len(per_engine) > 1 or (per_engine and any(
                "serving.health_state" in m for m in per_engine.values())):
            from thunder_tpu.serving.health import HEALTH_STATES

            lines.append("")
            lines.append("== serving fleet ==")
            fleet_slo = snap["gauges"].get("serving.fleet_slo_attainment")
            lines.append(f"  engines: {len(per_engine)}"
                         + (f"   fleet SLO attainment: {fleet_slo:g}"
                            if fleet_slo is not None else ""))
            for eid, m in sorted(per_engine.items()):
                code = m.get("serving.health_state")
                state = (HEALTH_STATES[int(code)]
                         if code is not None
                         and 0 <= int(code) < len(HEALTH_STATES) else "?")
                parts = [f"  {eid}: {state}"]
                for k, short in (("serving.queue_depth", "queue"),
                                 ("serving.active_requests", "active"),
                                 ("serving.kv_pages_free", "pages_free"),
                                 ("serving.slo_attainment", "slo"),
                                 ("serving.engine_restarts", "restarts")):
                    if k in m:
                        parts.append(f"{short}={m[k]:g}")
                lines.append(" ".join(parts))

    # -- fleet router (flight recorder) --------------------------------------
    # placement decisions, migrations, and rebalances from the always-on
    # flight ring — "why did this request land on that engine", registry-off
    router = _fleet_router_lines()
    if router:
        lines.append("")
        lines.append("== fleet router ==")
        lines.extend(router)

    # -- request timeline (flight recorder) ---------------------------------
    # sourced from the ALWAYS-ON flight ring, so it renders even when the
    # registry was never enabled — the postmortem reading of explain()
    timeline = _request_timeline_lines()
    if timeline:
        lines.append("")
        lines.append("== request timeline (flight recorder) ==")
        lines.extend(timeline)

    # -- step cost estimates ------------------------------------------------
    lines.append("")
    lines.append("== step estimates ==")
    try:
        from thunder_tpu.examine import comm_report, estimate_memory

        mem = estimate_memory(exec_trc)
        comm = comm_report(exec_trc)
        lines.append(f"liveness peak: {mem['peak_bytes'] / 1e6:.2f} MB "
                     f"(outputs {mem['output_bytes'] / 1e6:.2f} MB)")
        if comm["collectives"]:
            lines.append(f"collectives: " + ", ".join(
                f"{k} x{v['count']} ({(v['in_bytes'] + v['out_bytes']) / 1e6:.2f} MB)"
                for k, v in sorted(comm["collectives"].items())))
        else:
            lines.append("collectives: none (single-device program)")
    except Exception as e:  # estimates must never break the report
        lines.append(f"(estimates unavailable: {e})")

    return "\n".join(lines)
