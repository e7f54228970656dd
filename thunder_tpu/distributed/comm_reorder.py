"""Overlap-scheduling pass: pin, decompose, bucket, and schedule collectives.

The default stance is to let XLA's async-collective scheduler overlap
communication with compute (SURVEY §5 "Distributed communication backend").
NORTHSTAR r5 measured that stance underdelivering on a real pod — zero-2's
reduce-scatters rewritten into all-reduces (2.2x the bytes), 14% of
all-gathers async — so this pass owns the schedule at the trace level, the
surface the paper's trace-as-Python design was built to expose (the
reference reaches for the same control with ``sort_communication_ops`` /
``sort_waits``, ``thunder/distributed/utils.py:60,119,196``). Three stages:

1. :func:`decompose_collectives` — FULLY_SHARDED ``synchronize`` (the fsdp
   forward param gather, a synchronous composite) is rewritten into an
   explicit ``all_gather`` + ``wait`` issue/wait pair, so the forward
   gathers become hoistable and bucketable like the grad reduce-scatters
   already are. The ``all_gather``/``reduce_scatter`` lowerings are PINNED
   behind ``jax.lax.optimization_barrier`` (``distributed/prims.py``), so
   the schedule this pass emits is the schedule XLA compiles.
2. :func:`bucket_collectives` — sub-threshold all-gathers/reduce-scatters
   coalesce by (kind, dtype, mesh axis) into ONE fused issue/wait pair
   (``bucketed_all_gather`` / ``bucketed_reduce_scatter``), byte-model
   gated (``cost_model.comm_bucket_cost``), every bucket verdict recorded
   on ``CompileStats.last_decisions``.
3. :func:`sort_waits` — the greedy topological reschedule, now cost-aware:
   collective issues are hoisted as early as their dependencies allow
   SUBJECT TO an in-flight byte cap (issuing every collective at step start
   would blow the outstanding-buffer budget), waits sink as late as
   possible, and each (issue, wait) pair's overlap window is reported in
   modeled compute-µs against the collective's ring-model transfer time.

Scheduling is deterministic (category + original index as the tiebreak, no
clock or hash-order input), so every rank of an SPMD program reorders
identically and the collective issue ORDER is preserved rank-to-rank — the
no-deadlock invariant, property-tested in tests/test_overlap.py.
"""

from __future__ import annotations

from thunder_tpu.core.prims import PrimIDs
from thunder_tpu.core.trace import TraceCtx, from_trace, tracectx
from thunder_tpu.core.transform_common import Transform
from thunder_tpu.core.utils import consumed_vars, produced_vars


def _is_issue(bsym) -> bool:
    from thunder_tpu.core.proxies import FutureTensorProxy
    from thunder_tpu.core.pytree import tree_flatten

    outs, _ = tree_flatten(bsym.output)
    return any(isinstance(o, FutureTensorProxy) for o in outs)


def _is_wait(bsym) -> bool:
    from thunder_tpu.distributed.prims import DistPrimIDs

    return bsym.sym.id is DistPrimIDs.WAIT


def _proxy_bytes(p) -> int:
    """Bytes of a tensor-like proxy (TensorProxy or FutureTensorProxy)."""
    if not (hasattr(p, "shape") and hasattr(p, "dtype") and p.dtype is not None):
        return 0
    n = p.dtype.bytes
    for s in p.shape:
        n *= int(s)
    return n


# ---------------------------------------------------------------------------
# stage 1: decompose synchronous gathers into issue/wait pairs
# ---------------------------------------------------------------------------

def decompose_collectives(trc: TraceCtx) -> TraceCtx:
    """Rewrite FULLY_SHARDED ``synchronize`` bound symbols (the fsdp forward
    param gather — synchronous at the trace level, so invisible to the
    scheduler) into explicit ``all_gather`` + ``wait`` pairs. Runs after
    autodiff, so the grad flow (``_synchronize_vjp``'s reduce-scatter +
    mean) is already in the trace and unaffected. ``regather`` (ZeRO-3's
    token-pinned backward gather) is left alone — its barrier IS its
    schedule."""
    from thunder_tpu.core.proxies import DistParallelType, Proxy, Variable
    from thunder_tpu.distributed.prims import DistPrimIDs, all_gather, wait
    from thunder_tpu.observe import decisions as _decisions

    bsyms = list(trc.bound_symbols)
    out: list = []
    swap: dict = {}
    n_decomposed = 0
    for b in bsyms:
        if swap:
            b = b.from_bsym_swap_proxies(swap, skip_output=True)
        if (b.sym.id is DistPrimIDs.SYNCHRONIZE
                and len(b.args) >= 4
                and b.args[2] is DistParallelType.FULLY_SHARDED
                and isinstance(b.output, Proxy)):
            a, axis, _ptype, size = b.args[:4]
            scope: list = []
            with tracectx(trc):
                trc.push_scope(scope)
                gathered = wait(all_gather(a, axis, 0, size))
                trc.pop_scope()
            out.extend(scope)
            swap[Variable(b.output)] = gathered
            n_decomposed += 1
            continue
        out.append(b)
    if not n_decomposed:
        return trc
    if _decisions.active():
        _decisions.record(
            "comm", "synchronize", None, "decomposed",
            reason=(f"{n_decomposed} FULLY_SHARDED synchronize -> "
                    f"all_gather + wait issue/wait pair(s)"),
            cost={"decomposed": n_decomposed})
    new = from_trace(trc)
    new.bound_symbols = out
    new.set_provenance("Comm decompose (synchronize -> all_gather + wait)")
    return new


# ---------------------------------------------------------------------------
# stage 2: small-collective bucketing
# ---------------------------------------------------------------------------

def bucket_collectives(trc: TraceCtx, *, n_dev: int = 1,
                       bucket_bytes: int | None = None,
                       max_bucket_bytes: int | None = None,
                       ici_bw: float | None = None) -> TraceCtx:
    """Coalesce sub-threshold ``all_gather``/``reduce_scatter`` issue/wait
    pairs that share (kind, dtype, mesh axis, size) into one fused
    ``bucketed_*`` issue/wait pair plus per-member unpack slices. Byte-model
    gated: members must each be below ``bucket_bytes`` and a bucket's total
    payload never exceeds ``max_bucket_bytes`` (buckets close and a new one
    opens, in trace order — determinism). Every verdict — ``bucketed``,
    ``kept`` (singleton), and the pass summary — lands on the decision log.

    The rewrite places each fused group at the LAST member's issue site, so
    linear order is only locally violated for consumers of earlier members;
    the caller MUST re-sort with :func:`sort_waits` (the transform does)."""
    from thunder_tpu.core import cost_model as _cm
    from thunder_tpu.core.proxies import Proxy, Variable
    from thunder_tpu.core.pytree import tree_flatten
    from thunder_tpu.distributed.prims import (
        DistPrimIDs, bucket_unpack_gather, bucket_unpack_scatter,
        bucketed_all_gather, bucketed_reduce_scatter, wait)
    from thunder_tpu.observe import decisions as _decisions

    bucket_bytes = bucket_bytes if bucket_bytes is not None else _cm.COMM_BUCKET_MIN_BYTES
    max_bucket_bytes = (max_bucket_bytes if max_bucket_bytes is not None
                        else _cm.COMM_BUCKET_MAX_BYTES)
    ici_bw = ici_bw if ici_bw is not None else _cm.ICI_BW_BYTES_PER_S

    bsyms = list(trc.bound_symbols)

    # future var -> (consumer indices that are waits, consumer indices that
    # are anything else non-del)
    wait_of: dict = {}
    other_use: set = set()
    for i, b in enumerate(bsyms):
        is_del = b.sym.id is PrimIDs.PYTHON_DEL
        for v in consumed_vars(b):
            if is_del:
                continue
            if _is_wait(b):
                wait_of.setdefault(v, []).append(i)
            else:
                other_use.add(v)

    # candidate members: dim-0 all_gather/reduce_scatter whose future feeds
    # exactly one wait and nothing else
    members: list[dict] = []
    kept_large = 0
    for i, b in enumerate(bsyms):
        if b.sym.id not in (DistPrimIDs.ALL_GATHER, DistPrimIDs.REDUCE_SCATTER):
            continue
        if len(b.args) < 4 or b.args[2] != 0:
            continue
        fut = b.output
        if not isinstance(fut, Proxy):
            continue
        fv = Variable(fut)
        waits = wait_of.get(fv, [])
        if len(waits) != 1 or fv in other_use:
            continue
        a = b.args[0]
        payload = max(_proxy_bytes(a), _proxy_bytes(fut))
        if payload >= bucket_bytes:
            kept_large += 1
            continue
        members.append({
            "issue_idx": i, "wait_idx": waits[0], "a": a, "fut": fut,
            "out": bsyms[waits[0]].output, "payload": payload,
            "out_bytes": _proxy_bytes(fut),
            "kind": b.sym.id, "key": (b.sym.id, str(a.dtype), b.args[1], b.args[3]),
            "axis": b.args[1], "size": b.args[3]})

    # group into buckets per key, closing at the byte cap (trace order)
    by_key: dict = {}
    for m in members:
        by_key.setdefault(m["key"], []).append(m)
    buckets: list[list[dict]] = []
    singletons = 0
    for key in sorted(by_key, key=str):
        cur: list[dict] = []
        cur_bytes = 0
        for m in by_key[key]:
            if cur and cur_bytes + m["payload"] > max_bucket_bytes:
                buckets.append(cur)
                cur, cur_bytes = [], 0
            cur.append(m)
            cur_bytes += m["payload"]
        if cur:
            buckets.append(cur)
    small = [b for b in buckets if len(b) < 2]
    buckets = [b for b in buckets if len(b) >= 2]
    singletons = len(small)

    if _decisions.active():
        for b1 in small:
            m = b1[0]
            _decisions.record(
                "comm", bsyms[m["issue_idx"]].sym.name, None, "kept",
                reason="singleton bucket — nothing to coalesce with",
                cost={"payload_bytes": m["payload"]})
        _decisions.record(
            "comm", "comm_bucketing", None, "scheduled",
            reason=(f"{len(members)} sub-threshold candidate(s): "
                    f"{len(buckets)} bucket(s), {singletons} singleton(s), "
                    f"{kept_large} above threshold"),
            cost={"candidates": len(members), "buckets": len(buckets),
                  "singletons": singletons, "kept_large": kept_large,
                  "bucket_bytes_min": bucket_bytes,
                  "bucket_bytes_max": max_bucket_bytes})
    if not buckets:
        return trc

    drop: set[int] = set()
    dropped_futs: set = set()
    swap: dict = {}
    insert_at: dict[int, list] = {}
    for bucket in buckets:
        anchor = max(m["issue_idx"] for m in bucket)
        axis, size = bucket[0]["axis"], bucket[0]["size"]
        is_gather = bucket[0]["kind"] is DistPrimIDs.ALL_GATHER
        scope: list = []
        with tracectx(trc):
            trc.push_scope(scope)
            if is_gather:
                fut = bucketed_all_gather(axis, size, *[m["a"] for m in bucket])
            else:
                fut = bucketed_reduce_scatter(axis, size, *[m["a"] for m in bucket])
            got = wait(fut)
            offset = 0
            for m in bucket:
                shape = tuple(m["out"].shape)
                numel = 1
                for d in shape:
                    numel *= int(d)
                if is_gather:
                    unpacked = bucket_unpack_gather(got, offset, shape)
                    offset += numel // size  # per-device run length
                else:
                    unpacked = bucket_unpack_scatter(got, offset, shape)
                    offset += numel
                swap[Variable(m["out"])] = unpacked
            trc.pop_scope()
        insert_at.setdefault(anchor, []).extend(scope)
        for m in bucket:
            drop.add(m["issue_idx"])
            drop.add(m["wait_idx"])
            dropped_futs.add(Variable(m["fut"]))
        if _decisions.active():
            kind_name = "bucketed_all_gather" if is_gather else "bucketed_reduce_scatter"
            cost = _cm.comm_bucket_cost(
                kind_name, [m["out_bytes"] for m in bucket], n_dev, ici_bw)
            cost["dtype"] = bucket[0]["key"][1]
            cost["mesh_axis"] = axis
            _decisions.record(
                "comm", kind_name, None, "bucketed",
                reason=(f"{len(bucket)} {bsyms[bucket[0]['issue_idx']].sym.name}(s) "
                        f"({bucket[0]['key'][1]}, axis {axis!r}) -> 1 fused "
                        f"issue/wait pair, est {cost['est_saved_us']:.1f} µs saved"),
                cost=cost)

    out: list = []
    for i, b in enumerate(bsyms):
        if i in insert_at:
            out.extend(insert_at[i])
        if i in drop:
            continue
        if b.sym.id is PrimIDs.PYTHON_DEL \
                and any(v in dropped_futs for v in consumed_vars(b)):
            continue
        if swap:
            b = b.from_bsym_swap_proxies(swap, skip_output=True)
        out.append(b)

    new = from_trace(trc)
    new.bound_symbols = out
    new.set_provenance(f"Comm bucketing ({len(buckets)} fused bucket(s))")
    return new


# ---------------------------------------------------------------------------
# stage 3: the cost-aware reschedule
# ---------------------------------------------------------------------------

def sort_waits(trc: TraceCtx, *, n_dev: int = 1,
               ici_bw: float | None = None,
               inflight_cap_bytes: int | None = None) -> TraceCtx:
    """Reorder ``trc`` so collective issues run ASAP — subject to the
    in-flight byte cap — and waits run ALAP.

    Comments/dels are pinned to their predecessor op; the return stays last.
    While scheduling, a modeled clock accrues each emitted group's compute
    time (``cost_model.bsym_us``); a collective's overlap window is the
    clock delta between its issue and its wait, compared against its
    ring-model transfer time. When issuing one more collective would push
    the outstanding future payload past ``inflight_cap_bytes``, the issue
    defers (compute and covered waits run first) — hoisting every
    collective to step start is exactly the buffer blow-up this cap
    prevents."""
    from thunder_tpu.core import cost_model as _cm
    from thunder_tpu.observe import decisions as _decisions

    ici_bw = ici_bw if ici_bw is not None else _cm.ICI_BW_BYTES_PER_S
    cap = (inflight_cap_bytes if inflight_cap_bytes is not None
           else _cm.COLLECTIVE_INFLIGHT_CAP_BYTES)

    bsyms = list(trc.bound_symbols)

    # pin non-semantic markers (comments, dels, prints) to their predecessor
    groups: list[list] = []
    for b in bsyms:
        if b.sym.id in (PrimIDs.COMMENT, PrimIDs.PYTHON_DEL, PrimIDs.PYTHON_PRINT) and groups:
            groups[-1].append(b)
        else:
            groups.append([b])

    n = len(groups)
    produced_by: dict = {}
    for gi, grp in enumerate(groups):
        for b in grp:
            for v in produced_vars(b):
                produced_by[v] = gi

    deps: list[set] = [set() for _ in range(n)]
    consumers: dict = {}   # var -> groups with a NON-del use
    for gi, grp in enumerate(groups):
        for b in grp:
            is_del = b.sym.id is PrimIDs.PYTHON_DEL
            for v in consumed_vars(b):
                src = produced_by.get(v)
                if src is not None and src != gi:
                    deps[gi].add(src)
                if not is_del:
                    consumers.setdefault(v, set()).add(gi)
    # a group carrying `del x` must run after EVERY group that uses x —
    # producer→consumer edges alone would let independent compute (and its
    # pinned del) overtake a consumer waiting on a sunk collective
    for gi, grp in enumerate(groups):
        for b in grp:
            if b.sym.id is PrimIDs.PYTHON_DEL:
                for v in consumed_vars(b):
                    for cg in consumers.get(v, ()):
                        if cg != gi:
                            deps[gi].add(cg)

    ret_idx = next((gi for gi, grp in enumerate(groups)
                    if grp[0].sym.id is PrimIDs.PYTHON_RETURN), None)

    indegree = [len(d) for d in deps]
    dependents: list[list] = [[] for _ in range(n)]
    for gi, d in enumerate(deps):
        for src in d:
            dependents[src].append(gi)

    # per-group scheduling metadata
    CAT_ISSUE, CAT_OTHER, CAT_WAIT = 0, 1, 2
    cat = [CAT_OTHER] * n
    group_us = [0.0] * n
    fut_bytes = [0] * n
    transfer_us = [0.0] * n
    fut_vars: list[list] = [[] for _ in range(n)]
    from thunder_tpu.core.proxies import FutureTensorProxy, Variable
    from thunder_tpu.core.pytree import tree_flatten

    for gi, grp in enumerate(groups):
        head = grp[0]
        if _is_issue(head):
            cat[gi] = CAT_ISSUE
            outs, _ = tree_flatten(head.output)
            for o in outs:
                if isinstance(o, FutureTensorProxy):
                    fut_vars[gi].append(Variable(o))
                    fut_bytes[gi] += _proxy_bytes(o)
            transfer_us[gi] = _cm.collective_transfer_us(
                head.sym.name, fut_bytes[gi], n_dev, ici_bw)
        elif _is_wait(head):
            cat[gi] = CAT_WAIT
        else:
            group_us[gi] = sum(_cm.bsym_us(b) for b in grp)

    # deterministic greedy selection: category preference with the ORIGINAL
    # group index as the only tiebreak. No clock, no hash order — every SPMD
    # rank schedules identically (the no-deadlock invariant).
    ready: list[set] = [set(), set(), set()]  # by category
    for gi in range(n):
        if indegree[gi] == 0 and gi != ret_idx:
            ready[cat[gi]].add(gi)

    order: list[int] = []
    t_now = 0.0
    inflight = 0
    open_futs: dict = {}  # Variable -> issue info
    pairs: list[dict] = []
    cap_deferrals = 0
    cap_forced = 0
    new_pos_of: dict[int, int] = {}

    def covered(wg: int) -> bool:
        for v in consumed_vars(groups[wg][0]):
            info = open_futs.get(v)
            if info is not None and (t_now - info["issue_t"]) < info["transfer_us"]:
                return False
        return True

    while ready[0] or ready[1] or ready[2]:
        pick = None
        if ready[CAT_ISSUE]:
            for gi in sorted(ready[CAT_ISSUE]):
                if inflight + fut_bytes[gi] <= cap:
                    pick = gi
                    break
            if pick is None:
                cap_deferrals += 1
        if pick is None and ready[CAT_ISSUE] and ready[CAT_WAIT]:
            # cap-blocked: retire a covered wait to free in-flight budget
            cov = [wg for wg in sorted(ready[CAT_WAIT]) if covered(wg)]
            if cov:
                pick = cov[0]
        if pick is None and ready[CAT_OTHER]:
            pick = min(ready[CAT_OTHER])
        if pick is None and ready[CAT_WAIT]:
            pick = min(ready[CAT_WAIT])
        if pick is None:  # only cap-blocked issues remain: forced
            pick = min(ready[CAT_ISSUE])
            cap_forced += 1

        ready[cat[pick]].discard(pick)
        new_pos_of[pick] = len(order)
        order.append(pick)
        if cat[pick] == CAT_ISSUE:
            for v in fut_vars[pick]:
                open_futs[v] = {"issue_gi": pick, "issue_t": t_now,
                                "transfer_us": transfer_us[pick],
                                "bytes": fut_bytes[pick]}
            inflight += fut_bytes[pick]
        elif cat[pick] == CAT_WAIT:
            for v in consumed_vars(groups[pick][0]):
                info = open_futs.pop(v, None)
                if info is None:
                    continue
                inflight -= info["bytes"]
                window = t_now - info["issue_t"]
                pairs.append({
                    "issue_gi": info["issue_gi"], "wait_gi": pick,
                    "bytes": info["bytes"],
                    "window_us": window, "transfer_us": info["transfer_us"],
                    "overlap_us": min(window, info["transfer_us"]),
                    "covered": window >= info["transfer_us"]})
        t_now += group_us[pick]
        for dep in dependents[pick]:
            indegree[dep] -= 1
            if indegree[dep] == 0 and dep != ret_idx:
                ready[cat[dep]].add(dep)

    if ret_idx is not None:
        new_pos_of[ret_idx] = len(order)
        order.append(ret_idx)
    if len(order) != n:  # cycle (malformed trace): bail out, VISIBLY
        if _decisions.active():
            _decisions.record(
                "comm", "comm_reorder", None, "bailout",
                reason=(f"dependency cycle: {n - len(order)} of {n} group(s) "
                        f"unschedulable — trace left unscheduled"),
                cost={"groups": n, "scheduled": len(order)})
        return trc

    _report(groups, order, new_pos_of, pairs,
            {"n_dev": n_dev, "inflight_cap_bytes": cap,
             "cap_deferrals": cap_deferrals, "cap_forced": cap_forced})

    new = from_trace(trc)
    for gi in order:
        new.bound_symbols.extend(groups[gi])
    new.set_provenance("Comm reorder (cost-aware issue hoist, wait sink)")
    return new


def _report(groups, order, new_pos, pairs, sched_stats) -> None:
    """Record what the reschedule DID as decisions (kind ``comm``): the pass
    summary (hoists, sinks, covered/exposed windows, cap pressure) and one
    ``overlap_window`` decision PER (issue, wait) pair — a wait that retires
    several futures reports each pair, and every window carries modeled
    compute-µs against the collective's ring-model transfer time, not just
    group-index distance. Rendered by ``observe.explain()``'s comm section."""
    from thunder_tpu.distributed import prims as dist_prims
    from thunder_tpu.observe import decisions as _decisions

    if not _decisions.active():
        return
    issues = [gi for gi in range(len(groups)) if _is_issue(groups[gi][0])]
    waits = [gi for gi in range(len(groups)) if _is_wait(groups[gi][0])]
    if not issues and not waits:
        return
    hoisted = sum(1 for gi in issues if new_pos[gi] < gi)
    sunk = sum(1 for gi in waits if new_pos[gi] > gi)
    n_covered = sum(1 for p in pairs if p["covered"])
    modeled_overlap = sum(p["overlap_us"] for p in pairs)
    _decisions.record(
        "comm", "comm_reorder", None, "scheduled",
        reason=(f"{hoisted} issue(s) hoisted, {sunk} wait(s) sunk; "
                f"{n_covered}/{len(pairs)} window(s) cover their transfer"),
        cost={"hoisted_issues": hoisted, "sunk_waits": sunk,
              "issues": len(issues), "waits": len(waits),
              "covered_windows": n_covered,
              "exposed_windows": len(pairs) - n_covered,
              "modeled_overlap_us": round(modeled_overlap, 3),
              **sched_stats})
    pinned = sum(1 for gi in issues
                 if groups[gi][0].sym.name in ("reduce_scatter",
                                               "bucketed_reduce_scatter"))
    if pinned and dist_prims.pin_collectives():
        _decisions.record(
            "comm", "reduce_scatter", None, "pinned",
            reason=(f"{pinned} grad reduce-scatter(s) lowered behind "
                    f"optimization_barrier (prims.pin_collectives()) — "
                    f"XLA cannot rewrite them into all-reduces"),
            cost={"count": pinned})
    from thunder_tpu.core import cost_model as _cm

    n_dev = sched_stats.get("n_dev", 1)
    for p in sorted(pairs, key=lambda q: (new_pos[q["issue_gi"]],
                                          new_pos[q["wait_gi"]])):
        src, wg = p["issue_gi"], p["wait_gi"]
        kind = groups[src][0].sym.name
        _decisions.record(
            "comm", kind, None, "overlap_window",
            reason=(f"issue@{new_pos[src]} wait@{new_pos[wg]} — "
                    f"{'covered' if p['covered'] else 'exposed'}"),
            # transfer_us doubles as this pair's est prediction
            # (est_transfer_us) so the residual ledger joins measured
            # issue->wait windows against the ICI model; recv_bytes is the
            # fit component observe.calibrate regresses ICI_BW_BYTES_PER_S /
            # COLLECTIVE_LAUNCH_US against
            cost=_cm.stamp_calibration(
                {"issue_at": new_pos[src], "wait_at": new_pos[wg],
                 "distance": new_pos[wg] - new_pos[src],
                 "distance_before": wg - src,
                 "recv_bytes": _cm.ring_recv_bytes(
                     kind, p.get("bytes", 0), n_dev),
                 "n_dev": n_dev,
                 "window_us": round(p["window_us"], 3),
                 "transfer_us": round(p["transfer_us"], 3),
                 "est_transfer_us": round(p["transfer_us"], 3),
                 "overlap_us": round(p["overlap_us"], 3),
                 "covered": p["covered"]}))


class CommReorderTransform(Transform):
    """The overlap-scheduling pass as a trace transform: decompose
    synchronous gathers, bucket sub-threshold collectives, then run the
    cost-aware reschedule — all BEFORE executor dispatch/fusion, so the
    scheduled issue/wait positions shape the order of collective calls in
    the generated program (inside fusion regions too). Pass via
    ``transforms=[CommReorderTransform(...)]`` or ``comm_reorder=True`` /
    ``comm_reorder={...options}`` on the distributed wrappers (which plumb
    the mesh's collective-axis size through ``n_dev``)."""

    def __init__(self, *, n_dev: int = 1, ici_bw: float | None = None,
                 inflight_cap_bytes: int | None = None,
                 bucket_bytes: int | None = None,
                 max_bucket_bytes: int | None = None,
                 decompose: bool = True, bucket: bool = True):
        self.n_dev = n_dev
        self.ici_bw = ici_bw
        self.inflight_cap_bytes = inflight_cap_bytes
        self.bucket_bytes = bucket_bytes
        self.max_bucket_bytes = max_bucket_bytes
        self.decompose = decompose
        self.bucket = bucket

    def transform_traces_pre_prologue(self, prologue_trc, computation_trc,
                                      epilogue_trc, **kw):
        from thunder_tpu.observe import decisions as _decisions

        trc = computation_trc
        if self.decompose:
            trc = decompose_collectives(trc)
        bucketed = trc
        if self.bucket:
            bucketed = bucket_collectives(
                trc, n_dev=self.n_dev, bucket_bytes=self.bucket_bytes,
                max_bucket_bytes=self.max_bucket_bytes, ici_bw=self.ici_bw)
        sched = sort_waits(bucketed, n_dev=self.n_dev, ici_bw=self.ici_bw,
                           inflight_cap_bytes=self.inflight_cap_bytes)
        if sched is bucketed and bucketed is not trc:
            # the bucket rewrite introduced a dependency cycle (a member's
            # input depended on another member's output): fall back to
            # scheduling the unbucketed trace rather than skipping the pass
            if _decisions.active():
                _decisions.record(
                    "comm", "comm_bucketing", None, "fallback",
                    reason=("bucketed trace has a dependency cycle; "
                            "scheduling the unbucketed trace instead"))
            sched = sort_waits(trc, n_dev=self.n_dev, ici_bw=self.ici_bw,
                               inflight_cap_bytes=self.inflight_cap_bytes)
        return prologue_trc, sched, epilogue_trc
