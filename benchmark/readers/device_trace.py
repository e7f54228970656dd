"""Reader ``device_trace``: the reduction from the profiler's trace to
numbers. Busy and idle share, time by kernel, the idle gaps by what the host
was doing, collective time that no compute hides, and a kernel's share of
its roofline.

``reduce`` works on plain lists (so a small recorded trace can check it):

    device_events  {device: [(name, start_ns, dur_ns), ...]}   the op line
    host_spans     [(name, start_ns, end_ns), ...]              same clock
    window         (start_ns, end_ns)
"""

from __future__ import annotations

import glob
import os
import re
import shutil

OP_LINE = "XLA Ops"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|all-to-all|reduce-scatter|collective-permute"
    r"|^send|^recv", re.I)


def op_name(event_name: str) -> str:
    """The op's short name out of the trace's event name, which is the HLO
    line (``%fusion.49 = bf16[...] fusion(...)``). Pallas kernels lose the
    instance suffix the compiler adds (``_1.3``), so a kernel's launches sum
    under one name."""
    name = re.match(r"%?([^\s=]+)", event_name).group(1)
    if name.startswith("pallas_"):
        name = re.sub(r"(_\d+)?(\.\d+)?$", "", name)
    return name


def union(intervals) -> list:
    """Sorted, merged copy of [(start, end), ...]."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        elif e > s:
            out.append([s, e])
    return out


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> list:
    """Parts of merged intervals ``a`` that merged intervals ``b`` leave."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k, cur = j, s
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def clip(events, window):
    w0, w1 = window
    for name, s, d in events:
        a, b = max(s, w0), min(s + d, w1)
        if b > a:
            yield name, a, b


def attribute_gaps(gaps, host_spans) -> dict:
    """Each idle gap goes to the host spans that cover it, the shortest span
    first (the innermost says most about what the host did); what no span
    covers is ``unattributed``."""
    spans = sorted(host_spans, key=lambda x: x[2] - x[1])
    by: dict = {}
    for g in gaps:
        left = [list(g)]
        for name, s, e in spans:
            if e <= g[0] or s >= g[1] or not left:
                continue
            hit = total([[max(a, s), min(b, e)] for a, b in left
                         if min(b, e) > max(a, s)])
            if hit:
                by[name] = by.get(name, 0.0) + hit
                left = subtract(left, [[s, e]])
        rest = total(left)
        if rest:
            by["unattributed"] = by.get("unattributed", 0.0) + rest
    return by


def reduce(device_events: dict, host_spans: list, window: tuple) -> dict:
    w0, w1 = window
    n_dev = max(len(device_events), 1)
    busy = exposed = 0.0
    ops: dict = {}
    gaps_by: dict = {}
    for dev in sorted(device_events):
        evs = list(clip(device_events[dev], window))
        merged = union((a, b) for _, a, b in evs)
        busy += total(merged)
        for name, a, b in evs:
            ops[name] = ops.get(name, 0.0) + (b - a)
        coll = union((a, b) for n, a, b in evs if COLLECTIVE.search(n))
        comp = union((a, b) for n, a, b in evs if not COLLECTIVE.search(n))
        exposed += total(subtract(coll, comp))
        if dev == min(device_events):       # the host's spans, first device
            gaps_by = attribute_gaps(subtract([[w0, w1]], merged), host_spans)
    ns = 1e-9
    top = lambda d: [[k, v] for k, v in
                     sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    return {
        "busy_s": busy / n_dev * ns, "window_s": (w1 - w0) * ns,
        "ops_s": {k: v / n_dev * ns for k, v in ops.items()},
        "collective_exposed_s": exposed / n_dev * ns,
        "breakdown": {
            "device_ops": top({k: v / n_dev * ns for k, v in ops.items()}),
            "idle_gaps": top({k: v * ns for k, v in gaps_by.items()})}}


# ---------------------------------------------------------------------------
# from the profiler's file
# ---------------------------------------------------------------------------

def load_xplane(trace_dir: str) -> dict:
    """The device op lines and the host's trace annotations out of the one
    ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {paths}")
    data = ProfileData.from_file(paths[0])
    device, host, lines = {}, [], []
    for plane in data.planes:
        for line in plane.lines:
            lines.append(f"{plane.name} | {line.name}")
            if plane.name.startswith("/device:TPU:") and line.name == OP_LINE:
                device[plane.name] = [
                    (op_name(e.name), e.start_ns, e.duration_ns)
                    for e in line.events]
            elif plane.name.startswith("/host:"):
                host.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events if e.name.startswith("bench:"))
    return {"device": device, "host": host, "lines": lines}


def reduce_run(ctx) -> dict:
    """Reduce this run's trace; the program's spans and the harness's own go
    onto the trace's clock through the ``bench:sync`` annotation."""
    from thunder_tpu import observe

    raw = load_xplane(ctx.trace_dir)
    if not raw["device"]:
        raise RuntimeError(f"no '{OP_LINE}' line on a TPU plane; the trace "
                           f"has: {raw['lines']}")
    sync = [s for s in raw["host"] if s[0] == "bench:sync"]
    if not sync:
        raise RuntimeError("the trace lacks the bench:sync annotation")
    pc0, us0 = ctx.clock_sync
    t0 = sync[0][1]
    on_trace = lambda pc: t0 + (pc - pc0) * 1e9
    spans = [(n, on_trace(a), on_trace(b)) for n, a, b in ctx.bench_spans]
    for s in observe.get_registry().spans:
        a = t0 + (s["ts_us"] - us0) * 1e3
        spans.append((s["name"], a, a + s["dur_us"] * 1e3))
    window = (on_trace(ctx.t_trace_open), on_trace(ctx.t_trace_close))
    out = reduce(raw["device"], spans, window)
    if os.environ.get("BENCH_KEEP_TRACE"):
        ctx.write_json("trace_events.json", {
            "device": raw["device"], "spans": spans, "window": window,
            "lines": raw["lines"]})
    shutil.rmtree(ctx.trace_dir, ignore_errors=True)
    return out


# ---------------------------------------------------------------------------
# the metrics read from it
# ---------------------------------------------------------------------------

def kernel_seconds(trace: dict, patterns) -> float:
    rx = [re.compile(p) for p in patterns]
    return sum(v for k, v in trace["ops_s"].items()
               if any(r.search(k) for r in rx))


def read(ctx, quantity: str, kernels=None, counts=None, per="steps"):
    """``quantity``: ``idle_share`` (%), ``kernel_ms`` (ms a step of the ops
    whose names match ``kernels``), ``collective_exposed_ms`` (ms a step),
    ``roofline`` (% of the least time the work named by ``counts`` needs,
    against the device time of the ops that do it)."""
    tr = ctx.readings.get("trace")
    if tr is None:
        return None
    n = ctx.readings["counts"].get(per)
    if quantity == "idle_share":
        return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
    if quantity == "kernel_ms":
        s = kernel_seconds(tr, kernels)
        return s * 1e3 / n if s and n else None
    if quantity == "collective_exposed_ms":
        return tr["collective_exposed_s"] * 1e3 / n if n else None
    if quantity == "roofline":
        s = kernel_seconds(tr, kernels)
        work = ctx.readings["counts"].get(counts)   # summed over the window
        if not s or not work:
            return None
        least = max(work["flops"] / ctx.peaks["flops_bf16"],
                    work["bytes"] / ctx.peaks["hbm_bytes_per_s"])
        return 100.0 * least / s
    raise ValueError(f"device_trace: no quantity {quantity!r}")
