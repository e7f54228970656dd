"""Reader ``moe_route``: what the traced window's ``moe_route`` events (one a
layer a decode step: ``hit`` held experts, ``local_picks`` assignments,
``max_load``, ``streamed`` held experts) say of the expert layer, and the
two shares whose bytes depend on them. The family's ``decode_step_counts``
holds no routed expert (the harness hands it no routing); this reader adds
``routed_counts`` of what the events report: the bytes of the experts the
kernel STREAMED (the program gives every held expert a row, so these are
more than the hit ones), the operations of the picks — what the step moved,
never more.

A program without the event (the parent of the PR that brought it) gives
``None``, and the line leaves the metric out.
"""

from __future__ import annotations


def events(ctx) -> list:
    from thunder_tpu import observe

    window = ctx.load("readers", "program_events").traced_window_us(ctx)
    if window is None:
        return []
    w0, w1 = window
    return [e for e in observe.get_registry().events
            if e["kind"] == "moe_route" and w0 <= e["ts_us"] < w1]


def read(ctx, quantity: str, kernels=None):
    """``hit_mean`` / ``local_picks_mean``: a layer-step's mean;
    ``mfu``: the whole decode step's share of the roofline (the family's
    step counts plus the routed experts' of the events) against the decode
    steps' seconds; ``roofline``: the expert kernel's (``kernels``) share,
    least time of ``moe_block_counts`` over the events / its device time."""
    ev = events(ctx)
    if not ev:
        return None
    streamed = lambda e: e.get("streamed", e["hit"])
    if quantity == "hit_mean":
        return sum(e["hit"] for e in ev) / len(ev)
    if quantity == "local_picks_mean":
        return sum(e["local_picks"] for e in ev) / len(ev)
    fam, spec, peaks = ctx.family, ctx.spec, ctx.peaks
    if peaks is None:
        return None
    slots = ctx.traffic["engine"]["max_slots"]
    least = lambda w: max(w["flops"] / peaks["flops_bf16"],
                          w["bytes"] / peaks["hbm_bytes_per_s"])
    total = lambda works: {k: sum(w[k] for w in works)
                           for k in ("flops", "bytes")}
    if quantity == "mfu":
        base = ctx.readings["counts"].get("step_decode")
        seconds = ctx.readings["counts"].get("decode_s")
        if not base or not seconds:
            return None
        work = total([base] + [fam.routed_counts(spec, streamed(e),
                                                  e["local_picks"])
                               for e in ev])
        return 100.0 * least(work) / ctx.chips / seconds
    if quantity == "roofline":
        tr = ctx.readings.get("trace")
        if tr is None:
            return None
        dt = ctx.load("readers", "device_trace")
        s = dt.kernel_seconds(tr, kernels)
        if not s:
            return None
        work = total([fam.moe_block_counts(spec, slots, streamed(e),
                                           e["local_picks"]) for e in ev])
        return 100.0 * least(work) / s
    raise ValueError(f"moe_route: no quantity {quantity!r}")
