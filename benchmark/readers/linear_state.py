"""Reader ``linear_state``: the delta rule's decode kernel (a linear-attention
layer's recurrent state, read and written once a step) against its
roofline. The least time is the family's ``kda_decode_counts`` over the rows
whose state each decode step of the traced window read and wrote (the
``state_rows`` argument of its ``decode_dispatch`` span: every slot's row,
since the kernel walks the whole pool); the time is the
device's, of the ops named by ``kernels``.

A program without the argument (the parent of the PR that brought it), or a
family without the counts, gives ``None``, and the line leaves the metric
out.
"""

from __future__ import annotations


def state_rows(ctx) -> list:
    from thunder_tpu import observe

    window = ctx.load("readers", "program_events").traced_window_us(ctx)
    if window is None:
        return []
    w0, w1 = window
    return [s["args"]["state_rows"] for s in observe.get_registry().spans
            if s["name"] == "decode_dispatch" and w0 <= s["ts_us"] < w1
            and "state_rows" in (s.get("args") or {})]


def read(ctx, kernels):
    counts = getattr(ctx.family, "kda_decode_counts", None)
    tr, peaks = ctx.readings.get("trace"), ctx.peaks
    rows = state_rows(ctx)
    if counts is None or tr is None or peaks is None or not rows:
        return None
    seconds = ctx.load("readers", "device_trace").kernel_seconds(tr, kernels)
    if not seconds:
        return None
    works = [counts(ctx.spec, r) for r in rows]
    least = max(sum(w["flops"] for w in works) / peaks["flops_bf16"],
                sum(w["bytes"] for w in works) / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
