"""Reader ``host_spans``: the program's own spans (``observe`` registry, host
clock), summed or taken by the step. ``when`` keeps the spans of the traced
window or those of set-up."""

from __future__ import annotations

import statistics


def read(ctx, names, stat: str, when: str = "window", per: str = "steps"):
    from thunder_tpu import observe

    if ctx.clock_sync is None:
        return None
    pc0, us0 = ctx.clock_sync
    w0 = us0 + (ctx.t_trace_open - pc0) * 1e6
    w1 = us0 + (ctx.t_trace_close - pc0) * 1e6
    inside = (lambda s: w0 <= s["ts_us"] < w1) if when == "window" \
        else (lambda s: s["ts_us"] < w0)
    ms = [s["dur_us"] / 1e3 for s in observe.get_registry().spans
          if s["name"] in names and inside(s)]
    if not ms:
        return None
    if stat == "sum_ms":
        return sum(ms)
    if stat == "p50_ms":
        return statistics.median(ms)
    if stat == "per_ms":
        n = ctx.readings["counts"].get(per)
        return sum(ms) / n if n else None
    raise ValueError(f"host_spans: no statistic {stat!r}")
