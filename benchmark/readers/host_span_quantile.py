"""Reader ``host_span_quantile``: a nearest-rank percentile of the lengths,
in ms, of the program's spans of the given names inside the traced window
(``host_spans`` stops at the median)."""

from __future__ import annotations


def read(ctx, names, q: float):
    from thunder_tpu import observe

    window = ctx.load("readers", "program_events").traced_window_us(ctx)
    if window is None:
        return None
    w0, w1 = window
    ms = sorted(s["dur_us"] / 1e3 for s in observe.get_registry().spans
                if s["name"] in names and w0 <= s["ts_us"] < w1)
    if not ms:
        return None
    return ms[min(len(ms) - 1, int(len(ms) * q / 100.0))]
