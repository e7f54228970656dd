"""Reader ``program_events``: how many events of one kind the program
emitted (``observe`` registry) inside the traced window, as ``host_spans``
takes it. ``tt.jit``'s ``cache_miss`` is the guard's own count of
recompiles, beside the backend compiles that ``compile_events`` counts; each
such event names its cause under ``reason``."""

from __future__ import annotations


def traced_window_us(ctx):
    """The traced window's edges on the registry's clock, or ``None`` where
    no trace was taken."""
    if ctx.clock_sync is None:
        return None
    pc0, us0 = ctx.clock_sync
    return (us0 + (ctx.t_trace_open - pc0) * 1e6,
            us0 + (ctx.t_trace_close - pc0) * 1e6)


def read(ctx, kind: str):
    from thunder_tpu import observe

    window = traced_window_us(ctx)
    if window is None:
        return None
    w0, w1 = window
    hits = [e for e in observe.get_registry().events
            if e["kind"] == kind and w0 <= e["ts_us"] < w1]
    for e in hits:
        ctx.log(f"{kind} in the window: {e}")
    return len(hits)
