"""Reader ``span_arg``: a numeric argument of the program's spans of the given
names inside the traced window, summed and taken by the step (``per``). A
span that lacks the argument (a program older than the argument) counts for
nothing; where none has it, ``None``."""

from __future__ import annotations


def read(ctx, names, arg: str, per: str = "steps"):
    from thunder_tpu import observe

    window = ctx.load("readers", "program_events").traced_window_us(ctx)
    if window is None:
        return None
    w0, w1 = window
    values = [s["args"][arg] for s in observe.get_registry().spans
              if s["name"] in names and w0 <= s["ts_us"] < w1
              and arg in (s.get("args") or {})]
    n = ctx.readings["counts"].get(per)
    if not values or not n:
        return None
    return sum(values) / n
