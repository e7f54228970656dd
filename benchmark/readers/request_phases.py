"""Reader ``request_phases``: a request's road to its first token, as four
phases that add up to the program's own time to first token, joined on
``request`` from records that all close by then (the program's lifecycle
spans and its ``serving_first_token`` event):

    queue         submit -> the last admission before the token: every
                  ``queued`` span adds up, and so does a residency that a
                  preemption or a restart threw away (it gave nothing)
    prefill_wait  that admission -> the start of its first ``prefill_chunk``
    prefill       that start -> the prompt resident (``resident_us``)
    first_decode  resident -> the event

Counted: every request whose first token fell at or after the traced
window's opening — the whole measured window, and not only the seconds the
profiler ran. A request with no first token yet is left out, as is one whose
token came before the opening.
"""

from __future__ import annotations

import statistics

PHASES = ("queue", "prefill_wait", "prefill", "first_decode")


def phases(spans, events, since_us: float = float("-inf")) -> dict:
    """``{request: {phase: ms}}`` for every ``serving_first_token`` event at
    or after ``since_us`` that carries ``request`` and ``resident_us``."""
    queued: dict = {}
    chunks: dict = {}
    for s in spans:
        r = s.get("args", {}).get("request")
        if s["name"] == "queued":
            queued.setdefault(r, []).append(s)
        elif s["name"] == "prefill_chunk":
            chunks.setdefault(r, []).append(s["ts_us"])
    out = {}
    for e in events:
        if e.get("kind") != "serving_first_token" or e["ts_us"] < since_us \
                or e.get("resident_us") is None:
            continue
        r, first, resident = e["request"], e["ts_us"], e["resident_us"]
        waits = [s for s in queued.get(r, ())
                 if s["ts_us"] + s["dur_us"] <= resident]
        if not waits:
            continue
        submitted = min(s["ts_us"] for s in waits)
        admitted = max(s["ts_us"] + s["dur_us"] for s in waits)
        # a forked clone, or a prompt the prefix cache held whole, has no
        # chunk of its own: resident as it is admitted
        chunk0 = min((t for t in chunks.get(r, ()) if admitted <= t <= resident),
                     default=resident)
        out[r] = {"queue": (admitted - submitted) / 1e3,
                  "prefill_wait": (chunk0 - admitted) / 1e3,
                  "prefill": (resident - chunk0) / 1e3,
                  "first_decode": (first - resident) / 1e3}
    return out


def read(ctx, phase: str, stat: str = "mean"):
    from thunder_tpu import observe

    if phase not in PHASES:
        raise ValueError(f"request_phases: no phase {phase!r}")
    window = ctx.load("readers", "program_events").traced_window_us(ctx)
    if window is None:
        return None
    reg = observe.get_registry()
    got = phases(list(reg.spans), list(reg.events), since_us=window[0])
    ms = [p[phase] for p in got.values()]
    if not ms:
        return None
    if stat == "mean":
        return statistics.fmean(ms)
    if stat == "p50":
        return statistics.median(ms)
    raise ValueError(f"request_phases: no statistic {stat!r}")
