"""Reader ``compile_events``: JAX's own compile events, as the harness's
``CompileMeter`` summed them up to the window's opening and inside it."""

from __future__ import annotations


def read(ctx, field: str):
    c = ctx.readings.get("compile", {})
    if field == "programs_in_window":
        return c.get("programs_in_window")
    return c.get("at_open", {}).get(field)
