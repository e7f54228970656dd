"""Reader ``memory_stats``: ``peak_bytes_in_use`` of the fullest device, read
once the window has closed and before the reference runs."""

from __future__ import annotations


def read(ctx):
    return ctx.readings.get("memory_peak_bytes")
