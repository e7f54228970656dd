"""Reader ``utilization``: the least time the chips could take for work the
family counted from shapes (the larger of operations over peak FLOP/s and
bytes over peak bytes/s), against the time it took. ``work`` names the
counts the driver summed over the traced window; ``seconds`` the time."""

from __future__ import annotations


def read(ctx, work: str, seconds: str):
    w = ctx.readings["counts"].get(work)
    s = ctx.readings["counts"].get(seconds)
    if not w or not s or ctx.peaks is None:
        return None
    least = max(w["flops"] / ctx.peaks["flops_bf16"],
                w.get("bytes", 0.0) / ctx.peaks["hbm_bytes_per_s"]) / ctx.chips
    return 100.0 * least / s
