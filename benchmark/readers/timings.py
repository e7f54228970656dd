"""Reader ``timings``: a statistic of a series the driver took on the host's
clock (``ctx.readings["series"]``)."""

from __future__ import annotations

import statistics


def stat_of(values, stat: str):
    v = sorted(values)
    if not v:
        return None
    if stat == "p50":
        return statistics.median(v)
    if stat == "max":
        return v[-1]
    if stat == "mean":
        return statistics.fmean(v)
    if stat.startswith("p"):                 # nearest-rank percentile
        return v[min(len(v) - 1, int(len(v) * float(stat[1:]) / 100.0))]
    raise ValueError(f"timings: no statistic {stat!r}")


def read(ctx, series: str, stat: str):
    return stat_of(ctx.readings["series"].get(series, []), stat)
