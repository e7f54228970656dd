"""Driver for traffic of kind ``serve_open``: requests arrive on a schedule
drawn from the seed, at the rate the traffic file fixes, whatever the engine
does. Every latency runs from the time a request was DUE.

    ttft_mean_ms = mean over requests due in the window of first token - due
    itl_p95_ms   = 95th percentile of every gap between consecutive tokens
"""

from __future__ import annotations

import statistics


class Schedule:
    """Poisson arrivals as a cycle as long as the window: a fixed set of
    exponential gaps (mid-quantiles, scaled to fill the window) in a fixed
    order, so that every window holds the same arrivals. The seed draws the
    phase at which the cycle is entered (and every prompt's tokens); ramp and
    window run through the same cycle."""

    def __init__(self, ctx, serving):
        import numpy as np

        t = ctx.traffic
        n = max(1, round(t["rate_per_s"] * ctx.seconds))
        order = np.random.default_rng(t["order_seed"])
        gaps = order.permutation(-np.log(1.0 - (np.arange(n) + 0.5) / n))
        self.at = np.cumsum(gaps) * ctx.seconds / gaps.sum()   # in (0, seconds]
        self.plen = order.permutation(serving.quantile_lengths(t["prompt"], n))
        self.olen = order.permutation(serving.quantile_lengths(t["output"], n))
        self.rng = np.random.default_rng([ctx.seed, 12])
        self.phase = self.rng.uniform(0.0, ctx.seconds)
        self.ctx, self.todo = ctx, []

    def start(self, now: float, ramp_s: float) -> None:
        import numpy as np

        span = self.ctx.seconds
        dues = sorted((((a - self.phase) % span) + j * span, k)
                      for k, a in enumerate(self.at)
                      for j in range(int(ramp_s / span) + 3))
        self.todo = [(now + off, {
            "prompt": self.rng.integers(1, self.ctx.spec.V,
                                        size=int(self.plen[k]), dtype=np.int32),
            "max_new": int(self.olen[k])}) for off, k in dues][::-1]

    def admit(self, sv, now: float) -> None:
        while self.todo and self.todo[-1][0] <= now:
            due, rec = self.todo.pop()
            rec["due"] = due
            sv.submit(rec, now)


def run(ctx) -> dict:
    serving = ctx.load("drivers", "serving")
    t = ctx.traffic
    sv = serving.Serving(ctx)
    sv.warm_up()
    m = serving.run_window(ctx, sv, Schedule(ctx, serving))
    gaps = sorted(m["gaps_ms"])
    return serving.finish(ctx, sv, m, {
        "ttft_mean_ms": statistics.fmean(m["ttft_ms"]) if m["ttft_ms"] else None,
        "itl_p95_ms": gaps[int(0.95 * len(gaps))] if gaps else None})
