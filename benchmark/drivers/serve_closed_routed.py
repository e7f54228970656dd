"""Driver for traffic of kind ``serve_closed_routed``: ``serve_closed``'s
clients, ramp, window and rate, unedited, for a model that routes its tokens
to experts. What differs is what is compared.

``serving.served_gaps`` keeps the WORST served token's gap. In a routed model
that token is a near-tie among the router's picks: any rounding of the hidden
state flips it, a flipped expert moves a logit by tenths, and the worst of
thousands of tokens reads the same under bfloat16 and under fp8 (PERF.md §6,
PR 35). How OFTEN a served token falls below the reference's best, and by how
much, is what tells the precisions apart, so this driver compares two numbers
over the same sample, from one pass of the reference:

    served_logit_gap       the worst token's, as every serving cell has it
                           (a wrong mask or block table shows here)
    served_logit_gap_mean  the mean over every served token compared
                           (a lower precision shows here)

``served_gaps.json`` in the run's directory keeps every token's gap, sorted,
and ``steps.json`` every engine step of the window, in ms.

What counts as attempted differs too. ``serving.window_metrics`` counts the
requests that fall DUE inside the window; a mix whose every request is admitted
in the ramp and outlasts the window (``serve_agent_sat``) has none, and a line
with ``attempted`` 0 is not a result. Here an operation is a request the window
SERVED: one that had a token delivered inside it, or that failed.
"""

from __future__ import annotations

import gc
import math
import time


def served_gaps(ctx, sample: list, control: str | None = None) -> dict:
    """As ``serving.served_gaps`` (the reference once over each prompt with
    its served tokens; with ``control``, the tokens a reference computed in
    that lower precision puts first), keeping every token's gap."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    fam, spec = ctx.family, ctx.spec
    if not sample:
        return {"served_logit_gap": None, "served_logit_gap_mean": None}
    longest = max(len(s["prompt"]) + len(s["tokens"]) for s in sample)
    pad = max(256, 2 ** math.ceil(math.log2(longest)))

    def gaps_fn(params, seq, served, start, precision):
        ref = fam.ref_logits(params, seq, spec)
        pos = start + jnp.arange(served.shape[0])
        rows = ref[pos]
        if precision is not None:
            served = jnp.argmax(
                fam.ref_logits(params, seq, spec, precision)[pos], -1)
        return rows.max(-1) - jnp.take_along_axis(rows, served[:, None], 1)[:, 0]

    gaps_jit = jax.jit(gaps_fn, static_argnames=("precision",))
    gaps = []
    with jax.default_matmul_precision("highest"):
        params = fam.init_params(spec, ctx.seed)
        for s in sample:
            n_p, n_t = len(s["prompt"]), len(s["tokens"])
            seq = np.zeros(pad, np.int32)
            seq[: n_p + n_t] = np.concatenate([s["prompt"], s["tokens"]])
            served = np.zeros(pad, np.int32)
            served[:n_t] = s["tokens"]
            gaps.append(np.asarray(gaps_jit(params, seq, served, n_p - 1,
                                            precision=control))[:n_t])
    gaps = np.sort(np.concatenate(gaps))
    ctx.log(f"compared {gaps.size} served tokens of {len(sample)} requests "
            f"(padded to {pad}); {int((gaps > 0).sum())} below the "
            f"reference's best")
    ctx.write_json(f"served_gaps{'_' + control if control else ''}.json",
                   [float(g) for g in gaps])
    return {"served_logit_gap": float(gaps[-1]),
            "served_logit_gap_mean": float(gaps.mean())}


def served_in_window(records: list, t_open: float, t_close: float) -> dict:
    """The requests the window served (a token delivered inside it) or that
    failed, and how many of them failed."""
    failed = sum(1 for r in records if r["failed"])
    served = sum(1 for r in records if not r["failed"]
                 and any(t_open <= t < t_close for t in r["stamps"]))
    return {"attempted": served + failed, "failed": failed}


def finish(ctx, serving, sv, m: dict, metrics: dict) -> dict:
    """``serving.finish``, with this driver's comparison and its count of
    the requests attempted."""
    sample = serving.sample_finished(ctx, sv, m["t_open"], m["t_close"])
    sv.eng = None

    def compare():
        gc.collect()
        t0 = time.perf_counter()
        got = served_gaps(ctx, sample)
        ctx.log(f"reference: {time.perf_counter() - t0:.1f} s")
        return {k: {"value": v, "limit": ctx.limits.get(k)}
                for k, v in got.items()}

    def control():
        low = {"bfloat16": "fp8", "float32": "bfloat16"}[ctx.spec.dtype]
        return {low: served_gaps(ctx, sample, control=low)}

    return {"t_open": m["t_open"], "metrics": metrics,
            **served_in_window(sv.records, m["t_open"], m["t_close"]),
            "compare": compare, "control": control}


def run(ctx) -> dict:
    serving = ctx.load("drivers", "serving")
    closed = ctx.load("drivers", "serve_closed")
    t = ctx.traffic
    sv = serving.Serving(ctx)
    sv.warm_up()
    n = t["clients"] * t["requests_per_client"]
    m = serving.run_window(ctx, sv, closed.Clients(
        serving.make_requests(ctx, n), t["clients"]))
    # every step of the window, for telling a run's stalls from its pace
    ctx.write_json("steps.json", [
        round((b - a) * 1e3, 3) for a, b, *_ in sv.steps
        if m["t_open"] <= a and b <= m["t_close"]])
    return finish(ctx, serving, sv, m,
                  {"serve_out_tok_s": m["tokens"] / m["window_s"]})
