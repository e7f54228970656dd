"""Driver for traffic of kind ``serve_closed_state``: ``serve_closed_routed``
(its clients, window and rate, its two served-token gaps, its count of the
requests attempted), for a routed model whose layers keep a recurrent state
a slot. One number more is compared:

    served_state_bf16_share   of the nonzero entries of the state that the
                              compared requests' slots hold at the window's
                              close (every layer of a state kind, the arrays
                              the family names ``STATE_FLOAT32``), the share
                              that bfloat16 holds exactly

The configuration states a float32 state. Held in bfloat16 instead, it moves
the served tokens less than the program's bfloat16 activations do: the
decay and the delta rule's erasure keep ~100 tokens of history, so the
rounding never piles up. Neither served-token gap, nor the state's own
distance from the reference's token scan, tells the two apart (PERF.md §6).
The entries do: a float32 value that bfloat16 holds exactly has the
low 16 bits of its mantissa zero, a few in 100,000 computed values; a state
rounded to bfloat16 at any step reads 1, and one held in any format of 13
mantissa bits or fewer reads at least 2**-6.

The requests read are those of the served-token sample that still hold a
slot at the close (every one of them, in a window that finishes none), else
the first residents that have decoded.
"""

from __future__ import annotations


def state_bf16_share(ctx, serving, sv, m) -> float | None:
    """The share above, read from the engine before it is let go."""
    import numpy as np

    eng, names = sv.eng, ctx.family.STATE_FLOAT32
    sample = serving.sample_finished(ctx, sv, m["t_open"], m["t_close"])
    resident = [r for r in eng.slots if r is not None and r.generated]
    held = [r for s in sample for r in resident
            if np.array_equal(r.prompt, s["prompt"])] \
        or resident[: ctx.traffic["check_requests"]]
    exact = total = 0
    for r in held:
        for layer in eng.request_state(r):
            for name in names:
                a = np.asarray(layer[name])
                if a.dtype != np.float32:
                    return None             # not held in float32 at all
                a = a[a != 0]
                exact += int(((a.view(np.uint32) & 0xFFFF) == 0).sum())
                total += a.size
    ctx.log(f"state of {len(held)} requests: {exact} of {total} nonzero "
            f"entries held exactly by bfloat16")
    return exact / total if total else None


def run(ctx) -> dict:
    serving = ctx.load("drivers", "serving")
    closed = ctx.load("drivers", "serve_closed")
    routed = ctx.load("drivers", "serve_closed_routed")
    t = ctx.traffic
    sv = serving.Serving(ctx)
    sv.warm_up()
    n = t["clients"] * t["requests_per_client"]
    m = serving.run_window(ctx, sv, closed.Clients(
        serving.make_requests(ctx, n), t["clients"]))
    ctx.write_json("steps.json", [
        round((b - a) * 1e3, 3) for a, b, *_ in sv.steps
        if m["t_open"] <= a and b <= m["t_close"]])
    share = state_bf16_share(ctx, serving, sv, m)
    out = routed.finish(ctx, serving, sv, m,
                        {"serve_out_tok_s": m["tokens"] / m["window_s"]})
    gaps = out["compare"]

    def compare():
        return {**gaps(), "served_state_bf16_share": {
            "value": share,
            "limit": ctx.limits.get("served_state_bf16_share")}}

    out["compare"] = compare
    return out
