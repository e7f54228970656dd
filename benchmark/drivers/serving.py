"""What the two serving drivers share: the engine built from the cell's
files, requests drawn from the seed, the loop that steps the engine and
stamps every token as it is delivered, and the comparison with the plain
reference once the window has closed.

Lengths (and, open loop, the gaps between arrivals) are a fixed set of
quantiles of the mix's distributions, put in another order by each seed: the
seed moves the order of the work and not its amount.
"""

from __future__ import annotations

import gc
import math
import statistics
import time


def quantile_lengths(dist: dict, n: int):
    """``n`` lengths at the mid-quantiles of ``dist`` (uniform or lognormal),
    clipped to its range."""
    import numpy as np

    q = (np.arange(n) + 0.5) / n
    if dist["dist"] == "uniform":
        x = dist["min"] + q * (dist["max"] - dist["min"])
    elif dist["dist"] == "lognormal":
        z = np.array([statistics.NormalDist().inv_cdf(float(v)) for v in q])
        x = dist["median"] * np.exp(dist["sigma"] * z)
    else:
        raise ValueError(f"no length distribution {dist['dist']!r}")
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(int)


def make_requests(ctx, n: int) -> list[dict]:
    """``n`` requests: prompt tokens and an output length each."""
    import numpy as np

    t = ctx.traffic
    rng = np.random.default_rng([ctx.seed, 11])
    plen = rng.permutation(quantile_lengths(t["prompt"], n))
    olen = rng.permutation(quantile_lengths(t["output"], n))
    return [{"prompt": rng.integers(1, ctx.spec.V, size=int(p), dtype=np.int32),
             "max_new": int(o), "due": None} for p, o in zip(plen, olen)]


def ladder_rungs(engine: dict, lo: int, hi: int) -> list[int]:
    """The prefill chunk sizes prompts of ``lo``..``hi`` tokens can reach
    (the engine's ladder: powers-of-two multiples of the page, up to the
    chunk cap)."""
    page, cap = engine["page_size"], engine["prefill_chunk"]
    ladder, b = [], page
    while b < cap:
        ladder.append(b)
        b *= 2
    ladder.append(cap)
    bucket = lambda n: next(r for r in ladder if r >= n)
    rungs = set()
    for n in range(lo, hi + 1):
        if n >= cap:
            rungs.add(cap)
        if n % cap:
            rungs.add(bucket(n % cap))
    return sorted(rungs)


class Serving:
    """The engine with its requests in flight, and the record of every token
    delivered."""

    def __init__(self, ctx):
        from thunder_tpu.serving import ServingEngine

        fam, spec, e = ctx.family, ctx.spec, ctx.traffic["engine"]
        self.ctx = ctx
        self.cfg = fam.program_config(spec, max_seq_len=e["max_context"])
        self.eng = ServingEngine(
            fam.init_params(spec, ctx.seed), self.cfg,
            max_slots=e["max_slots"], page_size=e["page_size"],
            max_context=e["max_context"], prefill_chunk=e["prefill_chunk"])
        self.live: list = []            # [request record, engine Request]
        self.records: list = []         # every request submitted
        self.steps: list = []           # (t0, t1, decoding, live_tokens, pure)
        self.backlog: list = []         # (t, requests queued for a slot)

    def warm_up(self) -> None:
        """One prompt a prefill rung the mix can reach, two tokens each: the
        prefill programs and the decode program, and nothing else."""
        import numpy as np

        t = self.ctx.traffic
        for r in ladder_rungs(t["engine"], t["prompt"]["min"], t["prompt"]["max"]):
            self.eng.submit(np.ones(r, np.int32), 2)
        self.eng.drain()
        self.eng.completed.clear()

    def submit(self, rec: dict, now: float) -> None:
        rec.update(submitted=now, stamps=[], done=False, failed=False)
        req = self.eng.submit(rec["prompt"], rec["max_new"])
        self.live.append((rec, req))
        self.records.append(rec)

    def step(self) -> bool:
        """One engine iteration; stamps the tokens it delivered."""
        eng = self.eng
        decoding = [r for r in eng.slots if r is not None and r.state == "decode"]
        pure = bool(decoding) and not eng.queue and not any(
            r is not None and r.state == "prefill" for r in eng.slots)
        live_tokens = sum(r.length for r in decoding)
        t0 = time.perf_counter()
        worked = eng.step()
        t1 = time.perf_counter()
        if worked:
            self.steps.append((t0, t1, len(decoding), live_tokens, pure))
            self.backlog.append((t1, len(eng.queue)))
        keep = []
        for rec, req in self.live:
            new = len(req.generated) - len(rec["stamps"])
            if new:
                rec["stamps"].extend([t1] * new)
            if req.done or req.failed:
                rec.update(done=req.done, failed=req.failed,
                           tokens=list(req.generated))
            else:
                keep.append((rec, req))
        self.live = keep
        return worked

    def close(self) -> None:
        """Requests in flight at the close keep what they delivered."""
        for rec, req in self.live:
            rec["tokens"] = list(req.generated)
        self.live = []


def window_metrics(sv: Serving, t_open: float, t_close: float) -> dict:
    """What the window delivered: output tokens, gaps between consecutive
    tokens of a request (a gap counts where its later token is inside), and
    time to first token from the DUE time of every request due inside."""
    inside = lambda t: t_open <= t < t_close
    tokens, gaps, ttft, lag = 0, [], [], []
    for rec in sv.records:
        st = rec["stamps"]
        tokens += sum(1 for t in st if inside(t))
        gaps += [(b - a) * 1e3 for a, b in zip(st, st[1:]) if inside(b)]
        due = rec["due"] if rec["due"] is not None else rec["submitted"]
        if inside(due):
            first = st[0] if st and st[0] < t_close else t_close
            ttft.append((first - due) * 1e3)
            lag.append((rec["submitted"] - due) * 1e3)
    return {"tokens": tokens, "gaps_ms": gaps, "ttft_ms": ttft, "lag_ms": lag,
            "attempted": len(ttft),
            "failed": sum(1 for r in sv.records if r["failed"])}


def traced_counts(ctx, sv: Serving, w0: float, w1: float) -> None:
    """Series and work counts of the steps inside the traced window."""
    fam, spec = ctx.family, ctx.spec
    steps = [s for s in sv.steps if w0 <= s[0] and s[1] <= w1]
    slots = ctx.traffic["engine"]["max_slots"]
    ctx.readings["series"].update(
        decode_step_ms=[(b - a) * 1e3 for a, b, _, _, pure in steps if pure],
        occupancy=[100.0 * d / slots for _, _, d, _, _ in steps if d])
    dec = [s for s in steps if s[2]]

    def summed(count, scale=1):
        works = [count(spec, slots, lt) for _, _, _, lt, _ in dec]
        return {k: scale * sum(w[k] for w in works)
                for k in ("flops", "bytes")} if works else None

    ctx.readings["counts"].update(
        steps=len(dec), decode_s=sum(b - a for a, b, *_ in dec),
        decode_attn_block=summed(fam.decode_attn_block_counts, spec.L),
        step_decode=summed(fam.decode_step_counts))


def run_window(ctx, sv: Serving, arrivals) -> dict:
    """Ramp, then the window: ``arrivals(now)`` submits what is due."""
    t = ctx.traffic
    t_ramp = time.perf_counter()
    arrivals.start(t_ramp, t["ramp_s"])
    while time.perf_counter() - t_ramp < t["ramp_s"]:
        arrivals.admit(sv, time.perf_counter())
        if not sv.step():
            time.sleep(0.0005)
    gc.collect()
    gc.freeze()
    t_open = ctx.open_window()
    if ctx.trace:
        ctx.start_trace()
    tracing = ctx.trace
    while True:
        now = time.perf_counter()
        if now - t_open >= ctx.seconds:
            break
        if tracing and now - ctx.t_trace_open >= t["trace_s"]:
            ctx.stop_trace()
            tracing = False
        arrivals.admit(sv, now)
        with ctx.span("bench:step"):
            worked = sv.step()
        if not worked:
            with ctx.span("bench:wait"):
                time.sleep(0.0005)
    if tracing:
        ctx.stop_trace()
    t_close = ctx.close_window()
    gc.unfreeze()
    sv.close()
    if ctx.trace:
        traced_counts(ctx, sv, ctx.t_trace_open, ctx.t_trace_close)
    m = window_metrics(sv, t_open, t_close)
    ctx.readings["series"].update(gaps_ms=m["gaps_ms"], ttft_ms=m["ttft_ms"],
                                  lag_ms=m["lag_ms"])
    ctx.write_json("window.json", {
        "window_s": t_close - t_open, "tokens": m["tokens"],
        "requests": len(sv.records), "attempted": m["attempted"],
        "finished": sum(1 for r in sv.records if r["done"]),
        "steps": len(sv.steps), "ttft_ms": m["ttft_ms"], "lag_ms": m["lag_ms"],
        "queued_by_third": [
            max([q for t, q in sv.backlog
                 if t_open + i * (t_close - t_open) / 3 <= t
                 < t_open + (i + 1) * (t_close - t_open) / 3] or [0])
            for i in range(3)]})
    m.update(t_open=t_open, t_close=t_close, window_s=t_close - t_open)
    return m


# ---------------------------------------------------------------------------
# what is compared
# ---------------------------------------------------------------------------

def sample_finished(ctx, sv: Serving, t_open: float, t_close: float) -> list:
    """A sample, drawn from the seed, of the requests the window finished,
    the longest among them."""
    import numpy as np

    done = [r for r in sv.records
            if r["done"] and t_open <= r["stamps"][-1] < t_close]
    if not done:    # a window too short to finish one: what it served so far
        done = [r for r in sv.records if r.get("tokens")]
    if not done:
        return []
    n = min(ctx.traffic["check_requests"], len(done))
    size = lambda r: len(r["prompt"]) + len(r["tokens"])
    longest = max(range(len(done)), key=lambda i: size(done[i]))
    rest = [i for i in range(len(done)) if i != longest]
    pick = np.random.default_rng([ctx.seed, 13]).permutation(rest)[: n - 1]
    return [{"prompt": done[i]["prompt"], "tokens": done[i]["tokens"]}
            for i in [longest, *pick.tolist()]]


def served_gaps(ctx, sample: list, control: str | None = None) -> dict:
    """The reference once over each prompt with its served tokens. For every
    served token, how far its logit lies below the reference's best; with
    ``control``, the same for the token a reference computed in that lower
    precision puts first."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    fam, spec = ctx.family, ctx.spec
    if not sample:
        return {"served_logit_gap": None}
    longest = max(len(s["prompt"]) + len(s["tokens"]) for s in sample)
    pad = max(256, 2 ** math.ceil(math.log2(longest)))
    worst, n_tok = 0.0, 0

    def gaps_fn(params, seq, served, start, precision):
        ref = fam.ref_logits(params, seq, spec)
        pos = start + jnp.arange(served.shape[0])
        rows = ref[pos]
        if precision is not None:
            served = jnp.argmax(
                fam.ref_logits(params, seq, spec, precision)[pos], -1)
        return rows.max(-1) - jnp.take_along_axis(rows, served[:, None], 1)[:, 0]

    gaps_jit = jax.jit(gaps_fn, static_argnames=("precision",))
    with jax.default_matmul_precision("highest"):
        params = fam.init_params(spec, ctx.seed)
        for s in sample:
            n_p, n_t = len(s["prompt"]), len(s["tokens"])
            seq = np.zeros(pad, np.int32)
            seq[: n_p + n_t] = np.concatenate([s["prompt"], s["tokens"]])
            served = np.zeros(pad, np.int32)
            served[:n_t] = s["tokens"]
            g = np.asarray(gaps_jit(params, seq, served, n_p - 1,
                                    precision=control))[:n_t]
            worst, n_tok = max(worst, float(g.max())), n_tok + n_t
    ctx.log(f"compared {n_tok} served tokens of {len(sample)} requests "
            f"(padded to {pad})")
    return {"served_logit_gap": worst}


def finish(ctx, sv: Serving, m: dict, metrics: dict) -> dict:
    """Free the engine, hand back the result with the comparison to run once
    the peak has been read."""
    sample = sample_finished(ctx, sv, m["t_open"], m["t_close"])
    sv.eng = None

    def compare():
        gc.collect()
        t0 = time.perf_counter()
        got = served_gaps(ctx, sample)
        ctx.log(f"reference: {time.perf_counter() - t0:.1f} s")
        return {k: {"value": v, "limit": ctx.limits.get(k)}
                for k, v in got.items()}

    def control():
        """The token a reference one precision below the configuration's
        puts first, at every position of the same prompts and tokens."""
        low = {"bfloat16": "fp8", "float32": "bfloat16"}[ctx.spec.dtype]
        return {low: served_gaps(ctx, sample, control=low)}

    return {"t_open": m["t_open"], "metrics": metrics,
            "attempted": m["attempted"], "failed": m["failed"],
            "compare": compare, "control": control}
