"""Driver for traffic of kind ``train``.

One object — the compiled step with its state — is built in set-up, driven
from the seed through its first steps (which the reference follows), warmed
up, and handed to the window. The window dispatches whole groups of steps
with no fence between them, fences and fetches the loss at each group's end
(a trainer's logging interval), and closes on a group's fence:

    train_tok_s = tokens of every group / (closing fence - opening fence)

``--trace 1`` fences every step of a short profiled window instead.
"""

from __future__ import annotations

import gc
import statistics
import time


def leaf_names(tree) -> list[str]:
    import jax

    return [jax.tree_util.keystr(p)
            for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def leaf_norms(tree, minus=None, scale: float = 1.0) -> list[float]:
    """Per-leaf L2 norms (of ``tree - minus`` where given), in float32, one
    jitted call and one fetch."""
    import jax
    import jax.numpy as jnp

    def norms(a, b):
        f = lambda x: x.astype(jnp.float32)
        diff = a if b is None else jax.tree_util.tree_map(
            lambda x, y: f(x) - f(y), a, b)
        return [jnp.sqrt(jnp.sum(jnp.square(f(x))))
                for x in jax.tree_util.tree_leaves(diff)]

    return [float(n) * scale for n in jax.jit(norms)(tree, minus)]


def make_batches(ctx, n_steps: int, stream: int):
    """``n_steps`` batches of (tokens, targets), every row drawn anew."""
    import numpy as np

    t = ctx.traffic
    rng = np.random.default_rng([ctx.seed, stream])
    x = rng.integers(0, ctx.spec.V, size=(n_steps, t["batch"], t["seq"] + 1),
                     dtype=np.int32)
    return x[:, :, :-1].copy(), x[:, :, 1:].copy()


class Program:
    """The compiled train step with its state: what set-up builds and the
    window drives."""

    def __init__(self, ctx):
        import thunder_tpu as tt
        from thunder_tpu.core import dtypes
        from thunder_tpu.optim import AdamW

        fam, spec, o = ctx.family, ctx.spec, ctx.conf["optimizer"]
        self.ctx = ctx
        self.cfg = fam.program_config(spec, max_seq_len=ctx.traffic["seq"])
        self.opt = AdamW(lr=o["lr"], beta1=o["beta1"], beta2=o["beta2"],
                         eps=o["eps"], weight_decay=o["weight_decay"],
                         state_dtype=getattr(dtypes, o["m_dtype"]),
                         v_dtype=getattr(dtypes, o["v_dtype"]))
        loss_fn, opt = fam.make_loss(spec, self.cfg), self.opt

        def train_step(params, opt_state, tokens, targets):
            loss, grads = tt.value_and_grad(
                lambda p: loss_fn(p, tokens, targets))(params)
            new_params, new_state = opt.update(params, grads, opt_state)
            return loss, new_params, new_state

        if ctx.traffic.get("parallel"):
            self.jstep = fam.parallel_step(train_step, ctx.traffic["parallel"])
        else:
            self.jstep = tt.jit(train_step, donate_argnums=(0, 1))
        self.params = fam.init_params(spec, ctx.seed)
        self.state = fam.init_state(self.opt, self.params) \
            if hasattr(fam, "init_state") else self.opt.init(self.params)
        self.loss = None

    def step(self, tokens, targets):
        """The window's own call: dispatches one step, fences nothing."""
        self.loss, self.params, self.state = self.jstep(
            self.params, self.state, tokens, targets)

    def fence(self) -> float:
        import jax

        jax.block_until_ready((self.params, self.state))
        return float(self.loss)


def first_steps(prog: Program, tokens, targets) -> dict:
    """The program's first steps through the window's own call; what the
    reference will be held against."""
    ctx, fam, opt = prog.ctx, prog.ctx.family, prog.opt
    out = {"losses": [], "names": leaf_names(prog.params)}
    for i in range(len(tokens)):
        prog.step(tokens[i], targets[i])
        out["losses"].append(prog.fence())
        if i == 0:      # the first gradient as the optimizer got it
            out["grad_norms"] = leaf_norms(prog.state["m"],
                                           scale=1.0 / (1.0 - opt.beta1))
    p0 = fam.init_params(ctx.spec, ctx.seed)
    out["dparam_norms"] = leaf_norms(prog.params, minus=p0)
    del p0
    return out


def reference_steps(ctx, tokens, targets, precision="float32",
                    rows=None, no_exchange=False) -> dict:
    """The family's plain reference through the same first steps: float32
    arithmetic at ``highest``, storage in the types the configuration
    states. ``precision`` below float32 is the control; ``rows`` leaves part
    of the batch out and ``no_exchange`` the exchange between chips (faults
    planted to read what they do to the numbers compared)."""
    import jax
    import jax.numpy as jnp

    fam, spec, o = ctx.family, ctx.spec, ctx.conf["optimizer"]
    tmap = jax.tree_util.tree_map
    B, T = tokens.shape[1], tokens.shape[2]
    rows = list(range(B) if rows is None else rows)
    n_tok = len(rows) * T
    b1, b2 = o["beta1"], o["beta2"]

    # what of a step's loss spans its rows (a family's own: expert load)
    step_context = getattr(fam, "ref_step_context", None)

    ep = (ctx.traffic.get("parallel") or {}).get("ep") if no_exchange else None

    def row_grad(p, acc, t, y, context, rank):
        extra = {} if context is None else {"context": context}
        if ep:
            extra["chip"] = (rank, ep)
        nll, g = jax.value_and_grad(
            lambda q: fam.ref_nll_sum(q, t, y, spec, precision, **extra))(p)
        return nll, tmap(lambda a, b: a + b.astype(jnp.float32), acc, g)

    row_grad = jax.jit(row_grad, donate_argnums=(1,))

    def adamw(p, g, m, v, step):
        bc1, bc2 = 1.0 - b1 ** step, 1.0 - b2 ** step

        def upd(p, g, m, v):
            g = g / n_tok
            m_new = b1 * m.astype(jnp.float32) + (1.0 - b1) * g
            v_new = b2 * v.astype(jnp.float32) + (1.0 - b2) * g * g
            u = (m_new / bc1) / (jnp.sqrt(v_new / bc2) + o["eps"])
            pf = p.astype(jnp.float32)
            p_new = pf - o["lr"] * (u + o["weight_decay"] * pf)
            return (p_new.astype(p.dtype), m_new.astype(m.dtype),
                    v_new.astype(v.dtype))

        out = tmap(upd, p, g, m, v)
        pick = lambda i: tmap(lambda t: t[i], out,
                              is_leaf=lambda x: isinstance(x, tuple))
        return pick(0), pick(1), pick(2)

    adamw = jax.jit(adamw, donate_argnums=(0, 1, 2, 3))

    out = {"losses": []}
    with jax.default_matmul_precision("highest"):
        p = fam.init_params(spec, ctx.seed)
        m = tmap(lambda x: jnp.zeros_like(x, dtype=o["m_dtype"]), p)
        v = tmap(lambda x: jnp.zeros_like(x, dtype=o["v_dtype"]), p)
        for s in range(len(tokens)):
            acc = tmap(lambda x: jnp.zeros_like(x, dtype=jnp.float32), p)
            shards = (ctx.traffic.get("parallel") or {}).get("ep", 1)
            context = step_context(p, tokens[s, rows], spec, precision,
                                   shards=shards) if step_context else None
            total = 0.0
            for i, b in enumerate(rows):
                nll, acc = row_grad(p, acc, tokens[s, b], targets[s, b],
                                    context and context[i], b * (ep or 1) // B)
                total += float(nll)
            out["losses"].append(total / n_tok)
            if s == 0:
                out["grad_norms"] = leaf_norms(acc, scale=1.0 / n_tok)
            p, m, v = adamw(p, acc, m, v, float(s + 1))
            del acc
        del m, v
        p0 = fam.init_params(spec, ctx.seed)
        out["dparam_norms"] = leaf_norms(p, minus=p0)
    return out


def gaps(got: dict, ref: dict) -> dict:
    """The numbers compared: the worst step's loss, and two norms by the worst leaf:
    the gap between the two norms against the reference's norm of that leaf
    or of the median leaf, whichever is larger. Leaves whose reference
    gradient is under a thousandth of the median leaf's move by round-off
    alone and are left out of the change."""
    compared = {"loss_gap": max(abs(a - b) / abs(b) for a, b in
                                zip(got["losses"], ref["losses"]))}
    g_med = statistics.median(ref["grad_norms"])
    compared["grad1_gap"] = max(
        abs(a - b) / max(b, g_med)
        for a, b in zip(got["grad_norms"], ref["grad_norms"]))
    live = [i for i, g in enumerate(ref["grad_norms"]) if g >= 1e-3 * g_med]
    d_med = statistics.median(ref["dparam_norms"][i] for i in live)
    compared["dparam3_gap"] = max(
        abs(got["dparam_norms"][i] - ref["dparam_norms"][i])
        / max(ref["dparam_norms"][i], d_med) for i in live) if d_med > 0 \
        else None
    return compared


def compare(ctx, got: dict, ref: dict) -> dict:
    """Each number compared beside its limit. A number the cell's limits
    file does not name is read and logged, not compared (the worst step's
    loss: no control and no fault reads far enough above sound runs)."""
    all_gaps = gaps(got, ref)
    ctx.log(f"gaps read: {all_gaps}")
    return {k: {"value": v, "limit": ctx.limits[k]}
            for k, v in all_gaps.items() if k in ctx.limits}


def run(ctx) -> dict:
    t = ctx.traffic
    G, B, T = t["group_steps"], t["batch"], t["seq"]
    prog = Program(ctx)
    first_tok, first_tgt = make_batches(ctx, t["compare_steps"], stream=1)
    got = first_steps(prog, first_tok, first_tgt)
    ctx.log(f"first steps: losses {got['losses']}")

    warm_tok, warm_tgt = make_batches(ctx, t["warmup_steps"], stream=2)
    half = t["warmup_steps"] // 2
    for i in range(t["warmup_steps"]):
        if i == half:
            prog.fence()
            t0 = time.perf_counter()
        prog.step(warm_tok[i], warm_tgt[i])
    prog.fence()
    step_s = (time.perf_counter() - t0) / (t["warmup_steps"] - half)
    ctx.log(f"warm-up: {step_s * 1e3:.1f} ms/step")

    if ctx.trace:
        n = t["trace_steps"]
        tok, tgt = make_batches(ctx, n, stream=3)
        gc.collect()
        gc.freeze()
        prog.fence()
        t_open = ctx.open_window()
        ctx.start_trace()
        step_ms = []
        for i in range(n):
            t1 = time.perf_counter()
            with ctx.span("bench:dispatch"):
                prog.step(tok[i], tgt[i])
            with ctx.span("bench:fence"):
                prog.fence()
            step_ms.append((time.perf_counter() - t1) * 1e3)
        ctx.stop_trace()
        t_close = ctx.close_window()
        ctx.readings["series"]["step_ms"] = step_ms
        n_steps, window_s = n, sum(step_ms) / 1e3
    else:
        n_groups = int(3 * ctx.seconds / (step_s * G)) + 3     # never run dry
        tok, tgt = make_batches(ctx, n_groups * G, stream=3)
        gc.collect()
        gc.freeze()
        prog.fence()
        t_open = ctx.open_window()
        fences, losses, g = [t_open], [], 0
        while fences[-1] - t_open < ctx.seconds and g < n_groups:
            for i in range(g * G, (g + 1) * G):
                prog.step(tok[i], tgt[i])
            losses.append(prog.fence())
            fences.append(time.perf_counter())
            g += 1
        t_close = ctx.close_window()
        if fences[-1] - t_open < ctx.seconds:
            raise RuntimeError("the window ran out of batches before --seconds")
        n_steps, window_s = g * G, fences[-1] - t_open
        ctx.write_json("groups.json", {
            "group_steps": G, "tokens_per_step": B * T,
            "group_s": [b - a for a, b in zip(fences, fences[1:])],
            "window_s": window_s, "losses": losses})
    gc.unfreeze()
    rate = n_steps * B * T / window_s
    ctx.readings["counts"].update(
        ctx.family.train_window_counts(ctx.spec, n_steps, B, T),
        steps=n_steps, window_s=window_s)
    ctx.log(f"window: {n_steps} steps in {window_s:.3f} s")
    del prog                              # the program's state is freed

    kept = {}

    def compare_after_window():
        gc.collect()
        t0 = time.perf_counter()
        ref = kept["ref"] = reference_steps(ctx, first_tok, first_tgt)
        ctx.log(f"reference: losses {ref['losses']} "
                f"({time.perf_counter() - t0:.1f} s)")
        ctx.write_json("compared.json", {"program": got, "reference": ref})
        return compare(ctx, got, ref)

    def control():
        """The reference in the program's place: one precision below the
        configuration's, and with half of the batch left out."""
        low = {"bfloat16": "fp8", "float32": "bfloat16"}[ctx.spec.dtype]
        out = {low: gaps(reference_steps(ctx, first_tok, first_tgt, low),
                         kept["ref"]),
               "half_batch": gaps(reference_steps(
                   ctx, first_tok, first_tgt, rows=range(B // 2)), kept["ref"])}
        if ctx.traffic.get("parallel"):
            out["no_exchange"] = gaps(reference_steps(
                ctx, first_tok, first_tgt, no_exchange=True), kept["ref"])
        return out

    return {"t_open": t_open, "metrics": {"train_tok_s": rate},
            "attempted": n_steps, "failed": 0, "compare": compare_after_window,
            "control": control}
