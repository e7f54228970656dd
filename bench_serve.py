"""Serving benchmark: continuous batching vs sequential single-stream.

The committed multi-request throughput story for ``thunder_tpu/serving/``
(ROADMAP item 1), next to the per-stream numbers in ``bench_generate.py``:

- **workload**: ``SERVE_REQUESTS`` requests with MIXED prompt lengths and
  Poisson arrivals (rate ``SERVE_RATE``/s, seeded — the same draw every
  run), each decoding ``SERVE_DECODE`` tokens greedily.
- **continuous**: the ``ServingEngine`` — paged KV cache, chunked prefill
  interleaving, one bound batched decode step for all resident requests.
- **sequential baseline**: the pre-serving story — one request at a time
  through the dense-cache ``bind()`` decode loop (``models.llama``'s step
  functions, bucketed prefill), exactly what ``bench_generate.py`` measures
  per-stream.

Both sides are compile-warmed before timing; the wall clock covers
first-submit → last-completion. Prints one JSON line per serving mode:
aggregate decode tokens/s, requests/s, p50/p99 TTFT (the latency SLO
axis), p99 per-request decode duration, and peak KV page utilization.
``vs_baseline`` on the continuous line is the aggregate-throughput ratio
over sequential — the number the ≥4x acceptance gate reads.

``--overload`` replaces the comparison with the OVERLOAD scenario (arrival
rate > capacity): requests carry mixed priorities and a deadline SLO, the
admission queue is bounded, and traffic flows through an
``EngineSupervisor``. The JSON line stamps ``shed_rate`` (bounded-queue +
priority shedding over all offered requests), ``deadline_miss_rate``
(late completions among accepted non-shed requests — the acceptance gate
wants this at zero for the smoke SLO) and ``slo_attainment`` (the
engine's rolling on-time ratio over every terminal request).

The continuous line also stamps the schema-6 **request-timeline summary**
from the serving lifecycle tracing: per-request queue-time percentiles
(``queue_ms_p50/p99``), the scheduler-iteration split between host
scheduling and device dispatch (``sched_host_ms_mean`` /
``decode_dispatch_ms_mean``), total prefill chunks, and the flight-
recorder record count. ``SERVE_TRACE=/path.json`` additionally exports
the Perfetto serving timeline (per-request tracks + scheduler track +
queue/slots/pages counter tracks) of the winning round.

``--prefix`` runs the SHARED-PREFIX scenario (ISSUE 14): every request
shares a multi-page system prompt, the engine runs with the
cross-request prefix cache on, and each round measures a COLD batch
(trie cleared, full prefills, completions donate the prompt pages) then
a WARM batch of the same prompts (admission probe-hits the system pages;
prefill collapses to one tail chunk). The schema-7 JSON line stamps
``ttft_cold_ms_p50`` / ``ttft_warm_ms_p50`` (the acceptance gate wants
warm >= 2x better), ``prefix_hit_rate``,
``cached_prefill_skipped_tokens``, plus the best-of-N fork story on the
same prompt: ``cow_copies`` (partial-tail copy-on-write copies) and
``bestof_page_amplification`` (pages allocated by best-of-4 over
best-of-1 — the gate wants < 1.5x, because N branches share ONE
prefill). Warm outputs are checked token-identical to cold, and the
fixed-seed sampled best-of outputs reproduce run-to-run.

The schema-8 continuous line additionally stamps the DECODE PROGRAM's
compiled-program census (``observe.census``):
``census_decode_collective_instructions`` (0 is the healthy single-chip
value — nonzero IS the regression), ``census_decode_hlo_fusions``,
guarded ``census_decode_errors``, and any sentinel
``census_decode_pessimizations`` kinds.

Schema 12: every engine-backed JSON line stamps the engine's
process-unique ``engine_id``, gauge-sourced numbers come off the TIMED
engine's **labeled** series (``eng.obs.snapshot()`` — immune to
last-writer-wins clobbering when warm pools, baselines, or sibling
engines share the process registry), and the continuous line adds the
fleet view (``fleet_engines`` / ``fleet_health`` /
``fleet_slo_attainment``) from a post-timing ``FleetObservatory`` check
over the timed engine.

``--mesh`` runs the TENSOR-PARALLEL scenario: the engine builds over a
``SERVE_TP``-way (default 8) 1-D mesh — column/row-sharded weights,
kv-head-sharded paged pool, replicated activations — and the schema-11
JSON line stamps ``mesh_shape`` / ``tp_degree`` / ``per_shard_toks_s``
(aggregate tokens/s over the shard count) next to the TTFT percentiles,
plus the MESHED decode program's census collective counts
(``census_decode_collectives`` per kind and
``census_decode_all_reduces_per_layer`` — the committed
CENSUS_BUDGETS.json budget is ≤2 per layer with zero gathers) and the
``serving_mesh`` flight-ring record count. On CPU the mesh is forced via
``--xla_force_host_platform_device_count``; the smoke uses the tiny-tp
geometry (everything divides tp=8).

``--fleet`` runs the FLEET-ROUTER scenario (ISSUE 20): ``SERVE_GROUPS``
prefix groups (each a shared multi-page prefix + per-request suffix) with
INTERLEAVED arrivals, served three ways with identical per-engine
geometry — ONE engine (whose prefix-cache pool cannot park every group's
chain: the trie thrashes and prefills run cold), then
``SERVE_FLEET_ENGINES`` engines behind a ``FleetRouter`` with the
default health-gated / prefix-affine / least-loaded chain (each engine
keeps its share of the groups warm — placement as a performance
optimization), then the same fleet behind a seeded RANDOM-placement
control arm. The schema-13 JSON line stamps ``fleet_engines``,
``aggregate_toks_s``, ``scaling_vs_single`` (the acceptance gate wants
>= 1.8x on 2 engines), ``affinity_hit_rate`` vs ``random_hit_rate``
(affinity must beat random), ``ttft_ms_p50/p99`` from the affinity arm,
and ``migrated_requests`` from a mid-run engine kill: a zero-restart-
budget engine dies mid-decode, the router re-admits its in-flight
requests on the survivor token-identically with zero deadline misses.

Env: SERVE_MODEL, SERVE_LAYERS, SERVE_REQUESTS, SERVE_DECODE, SERVE_SLOTS,
SERVE_CONTEXT, SERVE_PAGE, SERVE_CHUNK, SERVE_RATE, SERVE_DEADLINE_S,
SERVE_QUEUE, SERVE_SYS, SERVE_BESTOF, SERVE_TP, SERVE_TRACE,
SERVE_FLEET_ENGINES, SERVE_GROUPS, SERVE_GROUP_REQUESTS,
SERVE_POOL_PAGES, SERVE_PREFIX_PAGES. ``--smoke``: tiny GQA geometry on
CPU (tiny-tp under ``--mesh``).
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np


def _percentile(sorted_vals, q):
    if not sorted_vals:
        return float("nan")
    i = min(len(sorted_vals) - 1, int(round(q * (len(sorted_vals) - 1))))
    return sorted_vals[i]


def main():
    import jax

    smoke = "--smoke" in sys.argv
    overload = "--overload" in sys.argv
    prefix = "--prefix" in sys.argv
    mesh = "--mesh" in sys.argv
    fleet = "--fleet" in sys.argv
    if mesh and "tpu" not in os.environ.get("JAX_PLATFORMS", ""):
        # the CPU mesh needs its devices BEFORE the backend initializes:
        # tp host devices (tp from SERVE_TP, default 8), same trick as
        # tests/conftest.py
        flags = os.environ.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count="
                + os.environ.get("SERVE_TP", "8")).strip()
    if mesh and smoke:
        # mesh smoke: the tiny-tp geometry (8 heads / 8 kv-heads / 192
        # intermediate — everything divides tp=8), short decodes; the
        # scenario's story is the census + per-shard split, not raw speed
        os.environ.setdefault("SERVE_MODEL", "tiny-tp")
        os.environ.setdefault("SERVE_LAYERS", "2")
        os.environ.setdefault("SERVE_DECODE", "32")
        os.environ.setdefault("SERVE_SLOTS", "4")
        os.environ.setdefault("SERVE_PAGE", "8")
        os.environ.setdefault("SERVE_CHUNK", "32")
    if overload and smoke:
        # overload smoke: enough offered load to overflow the bounded queue
        # while each accepted request keeps a wide SLO margin
        os.environ.setdefault("SERVE_REQUESTS", "24")
        os.environ.setdefault("SERVE_DECODE", "32")
    if prefix and smoke:
        # prefix smoke: a 12-page system prompt + short suffixes, short
        # decodes (TTFT is the story), context wide enough for prompt+decode
        os.environ.setdefault("SERVE_CONTEXT", "256")
        os.environ.setdefault("SERVE_DECODE", "16")
    if fleet and smoke:
        # fleet smoke: prompts of 9 prefix pages + 1 suffix page on a pool
        # that cannot park every group's chain at once — the single-engine
        # arm MUST thrash (that capacity cliff, not parallel compute, is
        # what affinity routing recovers; on a 1-core host the engines
        # can't overlap anyway); short decodes keep prefill dominant
        os.environ.setdefault("SERVE_LAYERS", "1")
        os.environ.setdefault("SERVE_DECODE", "5")
        os.environ.setdefault("SERVE_SLOTS", "2")
        os.environ.setdefault("SERVE_CONTEXT", "176")
        os.environ.setdefault("SERVE_PAGE", "16")
        os.environ.setdefault("SERVE_CHUNK", "16")
    if smoke:
        os.environ.setdefault("SERVE_MODEL", "tiny-gqa")
        os.environ.setdefault("SERVE_LAYERS", "1")
        os.environ.setdefault("SERVE_REQUESTS", "8")
        os.environ.setdefault("SERVE_DECODE", "64")
        os.environ.setdefault("SERVE_SLOTS", "8")
        os.environ.setdefault("SERVE_CONTEXT", "128")
        os.environ.setdefault("SERVE_PAGE", "16")
        os.environ.setdefault("SERVE_CHUNK", "64")
        os.environ.setdefault("SERVE_RATE", "5000")
    # --smoke runs on whatever platform JAX resolves: a CPU rehearsal is
    # asked for from outside (JAX_PLATFORMS=cpu), never chosen here
    import jax.numpy as jnp

    import thunder_tpu as tt

    print(f"device: {jax.devices()[0].platform} {jax.devices()[0].device_kind} "
          f"x{len(jax.devices())}; compile cache: "
          f"{tt.enable_compilation_cache()}", file=sys.stderr)
    from bench import METRICS_SCHEMA
    from thunder_tpu import observe
    from thunder_tpu.data import LengthBucketer
    from thunder_tpu.models import llama
    from thunder_tpu.serving import ServingEngine

    model = os.environ.get("SERVE_MODEL", "llama2-7b-bench")
    n_layers = int(os.environ.get("SERVE_LAYERS", "2"))
    n_requests = int(os.environ.get("SERVE_REQUESTS", "16"))
    n_decode = int(os.environ.get("SERVE_DECODE", "64"))
    slots = int(os.environ.get("SERVE_SLOTS", "8"))
    max_context = int(os.environ.get("SERVE_CONTEXT", "512"))
    page = int(os.environ.get("SERVE_PAGE", "16"))
    chunk = int(os.environ.get("SERVE_CHUNK", "128"))
    rate = float(os.environ.get("SERVE_RATE", "100.0"))
    cfg = llama.CONFIGS[model]
    params = jax.device_put(llama.init_params(cfg, seed=0, scale_layers=n_layers))

    rng = np.random.RandomState(0)
    len_mix = [5, 12, 24, 40, 64, 96, 160, 240]
    len_mix = [l for l in len_mix if l + n_decode + 1 <= max_context] or [8]
    lens = rng.choice(len_mix, size=n_requests)
    prompts = [rng.randint(1, cfg.vocab_size, size=int(L)).astype(np.int32)
               for L in lens]
    arrivals = np.cumsum(rng.exponential(1.0 / rate, size=n_requests))
    total_tokens = n_requests * n_decode
    geom = f"{model.replace('-bench', '')}-geometry({n_layers}L,s{slots})"

    # observe is ON for BOTH timed phases (the engine's serving.* metrics
    # need the registry; the baseline runs under the same instrumentation
    # so the comparison carries identical per-dispatch overhead)
    observe.enable(clear=True)

    # ---- tensor-parallel mesh scenario: pjit-sharded prefill/decode -------
    if mesh:
        tp = int(os.environ.get("SERVE_TP", "8"))
        need = -(-int(max(len(p) for p in prompts) + n_decode) // page)
        eng = ServingEngine(params, cfg, max_slots=slots, page_size=page,
                            max_context=max_context, n_layers=n_layers,
                            prefill_chunk=chunk, num_pages=slots * need + 1,
                            mesh=tp)
        # warm the real length mix + the sharded decode program
        for L in sorted({int(l) for l in lens}):
            eng.submit(rng.randint(1, cfg.vocab_size,
                                   size=L).astype(np.int32),
                       max_new_tokens=2)
        eng.drain()

        def run_round():
            eng.completed.clear()
            eng.cache.reset_peak()
            pending = sorted(zip(arrivals.tolist(), prompts),
                             key=lambda x: x[0])
            reqs = []
            t0 = time.perf_counter()
            while pending or eng.queue or eng.active_requests:
                now = time.perf_counter() - t0
                while pending and pending[0][0] <= now:
                    reqs.append(eng.submit(pending.pop(0)[1], n_decode))
                if not eng.step() and pending:
                    time.sleep(max(0.0, min(pending[0][0] - now, 1e-3)))
            wall = time.perf_counter() - t0
            return wall, {
                "ttfts": sorted(r.ttft_s * 1e3 for r in reqs),
                "util_peak": (eng.cache.peak_pages_used
                              / eng.cache.pages_total),
            }

        rounds = 3 if smoke else 2
        best = None
        for _ in range(rounds):
            w, stats = run_round()
            if best is None or w < best[0]:
                best = (w, stats)
        eng.assert_quiescent()
        wall, stats = best
        tok_s = total_tokens / wall
        ttfts = stats["ttfts"]
        # the MESHED decode program's census: the collective ledger IS the
        # scenario's acceptance surface (CENSUS_BUDGETS.json pins ≤2
        # all-reduces per layer and zero gathers for the tiny-tp config;
        # here the live numbers ride the JSON line). mesh_shape/tp_degree
        # come off the census itself — stamped from the runner's
        # census_context, so the line reports what actually compiled.
        dec_cens = tt.compile_stats(eng.runner.decode_jit).last_census or {}
        per_kind = {k: int(v["count"]) for k, v in
                    ((dec_cens.get("collectives") or {}).get("per_kind")
                     or {}).items()}
        mesh_shape = list(dec_cens.get("mesh_shape") or [tp])
        tp_deg = int(dec_cens.get("tp_degree") or tp)
        # the flight ring holds the serving_mesh build event (mesh_shape in
        # the record) — the postmortem story the acceptance gate wants
        mesh_recs = [r for r in observe.flight.snapshot()
                     if r.get("kind") == "serving_mesh"]
        ar_per_layer = per_kind.get("all-reduce", 0) / max(n_layers, 1)
        print(f"mesh: tp={tp_deg} over mesh {mesh_shape}, {n_requests} "
              f"requests — {tok_s:.1f} tok/s aggregate "
              f"({tok_s / tp_deg:.1f}/shard), TTFT p99 "
              f"{_percentile(ttfts, 0.99):.1f} ms, decode collectives "
              f"{per_kind or '{}'} ({ar_per_layer:g} all-reduce/layer), "
              f"{len(mesh_recs)} serving_mesh flight records",
              file=sys.stderr)
        print(json.dumps({
            "metrics_schema": METRICS_SCHEMA,
            "engine_id": eng.engine_id,
            "metric": f"{geom} tensor-parallel (tp={tp_deg}) aggregate "
                      f"decode tokens/s",
            "value": round(tok_s, 1), "unit": "tokens/s", "vs_baseline": 1.0,
            "requests": n_requests, "decode_tokens": n_decode,
            # schema-11 tensor-parallel fields
            "mesh_shape": mesh_shape,
            "tp_degree": tp_deg,
            "per_shard_toks_s": round(tok_s / tp_deg, 2),
            "ttft_ms_p50": round(_percentile(ttfts, 0.50), 2),
            "ttft_ms_p99": round(_percentile(ttfts, 0.99), 2),
            "kv_page_util_peak": round(stats["util_peak"], 4),
            "census_decode_collectives": per_kind,
            "census_decode_all_reduces_per_layer": round(ar_per_layer, 3),
            "census_decode_pessimizations": sorted(
                {f["kind"] for f in (dec_cens.get("findings") or [])}),
            "flight_mesh_records": len(mesh_recs)}))
        return

    # ---- shared-prefix scenario: COW prefix cache + in-graph sampling -----
    if prefix:
        from thunder_tpu.serving import SamplingParams

        sys_tokens = int(os.environ.get("SERVE_SYS", str(12 * page)))
        best_of = int(os.environ.get("SERVE_BESTOF", "4"))
        sysp = rng.randint(1, cfg.vocab_size, size=sys_tokens).astype(np.int32)
        # suffixes: page-UNALIGNED total so the best-of fork exercises the
        # partial-tail copy-on-write path (cow_copies > 0)
        sfx = max(4, (3 * page) // 4)
        shared_prompts = [np.concatenate(
            [sysp, rng.randint(1, cfg.vocab_size, size=sfx).astype(np.int32)])
            for _ in range(n_requests)]
        need = -(-int(sys_tokens + sfx + n_decode + page) // page)
        eng = ServingEngine(params, cfg, max_slots=slots, page_size=page,
                            max_context=max_context, n_layers=n_layers,
                            prefill_chunk=chunk, prefix_cache=True,
                            num_pages=slots * need + sys_tokens // page + 2)
        # compile-warm every shape on UNRELATED prompts (their donations are
        # cleared with the trie before each cold round)
        for L in {len(p) for p in shared_prompts} | {sys_tokens + sfx}:
            eng.submit(rng.randint(1, cfg.vocab_size, size=L).astype(np.int32),
                       max_new_tokens=2)
        eng.drain()

        def run_batch():
            # The cold/warm TTFT percentiles are measured over each batch's
            # FIRST ADMISSION WAVE only, split by per-request hit status:
            # when requests outnumber slots, later waves (a) queue behind
            # the first wave's decodes — TTFT then measures decode capacity,
            # not prefill work — and (b) in the "cold" batch admit AFTER
            # the first wave completed and DONATED, so they are warm in
            # every sense that matters. First-wave requests admit
            # immediately on an idle engine, so their TTFT is the prefill
            # path the stamp claims to measure, on both sides.
            eng.completed.clear()
            reqs = [eng.submit(p, n_decode) for p in shared_prompts]
            t0 = time.perf_counter()
            while not eng.idle:
                eng.step()
            wall = time.perf_counter() - t0
            wave = sorted(reqs, key=lambda r: r.admit_seq)[:slots]
            return {
                "wall": wall,
                "cold_ttfts": sorted(r.ttft_s * 1e3 for r in wave
                                     if r.prefix_hit_tokens == 0),
                "warm_ttfts": sorted(r.ttft_s * 1e3 for r in wave
                                     if r.prefix_hit_tokens > 0),
                "outs": [list(r.output()) for r in reqs],
                "hit_tokens": sum(r.prefix_hit_tokens for r in reqs),
            }

        rounds = 3 if smoke else 2
        cold = warm = None
        for _ in range(rounds):
            eng.prefix.clear()          # cold: every prompt page re-prefills
            c = run_batch()             # miss-TTFTs (+ donations mid-batch)
            w = run_batch()             # trie holds the donated system pages
            if cold is None or c["wall"] < cold["wall"]:
                cold = c
            if warm is None or w["wall"] < warm["wall"]:
                warm = w
        assert cold["cold_ttfts"] and warm["warm_ttfts"], \
            "prefix scenario produced no cold misses or no warm hits"
        # WARM-batch hit rate (cached tokens over the batch's prompt
        # tokens) — the cumulative serving.prefix_hit_rate gauge blends in
        # the cold batches' misses, which is not what this stamp means
        hit_rate = warm["hit_tokens"] / sum(len(p) for p in shared_prompts)
        identical = cold["outs"] == warm["outs"]
        cold_p50 = _percentile(cold["cold_ttfts"], 0.50)
        warm_p50 = _percentile(warm["warm_ttfts"], 0.50)

        # best-of-N fork story on the shared prompt: one prefill, N branches
        def bestof(n):
            b = ServingEngine(params, cfg, max_slots=max(slots, n),
                              page_size=page, max_context=max_context,
                              n_layers=n_layers, prefill_chunk=chunk)
            prim = b.submit(shared_prompts[0], n_decode, best_of=n,
                            sampling=SamplingParams(temperature=0.8,
                                                    top_k=40, seed=1234))
            b.drain()
            outs = [list(r.output()) for r in prim.fork_group]
            b.assert_quiescent()
            return b.cache.pages_allocated, b.cache.cow_copies, outs

        pages_bn, cow, outs_a = bestof(best_of)
        pages_b1, _, _ = bestof(1)
        _, _, outs_b = bestof(best_of)      # fixed seed: reproducible
        amp = pages_bn / pages_b1
        eng.assert_quiescent()
        print(f"prefix: {n_requests} requests sharing a "
              f"{sys_tokens // page}-page system prompt — TTFT p50 "
              f"{cold_p50:.1f} ms cold -> {warm_p50:.1f} ms warm "
              f"({cold_p50 / warm_p50:.2f}x), hit rate {hit_rate:.3f}, "
              f"tokens identical: {identical}", file=sys.stderr)
        print(f"best-of-{best_of}: {pages_bn} pages vs {pages_b1} for "
              f"best-of-1 ({amp:.2f}x amplification), {cow} COW tail "
              f"copies, seeded outputs reproducible: {outs_a == outs_b}",
              file=sys.stderr)
        print(json.dumps({
            "metrics_schema": METRICS_SCHEMA,
            "engine_id": eng.engine_id,
            "metric": f"{geom} shared-prefix warm/cold TTFT p50 speedup "
                      f"({sys_tokens}-token system prompt)",
            "value": round(cold_p50 / warm_p50, 2), "unit": "x",
            "vs_baseline": round(cold_p50 / warm_p50, 2),
            "requests": n_requests, "decode_tokens": n_decode,
            "sys_tokens": sys_tokens,
            "ttft_cold_ms_p50": round(cold_p50, 2),
            "ttft_warm_ms_p50": round(warm_p50, 2),
            "prefix_hit_rate": round(hit_rate, 4),
            "cached_prefill_skipped_tokens": int(warm["hit_tokens"]),
            "cow_copies": int(cow),
            "bestof_n": best_of,
            "bestof_page_amplification": round(amp, 3),
            "warm_tokens_identical": bool(identical),
            "sampled_reproducible": bool(outs_a == outs_b)}))
        return

    # ---- overload scenario: arrival rate > capacity, SLOs + supervision ---
    if overload:
        from thunder_tpu.serving import AdmissionRejected, EngineSupervisor

        deadline = float(os.environ.get("SERVE_DEADLINE_S",
                                        "120" if smoke else "60"))
        qbound = int(os.environ.get("SERVE_QUEUE", str(slots)))
        need = -(-int(max(len(p) for p in prompts) + n_decode) // page)
        eng = ServingEngine(params, cfg, max_slots=slots, page_size=page,
                            max_context=max_context, n_layers=n_layers,
                            prefill_chunk=chunk, num_pages=slots * need + 1)
        # warm the real length mix + decode program with the queue unbounded
        for L in sorted({int(l) for l in lens}):
            eng.submit(rng.randint(1, cfg.vocab_size, size=L).astype(np.int32),
                       max_new_tokens=2)
        eng.drain()
        eng.completed.clear()
        eng.shed.clear()
        eng.cache.reset_peak()
        eng.reset_slo_window()          # warm requests are not SLO traffic
        observe.reset()                 # warmup compiles pollute the stats
        eng.max_queue = qbound          # bound admissions for the timed run
        sup = EngineSupervisor(eng)
        prios = rng.randint(0, 3, size=n_requests)
        pending = sorted(zip(arrivals.tolist(), prompts, prios.tolist()),
                         key=lambda x: x[0])
        accepted, rejected = [], 0
        t0 = time.perf_counter()
        while pending or not eng.idle:
            now = time.perf_counter() - t0
            while pending and pending[0][0] <= now:
                _, p, pr = pending.pop(0)
                try:
                    accepted.append(sup.submit(p, n_decode,
                                               deadline_s=deadline,
                                               priority=int(pr)))
                except AdmissionRejected:
                    rejected += 1       # shed at submit (queue full)
            if not sup.step() and pending:
                time.sleep(max(0.0, min(pending[0][0] - now, 1e-3)))
        sup.drain()                     # stamps serving.drain_ms; engine idle
        wall = time.perf_counter() - t0
        eng.assert_quiescent()          # leak audit: overload must not leak
        # the TIMED engine's labeled series (schema 12): a sibling engine
        # or warm pool sharing the registry cannot clobber these reads
        esnap = eng.obs.snapshot()
        done = [r for r in accepted if r.done]
        late = sum(1 for r in done if r.deadline_at is not None
                   and r.finished_s > r.deadline_at)
        shed_total = len(eng.shed)      # queue/priority shed + rejected
        slo = esnap["gauges"].get("serving.slo_attainment", float("nan"))
        tok_s = sum(len(r.generated) for r in done) / wall
        print(f"overload: {n_requests} offered at {rate:g}/s, queue bound "
              f"{qbound}: {len(done)} completed, {shed_total} shed "
              f"({rejected} at submit), {late} late — slo {slo:.3f}, "
              f"{tok_s:.1f} tok/s aggregate", file=sys.stderr)
        print(json.dumps({
            "metrics_schema": METRICS_SCHEMA,
            "engine_id": eng.engine_id,
            "metric": f"{geom} overload slo_attainment "
                      f"(rate>capacity, deadline {deadline:g}s)",
            "value": round(slo, 4), "unit": "ratio", "vs_baseline": 1.0,
            "requests": n_requests, "decode_tokens": n_decode,
            "queue_bound": qbound, "deadline_s": deadline,
            "completed": len(done),
            "shed_rate": round(shed_total / n_requests, 4),
            "deadline_miss_rate": round(late / max(1, len(done)), 4),
            "slo_attainment": round(slo, 4),
            "engine_restarts": int(esnap["counters"].get(
                "serving.engine_restarts", 0)),
            "tokens_per_s": round(tok_s, 1)}))
        trace_path = os.environ.get("SERVE_TRACE")
        if trace_path:
            # the overload run is single-round; the registry holds exactly
            # its spans (reset after warmup), counter tracks ride the ring
            n = observe.export_chrome_trace(trace_path)
            print(f"serving timeline: {n} trace events -> {trace_path}",
                  file=sys.stderr)
        return

    # ---- fleet scenario: health-aware cache-affine routing ----------------
    if fleet:
        from thunder_tpu.runtime import faults
        from thunder_tpu.runtime.faults import FaultPlan, FaultSpec
        from thunder_tpu.runtime.retry import RestartBudget, RetryPolicy
        from thunder_tpu.serving import (
            DEAD,
            EngineSupervisor,
            FleetObservatory,
            FleetRouter,
            HealthGate,
            HealthPolicy,
            RandomPlacement,
        )

        n_engines = int(os.environ.get("SERVE_FLEET_ENGINES", "2"))
        groups = int(os.environ.get("SERVE_GROUPS", "6"))
        per_group = int(os.environ.get("SERVE_GROUP_REQUESTS", "6"))
        pool_pages = int(os.environ.get("SERVE_POOL_PAGES", "56"))
        prefix_pages = int(os.environ.get("SERVE_PREFIX_PAGES", "9"))
        pre_len, sfx_len = prefix_pages * page, page
        # G prefix groups with INTERLEAVED arrivals: the worst case for one
        # engine's LRU trie (the pool can't park every group's chain, so
        # each arrival evicts the next group's pages), the best case for
        # affinity routing (each engine keeps its share of the groups warm)
        group_prefixes = [rng.randint(1, cfg.vocab_size,
                                      size=pre_len).astype(np.int32)
                          for _ in range(groups)]
        fleet_prompts = [np.concatenate(
            [group_prefixes[g],
             rng.randint(1, cfg.vocab_size, size=sfx_len).astype(np.int32)])
            for _ in range(per_group) for g in range(groups)]
        n_fleet = len(fleet_prompts)
        fleet_tokens = n_fleet * n_decode

        def mk_engine():
            return ServingEngine(
                params, cfg, max_slots=slots, page_size=page,
                max_context=max_context, n_layers=n_layers,
                prefill_chunk=chunk, prefix_cache=True,
                num_pages=pool_pages,
                retry_policy=RetryPolicy(max_attempts=2, base_delay_s=0.001,
                                         max_delay_s=0.01))

        def warm(eng):
            # compile-warm prefill + decode at the real lengths, then clear
            # the trie/completions so every timed round starts cold
            for _ in range(2):
                eng.submit(rng.randint(1, cfg.vocab_size,
                                       size=pre_len + sfx_len)
                           .astype(np.int32), max_new_tokens=2)
            eng.drain()
            eng.prefix.clear()
            eng.completed.clear()

        def run_round(submit, drain, engines):
            for e in engines:
                e.prefix.clear()
                e.completed.clear()
            t0 = time.perf_counter()
            reqs = [submit(p, n_decode) for p in fleet_prompts]
            drain()
            wall = time.perf_counter() - t0
            hit = sum(1 for r in reqs if r.prefix_hit_tokens > 0) / len(reqs)
            return wall, hit, sorted(r.ttft_s * 1e3 for r in reqs)

        def best_of(submit, drain, engines, rounds):
            best = None
            for _ in range(rounds):
                w, hit, ttfts = run_round(submit, drain, engines)
                if best is None or w < best[0]:
                    best = (w, hit, ttfts)
            return best

        rounds = 3 if smoke else 2
        single = mk_engine()
        warm(single)
        s_wall, s_hit, _ = best_of(single.submit, single.drain, [single],
                                   rounds)
        single.assert_quiescent()

        def mk_router(policies=None):
            sups = [EngineSupervisor(mk_engine()) for _ in range(n_engines)]
            for s in sups:
                warm(s.engine)
            # this workload deliberately runs the pool full of PARKED
            # prefix pages (refcount 0, evictable on demand) — low
            # pages_free is the design, not page pressure, so the gate
            # must not read it as DEGRADED
            return FleetRouter(sups, policies=policies,
                               observatory=FleetObservatory(
                                   policy=HealthPolicy(
                                       page_free_degraded=0.0)))

        aff = mk_router()               # default health/affinity/load chain
        a_wall, a_hit, a_ttfts = best_of(
            aff.submit, aff.drain, list(aff.engines.values()), rounds)
        aff.assert_quiescent()
        rnd = mk_router([HealthGate(), RandomPlacement(seed=0)])
        r_wall, r_hit, _ = best_of(
            rnd.submit, rnd.drain, list(rnd.engines.values()), rounds)
        rnd.assert_quiescent()

        # -- mid-run kill: failover re-admission stays token-identical ------
        kill_prompts = [rng.randint(1, cfg.vocab_size,
                                    size=24).astype(np.int32)
                        for _ in range(6)]
        kill_refs = [np.asarray(llama.generate(params, cfg, p[None],
                                               n_decode,
                                               n_layers=n_layers))[0]
                     for p in kill_prompts]
        # zero restart budget: the first crash is terminal, so recovery IS
        # the router's failover (zero headroom reads DEGRADED under the
        # default health policy — this fleet runs without restart masking)
        ksups = [EngineSupervisor(mk_engine(), restart_budget=RestartBudget(
                     max_restarts=0, window_s=3600.0)) for _ in range(2)]
        for s in ksups:
            warm(s.engine)
        krouter = FleetRouter(ksups, observatory=FleetObservatory(
            policy=HealthPolicy(restart_headroom_min=0)))
        kreqs = [krouter.submit(p, n_decode, deadline_s=120.0)
                 for p in kill_prompts]
        with faults.active(FaultPlan([FaultSpec("serving:engine",
                                                every_n=8, max_fires=1)])):
            krouter.drain()
        assert all(r.done for r in kreqs), "kill run lost requests"
        for r, ref in zip(kreqs, kill_refs):
            np.testing.assert_array_equal(r.output(), ref)
        assert sum(1 for st in krouter.states.values() if st == DEAD) == 1
        migrated = [d for d in krouter.decisions if d["kind"] == "migrate"]
        assert migrated, "the killed engine had nothing in flight"
        krouter.assert_quiescent()      # the dead engine's pools included
        misses = int(observe.snapshot()["counters"].get(
            "serving.deadline_misses", 0))
        assert misses == 0, f"failover caused {misses} deadline misses"

        s_tok, a_tok, r_tok = (fleet_tokens / w
                               for w in (s_wall, a_wall, r_wall))
        scaling = a_tok / s_tok
        assert scaling >= 1.8, (
            f"fleet scaling {scaling:.2f}x < 1.8x over single engine")
        assert a_hit > r_hit, (
            f"affinity hit rate {a_hit:.2f} <= random {r_hit:.2f}")
        print(f"fleet: {n_engines} engines, {groups} prefix groups x "
              f"{per_group} requests — single {s_tok:.0f} tok/s (hit "
              f"{s_hit:.2f}), affinity {a_tok:.0f} tok/s (hit {a_hit:.2f}, "
              f"{scaling:.2f}x), random {r_tok:.0f} tok/s (hit {r_hit:.2f})"
              f"; kill migrated {len(migrated)} token-identical, "
              f"{misses} deadline misses", file=sys.stderr)
        print(json.dumps({
            "metrics_schema": METRICS_SCHEMA,
            "metric": f"{geom} fleet ({n_engines} engines) aggregate "
                      f"decode tokens/s",
            "value": round(a_tok, 1), "unit": "tokens/s",
            "vs_baseline": round(scaling, 3),
            "requests": n_fleet, "decode_tokens": n_decode,
            # schema-13 fleet-router fields
            "fleet_engines": n_engines,
            "aggregate_toks_s": round(a_tok, 1),
            "single_toks_s": round(s_tok, 1),
            "random_toks_s": round(r_tok, 1),
            "scaling_vs_single": round(scaling, 3),
            "affinity_hit_rate": round(a_hit, 3),
            "random_hit_rate": round(r_hit, 3),
            "single_hit_rate": round(s_hit, 3),
            "migrated_requests": len(migrated),
            "ttft_ms_p50": round(_percentile(a_ttfts, 0.50), 2),
            "ttft_ms_p99": round(_percentile(a_ttfts, 0.99), 2)}))
        return

    # ---- sequential single-stream baseline (dense cache + bind) -----------
    step_fn, prefill_fn = llama._get_step_fns(cfg, n_layers)
    buckets = []
    b = page
    while b < max_context:
        buckets.append(b)
        b *= 2
    buckets.append(max_context)
    bucketer = LengthBucketer(buckets)

    def seq_serve(prompt):
        cache = llama.init_kv_cache(cfg, 1, max_context, n_layers=n_layers)
        Tp = int(prompt.shape[0])
        Tb = bucketer.bucket_for(Tp)
        padded = np.zeros((1, Tb), np.int32)
        padded[0, :Tp] = prompt
        last, cache = prefill_fn(params, padded, cache, jnp.int32(0),
                                 jnp.int32(Tp))
        tok = np.asarray(last).argmax(-1).astype(np.int32)
        out = [int(tok[0])]
        for i in range(1, n_decode):
            last, cache = bound(params, tok[:, None], cache,
                                jnp.int32(Tp + i - 1))
            tok = np.asarray(last).argmax(-1).astype(np.int32)
            out.append(int(tok[0]))
        return out

    # warm every compiled shape the baseline will touch, then bind decode
    cache0 = llama.init_kv_cache(cfg, 1, max_context, n_layers=n_layers)
    bound = step_fn.bind(params, np.zeros((1, 1), np.int32), cache0,
                         jnp.int32(0))
    for Tb in sorted({bucketer.bucket_for(int(l)) for l in lens}):
        c = llama.init_kv_cache(cfg, 1, max_context, n_layers=n_layers)
        prefill_fn(params, np.ones((1, Tb), np.int32), c, jnp.int32(0),
                   jnp.int32(Tb))
    seq_outputs = [seq_serve(p) for p in prompts]  # warm + reference outputs

    def run_sequential():
        t0 = time.perf_counter()
        outs = [seq_serve(p) for p in prompts]
        return time.perf_counter() - t0, outs

    # ---- continuous batching engine ---------------------------------------
    # SERVE_TRACE=/path.json: capture the Perfetto serving timeline of the
    # winning continuous round for chrome://tracing / ui.perfetto.dev
    trace_path = os.environ.get("SERVE_TRACE")
    # pool sized to the workload's full residency (not the whole context
    # window): the scatter-write copies the pool per step on backends
    # without donation, so dead pages cost real bandwidth
    need = -(-int(max(len(p) for p in prompts) + n_decode) // page)
    eng = ServingEngine(params, cfg, max_slots=slots, page_size=page,
                        max_context=max_context, n_layers=n_layers,
                        prefill_chunk=chunk, num_pages=slots * need + 1)
    # warm: the real length mix (same prefill chunk entries) + decode program
    for L in sorted({int(l) for l in lens}):
        eng.submit(rng.randint(1, cfg.vocab_size, size=L).astype(np.int32),
                   max_new_tokens=2)
    eng.drain()
    # decode fusion shape, published by the runner at bind time from the
    # compiled program's executor assignments (registry gauges, NOT trace
    # grepping) — captured here because the timed rounds reset the registry,
    # and read off the TIMED engine's LABELED series (schema 12): the
    # process-wide gauge is last-writer-wins, so any sibling engine binding
    # later in this process would clobber it silently.
    # decode_layer_fusions counts whole-decode-layer megakernel claims;
    # launches is the Pallas dispatch count of ONE decode step (one token
    # across the whole batch). 0/0 on stacks where Pallas is unavailable
    # (e.g. this CPU smoke) — the decode trace then runs the XLA
    # decomposition and the stamped shape says so.
    snap0 = eng.obs.snapshot()
    decode_layer_fusions = int(snap0["gauges"].get(
        "serving.decode_layer_fusions", 0))
    decode_launches = int(snap0["gauges"].get(
        "serving.decode_pallas_launches", 0))

    def run_continuous():
        eng.completed.clear()
        eng.cache.reset_peak()
        observe.reset()  # per-round metrics (warmup compiles pollute p99)
        flight_base = observe.flight.get_recorder().total
        pending = sorted(zip(arrivals.tolist(), prompts), key=lambda x: x[0])
        reqs = []
        t0 = time.perf_counter()
        while pending or eng.queue or eng.active_requests:
            now = time.perf_counter() - t0
            while pending and pending[0][0] <= now:
                reqs.append(eng.submit(pending.pop(0)[1], n_decode))
            if not eng.step() and pending:
                time.sleep(max(0.0, min(pending[0][0] - now, 1e-3)))
        wall = time.perf_counter() - t0
        snap = observe.snapshot()
        # request-timeline summary (schema 6): the lifecycle tracing's
        # scheduler-iteration spans split host scheduling from dispatch,
        # and per-request queued time comes off the Request objects
        sched = [s for s in snap["spans"] if s["cat"] == "serving:sched"]
        host = [s["dur_us"] / 1e3 for s in sched if s["name"] == "schedule"]
        disp = [s["dur_us"] / 1e3 for s in sched
                if s["name"] == "decode_dispatch"]
        stats = {
            "wall": wall,
            "ttfts": sorted(r.ttft_s * 1e3 for r in reqs),
            "reqs": reqs,
            "preempted": snap["counters"].get("serving.preempted_requests", 0),
            "util_peak": eng.cache.peak_pages_used / eng.cache.pages_total,
            "queue_ms": sorted(r.queued_ms for r in reqs),
            "sched_host_ms_mean": sum(host) / len(host) if host else 0.0,
            "decode_dispatch_ms_mean": sum(disp) / len(disp) if disp else 0.0,
            "prefill_chunks": sum(r.prefill_chunks for r in reqs),
            # per-round delta, not the process-lifetime cumulative total:
            # the stat must describe THIS round like every other stat
            "flight_records": observe.flight.get_recorder().total - flight_base,
        }
        if trace_path:
            # capture per round so the file written at the end really is
            # the WINNING round's span timeline (the registry resets each
            # round; counter tracks come from the flight ring and span the
            # whole process — warmup included — which is documented)
            stats["trace"] = observe.chrome_trace_dict()
        return wall, stats

    # best-of-N, ALTERNATING the two serving modes per round: single-trial
    # walls swing with machine weather (the bench.py / bench_generate.py
    # min-over-interleaved-rounds discipline), and alternation gives both
    # modes the same weather
    rounds = 3 if smoke else 2
    seq_wall, cont = float("inf"), None
    for _ in range(rounds):
        w, _outs = run_sequential()
        seq_wall = min(seq_wall, w)
        w, stats = run_continuous()
        if cont is None or w < cont["wall"]:
            cont = stats
    # decode-program census (schema 8): the compiled decode step's HLO-level
    # accounting next to the trace-level launch gauges stamped above — a
    # collective appearing in the single-chip decode program or a fusion
    # regression is a diff in CI. After the timed rounds: the first access
    # pays the census's one memoized AOT compile (observe.census).
    dec_cens = tt.compile_stats(eng.runner.decode_jit).last_census or {}
    dec_async = dec_cens.get("async") or {}
    # fleet view (schema 12): wrap the timed engine in a supervisor +
    # FleetObservatory AFTER timing (the health check is pure attribute
    # reads — no traffic, no steps) so the line carries the same verdict a
    # production observatory would compute from this engine's state
    from thunder_tpu.serving import EngineSupervisor, FleetObservatory

    fleet = FleetObservatory()
    fleet.add(EngineSupervisor(eng))
    fleet_health = fleet.check()
    fleet_slo = fleet.slo_attainment()

    seq_tps = total_tokens / seq_wall
    wall = cont["wall"]
    cont_tps = total_tokens / wall
    ttfts = cont["ttfts"]
    preempted = cont["preempted"]
    print(f"sequential: {seq_wall * 1e3:.1f} ms total, {seq_tps:.1f} tok/s "
          f"aggregate", file=sys.stderr)
    print(f"continuous: {wall * 1e3:.1f} ms total, {cont_tps:.1f} tok/s "
          f"aggregate ({cont_tps / seq_tps:.2f}x sequential)", file=sys.stderr)

    # correctness spot check: continuous outputs match sequential greedily
    for r, ref in zip(cont["reqs"], seq_outputs):
        if list(r.output()) != ref:
            print(f"WARNING: request {r.request_id} diverged from the "
                  f"sequential baseline", file=sys.stderr)

    print(json.dumps({
        "metrics_schema": METRICS_SCHEMA,
        "metric": f"{geom} sequential single-stream aggregate decode tokens/s",
        "value": round(seq_tps, 1), "unit": "tokens/s", "vs_baseline": 1.0,
        "requests": n_requests, "decode_tokens": n_decode}))
    print(json.dumps({
        "metrics_schema": METRICS_SCHEMA,
        "engine_id": eng.engine_id,
        "metric": f"{geom} continuous batching aggregate decode tokens/s",
        "value": round(cont_tps, 1), "unit": "tokens/s",
        "vs_baseline": round(cont_tps / seq_tps, 4),
        "requests": n_requests, "decode_tokens": n_decode,
        "requests_per_s": round(n_requests / wall, 2),
        "ttft_ms_p50": round(_percentile(ttfts, 0.50), 2),
        "ttft_ms_p99": round(_percentile(ttfts, 0.99), 2),
        "decode_ms_p99": round(_percentile(sorted(
            (r.finished_s - r.decode_start_s) * 1e3
            for r in cont["reqs"] if r.decode_start_s is not None), 0.99), 2),
        "kv_page_util_peak": round(cont["util_peak"], 4),
        "kv_pages_total": eng.cache.pages_total,
        "preempted_requests": int(preempted),
        "decode_layer_fusions": decode_layer_fusions,
        "decode_pallas_launches_per_token": decode_launches,
        "decode_launches_per_layer_per_token": round(
            decode_launches / max(n_layers, 1), 3),
        # schema-6 request-timeline summary (lifecycle tracing + flight ring)
        "queue_ms_p50": round(_percentile(cont["queue_ms"], 0.50), 2),
        "queue_ms_p99": round(_percentile(cont["queue_ms"], 0.99), 2),
        "sched_host_ms_mean": round(cont["sched_host_ms_mean"], 3),
        "decode_dispatch_ms_mean": round(cont["decode_dispatch_ms_mean"], 3),
        "prefill_chunks_total": int(cont["prefill_chunks"]),
        "flight_records": int(cont["flight_records"]),
        # schema-8 decode-program census (observe.census)
        "census_decode_collective_instructions": int(
            dec_async.get("count", 0)),
        "census_decode_hlo_fusions": int(dec_cens.get("hlo_fusions", 0)),
        "census_decode_errors": int(dec_cens.get("census_errors", 0)),
        "census_decode_pessimizations": sorted(
            {f["kind"] for f in (dec_cens.get("findings") or [])}),
        # schema-12 fleet view (post-timing FleetObservatory check)
        "fleet_engines": len(fleet_health),
        "fleet_health": fleet_health,
        "fleet_slo_attainment": (None if fleet_slo is None
                                 else round(fleet_slo, 4))}))

    if trace_path:
        with open(trace_path, "w") as f:
            json.dump(cont["trace"], f, default=str)
        print(f"serving timeline: {len(cont['trace']['traceEvents'])} trace "
              f"events -> {trace_path}", file=sys.stderr)


if __name__ == "__main__":
    main()
    # what was timed is the program the planner chose, not a degraded one
    from thunder_tpu.runtime import quarantine

    quarantine.assert_clean()
