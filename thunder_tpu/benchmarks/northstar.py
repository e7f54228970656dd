"""North-star evidence pack: AOT-compile the REAL BASELINE configs for the
target TPU topologies and derive the memory / communication / MFU story from
the compiled executables — no chips required.

The driver's north star (BASELINE.md) is Llama-2-7B pretraining via jit+FSDP
on a v5p-32 at >=45% MFU. This environment has at most one 4-chip v5e host, so
the closest attainable evidence is exactly what the reference publishes for its
multi-GPU claim (a normalized-scaling plot, ``/root/reference/README.md:
60-63``): compile the real configs against the real topology and show, from
XLA's own accounting,

- per-device HBM fits the 95 GB budget (``memory_analysis``),
- collective bytes vs ICI bandwidth (trace-level ``comm_report``),
- cost-model step time -> projected MFU, arithmetic shown,
- the optimized HLO schedules collectives async (overlap markers).

Consumed by ``tests/test_northstar.py`` (regressions fail) and by
``python -m thunder_tpu.benchmarks.northstar`` (writes NORTHSTAR.md).
"""

from __future__ import annotations

import os

import numpy as np

# v5p chip datasheet numbers (public: jax-ml.github.io/scaling-book — the
# "How to Scale Your Model" hardware table).
V5P = {
    "peak_bf16_flops": 4.59e14,   # per chip
    "hbm_bytes": 95.74e9,         # per chip
    "hbm_bw": 2.765e12,           # bytes/s per chip
    "ici_bw_axis": 9e10,          # bytes/s one-way per link; 3 axes (3D torus)
    "ici_links": 6,
}

# topology names understood by the PJRT TPU compiler
TOPO_V5P_32 = "v5p:2x2x4"   # 16 chips = v5p-32 (cores x2 naming)
TOPO_V5P_16 = "v5p:2x2x2"   # 8 chips = v5p-16


def get_topology(name: str):
    # honor an explicit platform restriction: with JAX_PLATFORMS=cpu a
    # present-but-chipless libtpu must not be initialized — PJRT topology
    # setup blocks on the runtime socket instead of raising
    plats = os.environ.get("JAX_PLATFORMS", "")
    if plats and "tpu" not in plats.split(","):
        return None
    import jax
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name=name)
    except jax.errors.JaxRuntimeError:    # no TPU compiler on this host
        return None


# ---------------------------------------------------------------------------
# abstract (no-materialization) model/optimizer state
# ---------------------------------------------------------------------------

def abstract_llama_step(cfg_name: str, *, batch: int, seq: int, n_dev: int,
                        zero: int = 2, remat: bool = False,
                        fused_loss: bool = True):
    """(jstep, args) for a FULL fwd+bwd+AdamW train step with the params and
    optimizer state as ShapeDtypeStructs — 7B compiles without 7B of host
    RAM. ``batch`` is GLOBAL."""
    import jax

    import thunder_tpu as tt
    from thunder_tpu.core.devices import MeshSpec
    from thunder_tpu.distributed import fsdp
    from thunder_tpu.models import llama
    from thunder_tpu.optim import AdamW

    cfg = llama.CONFIGS[cfg_name]
    opt = AdamW(lr=1e-4)
    loss = llama.fused_loss_fn if fused_loss else llama.loss_fn

    def train_step(params, opt_state, tokens, targets):
        loss_v, grads = tt.value_and_grad(
            lambda p: loss(p, tokens, targets, cfg, remat=remat))(params)
        new_params, new_opt = opt.update(params, grads, opt_state)
        return loss_v, new_params, new_opt

    params_abs = jax.eval_shape(lambda: llama.init_params(cfg, seed=0))
    opt_abs = jax.eval_shape(opt.init, params_abs)
    rng = np.random.RandomState(0)
    tokens = rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32)
    targets = np.roll(tokens, -1, 1).astype(np.int32)

    jstep = fsdp(train_step, MeshSpec.make(fsdp=n_dev), zero=zero)
    return jstep, (params_abs, opt_abs, tokens, targets), cfg


def abstract_mixtral_ep_step(*, batch: int, seq: int, n_dev: int,
                             remat: bool = True):
    import dataclasses

    import jax

    import thunder_tpu as tt
    from thunder_tpu.core import dtypes
    from thunder_tpu.core.devices import MeshSpec
    from thunder_tpu.distributed import expert_parallel
    from thunder_tpu.models import mixtral
    from thunder_tpu.optim import AdamW

    # capacity_factor 1.25 (was the 2.0 default): the r4 verdict flagged the
    # EP config's flop pad — at cf the per-expert capacity executes
    # cf x the analytic top-k flops; 1.25 keeps the measured worst-layer
    # assignment drop at 7.2% on an UNTRAINED router (MIXTRAL_EP.md sweep;
    # the aux load-balancing loss drives it toward 0 in training) and takes
    # xla_flops/analytic from 2.07x to ~1.35x at tiny scale (r5 measured)
    cfg = dataclasses.replace(mixtral.CONFIGS["mixtral-8x7b"],
                              capacity_factor=1.25)
    # the 8x7B memory recipe: all-bf16 AdamW moments (12.9B params/8 chips
    # leave no room for f32 v; the v-freeze tradeoff is documented in
    # optim.AdamW), per-block remat, chunked-vocab fused loss. Without
    # these the compile is an honest 128.6 GB/chip OOM (measured r4).
    opt = AdamW(lr=1e-4, state_dtype=dtypes.bfloat16, v_dtype=dtypes.bfloat16)

    def train_step(params, opt_state, tokens, targets):
        loss, grads = tt.value_and_grad(
            lambda p: mixtral.fused_loss_fn(p, tokens, targets, cfg,
                                            remat=remat))(params)
        new_params, new_opt = opt.update(params, grads, opt_state)
        return loss, new_params, new_opt

    params_abs = jax.eval_shape(lambda: mixtral.init_params(cfg, seed=0))
    opt_abs = jax.eval_shape(opt.init, params_abs)
    rng = np.random.RandomState(0)
    tokens = rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32)
    targets = np.roll(tokens, -1, 1).astype(np.int32)

    jstep = expert_parallel(train_step, MeshSpec.make(ep=n_dev),
                            expert_patterns=mixtral.EP_PATTERNS)
    return jstep, (params_abs, opt_abs, tokens, targets), cfg


def compile_on(topo, jstep, args):
    """AOT-compile a DistributedFunction against topology devices."""
    jstep._mesh = jstep.mesh_spec.build(list(topo.devices))
    entry = jstep.compile(*args)
    assert entry.jit_obj is not None, "no whole-program jit entry"
    lowered = entry.jit_obj.lower(*entry.input_avals)
    return lowered.compile()


# ---------------------------------------------------------------------------
# analysis
# ---------------------------------------------------------------------------

def n_params_llama(cfg) -> int:
    kv_dim = cfg.kv_heads * cfg.head_dim
    per_layer = (2 * cfg.dim                      # norms
                 + 2 * cfg.dim * cfg.dim          # wq, wo
                 + 2 * kv_dim * cfg.dim           # wk, wv
                 + 3 * cfg.intermediate_size * cfg.dim)  # gate/up/down
    return (2 * cfg.vocab_size * cfg.dim + cfg.dim
            + cfg.n_layers * per_layer)


def analytic_train_flops(n_params: int, global_tokens: int, cfg=None,
                         seq: int | None = None) -> float:
    """6*N per token (fwd 2N + bwd 4N) + attention score flops
    12*L*T*d per token (fwd+bwd, causal halving folded in)."""
    flops = 6.0 * n_params * global_tokens
    if cfg is not None and seq is not None:
        att = 12.0 * cfg.n_layers * seq * (cfg.n_heads * cfg.head_dim) // 2
        flops += att * global_tokens
    return flops


# the instruction-level collective parser is now the per-compile observe
# surface's — ONE owner (thunder_tpu/observe/census.py); the bench imports
# it back so the offline evidence pack and the live census can never drift
from thunder_tpu.observe.census import hlo_collectives  # noqa: E402


def analyze(compiled, *, n_dev: int, analytic_flops: float,
            spec=V5P) -> dict:
    """Memory + cost + roofline-projected MFU from a compiled executable."""
    ma = compiled.memory_analysis()
    mem = {k: int(getattr(ma, k, 0) or 0)
           for k in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "generated_code_size_in_bytes",
                     "alias_size_in_bytes")}
    # arguments and outputs alias (donated params/opt state) — live HBM is
    # args + temps + code (+ outputs - aliased)
    live = (mem["argument_size_in_bytes"] + mem["temp_size_in_bytes"]
            + mem["generated_code_size_in_bytes"]
            + max(0, mem["output_size_in_bytes"] - mem["alias_size_in_bytes"]))

    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    ca = dict(ca)
    xla_flops = float(ca.get("flops", 0.0))
    hbm_bytes = float(ca.get("bytes accessed", 0.0))

    hlo = compiled.as_text()
    hlo_comm = hlo_collectives(hlo, n_dev)
    # legacy substring census kept for continuity with r4 artifacts; the
    # authoritative numbers (instruction counts, bytes, async fractions
    # WITH denominators) are in hlo_comm (VERDICT r4 #3)
    overlap = {
        "async_all_gather": hlo.count('async_collective_name="all-gather-start'),
        "async_reduce_scatter": hlo.count('async_collective_name="reduce-scatter'),
        "async_all_reduce": hlo.count('async_collective_name="all-reduce-start'),
        "all_gather_total": hlo.count("all-gather"),
        "reduce_scatter_total": hlo.count("reduce-scatter"),
        "all_reduce_total": hlo.count("all-reduce"),
        "all_to_all_total": hlo.count("all-to-all"),
    }

    # roofline projection, per device (comm term added by the caller once
    # collective bytes are known — see project()). Step TIME is bounded by
    # the flops XLA actually EXECUTES (xla_flops — e.g. the MoE capacity
    # pad); MFU's numerator stays the analytic useful flops (r5: the old
    # t_math-for-both gave the padded Mixtral config a fictitious 1.0).
    flops_dev = analytic_flops / n_dev
    t_math = flops_dev / spec["peak_bf16_flops"]
    # executed-flop time exactly as XLA counts it (0.91x analytic for the
    # causal-halved dense configs, 1.68x for the padded MoE); fall back to
    # analytic only when the backend reports no flops (CPU smoke)
    t_exec = (xla_flops / spec["peak_bf16_flops"]) if xla_flops > 0 else t_math
    t_hbm = hbm_bytes / spec["hbm_bw"]            # cost model is per-device
    t_overlapped = max(t_exec, t_hbm)
    t_serial = t_exec + t_hbm
    return {
        "memory": mem,
        "live_bytes_per_device": live,
        "fits_hbm": live < spec["hbm_bytes"],
        "xla_flops_per_device": xla_flops,
        "analytic_flops_per_device": flops_dev,
        "hbm_bytes_accessed": hbm_bytes,
        "overlap": overlap,
        "hlo_collectives": hlo_comm,
        "t_math_s": t_math,
        "t_exec_s": t_exec,
        "t_hbm_s": t_hbm,
        "step_time_overlapped_s": t_overlapped,
        "step_time_serial_s": t_serial,
        "mfu_projected_overlapped": t_math / t_overlapped,
        "mfu_projected_serial": t_math / t_serial,
    }


def comm_bytes_per_device(jstep) -> dict:
    """Trace-level collective byte counts from the examine tooling (bytes a
    single device sends/receives per step, by collective kind)."""
    from thunder_tpu.examine import comm_report

    rep = comm_report(jstep)
    return {
        "per_collective": {k: {kk: int(vv) for kk, vv in v.items()}
                           for k, v in rep["collectives"].items()},
        "total_in_bytes": int(rep["total_in_bytes"]),
        "total_out_bytes": int(rep.get("total_out_bytes", 0)),
    }


def project(metrics: dict, comm: dict, *, ici_axes_used: int = 1,
            spec=V5P) -> dict:
    """Fold the ICI term into the roofline: t_ici = received bytes / the
    ICI bandwidth actually usable (one torus axis by default — conservative;
    XLA stripes large collectives over more on a v5p 3D torus, reported as
    the _2axis variants). Step time uses EXECUTED flop time (t_exec_s);
    MFU's numerator is the analytic useful flops, capped at 1. Projections:

    - overlapped: collectives and HBM fully hidden behind the MXU
      (what the async markers show the scheduler arranging)
    - serial: nothing overlaps (hard floor)
    """
    t_math = metrics["t_math_s"]
    t_exec = metrics.get("t_exec_s", t_math)
    t_hbm = metrics["t_hbm_s"]
    t_ici = comm["total_in_bytes"] / (spec["ici_bw_axis"] * ici_axes_used)
    # absolute axis-count variants (independent of ici_axes_used, so a
    # caller passing 2 cannot silently double-discount)
    t_ici_2 = comm["total_in_bytes"] / (spec["ici_bw_axis"] * 2)
    t_over = max(t_exec, t_hbm, t_ici)
    t_serial = t_exec + t_hbm + t_ici
    t_over2 = max(t_exec, t_hbm, t_ici_2)
    t_serial2 = t_exec + t_hbm + t_ici_2
    return {
        "t_ici_s": t_ici,
        "t_ici_2axis_s": t_ici_2,
        "step_time_overlapped_s": t_over,
        "step_time_serial_s": t_serial,
        "step_time_overlapped_2axis_s": t_over2,
        "step_time_serial_2axis_s": t_serial2,
        "mfu_projected_overlapped": min(1.0, t_math / t_over),
        "mfu_projected_serial": min(1.0, t_math / t_serial),
        "mfu_projected_overlapped_2axis": min(1.0, t_math / t_over2),
        "mfu_projected_serial_2axis": min(1.0, t_math / t_serial2),
    }


def overlap_projection(entry: dict, *, spec=V5P) -> dict:
    """Re-derive a committed NORTHSTAR.json entry's roofline under the
    overlap-scheduling pass (``distributed/comm_reorder``): with the
    reduce-scatter lowering PINNED, XLA cannot rewrite zero-2's grad
    collectives into all-reduces, so the HLO recv bytes collapse from the
    measured ``recv_bytes_per_device_hlo`` (2.2x on the r5 7B run) back to
    the trace ring-model expectation — the ICI term is re-folded from
    ``recv_bytes_per_device_trace``. Pure arithmetic on the committed
    metrics (no chips): the model recorded here is a prediction no chip run
    has tested."""
    recv_pinned = int(entry["recv_bytes_per_device_trace"])
    recv_hlo = int(entry["recv_bytes_per_device_hlo"])
    proj = project({"t_math_s": entry["t_math_s"],
                    "t_exec_s": entry.get("t_exec_s", entry["t_math_s"]),
                    "t_hbm_s": entry["t_hbm_s"]},
                   {"total_in_bytes": recv_pinned}, spec=spec)
    return {
        "assumes": ("pinned reduce-scatter lowering + comm_reorder schedule: "
                    "HLO recv bytes == trace ring-model expectation"),
        "recv_bytes_per_device_pinned": recv_pinned,
        "recv_bytes_per_device_unpinned_hlo": recv_hlo,
        "recv_inflation_removed": (recv_hlo / recv_pinned) if recv_pinned else 1.0,
        **proj,
        # the zero-overlap floors this pass moves (vs the committed entry)
        "mfu_serial_floor_unpinned": entry.get("mfu_projected_serial"),
        "mfu_serial_floor_unpinned_2axis": entry.get("mfu_projected_serial_2axis"),
    }


def write_overlap_models(path: str = "NORTHSTAR.json") -> dict:
    """Stamp each fsdp entry of an existing NORTHSTAR.json with its
    re-derived ``overlap_model`` block (pure arithmetic — runs without a
    TPU, unlike :func:`main`)."""
    import json

    with open(path) as f:
        results = json.load(f)
    stamped = {}
    for name, entry in results.items():
        if isinstance(entry, dict) and "recv_bytes_per_device_trace" in entry:
            entry["overlap_model"] = stamped[name] = overlap_projection(entry)
    with open(path, "w") as f:
        json.dump(results, f, indent=1)
        f.write("\n")
    return stamped


# ---------------------------------------------------------------------------
# evidence-pack generator: python -m thunder_tpu.benchmarks.northstar
# ---------------------------------------------------------------------------

def _recv_bytes(comm: dict, n_dev: int) -> int:
    """Approximate bytes RECEIVED per device per step: for each collective,
    a device receives ~the larger of its local in/out payload minus its own
    shard — (N-1)/N of max(in, out)."""
    total = 0
    for e in comm["per_collective"].values():
        total += max(e["in_bytes"], e["out_bytes"]) * (n_dev - 1) // n_dev
    return total


def run_config(name: str, builder, topo_name: str, n_dev: int,
               global_tokens: int, n_params: int, analytic_flops: float) -> dict:
    import time as _t

    topo = get_topology(topo_name)
    if topo is None:
        raise RuntimeError(f"TPU topology {topo_name} unavailable")
    jstep, args, cfg = builder()
    t0 = _t.perf_counter()
    compiled = compile_on(topo, jstep, args)
    compile_s = _t.perf_counter() - t0
    m = analyze(compiled, n_dev=n_dev, analytic_flops=analytic_flops)
    comm = comm_bytes_per_device(jstep)
    recv_trace = _recv_bytes(comm, n_dev)
    # t_ici from the OPTIMIZED HLO's own collectives (r4 verdict #3: the
    # trace-level figure understates when XLA rewrites reduce-scatters into
    # all-reduces); trace-level kept alongside as the cross-check
    recv_hlo = m["hlo_collectives"]["recv_bytes_per_device_total"]
    recv = max(recv_hlo, recv_trace)
    proj = project(m, {"total_in_bytes": recv})
    m.update(proj)
    # throughput must reflect the post-ICI step time (code-review r5: the
    # pre-ICI figure from analyze() silently survived regeneration)
    m["tokens_per_s_per_chip_projected"] = (
        global_tokens / n_dev / proj["step_time_overlapped_s"])
    m["tokens_per_s_per_chip_projected_2axis"] = (
        global_tokens / n_dev / proj["step_time_overlapped_2axis_s"])
    m["comm"] = comm
    m["recv_bytes_per_device_trace"] = recv_trace
    m["recv_bytes_per_device_hlo"] = recv_hlo
    m["recv_bytes_per_device"] = recv
    m["compile_seconds"] = compile_s
    m["n_params"] = n_params
    m["config"] = name
    m["n_devices"] = n_dev
    m["global_tokens_per_step"] = global_tokens
    return m


def main():
    import json

    from thunder_tpu.models import llama, mixtral

    results = {}

    # 1. BASELINE config 3: Llama-2-7B FSDP(zero2) on v5p-32 (16 chips)
    cfg7 = llama.CONFIGS["llama2-7b"]
    n7 = n_params_llama(cfg7)
    results["llama2-7b-fsdp-v5p32"] = run_config(
        "llama2-7b-fsdp-v5p32",
        lambda: abstract_llama_step("llama2-7b", batch=16, seq=4096,
                                    n_dev=16, zero=2),
        TOPO_V5P_32, 16, 16 * 4096,
        n7, analytic_train_flops(n7, 16 * 4096, cfg7, 4096))
    print(json.dumps(results["llama2-7b-fsdp-v5p32"], indent=1, default=str),
          flush=True)

    # 2. BASELINE config 4: Llama-3-8B (GQA, 128k vocab, seq 8192), remat
    cfg8 = llama.CONFIGS["llama3-8b"]
    n8 = n_params_llama(cfg8)
    results["llama3-8b-fsdp-v5p32"] = run_config(
        "llama3-8b-fsdp-v5p32",
        lambda: abstract_llama_step("llama3-8b", batch=16, seq=8192,
                                    n_dev=16, zero=3, remat=True),
        TOPO_V5P_32, 16, 16 * 8192,
        n8, analytic_train_flops(n8, 16 * 8192, cfg8, 8192))
    print(json.dumps(results["llama3-8b-fsdp-v5p32"], indent=1, default=str),
          flush=True)

    # 3. BASELINE config 5: Mixtral-8x7B expert-parallel on v5p-16 (8 chips)
    mcfg = mixtral.CONFIGS["mixtral-8x7b"]
    n_m_active = 46.7e9 * 0  # computed analytically below
    # active params per token: attention + 2-of-8 experts + embeddings
    kv_dim = mcfg.kv_heads * mcfg.head_dim
    att = mcfg.n_layers * (2 * mcfg.dim * mcfg.dim + 2 * kv_dim * mcfg.dim
                           + 2 * mcfg.dim)
    expert = 3 * mcfg.intermediate_size * mcfg.dim
    router = mcfg.n_experts * mcfg.dim
    n_active = (2 * mcfg.vocab_size * mcfg.dim + mcfg.dim
                + att + mcfg.n_layers * (router + mcfg.top_k * expert))
    # batch shards over the ep axis, so global batch >= n_dev; the memory
    # lever at fixed batch is sequence length (tokens/step)
    results["mixtral-8x7b-ep-v5p16"] = run_config(
        "mixtral-8x7b-ep-v5p16",
        lambda: abstract_mixtral_ep_step(batch=8, seq=2048, n_dev=8),
        TOPO_V5P_16, 8, 8 * 2048,
        n_active, analytic_train_flops(n_active, 8 * 2048, mcfg, 2048))
    print(json.dumps(results["mixtral-8x7b-ep-v5p16"], indent=1, default=str),
          flush=True)

    with open("NORTHSTAR.json", "w") as f:
        json.dump(results, f, indent=1, default=str)
    print("wrote NORTHSTAR.json", flush=True)


if __name__ == "__main__":
    main()
