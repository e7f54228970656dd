"""Production-style pretraining throughput CLI.

Reference parity: ``thunder/benchmarks/benchmark_litgpt.py`` — model ×
parallelism-mode grid reporting tokens/s and model-flops utilization; here
the optimizer is part of the compiled step (the reference steps eager AdamW,
SURVEY §3.5 note).

Usage:
  python -m thunder_tpu.benchmarks.pretrain --model tiny --mode fsdp --steps 10
  python -m thunder_tpu.benchmarks.pretrain --model llama2-7b-bench --layers 2 --batch 1 --seq 2048
"""

from __future__ import annotations

import argparse
import sys
import time


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="tiny", help="llama config name")
    p.add_argument("--mode", default="single",
                   choices=["single", "fsdp", "hsdp", "ddp", "tp", "cp", "ep",
                            "tp_dp", "fsdp_tp"])
    p.add_argument("--replicas", type=int, default=2,
                   help="hsdp: replica-axis size (shard axis gets the rest)")
    p.add_argument("--tp", type=int, default=2,
                   help="tp_dp/fsdp_tp: tensor-parallel axis size "
                        "(the other axis gets the rest)")
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--seq", type=int, default=256)
    p.add_argument("--layers", type=int, default=None)
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--lr", type=float, default=1e-4)
    p.add_argument("--peak-tflops", type=float, default=197.0,
                   help="per-chip peak bf16 TFLOP/s (v5e=197, v5p=459)")
    p.add_argument("--devices", type=int, default=None,
                   help="force an N-device virtual CPU mesh (hermetic "
                        "distributed benchmarking without hardware)")
    p.add_argument("--data", default=None,
                   help="tokenized binary shard (.bin) to stream from via the "
                        "native input pipeline (epoch-exact shuffle, prefetch, "
                        "restart-deterministic); default: synthetic tokens")
    p.add_argument("--data-seed", type=int, default=0)
    p.add_argument("--start-step", type=int, default=0,
                   help="resume data position (the stream is a pure function "
                        "of step: restarting at step k replays exactly)")
    p.add_argument("--audit", action="store_true",
                   help="print per-step losses (costs one host sync per step "
                        "— replay verification, NOT for timing runs)")
    args = p.parse_args()

    import jax

    if args.devices:
        # jax is already imported (package __init__ pulls jax.numpy) but the
        # backend is not initialized until first use: XLA_FLAGS is read
        # lazily at backend init, and the platform switches via jax.config
        import os

        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + f" --xla_force_host_platform_device_count={args.devices}")
        jax.config.update("jax_platforms", "cpu")
    import numpy as np

    import thunder_tpu as tt
    from thunder_tpu.core.devices import MeshSpec
    from thunder_tpu.models import llama
    from thunder_tpu.optim import AdamW

    if args.mode == "ep":
        from thunder_tpu.models import mixtral as model_mod

        cfg = model_mod.CONFIGS["tiny-moe" if args.model == "tiny" else args.model]
        loss_mod = model_mod
    else:
        model_mod = llama
        cfg = llama.CONFIGS[args.model]
        loss_mod = llama
    n_layers = args.layers if args.layers is not None else cfg.n_layers
    opt = AdamW(lr=args.lr)

    def train_step(params, opt_state, tokens, targets):
        loss, grads = tt.value_and_grad(
            lambda p: loss_mod.loss_fn(p, tokens, targets, cfg))(params)
        return loss, *opt.update(params, grads, opt_state)

    n_dev = len(jax.devices())
    if args.mode == "single":
        # donated params/opt-state: in-place updates, halves weight memory
        jstep = tt.jit(train_step, donate_argnums=(0, 1))
    elif args.mode == "fsdp":
        from thunder_tpu.distributed import fsdp

        jstep = fsdp(train_step, MeshSpec.make(fsdp=n_dev))
    elif args.mode == "hsdp":
        from thunder_tpu.distributed import hsdp

        if args.replicas < 1 or n_dev % args.replicas:
            raise SystemExit(f"--replicas {args.replicas} must divide the "
                             f"device count {n_dev} (and leave a shard axis)")
        jstep = hsdp(train_step,
                     MeshSpec.make(dp=args.replicas, fsdp=n_dev // args.replicas))
    elif args.mode == "ddp":
        from thunder_tpu.distributed import ddp

        jstep = ddp(train_step, MeshSpec.make(dp=n_dev))
    elif args.mode == "cp":
        from thunder_tpu.distributed import context_parallel

        jstep = context_parallel(train_step, MeshSpec.make(sp=n_dev))
    elif args.mode == "ep":
        from thunder_tpu.distributed import expert_parallel
        from thunder_tpu.models import mixtral

        if cfg.n_experts % n_dev:
            raise SystemExit(f"n_experts {cfg.n_experts} must be divisible "
                             f"by the device count {n_dev}")
        if args.batch % n_dev:
            raise SystemExit(f"--batch {args.batch} must be divisible by the "
                             f"device count {n_dev} (the batch shards on the ep axis)")
        jstep = expert_parallel(train_step, MeshSpec.make(ep=n_dev),
                                expert_patterns=mixtral.EP_PATTERNS)
    elif args.mode == "tp":
        from thunder_tpu.distributed import tensor_parallel

        local_cfg = llama.tp_config(cfg, n_dev)
        cfg = local_cfg
        jstep = tensor_parallel(train_step, MeshSpec.make(tp=n_dev),
                                column_patterns=llama.TP_COLUMN_PATTERNS,
                                row_patterns=llama.TP_ROW_PATTERNS)
    elif args.mode in ("tp_dp", "fsdp_tp"):
        if args.tp < 1 or n_dev % args.tp:
            raise SystemExit(f"--tp {args.tp} must divide the device count {n_dev}")
        other = n_dev // args.tp
        cfg = llama.tp_config(cfg, args.tp)
        if args.mode == "tp_dp":
            from thunder_tpu.distributed import tensor_parallel

            jstep = tensor_parallel(train_step, MeshSpec.make(dp=other, tp=args.tp),
                                    column_patterns=llama.TP_COLUMN_PATTERNS,
                                    row_patterns=llama.TP_ROW_PATTERNS,
                                    data_parallel_axis="dp")
        else:
            from thunder_tpu.distributed import fsdp_tp

            jstep = fsdp_tp(train_step, MeshSpec.make(fsdp=other, tp=args.tp),
                            column_patterns=llama.TP_COLUMN_PATTERNS,
                            row_patterns=llama.TP_ROW_PATTERNS)

    params = model_mod.init_params(cfg if args.mode == "ep" else llama.CONFIGS[args.model],
                                   seed=0, scale_layers=n_layers)
    opt_state = opt.init(params)
    if args.data:
        from thunder_tpu.data import ShardedTokenStream

        stream = ShardedTokenStream(args.data, batch=args.batch, seq=args.seq,
                                    seed=args.data_seed)

        def data_fn(step):
            t, g = stream.batch_at(step)
            return np.clip(t, 0, cfg.vocab_size - 1), \
                np.clip(g, 0, cfg.vocab_size - 1)
    else:
        rng = np.random.RandomState(0)
        fixed = rng.randint(0, cfg.vocab_size, size=(args.batch, args.seq)).astype(np.int32)
        fixed_t = np.roll(fixed, -1, 1).astype(np.int32)

        def data_fn(step):
            return fixed, fixed_t

    tokens, targets = data_fn(args.start_step)

    def force_chain(loss, params):
        jax.block_until_ready((loss, params))

    t0 = time.perf_counter()
    loss, params, opt_state = jstep(params, opt_state, tokens, targets)
    force_chain(loss, params)
    compile_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    for k in range(args.steps):
        tokens, targets = data_fn(args.start_step + 1 + k)
        loss, params, opt_state = jstep(params, opt_state, tokens, targets)
        if args.audit:  # replay-audit mode: per-step loss (costs a sync)
            print(f"step {args.start_step + 1 + k} "
                  f"loss {float(np.asarray(loss)):.6f}", file=sys.stderr)
    force_chain(loss, params)
    dt = (time.perf_counter() - t0) / args.steps

    tokens_per_step = args.batch * args.seq
    tps = tokens_per_step / dt
    if args.mode == "ep":
        # MoE FLOPs/token: attention as dense + top_k of E expert MLPs
        base_cfg = cfg
        fpt = llama.flops_per_token(cfg, args.seq, n_layers) \
            * (1 + (cfg.top_k - 1) / max(1, cfg.n_experts))  # rough active-expert scale
    else:
        base_cfg = llama.CONFIGS[args.model]
        fpt = llama.flops_per_token(base_cfg, args.seq, n_layers)
    mfu = tps * fpt / (args.peak_tflops * 1e12 * max(1, n_dev))
    if args.mode == "ep":
        # expert-utilization report (VERDICT r2 item 10): routing health of
        # the trained params on the last batch
        import json

        from thunder_tpu.models import mixtral as _mx

        rep = _mx.expert_utilization(params, tokens, cfg)
        for li, r in enumerate(rep):
            print(f"expert-utilization layer{li}: {json.dumps(r)}", file=sys.stderr)
    print(f"model={args.model} layers={n_layers} mode={args.mode} devices={n_dev}")
    print(f"compile {compile_s:.1f}s | {dt*1e3:.1f} ms/step | {tps:,.0f} tokens/s "
          f"| MFU {mfu*100:.1f}% | loss {float(np.asarray(loss)):.4f}")


if __name__ == "__main__":
    main()
