"""Benchmark harness + workloads.

Reference parity: ``thunder/benchmarks/__init__.py`` (Benchmark/BenchmarkArg/
BenchmarkRunStatistics harness with median/IQR stats :53-308; nanoGPT/litgpt
module workloads :963+) re-built for JAX timing semantics
(``block_until_ready``, compile-time split out).
"""

from __future__ import annotations

import statistics
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Sequence


@dataclass
class BenchmarkRunStatistics:
    name: str
    times_s: list[float]
    compile_s: float

    @property
    def median_s(self) -> float:
        return statistics.median(self.times_s)

    @property
    def mean_s(self) -> float:
        return statistics.fmean(self.times_s)

    @property
    def iqr_s(self) -> float:
        qs = statistics.quantiles(self.times_s, n=4)
        return qs[2] - qs[0]

    def summary(self) -> str:
        return (f"{self.name}: median {self.median_s*1e3:.3f} ms "
                f"(mean {self.mean_s*1e3:.3f}, iqr {self.iqr_s*1e3:.3f}, "
                f"compile {self.compile_s:.2f} s, n={len(self.times_s)})")


def _sync(out):
    """Fence device work: JAX dispatch is asynchronous."""
    import jax

    return jax.block_until_ready(out)


def time_fn(fn: Callable, *args, warmup: int = 2, iters: int = 10, name: str = "fn",
            **kwargs) -> BenchmarkRunStatistics:
    import numpy as _np
    import jax.numpy as jnp

    # device_put inputs ONCE (the whole pytree): numpy args would otherwise
    # re-upload per call (hundreds of MB host-to-device — that's the
    # loader's job, not the op under measurement)
    import jax

    conv = lambda a: jnp.asarray(a) if isinstance(a, _np.ndarray) else a
    args = tuple(jax.tree_util.tree_map(conv, a) for a in args)
    kwargs = {k: jax.tree_util.tree_map(conv, v) for k, v in kwargs.items()}
    _sync(args)
    t0 = time.perf_counter()
    _sync(fn(*args, **kwargs))
    compile_s = time.perf_counter() - t0
    for _ in range(max(0, warmup - 1)):
        _sync(fn(*args, **kwargs))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        _sync(fn(*args, **kwargs))
        times.append(time.perf_counter() - t0)
    return BenchmarkRunStatistics(name, times, compile_s)


@dataclass
class Benchmark:
    """A workload: produces (fn, args) pairs and derived metrics."""

    name: str
    make: Callable[[], tuple[Callable, tuple]]
    tokens_per_iter: int | None = None

    def run(self, *, executors=None, warmup: int = 2, iters: int = 10) -> BenchmarkRunStatistics:
        import thunder_tpu as tt

        fn, args = self.make()
        jfn = tt.jit(fn, executors=executors)
        label = f"{self.name}[{','.join(e if isinstance(e, str) else e.name for e in (executors or ['default']))}]"
        return time_fn(jfn, *args, warmup=warmup, iters=iters, name=label)


# ---------------------------------------------------------------------------
# workloads (reference: nanoGPT CSA/MLP/Block, litgpt GELU/SDPA, llama2 MLP,
# cross-entropy microbenchmarks — thunder/benchmarks/__init__.py:963+)
# ---------------------------------------------------------------------------

def _np_rng(seed=0):
    import numpy as np

    return np.random.RandomState(seed)


def make_sdpa_benchmark(B=8, H=16, T=1024, hd=128, causal=True, dtype="bfloat16") -> Benchmark:
    def make():
        import numpy as np

        from thunder_tpu import ops

        rng = _np_rng()
        mk = lambda: rng.randn(B, H, T, hd).astype(np.float32)
        q, k, v = mk(), mk(), mk()

        def fn(q, k, v):
            return ops.scaled_dot_product_attention(q, k, v, is_causal=causal)

        return fn, (q, k, v)

    return Benchmark(f"sdpa_B{B}H{H}T{T}D{hd}", make)


def make_cross_entropy_benchmark(N=8192, V=32000) -> Benchmark:
    def make():
        import numpy as np

        from thunder_tpu import ops

        rng = _np_rng()
        logits = rng.randn(N, V).astype(np.float32)
        tgt = rng.randint(0, V, size=(N,)).astype(np.int32)

        def fn(logits):
            return ops.cross_entropy(logits, tgt)

        return fn, (logits,)

    return Benchmark(f"cross_entropy_N{N}V{V}", make)


def make_llama_mlp_benchmark(B=8, T=1024, D=4096, I=11008) -> Benchmark:
    def make():
        import numpy as np

        from thunder_tpu import ops

        rng = _np_rng()
        x = rng.randn(B, T, D).astype(np.float32)
        wg = (rng.randn(I, D) / np.sqrt(D)).astype(np.float32)
        wu = (rng.randn(I, D) / np.sqrt(D)).astype(np.float32)
        wd = (rng.randn(D, I) / np.sqrt(I)).astype(np.float32)

        def fn(x, wg, wu, wd):
            return ops.linear(ops.mul(ops.silu(ops.linear(x, wg)), ops.linear(x, wu)), wd)

        return fn, (x, wg, wu, wd)

    return Benchmark(f"llama_mlp_B{B}T{T}D{D}I{I}", make)


def make_rmsnorm_benchmark(N=8192, D=4096) -> Benchmark:
    def make():
        import numpy as np

        from thunder_tpu import ops

        rng = _np_rng()
        x = rng.randn(N, D).astype(np.float32)
        w = rng.randn(D).astype(np.float32)

        def fn(x, w):
            return ops.rms_norm(x, w)

        return fn, (x, w)

    return Benchmark(f"rms_norm_N{N}D{D}", make)


def make_train_step_benchmark(config: str = "tiny", batch: int = 4, seq: int = 256,
                              n_layers: int | None = None) -> Benchmark:
    def make():
        import numpy as np

        import thunder_tpu as tt
        from thunder_tpu.models import llama
        from thunder_tpu.optim import AdamW

        cfg = llama.CONFIGS[config]
        params = llama.init_params(cfg, seed=0, scale_layers=n_layers)
        opt = AdamW(lr=1e-4)
        rng = _np_rng()
        tokens = rng.randint(0, cfg.vocab_size, size=(batch, seq)).astype(np.int32)
        targets = np.roll(tokens, -1, 1).astype(np.int32)

        def fn(params, opt_state, tokens, targets):
            loss, grads = tt.value_and_grad(
                lambda p: llama.loss_fn(p, tokens, targets, cfg))(params)
            return loss, *opt.update(params, grads, opt_state)

        return fn, (params, opt.init(params), tokens, targets)

    b = Benchmark(f"llama_{config}_train_B{batch}T{seq}", make)
    b.tokens_per_iter = batch * seq
    return b


def make_gelu_benchmark(N=8192, D=11008) -> Benchmark:
    """Reference: LitGPT GELU microbenchmark (``thunder/benchmarks/targets.py``)."""
    def make():
        import numpy as np

        from thunder_tpu import ops

        x = _np_rng().randn(N, D).astype(np.float32)

        def fn(x):
            return ops.gelu(x, approximate="tanh")

        return fn, (x,)

    return Benchmark(f"gelu_N{N}D{D}", make)


def make_layernorm_benchmark(N=8192, D=4096) -> Benchmark:
    def make():
        import numpy as np

        from thunder_tpu import ops

        rng = _np_rng()
        x = rng.randn(N, D).astype(np.float32)
        w = rng.randn(D).astype(np.float32)
        b = rng.randn(D).astype(np.float32)

        def fn(x, w, b):
            return ops.layer_norm(x, (D,), w, b)

        return fn, (x, w, b)

    return Benchmark(f"layer_norm_N{N}D{D}", make)


def make_einsum_benchmark(B=8, I=512, J=512, K=512) -> Benchmark:
    """Reference: einsum benchmark family (``thunder/benchmarks/einsum.py``)."""
    def make():
        import numpy as np

        from thunder_tpu import ops

        rng = _np_rng()
        a = rng.randn(B, I, J).astype(np.float32)
        b = rng.randn(B, J, K).astype(np.float32)

        def fn(a, b):
            return ops.einsum("bij,bjk->bik", a, b)

        return fn, (a, b)

    return Benchmark(f"einsum_bij_bjk_B{B}", make)


def make_nanogpt_attn_benchmark(B=8, T=1024, config: str = "gpt2-tiny") -> Benchmark:
    """nanoGPT causal-self-attention module (reference ``NanoGPTCSABenchmark``)."""
    def make():
        import numpy as np

        from thunder_tpu import ops
        from thunder_tpu.models import nanogpt

        cfg = nanogpt.CONFIGS[config]
        D, H = cfg.n_embd, cfg.n_head
        rng = _np_rng()
        x = rng.randn(B, T, D).astype(np.float32)
        wqkv = (rng.randn(3 * D, D) / np.sqrt(D)).astype(np.float32)
        wo = (rng.randn(D, D) / np.sqrt(D)).astype(np.float32)

        def fn(x, wqkv, wo):
            qkv = ops.linear(x, wqkv)
            q, k, v = [ops.transpose(ops.reshape(t, (B, T, H, D // H)), (0, 2, 1, 3))
                       for t in ops.chunk(qkv, 3, -1)]
            o = ops.scaled_dot_product_attention(q, k, v, is_causal=True)
            return ops.linear(ops.reshape(ops.transpose(o, (0, 2, 1, 3)), (B, T, D)), wo)

        return fn, (x, wqkv, wo)

    return Benchmark(f"nanogpt_csa_B{B}T{T}", make)


def make_nanogpt_block_benchmark(config: str = "gpt2-tiny", B=8, T=1024) -> Benchmark:
    """One full nanoGPT block fwd (reference ``NanoGPTBlockBenchmark``)."""
    def make():
        import numpy as np

        from thunder_tpu.models import nanogpt

        cfg = nanogpt.CONFIGS[config]
        params = nanogpt.init_params(cfg, seed=0, scale_layers=1)
        rng = _np_rng()
        tokens = rng.randint(0, cfg.vocab_size, size=(B, min(T, cfg.block_size))).astype(np.int32)

        def fn(params, tokens):
            return nanogpt.forward(params, tokens, cfg)

        return fn, (params, tokens)

    return Benchmark(f"nanogpt_block_B{B}", make)


DEFAULT_BENCHMARKS: dict[str, Callable[[], Benchmark]] = {
    "sdpa": make_sdpa_benchmark,
    "cross_entropy": make_cross_entropy_benchmark,
    "llama_mlp": make_llama_mlp_benchmark,
    "rms_norm": make_rmsnorm_benchmark,
    "layer_norm": make_layernorm_benchmark,
    "gelu": make_gelu_benchmark,
    "einsum": make_einsum_benchmark,
    "nanogpt_csa": make_nanogpt_attn_benchmark,
    "nanogpt_block": make_nanogpt_block_benchmark,
    "train_step": make_train_step_benchmark,
}
