"""Bandwidth-bound fp8-vs-bf16 A/B (the r5 measurement that demoted the
"fp8 wins when HBM-bound" claim — ROADMAP D7 quotes what survives of it):
decode-geometry MLP stack where weight traffic dominates (batch 8, seq 1) —
flops/byte ~8 vs an MXU:HBM ratio of ~240, i.e. ~30x HBM-bound. Variants
interleave on the chip so machine conditions hit each equally.

Run: python -m thunder_tpu.benchmarks.fp8_bandwidth_ab  (real TPU)
"""


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from thunder_tpu.benchmarks.breakdown import time_fn

    L, D, I, B = 4, 4096, 11008, 8
    rng = np.random.RandomState(0)

    Wg16 = [jax.device_put((rng.randn(D, I) * 0.02).astype(jnp.bfloat16)) for _ in range(L)]
    Wu16 = [jax.device_put((rng.randn(D, I) * 0.02).astype(jnp.bfloat16)) for _ in range(L)]
    Wd16 = [jax.device_put((rng.randn(I, D) * 0.02).astype(jnp.bfloat16)) for _ in range(L)]

    def to8(w):
        scale = jnp.float32(jnp.max(jnp.abs(w.astype(jnp.float32))) / 448.0)
        return (w.astype(jnp.float32) / scale).astype(jnp.float8_e4m3fn), scale
    Wg8 = [to8(w) for w in Wg16]; Wu8 = [to8(w) for w in Wu16]; Wd8 = [to8(w) for w in Wd16]
    x0 = jax.device_put((rng.randn(B, D) * 0.1).astype(jnp.bfloat16))

    @jax.jit
    def f16(x, Wg, Wu, Wd):
        for g, u, d in zip(Wg, Wu, Wd):
            h = jax.nn.silu(x @ g) * (x @ u)
            x = (h @ d).astype(jnp.bfloat16)
        return x

    @jax.jit
    def f8(x, Wg, Wu, Wd):
        for (g8, gs), (u8, us), (d8, ds) in zip(Wg, Wu, Wd):
            g = (g8.astype(jnp.bfloat16) * gs.astype(jnp.bfloat16))
            u = (u8.astype(jnp.bfloat16) * us.astype(jnp.bfloat16))
            d = (d8.astype(jnp.bfloat16) * ds.astype(jnp.bfloat16))
            h = jax.nn.silu(x @ g) * (x @ u)
            x = (h @ d).astype(jnp.bfloat16)
        return x

    @jax.jit
    def f8_fused(x, Wg, Wu, Wd):
        # dequant INSIDE the dot via f32 accumulation on the fp8-operand matmul
        # (preferred_element_type): XLA may fuse the upcast into the operand read
        for (g8, gs), (u8, us), (d8, ds) in zip(Wg, Wu, Wd):
            a = jax.lax.dot_general(x.astype(jnp.float8_e4m3fn), g8, (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32) * gs
            b = jax.lax.dot_general(x.astype(jnp.float8_e4m3fn), u8, (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32) * us
            h = (jax.nn.silu(a) * b).astype(jnp.float8_e4m3fn)
            x = (jax.lax.dot_general(h, d8, (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32) * ds).astype(jnp.bfloat16)
        return x

    r = {}
    for name, fn, args in (("bf16 a", f16, (x0, Wg16, Wu16, Wd16)),
                           ("fp8-dequant a", f8, (x0, Wg8, Wu8, Wd8)),
                           ("fp8-fused a", f8_fused, (x0, Wg8, Wu8, Wd8)),
                           ("bf16 b", f16, (x0, Wg16, Wu16, Wd16)),
                           ("fp8-dequant b", f8, (x0, Wg8, Wu8, Wd8)),
                           ("fp8-fused b", f8_fused, (x0, Wg8, Wu8, Wd8))):
        try:
            r[name] = time_fn(fn, *args, steps=24, trials=3)
        except Exception as e:
            r[name] = None
            print(name, "FAILED:", str(e)[:90])
    wbytes16 = 3 * L * D * I * 2
    for k, v in r.items():
        if v is not None:
            print(f"{k}: {v*1e3:.2f} ms  (bf16 weight roofline {wbytes16/819e9*1e3:.2f} ms)")


if __name__ == "__main__":
    main()
