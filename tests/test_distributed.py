"""Distributed transform tests on the 8-device virtual CPU mesh.

Reference parity: ``thunder/tests/distributed/`` (test_ddp.py grad parity,
test_fsdp.py ZeRO + trace assertions on collective placement,
test_tensor_parallel.py) — but hermetic: the reference needs 2+ real GPUs
and NCCL; here collectives run on emulated devices (SURVEY §4 lesson).
"""

import jax
import numpy as np
import pytest

import thunder_tpu as tt
from thunder_tpu.core.devices import MeshSpec
from thunder_tpu.distributed import ddp, fsdp, tensor_parallel
from thunder_tpu.models import llama
from thunder_tpu.optim import AdamW, SGD

N = 8


def _make_step(cfg, opt):
    def train_step(params, opt_state, tokens, targets):
        loss, grads = tt.value_and_grad(lambda p: llama.loss_fn(p, tokens, targets, cfg))(params)
        new_params, new_state = opt.update(params, grads, opt_state)
        return loss, new_params, new_state

    return train_step


def _data(cfg, batch, seq, seed):
    rng = np.random.RandomState(seed)
    tokens = rng.randint(0, cfg.vocab_size, size=(batch, seq)).astype(np.int32)
    targets = np.roll(tokens, -1, axis=1).astype(np.int32)
    return tokens, targets


def _run_steps(jstep, params, opt_state, tokens, targets, n=3):
    losses = []
    for _ in range(n):
        loss, params, opt_state = jstep(params, opt_state, tokens, targets)
        losses.append(float(np.asarray(loss)))
    return losses, params


@pytest.mark.parametrize("mode", ["fsdp", "ddp"])
def test_data_parallel_matches_single_device(eight_devices, mode):
    cfg = llama.CONFIGS["tiny"]
    params = llama.init_params(cfg, seed=0, scale_layers=2)
    opt = SGD(lr=1e-2)
    tokens, targets = _data(cfg, N, 16, seed=0)

    # single-device reference
    ref_losses, ref_params = _run_steps(tt.jit(_make_step(cfg, opt)), params, opt.init(params),
                                        tokens, targets)

    wrap = fsdp if mode == "fsdp" else ddp
    jstep = wrap(_make_step(cfg, opt), MeshSpec.make(**{"fsdp" if mode == "fsdp" else "dp": N}))
    dist_losses, dist_params = _run_steps(jstep, params, opt.init(params), tokens, targets)

    np.testing.assert_allclose(ref_losses, dist_losses, atol=1e-5, rtol=1e-5)
    # updated params match (gather the distributed result automatically via
    # jax global arrays)
    flat_ref, _ = jax.tree_util.tree_flatten(ref_params)
    flat_dist, _ = jax.tree_util.tree_flatten(dist_params)
    for r, d in zip(flat_ref, flat_dist):
        np.testing.assert_allclose(np.asarray(r), np.asarray(d), atol=1e-5, rtol=1e-4)


def test_fsdp_adamw_zero_state_sharding(eight_devices):
    """AdamW moments are born sharded (ZeRO-1/2) and training still matches
    the single-device run."""
    cfg = llama.CONFIGS["tiny"]
    params = llama.init_params(cfg, seed=1, scale_layers=2)
    opt = AdamW(lr=3e-3)
    tokens, targets = _data(cfg, N, 8, seed=1)

    ref_losses, _ = _run_steps(tt.jit(_make_step(cfg, opt)), params, opt.init(params),
                               tokens, targets)
    jstep = fsdp(_make_step(cfg, opt), MeshSpec.make(fsdp=N))
    opt_state = opt.init(params)
    losses = []
    for _ in range(3):
        loss, params, opt_state = jstep(params, opt_state, tokens, targets)
        losses.append(float(np.asarray(loss)))
    np.testing.assert_allclose(ref_losses, losses, atol=1e-5, rtol=1e-5)
    # moment tensors come back sharded across the fsdp axis
    m_leaf = opt_state["m"]["tok_embedding"]
    assert len(m_leaf.sharding.device_set) == N


def test_fsdp_trace_contains_collectives(eight_devices):
    cfg = llama.CONFIGS["tiny"]
    params = llama.init_params(cfg, seed=2, scale_layers=1)
    opt = SGD(lr=1e-2)
    tokens, targets = _data(cfg, N, 8, seed=2)
    jstep = fsdp(_make_step(cfg, opt), MeshSpec.make(fsdp=N))
    jstep(params, opt.init(params), tokens, targets)
    src = tt.last_traces(jstep)[0].python()
    assert "synchronize" in src  # param all-gather in forward
    assert "reduce_scatter" in src  # grad reduce-scatter in backward
    assert "all_reduce" in src  # loss averaging


def test_ddp_trace_contains_allreduce(eight_devices):
    cfg = llama.CONFIGS["tiny"]
    params = llama.init_params(cfg, seed=3, scale_layers=1)
    opt = SGD(lr=1e-2)
    tokens, targets = _data(cfg, N, 8, seed=3)
    jstep = ddp(_make_step(cfg, opt), MeshSpec.make(dp=N))
    jstep(params, opt.init(params), tokens, targets)
    src = tt.last_traces(jstep)[0].python()
    assert "synchronize" in src
    assert "all_reduce" in src


def test_tensor_parallel_matches_single_device(eight_devices):
    cfg = llama.CONFIGS["tiny"]  # 4 heads, intermediate 176 -> tp=4
    tp_n = 4
    params = llama.init_params(cfg, seed=4, scale_layers=2)
    opt = SGD(lr=1e-2)
    tokens, targets = _data(cfg, 2, 8, seed=4)

    ref_losses, ref_params = _run_steps(tt.jit(_make_step(cfg, opt)), params, opt.init(params),
                                        tokens, targets)

    local_cfg = llama.tp_config(cfg, tp_n)
    jstep = tensor_parallel(_make_step(local_cfg, opt), MeshSpec.make(tp=tp_n),
                            column_patterns=llama.TP_COLUMN_PATTERNS,
                            row_patterns=llama.TP_ROW_PATTERNS)
    tp_losses, tp_params = _run_steps(jstep, params, opt.init(params), tokens, targets)
    np.testing.assert_allclose(ref_losses, tp_losses, atol=1e-5, rtol=1e-5)
    flat_ref, _ = jax.tree_util.tree_flatten(ref_params)
    flat_tp, _ = jax.tree_util.tree_flatten(tp_params)
    for r, d in zip(flat_ref, flat_tp):
        np.testing.assert_allclose(np.asarray(r), np.asarray(d), atol=1e-5, rtol=1e-4)


def test_collective_prims_lower_to_lax(eight_devices):
    """Direct semantics of the collective prim impls inside shard_map."""
    from jax.sharding import Mesh, PartitionSpec as P
    from thunder_tpu.distributed import prims as dp
    from thunder_tpu.executors.eagerjax import get_eager_impl

    mesh = Mesh(np.array(jax.devices()[:N]), ("x",))
    ag = get_eager_impl(dp.all_gather)
    rs = get_eager_impl(dp.reduce_scatter)
    ar = get_eager_impl(dp.all_reduce)

    x = np.arange(N * 4, dtype=np.float32).reshape(N, 4)

    def body(xs):
        g = ag(xs, "x", 0, N)  # (N, 4)
        s = ar(xs, "x", "sum")
        r = rs(g, "x", 0, N)
        return g, s, r

    f = jax.shard_map(body, mesh=mesh, in_specs=(P("x"),),
                      out_specs=(P(), P("x"), P("x")), check_vma=False)
    g, s, r = f(x)
    np.testing.assert_allclose(np.asarray(g), x)  # gather reassembles
    np.testing.assert_allclose(np.asarray(s), np.broadcast_to(x.sum(0, keepdims=True), (N, 4)))
    np.testing.assert_allclose(np.asarray(r), x * N)  # reduce_scatter of gathered


def test_context_parallel_ring_attention_matches_single(eight_devices):
    """Ring attention over a 4-way sequence shard reproduces single-device
    training exactly (NEW capability vs the reference)."""
    from thunder_tpu.distributed import context_parallel

    cfg = llama.CONFIGS["tiny"]
    cp_n = 4
    params = llama.init_params(cfg, seed=6, scale_layers=2)
    opt = SGD(lr=1e-2)
    tokens, targets = _data(cfg, 2, 32, seed=6)  # T=32 -> 8 per shard

    ref_losses, ref_params = _run_steps(tt.jit(_make_step(cfg, opt)), params, opt.init(params),
                                        tokens, targets)

    jstep = context_parallel(_make_step(cfg, opt), MeshSpec.make(sp=cp_n))
    cp_losses, cp_params = _run_steps(jstep, params, opt.init(params), tokens, targets)

    np.testing.assert_allclose(ref_losses, cp_losses, atol=1e-5, rtol=1e-5)
    flat_ref, _ = jax.tree_util.tree_flatten(ref_params)
    flat_cp, _ = jax.tree_util.tree_flatten(cp_params)
    for r, d in zip(flat_ref, flat_cp):
        np.testing.assert_allclose(np.asarray(r), np.asarray(d), atol=1e-5, rtol=1e-4)


def test_context_parallel_trace_has_ring(eight_devices):
    from thunder_tpu.distributed import context_parallel

    cfg = llama.CONFIGS["tiny"]
    params = llama.init_params(cfg, seed=7, scale_layers=1)
    opt = SGD(lr=1e-2)
    tokens, targets = _data(cfg, 2, 32, seed=7)
    jstep = context_parallel(_make_step(cfg, opt), MeshSpec.make(sp=4))
    jstep(params, opt.init(params), tokens, targets)
    src = tt.last_traces(jstep)[0].python()
    # the ring decomposes through autograd replay: K/V rotation collectives
    # and rank-dependent masking must be present
    assert "ppermute" in src
    assert "axis_index" in src


# ---------------------------------------------------------------------------
# pipeline parallelism (NEW capability — SURVEY §2.6: PP absent upstream)
# ---------------------------------------------------------------------------

def _make_pp_step(cfg, opt, n_microbatches):
    from thunder_tpu.distributed import make_pipeline_loss

    embed, stage, head = llama.pipeline_fns(cfg)
    ploss = make_pipeline_loss(embed, stage, head, n_microbatches=n_microbatches)

    def train_step(params, opt_state, tokens, targets):
        loss, grads = tt.value_and_grad(lambda p: ploss(p, tokens, targets))(params)
        new_params, new_state = opt.update(params, grads, opt_state)
        return loss, new_params, new_state

    return train_step


def test_pipeline_parallel_matches_single_device(eight_devices):
    from thunder_tpu.distributed import pipeline_parallel

    cfg = llama.CONFIGS["tiny"]
    params = llama.stack_layers(llama.init_params(cfg, seed=0))  # 4 stacked layers
    opt = SGD(lr=1e-2)
    tokens, targets = _data(cfg, 8, 16, seed=0)
    step = _make_pp_step(cfg, opt, n_microbatches=4)

    ref_losses, ref_params = _run_steps(tt.jit(step), params, opt.init(params), tokens, targets)
    # microbatched pipeline loss == plain whole-batch loss
    plain = tt.jit(_make_step(cfg, opt))(
        llama.init_params(cfg, seed=0), opt.init(llama.init_params(cfg, seed=0)), tokens, targets)
    np.testing.assert_allclose(ref_losses[0], float(np.asarray(plain[0])), atol=1e-4, rtol=1e-5)

    jstep = pipeline_parallel(step, MeshSpec.make(pp=4), stage_patterns=llama.PP_STAGE_PATTERNS)
    pp_losses, pp_params = _run_steps(jstep, params, opt.init(params), tokens, targets)

    np.testing.assert_allclose(ref_losses, pp_losses, atol=1e-5, rtol=1e-5)
    flat_ref, _ = jax.tree_util.tree_flatten(ref_params)
    flat_pp, _ = jax.tree_util.tree_flatten(pp_params)
    for r, d in zip(flat_ref, flat_pp):
        np.testing.assert_allclose(np.asarray(r), np.asarray(d), atol=2e-5, rtol=1e-3)


def test_pipeline_trace_contains_ppermute(eight_devices):
    from thunder_tpu.distributed import pipeline_parallel

    cfg = llama.CONFIGS["tiny"]
    params = llama.stack_layers(llama.init_params(cfg, seed=0))
    opt = SGD(lr=1e-2)
    tokens, targets = _data(cfg, 8, 16, seed=0)
    jstep = pipeline_parallel(_make_pp_step(cfg, opt, 4), MeshSpec.make(pp=4),
                              stage_patterns=llama.PP_STAGE_PATTERNS)
    jstep(params, opt.init(params), tokens, targets)
    src = tt.last_traces(jstep)[0].python()
    assert "ppermute" in src, "pipeline schedule should rotate activations via ppermute"
    assert "all_reduce" in src, "replicated embed/head grads should be sum-reduced"
    assert "axis_index" in src


def test_fsdp_zero3_regathers_in_backward(eight_devices):
    """zero=3 rewrites backward consumers of gathered params onto fresh
    ``regather`` ops (reference rematerialize_all_gather semantics), and
    training still matches the single-device run."""
    cfg = llama.CONFIGS["tiny"]
    params = llama.init_params(cfg, seed=4, scale_layers=2)
    opt = AdamW(lr=3e-3)
    tokens, targets = _data(cfg, N, 8, seed=4)

    ref_losses, _ = _run_steps(tt.jit(_make_step(cfg, opt)), params, opt.init(params),
                               tokens, targets)
    jstep = fsdp(_make_step(cfg, opt), MeshSpec.make(fsdp=N), zero=3)
    opt_state = opt.init(params)
    losses = []
    for _ in range(3):
        loss, params, opt_state = jstep(params, opt_state, tokens, targets)
        losses.append(float(np.asarray(loss)))
    np.testing.assert_allclose(ref_losses, losses, atol=1e-5, rtol=1e-5)

    # the final trace inlines collectives into the XLA fusion; assert on the
    # post-transform (pre-fusion) stage
    srcs = [t.python() for t in tt.last_traces(jstep)]
    n_regather = max(s.count("= regather") for s in srcs)
    # every sharded param with a backward consumer re-gathers: at least one
    # regather per transformer layer's weight set
    assert n_regather >= 4, n_regather

    # zero=2 (default) must NOT regather
    jstep2 = fsdp(_make_step(cfg, opt), MeshSpec.make(fsdp=N))
    p2 = llama.init_params(cfg, seed=4, scale_layers=2)
    jstep2(p2, opt.init(p2), tokens, targets)
    assert all("= regather" not in t.python() for t in tt.last_traces(jstep2))


def test_hsdp_2d_mesh_matches_single_device(eight_devices):
    """HSDP (NEW capability): params shard over fsdp (4), replicate over
    dp (2); batch shards over all 8; training matches single-device and
    the trace composes both synchronize VJPs (all-reduce across replicas +
    reduce-scatter within shards)."""
    from thunder_tpu.distributed import hsdp

    cfg = llama.CONFIGS["tiny"]
    params = llama.init_params(cfg, seed=6, scale_layers=2)
    opt = AdamW(lr=3e-3)
    tokens, targets = _data(cfg, N, 8, seed=6)

    ref_losses, ref_params = _run_steps(tt.jit(_make_step(cfg, opt)), params,
                                        opt.init(params), tokens, targets)

    jstep = hsdp(_make_step(cfg, opt), MeshSpec.make(dp=2, fsdp=4))
    p = llama.init_params(cfg, seed=6, scale_layers=2)
    s = opt.init(p)
    losses = []
    for _ in range(3):
        loss, p, s = jstep(p, s, tokens, targets)
        losses.append(float(np.asarray(loss)))
    np.testing.assert_allclose(ref_losses, losses, atol=1e-5, rtol=1e-5)

    flat_ref = jax.tree_util.tree_flatten(ref_params)[0]
    flat_h = jax.tree_util.tree_flatten(p)[0]
    for r, d in zip(flat_ref, flat_h):
        # 3 AdamW steps compound the cross-replica reduction-order noise
        # through rsqrt; 1e-5 abs was flaky (~2/4096 elements at ~3e-4)
        np.testing.assert_allclose(np.asarray(r), np.asarray(d), atol=5e-4, rtol=1e-3)

    # structure: both collectives appear — reduce_scatter (fsdp axis) AND a
    # grad all_reduce on the replica axis
    src = tt.last_traces(jstep)[0].python()
    assert "reduce_scatter" in src
    assert src.count("'dp'") >= 2 or src.count('"dp"') >= 2, "replica-axis collectives missing"


def test_hsdp_zero3_regathers(eight_devices):
    from thunder_tpu.distributed import hsdp

    cfg = llama.CONFIGS["tiny"]
    params = llama.init_params(cfg, seed=7, scale_layers=1)
    opt = SGD(lr=1e-2)
    tokens, targets = _data(cfg, N, 8, seed=7)
    jstep = hsdp(_make_step(cfg, opt), MeshSpec.make(dp=2, fsdp=4), zero=3)
    loss0 = float(np.asarray(jstep(params, opt.init(params), tokens, targets)[0]))
    srcs = [t.python() for t in tt.last_traces(jstep)]
    assert max(s.count("= regather") for s in srcs) >= 4
    # numerics still match single-device
    ref = float(np.asarray(tt.jit(_make_step(cfg, opt))(
        llama.init_params(cfg, seed=7, scale_layers=1),
        opt.init(params), tokens, targets)[0]))
    assert abs(loss0 - ref) < 1e-5


def test_tensor_parallel_x_data_parallel_matches_single_device(eight_devices):
    """Megatron 2D (NEW capability): tp=4 within, dp=2 across — training
    matches the single-device run exactly (TP boundary collectives + dp-mean
    shard grads via the replica synchronize)."""
    cfg = llama.CONFIGS["tiny"]
    tp_n, dp_n = 4, 2
    params = llama.init_params(cfg, seed=7, scale_layers=2)
    opt = SGD(lr=1e-2)
    tokens, targets = _data(cfg, 4, 8, seed=7)  # batch 4 -> 2 per dp rank

    ref_losses, ref_params = _run_steps(tt.jit(_make_step(cfg, opt)), params,
                                        opt.init(params), tokens, targets)

    local_cfg = llama.tp_config(cfg, tp_n)
    jstep = tensor_parallel(_make_step(local_cfg, opt),
                            MeshSpec.make(dp=dp_n, tp=tp_n),
                            column_patterns=llama.TP_COLUMN_PATTERNS,
                            row_patterns=llama.TP_ROW_PATTERNS,
                            data_parallel_axis="dp")
    td_losses, td_params = _run_steps(jstep, params, opt.init(params), tokens, targets)
    np.testing.assert_allclose(ref_losses, td_losses, atol=1e-5, rtol=1e-5)
    flat_ref, _ = jax.tree_util.tree_flatten(ref_params)
    flat_td, _ = jax.tree_util.tree_flatten(td_params)
    for r, d in zip(flat_ref, flat_td):
        np.testing.assert_allclose(np.asarray(r), np.asarray(d), atol=1e-5, rtol=1e-4)

    # explicit data_argnums override replaces the integer-dtype heuristic
    jstep2 = tensor_parallel(_make_step(local_cfg, opt),
                             MeshSpec.make(dp=dp_n, tp=tp_n),
                             column_patterns=llama.TP_COLUMN_PATTERNS,
                             row_patterns=llama.TP_ROW_PATTERNS,
                             data_parallel_axis="dp", data_argnums=(2, 3))
    l2, _, _ = jstep2(params, opt.init(params), tokens, targets)
    np.testing.assert_allclose(float(np.asarray(l2)), ref_losses[0], atol=1e-5)


def test_fsdp_non_divisible_param_grads_averaged(eight_devices):
    """Params whose dim-0 doesn't divide the mesh replicate as a fallback —
    their grads MUST still all-reduce-mean or the replicas silently diverge
    (each rank would apply only its own microbatch's grad)."""
    from thunder_tpu.distributed import hsdp

    rng = np.random.RandomState(0)
    params = {"W": rng.randn(7, 16).astype(np.float32) * 0.3,   # 7 % 8 != 0
              "V": rng.randn(16, 16).astype(np.float32) * 0.3}  # sharded
    x = rng.randn(16, 16).astype(np.float32)
    y = rng.randn(16, 7).astype(np.float32)
    opt = SGD(lr=0.1)

    def step(p, s, xb, yb):
        def loss_fn(pp):
            h = tt.ops.relu(tt.ops.matmul(xb, pp["V"]))
            out = tt.ops.matmul(h, tt.ops.transpose(pp["W"], (1, 0)))
            return tt.ops.mean(tt.ops.square(tt.ops.sub(out, yb)))

        loss, g = tt.value_and_grad(loss_fn)(p)
        p2, s2 = opt.update(p, g, s)
        return loss, p2, s2

    rp, rs = params, opt.init(params)
    ref_step = tt.jit(step)
    for _ in range(3):
        rl, rp, rs = ref_step(rp, rs, x, y)

    for mk in (lambda: fsdp(step, MeshSpec.make(fsdp=8), data_argnums=(2, 3)),
               lambda: hsdp(step, MeshSpec.make(dp=2, fsdp=4), data_argnums=(2, 3))):
        js = mk()
        dp_, ds = params, opt.init(params)
        for _ in range(3):
            dl, dp_, ds = js(dp_, ds, x, y)
        np.testing.assert_allclose(float(dl), float(rl), atol=1e-5)
        for k in params:
            np.testing.assert_allclose(np.asarray(dp_[k]), np.asarray(rp[k]), atol=1e-5)


def test_fsdp_x_tensor_parallel_matches_single_device(eight_devices):
    """FSDP×TP 2D (llama3-style, NEW capability): fsdp=4 shards data + dim-0
    of every param; tp=2 shards the megatron dims. Training matches the
    single-device run exactly."""
    from thunder_tpu.distributed import fsdp_tp

    cfg = llama.CONFIGS["tiny"]
    params = llama.init_params(cfg, seed=5, scale_layers=2)
    opt = SGD(lr=1e-2)
    tokens, targets = _data(cfg, 4, 8, seed=5)

    ref_losses, ref_params = _run_steps(tt.jit(_make_step(cfg, opt)), params,
                                        opt.init(params), tokens, targets)

    js = fsdp_tp(_make_step(llama.tp_config(cfg, 2), opt),
                 MeshSpec.make(fsdp=4, tp=2),
                 column_patterns=llama.TP_COLUMN_PATTERNS,
                 row_patterns=llama.TP_ROW_PATTERNS)
    losses, dparams = _run_steps(js, params, opt.init(params), tokens, targets)
    np.testing.assert_allclose(ref_losses, losses, atol=1e-5, rtol=1e-5)
    for r, d in zip(jax.tree_util.tree_flatten(ref_params)[0],
                    jax.tree_util.tree_flatten(dparams)[0]):
        np.testing.assert_allclose(np.asarray(r), np.asarray(d), atol=1e-5, rtol=1e-4)

    # the trace composes both comm families: fsdp gathers + tp boundary syncs
    src = tt.last_traces(js)[0].python()
    assert "synchronize_tp" in src and "synchronize(" in src


def test_fsdp_grad_accumulation_matches_combined_batch(eight_devices):
    """The reference's no_sync enables grad accumulation without per-step
    sync; here accumulation is functional — two microbatch grad evaluations
    averaged INSIDE one compiled fsdp step equal the combined-batch step
    (psum is linear, so XLA sees sum-of-psums == psum-of-sums)."""
    cfg = llama.CONFIGS["tiny"]
    params = llama.init_params(cfg, seed=3, scale_layers=1)
    opt = SGD(lr=1e-2)
    tokens, targets = _data(cfg, 16, 8, seed=3)

    def accum_step(p, s, tok, tgt):
        # NOTE: tok/tgt are the LOCAL shards here (batch 16 / 8 ranks = 2
        # rows); microbatches slice the local batch
        half = tok.shape[0] // 2

        def loss_fn_mb(pp, t_, g_):
            return llama.loss_fn(pp, t_, g_, cfg)

        l1, g1 = tt.value_and_grad(lambda pp: loss_fn_mb(pp, tok[:half], tgt[:half]))(p)
        l2, g2 = tt.value_and_grad(lambda pp: loss_fn_mb(pp, tok[half:], tgt[half:]))(p)
        g = jax.tree_util.tree_map(lambda a, b: tt.ops.mul(tt.ops.add(a, b), 0.5), g1, g2)
        loss = tt.ops.mul(tt.ops.add(l1, l2), 0.5)
        p2, s2 = opt.update(p, g, s)
        return loss, p2, s2

    def full_step(p, s, tok, tgt):
        loss, g = tt.value_and_grad(lambda pp: llama.loss_fn(pp, tok, tgt, cfg))(p)
        p2, s2 = opt.update(p, g, s)
        return loss, p2, s2

    ja = fsdp(accum_step, MeshSpec.make(fsdp=8), data_argnums=(2, 3))
    jf = fsdp(full_step, MeshSpec.make(fsdp=8), data_argnums=(2, 3))
    la, pa, _ = ja(params, opt.init(params), tokens, targets)
    lf, pf, _ = jf(params, opt.init(params), tokens, targets)
    np.testing.assert_allclose(float(la), float(lf), atol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(pa), jax.tree_util.tree_leaves(pf)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_out_specs_same_local_shape_param_families(eight_devices):
    """VERDICT r1 item 4 'done' criterion: two param families whose LOCAL
    shard shapes coincide but whose shardings differ train correctly — the
    round-1 local-shape matcher refused this with an ambiguity error; spec
    propagation derives out_specs from metadata."""
    from thunder_tpu.distributed.transforms import tensor_parallel

    rng = np.random.RandomState(9)
    # w_col: (64, 16) column-sharded over tp=8 -> local (8, 16)
    # w_rep: (8, 16) replicated                -> local (8, 16)  [same!]
    params = {"w_col": rng.randn(64, 16).astype(np.float32) * 0.1,
              "w_rep": rng.randn(8, 16).astype(np.float32) * 0.1}

    params["w_row"] = rng.randn(8, 64).astype(np.float32) * 0.1

    def step(p, x):
        def loss_fn(pp):
            h = tt.ops.linear(x, pp["w_col"])          # column: (B, 64)
            y = tt.ops.linear(h, pp["w_row"])          # row:    (B, 8)
            z = tt.ops.linear(x, pp["w_rep"])          # replicated: (B, 8)
            return tt.ops.mean(tt.ops.square(tt.ops.add(y, z)))
        loss, g = tt.value_and_grad(loss_fn)(p)
        new = {k: tt.ops.sub(p[k], tt.ops.mul(0.05, g[k])) for k in p}
        return loss, new

    x = rng.randn(4, 16).astype(np.float32)

    ref_loss, ref_new = tt.jit(step)(params, x)

    js = tensor_parallel(step, MeshSpec.make(tp=8), column_patterns=(r"w_col",),
                         row_patterns=(r"w_row",))
    loss, new = js(params, x)
    np.testing.assert_allclose(np.asarray(loss), np.asarray(ref_loss), atol=1e-5)
    for k in params:
        assert tuple(new[k].shape) == tuple(params[k].shape), k
        np.testing.assert_allclose(np.asarray(new[k]), np.asarray(ref_new[k]),
                                   atol=1e-5, rtol=1e-4, err_msg=k)


def test_fsdp_tp_zero3_regathers(eight_devices):
    """fsdp_tp now supports zero=3: the 2D layout's fsdp gathers are
    rematerialized in the backward (VERDICT r1 item 4 tail)."""
    from thunder_tpu.distributed import fsdp_tp

    cfg = llama.CONFIGS["tiny"]
    params = llama.init_params(cfg, seed=8, scale_layers=2)
    opt = SGD(lr=1e-2)
    tokens, targets = _data(cfg, 4, 8, seed=8)

    ref_losses, ref_params = _run_steps(tt.jit(_make_step(cfg, opt)), params,
                                        opt.init(params), tokens, targets)

    js = fsdp_tp(_make_step(llama.tp_config(cfg, 2), opt),
                 MeshSpec.make(fsdp=4, tp=2),
                 column_patterns=llama.TP_COLUMN_PATTERNS,
                 row_patterns=llama.TP_ROW_PATTERNS, zero=3)
    losses, dparams = _run_steps(js, params, opt.init(params), tokens, targets)
    np.testing.assert_allclose(ref_losses, losses, atol=1e-5, rtol=1e-5)
    for r, d in zip(jax.tree_util.tree_flatten(ref_params)[0],
                    jax.tree_util.tree_flatten(dparams)[0]):
        np.testing.assert_allclose(np.asarray(r), np.asarray(d), atol=1e-5, rtol=1e-4)

    # ZeRO-3 signature: regather ops in the backward window
    srcs = [t.python() for t in tt.last_traces(js)]
    assert max(s.count("= regather") for s in srcs) >= 4


def test_broadcast_collective_delivers_src_value(eight_devices):
    """The broadcast prim must deliver the SOURCE rank's value to every rank
    (round 1's identity impl was only correct for replicated operands)."""
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from thunder_tpu.distributed.prims import DistPrimIDs
    from thunder_tpu.executors.eagerjax import _impls

    sm = jax.shard_map
    bimpl = _impls[DistPrimIDs.BROADCAST]
    mesh = Mesh(np.array(jax.devices()[:8]), ("r",))
    f = jax.jit(sm(lambda x: bimpl(x[0], "r", 3)[None], mesh=mesh,
                   in_specs=P("r"), out_specs=P("r"), check_vma=False))
    out = f(jnp.arange(8.0))
    np.testing.assert_allclose(np.asarray(out), np.full(8, 3.0))
    # a different source index
    f5 = jax.jit(sm(lambda x: bimpl(x[0], "r", 5)[None], mesh=mesh,
                    in_specs=P("r"), out_specs=P("r"), check_vma=False))
    np.testing.assert_allclose(np.asarray(f5(jnp.arange(8.0))), np.full(8, 5.0))


def test_sort_waits_moves_wait_past_independent_compute(eight_devices):
    """VERDICT r1 item 9 'done' criterion: the comm-reorder pass demonstrably
    sinks a wait past independent compute in the printed trace (reference
    ``thunder/distributed/utils.py:60-196`` sort_communication_ops/sort_waits)."""
    from thunder_tpu.distributed import sort_waits
    from thunder_tpu.distributed import prims as dp
    from thunder_tpu.core.trace import TraceCtx, tracectx
    from thunder_tpu.core.proxies import TensorProxy
    from thunder_tpu.core import dtypes, prims as cp
    from thunder_tpu import ops

    trc = TraceCtx("computation")
    with tracectx(trc):
        a = TensorProxy("a", shape=(8, 8), dtype=dtypes.float32)
        b = TensorProxy("b", shape=(8, 8), dtype=dtypes.float32)
        fut = dp.all_reduce(a, "dp", "sum")
        red = dp.wait(fut)
        # independent compute that does NOT need the collective result
        c = ops.mul(b, b)
        d = ops.add(c, 1.0)
        out = ops.add(red, d)
        cp.python_return(out)
    trc.args = [a, b]
    trc.output = out

    before = [bs.sym.name for bs in trc.bound_symbols]
    assert before.index("wait") < before.index("mul")  # wait is early pre-pass

    new = sort_waits(trc)
    names = [bs.sym.name for bs in new.bound_symbols]
    # issue stays first, wait sinks past the independent mul/add chain
    assert names.index("all_reduce") < names.index("mul")
    assert names.index("wait") > names.index("mul")
    assert names.index("wait") > names.index("add")
    # the trace still computes: the reordered program is a valid topo order
    src = new.python()
    assert src.index("all_reduce") < src.index("mul(")


def test_comm_reorder_option_end_to_end(eight_devices):
    """comm_reorder=True wires the pass into a distributed step; numerics
    are unchanged."""
    cfg = llama.CONFIGS["tiny"]
    params = llama.init_params(cfg, seed=11, scale_layers=1)
    opt = SGD(lr=1e-2)
    tokens, targets = _data(cfg, N, 8, seed=11)

    ref_losses, _ = _run_steps(tt.jit(_make_step(cfg, opt)), params, opt.init(params),
                               tokens, targets)
    js = fsdp(_make_step(cfg, opt), MeshSpec.make(fsdp=N), comm_reorder=True)
    losses, _ = _run_steps(js, params, opt.init(params), tokens, targets)
    np.testing.assert_allclose(ref_losses, losses, atol=1e-5, rtol=1e-5)

    # the reordered program schedules differently from the default one:
    # the pass owns the comm machinery (decompose, bucket, reschedule), so
    # collective ops differ by design while the compute is untouched
    js2 = fsdp(_make_step(cfg, opt), MeshSpec.make(fsdp=N))
    js2(params, opt.init(params), tokens, targets)

    def names(jf):
        out = []

        def walk(bs):
            for b in bs:
                out.append(b.sym.name)
                walk(b.subsymbols)

        walk(tt.last_traces(jf)[-1].bound_symbols)
        return out

    COMM = {"synchronize", "wait", "all_gather", "reduce_scatter", "all_reduce",
            "bucketed_all_gather", "bucketed_reduce_scatter",
            "bucket_unpack_gather", "bucket_unpack_scatter"}
    n1, n2 = names(js), names(js2)
    assert sorted(x for x in n1 if x not in COMM) == \
           sorted(x for x in n2 if x not in COMM)  # same compute...
    assert n1 != n2                                # ...different schedule

    ISSUE = ("all_gather", "reduce_scatter", "all_reduce",
             "bucketed_all_gather", "bucketed_reduce_scatter")

    def sched(jf):
        """The deepest trace that carries collectives at the top level —
        the schedule the pass (or the default lowering) actually emitted."""
        for trc in reversed(tt.last_traces(jf)):
            seq = [b.sym.name for b in trc.bound_symbols]
            if any(nm in ISSUE for nm in seq):
                return seq
        raise AssertionError("no trace with top-level collectives")

    s1, s2 = sched(js), sched(js2)

    # bucketing collapsed the per-param gathers/scatters into fused issues
    assert "bucketed_all_gather" in s1 and "bucketed_reduce_scatter" in s1
    issues1 = sum(s1.count(x) for x in ISSUE)
    issues2 = sum(s2.count(x) for x in ISSUE) + s2.count("synchronize")
    assert issues1 < issues2

    def wait_gaps(seq):
        """distance from each collective issue to its wait (adjacent = 1)."""
        gaps = []
        pending = []
        for i, nm in enumerate(seq):
            if nm in ISSUE:
                pending.append(i)
            elif nm == "wait" and pending:
                gaps.append(i - pending.pop(0))
        return gaps

    g1, g2 = wait_gaps(s1), wait_gaps(s2)
    assert g1 and max(g1) > 1          # waits sank: windows are open
    assert all(g == 1 for g in g2)     # the default keeps them adjacent


def test_sort_waits_never_moves_del_before_use(eight_devices):
    """Code-review r2: a pinned `del x` group must not overtake another
    consumer of x that waits on a sunk collective."""
    from thunder_tpu.distributed import sort_waits
    from thunder_tpu.distributed import prims as dp
    from thunder_tpu.core.trace import TraceCtx, tracectx
    from thunder_tpu.core.proxies import TensorProxy
    from thunder_tpu.core import dtypes, prims as cp
    from thunder_tpu.core.prims import PrimIDs
    from thunder_tpu.executors.passes import del_last_used
    from thunder_tpu import ops

    trc = TraceCtx("computation")
    with tracectx(trc):
        a = TensorProxy("a", shape=(4, 4), dtype=dtypes.float32)
        red = dp.wait(dp.all_reduce(a, "dp", "sum"))
        c = ops.mul(a, red)       # consumer of a gated by the wait
        d = ops.add(a, 1.0)       # independent compute (del a pins here)
        out = ops.add(c, d)
        cp.python_return(out)
    trc.args = [a]
    trc.output = out

    new = sort_waits(del_last_used(trc))
    deleted: set = set()
    for b in new.bound_symbols:
        names = [x.name for x in b.flat_proxy_args() if hasattr(x, "name")]
        if b.sym.id is PrimIDs.PYTHON_DEL:
            deleted.update(names)
        else:
            assert not (set(names) & deleted), f"use after del: {names} in {b.sym.name}"


def test_ddp_float_image_batch_is_sharded(eight_devices):
    """VERDICT r1 weak #4: a FLOAT batch (images) under ddp must shard the
    batch dim — the round-1 integer-dtype heuristic silently replicated it
    (losing data parallelism); state leaves still replicate with params."""
    rng = np.random.RandomState(0)
    params = {"w": rng.randn(3 * 8 * 8, 10).astype(np.float32) * 0.1,
              "b": np.zeros(10, np.float32)}
    images = rng.randn(16, 3 * 8 * 8).astype(np.float32)   # FLOAT batch
    labels = rng.randint(0, 10, size=(16,)).astype(np.int32)

    def step(p, s, x, y):
        def loss_fn(pp):
            logits = tt.ops.add(tt.ops.matmul(x, pp["w"]), pp["b"])
            return tt.ops.cross_entropy(tt.ops.convert_element_type(
                logits, tt.core.dtypes.float32), y)
        loss, g = tt.value_and_grad(loss_fn)(p)
        new = {k: tt.ops.sub(p[k], tt.ops.mul(0.1, g[k]))
               for k in p}
        news = {k: tt.ops.add(s[k], tt.ops.mul(0.0, g[k])) for k in p}  # mirrors params
        return loss, new, news

    state = {k: np.zeros_like(v) for k, v in params.items()}
    ref_loss, ref_new, _ = tt.jit(step)(params, state, images, labels)

    js = ddp(step, MeshSpec.make(dp=N))
    loss, new, _ = js(params, state, images, labels)
    np.testing.assert_allclose(np.asarray(loss), np.asarray(ref_loss), atol=1e-5)
    for k in params:
        np.testing.assert_allclose(np.asarray(new[k]), np.asarray(ref_new[k]),
                                   atol=1e-5, rtol=1e-4)

    # the float image batch was actually SHARDED (not replicated): its leaf
    # plan carries the dp axis
    img_plan = [pl for pl, (path, leaf) in zip(
        js._plan,
        __import__("jax").tree_util.tree_flatten_with_path(
            ((params, state, images, labels), {}))[0])
        if hasattr(leaf, "shape") and tuple(leaf.shape) == (16, 3 * 8 * 8)]
    assert img_plan and img_plan[0].kind == "data_shard", img_plan
    # state leaves replicated with their params
    st_plans = [pl.kind for pl, (path, leaf) in zip(
        js._plan,
        __import__("jax").tree_util.tree_flatten_with_path(
            ((params, state, images, labels), {}))[0])
        if "w" == getattr(path[-1], "key", None) or "b" == getattr(path[-1], "key", None)]
    assert all(k in ("ddp_param", "replicate") for k in st_plans), st_plans


def test_ddp_bare_array_state_replicates(eight_devices):
    """Code-review r2: bare-array params (no key structure) fall back to the
    integer-dtype heuristic — a bare float momentum array must NOT be
    sharded as batch data."""
    rng = np.random.RandomState(4)
    w = rng.randn(16, 10).astype(np.float32) * 0.1
    mom = np.zeros((16, 10), np.float32)
    x = rng.randint(0, 16, size=(16,)).astype(np.int32)   # int batch

    def step(w, mom, x):
        def loss_fn(ww):
            picked = tt.ops.take(ww, x, 0)
            return tt.ops.mean(tt.ops.square(picked))
        loss, g = tt.value_and_grad(loss_fn)(w)
        mom2 = tt.ops.add(tt.ops.mul(0.9, mom), g)
        return loss, tt.ops.sub(w, tt.ops.mul(0.1, mom2)), mom2

    ref = tt.jit(step)(w, mom, x)
    js = ddp(step, MeshSpec.make(dp=N))
    got = js(w, mom, x)
    for r, g in zip(ref, got):
        np.testing.assert_allclose(np.asarray(r), np.asarray(g), atol=1e-5, rtol=1e-4)


def test_pipeline_remat_stages_parity(eight_devices):
    """remat_stages=True (the 1F1B memory profile via per-tick checkpoint)
    must be numerically identical to the plain schedule, and the trace must
    show the checkpoint regions + the opt_barrier pin that keeps XLA from
    CSE-ing the recompute away (PIPELINE.md)."""
    from thunder_tpu.distributed import make_pipeline_loss, pipeline_parallel

    cfg = llama.CONFIGS["tiny"]
    params = llama.stack_layers(llama.init_params(cfg, seed=0))
    opt = SGD(lr=1e-2)
    tokens, targets = _data(cfg, 8, 16, seed=0)

    def mk(remat):
        embed, stage, head = llama.pipeline_fns(cfg)
        ploss = make_pipeline_loss(embed, stage, head, n_microbatches=4,
                                   remat_stages=remat)

        def step(params, opt_state, tokens, targets):
            loss, grads = tt.value_and_grad(lambda p: ploss(p, tokens, targets))(params)
            newp, news = opt.update(params, grads, opt_state)
            return loss, newp, news

        return step

    losses = {}
    for remat in (False, True):
        jstep = pipeline_parallel(mk(remat), MeshSpec.make(pp=4),
                                  stage_patterns=llama.PP_STAGE_PATTERNS)
        loss, p2, _ = jstep(params, opt.init(params), tokens, targets)
        losses[remat] = float(np.asarray(loss))
        if remat:
            src = tt.last_traces(jstep)[0].python()
            assert "checkpoint" in src
            assert "opt_barrier" in src
    np.testing.assert_allclose(losses[False], losses[True], rtol=1e-6)


def test_pipeline_bubble_fraction():
    from thunder_tpu.distributed.pipeline import bubble_fraction

    assert bubble_fraction(8, 4) == pytest.approx(3 / 11)
    assert bubble_fraction(32, 4) == pytest.approx(3 / 35)
    assert bubble_fraction(1, 1) == 0.0


@pytest.mark.parametrize("mode", ["fsdp", "ddp"])
def test_size_1_mesh_degenerates_to_single_device(mode):
    """VERDICT r4 #1: every data-parallel mode must degrade to a working
    no-op on a 1-device mesh (a user on one chip running mesh code), not a
    SpecPropagationError. Parity: the reference's wrappers run unchanged at
    world size 1 (thunder/distributed/__init__.py:192-366)."""
    cfg = llama.CONFIGS["tiny"]
    params = llama.init_params(cfg, seed=0, scale_layers=2)
    opt = SGD(lr=1e-2)
    tokens, targets = _data(cfg, 2, 16, seed=0)

    ref_losses, _ = _run_steps(tt.jit(_make_step(cfg, opt)), params, opt.init(params),
                               tokens, targets)
    wrap = fsdp if mode == "fsdp" else ddp
    jstep = wrap(_make_step(cfg, opt), MeshSpec.make(**{"fsdp" if mode == "fsdp" else "dp": 1}))
    losses, _ = _run_steps(jstep, params, opt.init(params), tokens, targets)
    np.testing.assert_allclose(losses, ref_losses, rtol=2e-5)


def test_size_1_mesh_fsdp_zero3():
    cfg = llama.CONFIGS["tiny"]
    params = llama.init_params(cfg, seed=0, scale_layers=2)
    opt = SGD(lr=1e-2)
    tokens, targets = _data(cfg, 2, 16, seed=0)
    jstep = fsdp(_make_step(cfg, opt), MeshSpec.make(fsdp=1), zero=3)
    losses, _ = _run_steps(jstep, params, opt.init(params), tokens, targets)
    assert all(np.isfinite(l) for l in losses)


def test_clip_grad_norm_is_dist_aware(eight_devices):
    """optim.clip_grad_norm under FSDP: each rank holds grad SHARDS, so the
    local sum-of-squares must be all-reduced over the mesh axis — the
    distributed global norm (and the clipped update) must match the
    single-device run exactly."""
    from thunder_tpu import ops
    from thunder_tpu.core.pytree import tree_map
    from thunder_tpu.optim import clip_grad_norm

    cfg = llama.CONFIGS["tiny"]
    params = llama.init_params(cfg, seed=0, scale_layers=1)
    tokens, targets = _data(cfg, N, 8, seed=0)
    max_norm = 0.25  # well below the actual norm so clipping really fires

    def wrapped(params, tokens, targets):
        loss, grads = tt.value_and_grad(
            lambda p: llama.loss_fn(p, tokens, targets, cfg))(params)
        clipped, norm = clip_grad_norm(grads, max_norm, params=params)
        new_p = tree_map(ops.sub, params, clipped)
        return loss, new_p, norm

    jref = tt.jit(wrapped)
    _, p_ref, norm_ref = jref(params, tokens, targets)
    jdist = fsdp(wrapped, MeshSpec.make(fsdp=N))
    _, p_dist, norm_dist = jdist(params, tokens, targets)
    np.testing.assert_allclose(float(np.asarray(norm_dist)),
                               float(np.asarray(norm_ref)), rtol=1e-5)
    assert float(np.asarray(norm_ref)) > max_norm  # the clip actually engaged
    for r, d in zip(jax.tree_util.tree_leaves(p_ref),
                    jax.tree_util.tree_leaves(p_dist)):
        np.testing.assert_allclose(np.asarray(r), np.asarray(d),
                                   atol=1e-6, rtol=1e-5)


@pytest.mark.chaos
def test_numerics_guard_composes_with_fsdp(eight_devices):
    """NumericsGuardTransform on an FSDP step: the health word is all-reduced
    over the mesh axis (one packed collective), so every shard takes the
    same branch of the in-graph skip — an injected NaN-grad step holds the
    SHARDED state bit-identical on every rank."""
    from thunder_tpu import observe
    from thunder_tpu.runtime import faults
    from thunder_tpu.runtime.faults import FaultPlan, FaultSpec
    from thunder_tpu.transforms import NumericsGuardTransform

    cfg = llama.CONFIGS["tiny"]
    params = llama.init_params(cfg, seed=0, scale_layers=1)
    opt = AdamW(lr=1e-3)
    tokens, targets = _data(cfg, N, 8, seed=0)

    guard = NumericsGuardTransform()
    js = fsdp(_make_step(cfg, opt), MeshSpec.make(fsdp=N), transforms=[guard])
    ref_guard = NumericsGuardTransform()
    jref = tt.jit(_make_step(cfg, opt), transforms=[ref_guard])
    jref(params, opt.init(params), tokens, targets)
    observe.enable(clear=True)
    try:
        l1, p1, s1 = js(params, opt.init(params), tokens, targets)
        # the health word's global grad norm is the TRUE norm (sharded
        # leaves psum'd, replicated leaves local), matching single-device
        np.testing.assert_allclose(guard.sentinel.last_verdict.grad_norm,
                                   ref_guard.sentinel.last_verdict.grad_norm,
                                   rtol=1e-4)
        with faults.active(FaultPlan([FaultSpec("numerics:grads",
                                                at_steps={2})])):
            l2, p2, s2 = js(p1, s1, tokens, targets)
        for a, b in zip(jax.tree_util.tree_leaves((p1, s1)),
                        jax.tree_util.tree_leaves((p2, s2))):
            assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
        l3, p3, s3 = js(p2, s2, tokens, targets)
        assert np.isfinite(float(np.asarray(l3)))
        snap = observe.snapshot()
        assert snap["counters"]["runtime.skipped_steps"] == 1
        assert guard.sentinel.last_verdict.healthy
    finally:
        observe.disable()
        observe.reset()
        faults.clear()
