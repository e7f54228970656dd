"""Tooling tests: examine coverage reporter, memory estimator, checkpointing,
autocast (reference parity: thunder/examine, thunder/distributed/checkpoint,
autocast rules in thunder/core/transforms.py)."""

import os

import numpy as np
import pytest

import thunder_tpu as tt
from thunder_tpu import ops
from thunder_tpu.core import dtypes
from thunder_tpu.examine import estimate_memory, examine, get_fusions
from thunder_tpu.models import nanogpt


def test_examine_reports_ops_and_claims():
    def f(a, b):
        return ops.tanh(a @ b).sum()

    rng = np.random.RandomState(0)
    report = examine(f, rng.randn(4, 5).astype(np.float32), rng.randn(5, 3).astype(np.float32))
    assert "matmul" in report["ops_used"]
    assert "tanh" in report["ops_used"]
    assert report["num_fusions"] >= 1


def test_memory_estimate():
    def f(a, b):
        c = a + b
        return (c * a).sum()

    jf = tt.jit(f, executors=["eagerjax"])
    a = np.ones((128, 128), np.float32)
    jf(a, a)
    est = estimate_memory(tt.last_execution_trace(jf))
    nbytes = 128 * 128 * 4
    assert est["peak_bytes"] >= 3 * nbytes  # a, b, and one live intermediate
    assert est["peak_bytes"] <= 5 * nbytes


def test_checkpoint_roundtrip(tmp_path):
    from thunder_tpu.checkpoint import load_checkpoint, save_checkpoint

    state = {"w": np.arange(12, dtype=np.float32).reshape(3, 4),
             "opt": {"step": np.asarray(3.0, np.float32)},
             "layers": [np.ones((2,), np.float32), np.zeros((2,), np.float32)]}
    path = str(tmp_path / "ckpt")
    save_checkpoint(path, state)
    restored = load_checkpoint(path, template=state)
    flat_a, _ = tt.core.pytree.tree_flatten(state) if hasattr(tt, "core") else (None, None)
    import jax

    for a, b in zip(jax.tree_util.tree_leaves(state), jax.tree_util.tree_leaves(restored)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))


def test_checkpoint_resume_training():
    """Save mid-training, reload, and continue identically."""
    from thunder_tpu.checkpoint import load_checkpoint, save_checkpoint
    from thunder_tpu.models import llama
    from thunder_tpu.optim import SGD
    import tempfile

    cfg = llama.CONFIGS["tiny"]
    params = llama.init_params(cfg, seed=0, scale_layers=1)
    opt = SGD(lr=1e-2)

    def train_step(params, opt_state, tokens, targets):
        loss, grads = tt.value_and_grad(lambda p: llama.loss_fn(p, tokens, targets, cfg))(params)
        return loss, *opt.update(params, grads, opt_state)

    jstep = tt.jit(train_step)
    rng = np.random.RandomState(0)
    tokens = rng.randint(0, cfg.vocab_size, size=(2, 8)).astype(np.int32)
    targets = np.roll(tokens, -1, 1).astype(np.int32)

    opt_state = opt.init(params)
    _, params, opt_state = jstep(params, opt_state, tokens, targets)
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "ck")
        save_checkpoint(path, {"params": params, "opt": opt_state})
        l2a, params_a, _ = jstep(params, opt_state, tokens, targets)
        restored = load_checkpoint(path, template={"params": params, "opt": opt_state})
        l2b, params_b, _ = jstep(restored["params"], restored["opt"], tokens, targets)
    np.testing.assert_allclose(np.asarray(l2a), np.asarray(l2b))


def test_autocast_downcasts_matmuls():
    def f(a, b):
        with tt.autocast(dtypes.bfloat16):
            c = ops.matmul(a, b)
        d = ops.matmul(a, b)  # outside: stays f32
        return c, d

    rng = np.random.RandomState(0)
    a = rng.randn(8, 8).astype(np.float32)
    b = rng.randn(8, 8).astype(np.float32)
    jf = tt.jit(f)
    c, d = jf(a, b)
    assert str(c.dtype) == "bfloat16"
    assert str(d.dtype) == "float32"


def test_nanogpt_trains():
    cfg = nanogpt.CONFIGS["gpt2-tiny"]
    params = nanogpt.init_params(cfg, seed=0, scale_layers=2)
    from thunder_tpu.optim import AdamW

    opt = AdamW(lr=3e-3)

    def train_step(params, opt_state, tokens, targets):
        loss, grads = tt.value_and_grad(
            lambda p: nanogpt.loss_fn(p, tokens, targets, cfg))(params)
        return loss, *opt.update(params, grads, opt_state)

    jstep = tt.jit(train_step)
    rng = np.random.RandomState(0)
    tokens = rng.randint(0, cfg.vocab_size, size=(4, 32)).astype(np.int32)
    targets = np.roll(tokens, -1, 1).astype(np.int32)
    opt_state = opt.init(params)
    losses = []
    for _ in range(10):
        loss, params, opt_state = jstep(params, opt_state, tokens, targets)
        losses.append(float(np.asarray(loss)))
    assert losses[-1] < losses[0] * 0.8


def test_nanogpt_forward_matches_jax_reference():
    import jax
    import jax.numpy as jnp

    cfg = nanogpt.CONFIGS["gpt2-tiny"]
    params = nanogpt.init_params(cfg, seed=1, scale_layers=2)
    rng = np.random.RandomState(1)
    tokens = rng.randint(0, cfg.vocab_size, size=(2, 16)).astype(np.int32)

    got = np.asarray(tt.jit(lambda p, t: nanogpt.forward(p, t, cfg))(params, tokens))

    def ln(x, w, b):
        m = x.mean(-1, keepdims=True)
        v = ((x - m) ** 2).mean(-1, keepdims=True)
        return (x - m) / jnp.sqrt(v + 1e-5) * w + b

    def ref(p, toks):
        B, T = toks.shape
        D, H = cfg.n_embd, cfg.n_head
        hd = D // H
        h = p["wte"][toks] + p["wpe"][jnp.arange(T)]
        for blk in p["blocks"]:
            x = ln(h, blk["ln1"]["w"], blk["ln1"]["b"])
            qkv = x @ blk["attn_qkv"]["w"].T + blk["attn_qkv"]["b"]
            q, k, v = jnp.split(qkv, 3, axis=-1)
            q = q.reshape(B, T, H, hd).transpose(0, 2, 1, 3)
            k = k.reshape(B, T, H, hd).transpose(0, 2, 1, 3)
            v = v.reshape(B, T, H, hd).transpose(0, 2, 1, 3)
            s = (q @ k.transpose(0, 1, 3, 2)) / np.sqrt(hd)
            mask = jnp.tril(jnp.ones((T, T), bool))
            a = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1) @ v
            a = a.transpose(0, 2, 1, 3).reshape(B, T, D)
            h = h + a @ blk["attn_proj"]["w"].T + blk["attn_proj"]["b"]
            x = ln(h, blk["ln2"]["w"], blk["ln2"]["b"])
            m = jax.nn.gelu(x @ blk["mlp_fc"]["w"].T + blk["mlp_fc"]["b"], approximate=True)
            h = h + m @ blk["mlp_proj"]["w"].T + blk["mlp_proj"]["b"]
        h = ln(h, p["ln_f"]["w"], p["ln_f"]["b"])
        return h @ p["wte"].T

    want = np.asarray(ref(params, tokens))
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-3)


def test_native_dataloader():
    """C++ mmap token loader: deterministic sampling, correct windows."""
    import tempfile
    from thunder_tpu.data import TokenDataset, write_token_file, _native_lib

    rng = np.random.RandomState(0)
    tokens = rng.randint(0, 512, size=(10000,)).astype(np.uint16)
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "shard.bin")
        write_token_file(path, tokens)
        ds = TokenDataset(path, batch=4, seq=32, seed=7)
        assert ds.num_tokens == 10000
        t1, y1 = ds.sample(3)
        t2, y2 = ds.sample(3)
        np.testing.assert_array_equal(t1, t2)  # deterministic in (seed, step)
        assert t1.shape == (4, 32) and y1.shape == (4, 32)
        # targets are next-token shifted
        np.testing.assert_array_equal(t1[:, 1:], y1[:, :-1])
        # windows come from the file
        row = t1[0]
        idx = np.flatnonzero((np.lib.stride_tricks.sliding_window_view(
            tokens.astype(np.int32), 32) == row).all(1))
        assert len(idx) >= 1
    assert _native_lib() is not None, "native loader should build with g++"


def test_dataloader_feeds_training():
    import tempfile
    from thunder_tpu.data import TokenDataset, write_token_file
    from thunder_tpu.models import llama
    from thunder_tpu.optim import SGD

    cfg = llama.CONFIGS["tiny"]
    rng = np.random.RandomState(1)
    corpus = rng.randint(0, cfg.vocab_size, size=(5000,)).astype(np.uint16)
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "shard.bin")
        write_token_file(path, corpus)
        ds = TokenDataset(path, batch=2, seq=16)
        params = llama.init_params(cfg, seed=0, scale_layers=1)
        opt = SGD(lr=1e-2)

        def train_step(params, opt_state, tokens, targets):
            loss, grads = tt.value_and_grad(
                lambda p: llama.loss_fn(p, tokens, targets, cfg))(params)
            return loss, *opt.update(params, grads, opt_state)

        jstep = tt.jit(train_step)
        opt_state = opt.init(params)
        for step in range(3):
            tokens, targets = ds.sample(step)
            loss, params, opt_state = jstep(params, opt_state, tokens, targets)
        assert np.isfinite(np.asarray(loss))
        assert tt.cache_misses(jstep) == 1


# ---------------------------------------------------------------------------
# dev transforms (reference thunder/dev_utils/), langctx, numpy dialect
# ---------------------------------------------------------------------------

def test_debug_transform_sees_every_op():
    import thunder_tpu as tt
    from thunder_tpu import ops
    from thunder_tpu.dev_utils import DebugTransform
    import numpy as np

    seen = []
    tr = DebugTransform(lambda name, bsym, vals: seen.append(name))
    jf = tt.jit(lambda x: ops.add(ops.mul(x, 2.0), 1.0), transforms=[tr],
                executors=["eagerjax"])
    out = np.asarray(jf(np.ones(4, np.float32)))
    np.testing.assert_allclose(out, np.full(4, 3.0))
    assert len(seen) >= 2  # mul and add observed


def test_debug_transform_capture_ordering_and_values():
    """The per-op callback fires in PROGRAM order, after each op, with that
    op's concrete outputs — the contract golden-value capture relies on."""
    import thunder_tpu as tt
    from thunder_tpu import ops
    from thunder_tpu.dev_utils import DebugTransform
    import numpy as np

    seen = []
    tr = DebugTransform(lambda name, bsym, vals: seen.append(
        (name, [np.asarray(v).copy() for v in vals])))
    # whole_program_jit=False: under the outer jit the callback would see
    # tracers; the per-region path hands it concrete arrays (the documented
    # golden-value-capture mode)
    jf = tt.jit(lambda x: ops.add(ops.mul(x, 2.0), 1.0), transforms=[tr],
                executors=["eagerjax"], whole_program_jit=False)
    out = np.asarray(jf(np.ones(4, np.float32)))
    np.testing.assert_allclose(out, np.full(4, 3.0))

    names = [n for n, _ in seen]
    # mul's callback precedes add's: capture interleaves with execution
    # rather than batching at the end
    i_mul = next(i for i, n in enumerate(names) if "mul" in n)
    i_add = next(i for i, n in enumerate(names) if "add" in n)
    assert i_mul < i_add, names
    # each callback saw that op's OUTPUT values, not a later state
    np.testing.assert_allclose(seen[i_mul][1][0], np.full(4, 2.0))
    np.testing.assert_allclose(seen[i_add][1][0], np.full(4, 3.0))


def test_comm_report_byte_accounting_distributed_prims():
    """comm_report's in/out bytes follow each collective's semantics exactly:
    all_gather multiplies the payload by the axis size, reduce_scatter
    divides it, all_reduce preserves it."""
    from thunder_tpu.core.dtypes import float32
    from thunder_tpu.core.proxies import TensorProxy
    from thunder_tpu.core.trace import TraceCtx, tracectx
    from thunder_tpu.distributed import prims as dprims
    from thunder_tpu.examine import comm_report

    trc = TraceCtx("comm")
    with tracectx(trc):
        a = TensorProxy("a", shape=(4, 8), dtype=float32)   # 128 bytes local
        g = dprims.all_gather(a, "x", 0, 8)                 # out: (32, 8)
        r = dprims.all_reduce(a, "x")                       # out: (4, 8)
        s = dprims.reduce_scatter(a, "x", 0, 4)             # out: (1, 8)

    rep = comm_report(trc)
    nbytes = 4 * 8 * 4
    ag = rep["collectives"]["all_gather"]
    assert ag["count"] == 1
    assert ag["in_bytes"] == nbytes and ag["out_bytes"] == 8 * nbytes
    ar = rep["collectives"]["all_reduce"]
    assert ar["in_bytes"] == nbytes and ar["out_bytes"] == nbytes
    rs = rep["collectives"]["reduce_scatter"]
    assert rs["in_bytes"] == nbytes and rs["out_bytes"] == nbytes // 4
    assert rep["total_in_bytes"] == 3 * nbytes
    assert rep["total_out_bytes"] == 8 * nbytes + nbytes + nbytes // 4


def test_comm_report_fsdp_step_accounting(eight_devices):
    """End-to-end: on a real FSDP train step the gathers/scatters obey the
    world-size relationship (out = in * 8 for gathers of dim-0 shards) and
    composite-level collectives are not double-counted against their
    decompositions."""
    from thunder_tpu.distributed import fsdp, MeshSpec
    from thunder_tpu.examine import comm_report
    from thunder_tpu.models import llama
    from thunder_tpu.optim import SGD

    cfg = llama.CONFIGS["tiny"]
    params = llama.init_params(cfg, seed=0, scale_layers=1)
    opt = SGD(lr=1e-2)

    def step(p, s, tok, tgt):
        loss, g = tt.value_and_grad(lambda pp: llama.loss_fn(pp, tok, tgt, cfg))(p)
        p2, s2 = opt.update(p, g, s)
        return loss, p2, s2

    js = fsdp(step, MeshSpec.make(fsdp=8))
    rng = np.random.RandomState(0)
    tok = rng.randint(0, cfg.vocab_size, size=(8, 8)).astype(np.int32)
    js(params, opt.init(params), tok, np.roll(tok, -1, 1))

    rep = comm_report(js)
    colls = rep["collectives"]
    assert rep["total_in_bytes"] > 0 and rep["total_out_bytes"] > 0
    # param gathers: dim-0 sharded -> full, so out == 8 * in per op
    gathers = [colls[k] for k in ("synchronize", "regather", "all_gather")
               if k in colls]
    assert gathers, colls
    for c in gathers:
        assert c["out_bytes"] == 8 * c["in_bytes"], c
    # grad reduce-scatters go the other way
    if "reduce_scatter" in colls:
        c = colls["reduce_scatter"]
        assert c["in_bytes"] == 8 * c["out_bytes"], c


def test_profile_transform_preserves_results():
    import thunder_tpu as tt
    from thunder_tpu import ops
    from thunder_tpu.dev_utils import ProfileTransform
    import numpy as np

    jf = tt.jit(lambda x: ops.add(ops.mul(x, 2.0), 1.0), transforms=[ProfileTransform()])
    out = np.asarray(jf(np.ones(4, np.float32)))
    np.testing.assert_allclose(out, np.full(4, 3.0))


def test_langctx_resolution():
    from thunder_tpu.core.langctxs import Languages, langctx, resolve_method

    add_ops = resolve_method("add")
    with langctx(Languages.NUMPY):
        mult = resolve_method("multiply")
    assert callable(add_ops) and callable(mult)


def test_numpy_dialect_semantics():
    import thunder_tpu as tt
    import thunder_tpu.numpy as tnp
    import numpy as np

    def f(x):
        return tnp.sum(tnp.multiply(x, x), axis=1, keepdims=True)

    out = np.asarray(tt.jit(f)(np.arange(6, dtype=np.float32).reshape(2, 3)))
    ref = (np.arange(6, dtype=np.float32).reshape(2, 3) ** 2).sum(1, keepdims=True)
    np.testing.assert_allclose(out, ref)


def test_execution_file_dump_and_hand_patch(tmp_path):
    """Reference ``set_execution_callback_file`` (thunder/core/trace.py:612):
    the final generated program dumps to a file; an edited file is executed
    in place of the generated source."""
    import numpy as np
    import thunder_tpu as tt
    from thunder_tpu import ops

    path = tmp_path / "prog.py"

    def fn(a):
        return ops.add(a, 1.0)

    jfn = tt.jit(fn, execution_file=str(path))
    out = jfn(np.zeros((2,), np.float32))
    assert np.allclose(np.asarray(out), 1.0)
    src = path.read_text()
    assert "def computation" in src

    # hand-patch: make the program return input + 100 instead
    patched = src.replace("1.0", "100.0")
    assert patched != src
    path.write_text(patched)
    jfn2 = tt.jit(fn, execution_file=str(path))
    out2 = jfn2(np.zeros((2,), np.float32))
    assert np.allclose(np.asarray(out2), 100.0), np.asarray(out2)


def test_checkpoint_reshard_on_load(tmp_path, eight_devices):
    """Sharded save -> restore onto a DIFFERENT mesh layout via the template
    tree (reference distributed/checkpoint.py get/load_model_state_dict
    resharding semantics; here orbax + jax global arrays do the resharding)."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from thunder_tpu.checkpoint import load_checkpoint, save_checkpoint

    devs = np.array(jax.devices()[:8])
    mesh_a = Mesh(devs.reshape(8), ("x",))
    mesh_b = Mesh(devs.reshape(2, 4), ("y", "z"))

    w = np.arange(64 * 4, dtype=np.float32).reshape(64, 4)
    state = {"w": jax.device_put(w, NamedSharding(mesh_a, P("x", None))),
             "step": jax.device_put(np.float32(3.0), NamedSharding(mesh_a, P()))}
    path = tmp_path / "ckpt"
    save_checkpoint(str(path), state)

    template = {"w": jax.device_put(np.zeros_like(w), NamedSharding(mesh_b, P("z", "y"))),
                "step": jax.device_put(np.float32(0.0), NamedSharding(mesh_b, P()))}
    restored = load_checkpoint(str(path), template=template)
    np.testing.assert_array_equal(np.asarray(restored["w"]), w)
    assert float(restored["step"]) == 3.0
    # restored arrays carry the TEMPLATE's sharding, not the saved one
    assert restored["w"].sharding.spec == P("z", "y")


def test_examine_torch_coverage_report():
    """Reference examine() use case: report which torch ops a module calls
    and which the interop dialect lacks (thunder/examine/__init__.py:49)."""
    import torch

    from thunder_tpu.examine import examine_torch

    class M(torch.nn.Module):
        def __init__(self):
            super().__init__()
            self.lin = torch.nn.Linear(8, 8)

        def forward(self, x):
            y = torch.relu(self.lin(x))
            return torch.igamma(y.abs() + 1.0, y.abs() + 1.0)  # igamma: unsupported

    rep = examine_torch(M(), torch.randn(2, 8))
    assert any("relu" in k or "linear" in k for k in rep["supported"]), rep["supported"]
    assert any("igamma" in k for k in rep["unsupported"]), rep["unsupported"]
    assert 0.0 < rep["coverage"] < 1.0


def test_last_hlo_and_jaxpr():
    """Per-stage lowering dumps (SURVEY §7: per-stage HLO/jaxpr dumping is
    the multi-host debugging essential)."""
    import thunder_tpu as tt
    from thunder_tpu import ops

    jf = tt.jit(lambda a, b: ops.mul(ops.add(a, b), ops.sin(a)))
    x = np.random.rand(4, 4).astype(np.float32)
    jf(x, x)
    hlo = tt.last_hlo(jf)
    assert "sine" in hlo and "module" in hlo  # StableHLO text
    opt = tt.last_hlo(jf, optimized=True)
    assert len(opt) > 0
    jx = tt.last_jaxpr(jf)
    assert len(jx.jaxpr.eqns) >= 1

    # entries that cannot lower report actionable errors
    from thunder_tpu import ops as _ops
    ji = tt.jit(lambda a: _ops.item(_ops.sum(a)))
    ji(np.ones(3, np.float32))
    with pytest.raises(RuntimeError, match="whole-program"):
        tt.last_hlo(ji)


def test_last_hlo_distributed_shows_collectives(eight_devices):
    import thunder_tpu as tt
    from thunder_tpu.distributed import fsdp, MeshSpec
    from thunder_tpu.models import llama
    from thunder_tpu.optim import SGD

    cfg = llama.CONFIGS["tiny"]
    params = llama.init_params(cfg, seed=0, scale_layers=1)
    opt = SGD(lr=1e-2)

    def step(p, s, tok, tgt):
        loss, g = tt.value_and_grad(lambda pp: llama.loss_fn(pp, tok, tgt, cfg))(p)
        p2, s2 = opt.update(p, g, s)
        return loss, p2, s2

    js = fsdp(step, MeshSpec.make(fsdp=8))
    rng = np.random.RandomState(0)
    tok = rng.randint(0, cfg.vocab_size, size=(2, 8)).astype(np.int32)
    js(params, opt.init(params), tok, np.roll(tok, -1, 1))
    hlo = tt.last_hlo(js)
    assert "all_gather" in hlo or "all-gather" in hlo
    with pytest.raises(RuntimeError, match="last_hlo"):
        tt.last_jaxpr(js)  # per-shard jaxpr is not well-formed standalone


def test_compilation_cache_persists(tmp_path, monkeypatch):
    """tt.enable_compilation_cache writes XLA executables to disk (the
    ENABLE_NVFUSER_SERIALIZATION analog; kills the 20-40s TPU first-compile
    on warm starts) — and returns the directory in use."""
    import os
    import jax
    import thunder_tpu as tt
    from thunder_tpu import ops

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    cache = tmp_path / "xla-cache"
    assert tt.enable_compilation_cache(str(cache), min_compile_secs=0.0) == str(cache)
    try:
        jf = tt.jit(lambda a: tt.ops.sum(ops.matmul(a, a)))
        jf(np.random.rand(256, 256).astype(np.float32))
        assert len(os.listdir(cache)) >= 1
        # nothing but executables rides there: no quarantine set, no overlay
        assert not [f for f in os.listdir(cache) if f.endswith(".json")]
    finally:
        # back to the suite's own directory for every later test
        tt.enable_compilation_cache(before)


def test_compilation_cache_is_placed_from_outside(tmp_path, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: the helper returns it and configures
    NOTHING — JAX's own handling owns the cache."""
    import jax
    import thunder_tpu as tt

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "outside"))
    assert tt.enable_compilation_cache(str(tmp_path / "ignored")) == \
        str(tmp_path / "outside")
    assert jax.config.jax_compilation_cache_dir == before


def test_examine_torch_lists_all_unmapped_ops():
    """VERDICT r1 item 7 'done' criterion: examine on a model using 3
    unmapped torch ops lists all 3 WITHOUT raising (reference
    ``thunder/examine/__init__.py:17-49,174`` collector mode)."""
    import torch
    import torch.nn as nn

    from thunder_tpu.examine import examine_torch

    class Weird(nn.Module):
        def __init__(self):
            super().__init__()
            self.fc = nn.Linear(4, 4)

        def forward(self, x):
            h = self.fc(x)
            h = torch.special.bessel_j0(h)                       # unmapped
            h = torch.nanquantile(h.double(), 0.5, dim=-1,
                                  keepdim=True).float()          # unmapped
            return torch.combinations(h.flatten()[:4]).sum() + h.sum()  # unmapped

    rep = examine_torch(Weird(), torch.randn(2, 4))
    found = {k.split(".")[-1] for k in rep["unsupported"]}
    assert {"bessel_j0", "nanquantile", "combinations"} <= found
    # supported ops (linear, getitem, sum, flatten) are NOT false positives
    assert "torch.Tensor.__getitem__" not in rep["unsupported"]
    assert any("linear" in k for k in rep["supported"])
    assert 0.0 < rep["coverage"] < 1.0


def test_length_bucketing_bounds_compilations():
    """VERDICT r1 item 10 'done' criterion: a mixed-length stream compiles at
    most len(buckets) programs (the honest static-shape mitigation)."""
    import thunder_tpu as tt
    from thunder_tpu import ops
    from thunder_tpu.data import LengthBucketer, default_buckets

    buckets = default_buckets(512)          # [128, 256, 512]
    assert buckets == [128, 256, 512]
    b = LengthBucketer(buckets)
    assert b.bucket_for(1) == 128 and b.bucket_for(300) == 512

    jf = tt.jit(lambda toks, mask: ops.sum(
        ops.mul(ops.convert_element_type(toks, tt.core.dtypes.float32),
                ops.convert_element_type(mask, tt.core.dtypes.float32))))

    rng = np.random.RandomState(0)
    lengths = [5, 100, 130, 200, 260, 400, 90, 511, 17, 256]
    for L in lengths:
        batch = [rng.randint(0, 100, size=rng.randint(max(1, L - 4), L + 1))
                 for _ in range(4)]
        toks, mask = b.pad_batch(batch, pad_id=0)
        assert toks.shape[1] in buckets
        jf(toks, mask)
    # 10 distinct raw lengths, at most 3 compiled programs
    assert jf.cache_misses <= len(buckets), jf.cache_misses
    assert jf.cache_hits >= len(lengths) - len(buckets)

    import pytest as _pytest
    with _pytest.raises(ValueError, match="exceeds the largest bucket"):
        b.bucket_for(513)


def test_examine_torch_claims_breakdown():
    """claims=True adds per-executor claim + operand-dtype views
    (VERDICT r2 weak #5)."""
    torch = pytest.importorskip("torch")
    from thunder_tpu.examine import examine_torch

    class M(torch.nn.Module):
        def __init__(self):
            super().__init__()
            self.lin = torch.nn.Linear(8, 8)

        def forward(self, x):
            return torch.tanh(self.lin(x)).sum()

    rep = examine_torch(M(), torch.randn(4, 8), claims=True)
    assert rep["unsupported"] == {}
    assert "claims_by_executor" in rep
    # everything lands in a claiming executor (xla fusions or eagerjax tail)
    total = sum(sum(c.values()) for c in rep["claims_by_executor"].values())
    assert total > 0
    assert any(sigs for sigs in rep["op_dtypes"].values())


def test_xla_memory_and_cost():
    from thunder_tpu import ops
    from thunder_tpu.examine import xla_cost, xla_memory

    jf = tt.jit(lambda a, b: ops.matmul(a, b))
    jf(np.ones((64, 64), np.float32), np.ones((64, 64), np.float32))
    m = xla_memory(jf)
    assert m["argument_size_in_bytes"] >= 2 * 64 * 64 * 4
    c = xla_cost(jf)
    assert c.get("flops", 0) >= 2 * 64 ** 3 * 0.9  # XLA counts FMA as 2
