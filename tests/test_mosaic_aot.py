"""Every Pallas claim family through Mosaic, without a chip (tier-1).

Interpret mode — how the rest of the suite runs these kernels — has no VMEM
limit and no tiling rules, so it cannot say whether the real compiler
accepts a kernel. The installed libtpu can: ``jax.experimental.topologies``
hands out v5e devices to AOT-compile against from this CPU sandbox, and the
Mosaic custom calls are compiled as part of that. PR 21 found five refusals
this way before touching the chip (``pl.load``/``pl.store`` gone, a packed
rotate, ``erf``/``erfc`` unimplemented, a one-row block, scoped VMEM 8 KB
over) plus ``Mosaic kernels cannot be automatically partitioned`` under a
mesh. Shapes here are small but TPU-legal; ``chip_smoke.py`` carries the
full Llama-2-7B widths.
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

from thunder_tpu.executors import pallasex as px

bf16, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def v5e():
    """Devices of a v5e 2x2 topology to compile against. Asked of a child
    process first: on a host whose libtpu cannot describe a topology the
    call may block instead of raising, and that must cost one skipped
    module, not the suite."""
    import subprocess
    import sys

    ask = ("from jax.experimental import topologies as t; "
           "print(t.get_topology_desc(platform='tpu', "
           "topology_name='v5e:2x2').devices[0].device_kind)")
    try:
        probe = subprocess.run([sys.executable, "-c", ask], timeout=120,
                               capture_output=True, text=True)
    except subprocess.TimeoutExpired:
        pytest.skip("TPU compiler unavailable: topology query timed out")
    if probe.returncode != 0:
        pytest.skip(f"TPU compiler unavailable: {probe.stderr[-300:]}")
    assert probe.stdout.strip().splitlines()[-1] == "TPU v5 lite"
    from jax.experimental import topologies

    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2").devices


@pytest.fixture(autouse=True)
def _real_lowering(monkeypatch):
    # the real lowering, not the interpreter; claims as on the chip
    monkeypatch.delenv("THUNDER_TPU_PALLAS_INTERPRET", raising=False)
    monkeypatch.setattr(px, "_on_tpu", lambda: True)


def _compile(devices, fn, *avals, sharding=None):
    s = sharding or SingleDeviceSharding(devices[0])
    avals = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=getattr(a, "sharding", None) or s),
        avals)
    # the suite's conftest asks for "highest" matmul precision (exact CPU
    # comparisons); the chip runs the default, and Mosaic refuses an fp32
    # contraction of bf16 operands
    with jax.default_matmul_precision("default"):
        return jax.jit(fn).lower(*avals).compile()


def sds(shape, dtype=bf16, sharding=None):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _bwd_rung_compiled(v5e, shape, dtype):
    """Compile the causal backward at ``shape`` for the chip; the rung the
    dispatch recorded for it (``pallas.sdpa_bwd.<rung>``)."""
    from thunder_tpu.observe import registry as obs

    q = sds(shape, dtype)
    obs.enable(clear=True)
    try:
        _compile(v5e, functools.partial(px.pallas_sdpa_bwd, is_causal=True),
                 q, q, q, q, q, sds(shape[:-1], f32))
        counters = obs.snapshot()["counters"]
    finally:
        obs.disable()
        obs.reset()
    (name, n), = [(k, c) for k, c in counters.items()
                  if k.startswith("pallas.sdpa_bwd.")]
    assert n == 1
    return name.rsplit(".", 1)[1]


def test_flash_attention_every_rung(v5e):
    # T x hd walks the causal dispatch ladder: resident forward + one-pass
    # backward, the K/V-resident backward pair, and the grid-streaming
    # forward/backward above both
    for T, hd, fwd, rung in ((512, 128, True, "one_pass"),
                             (2048, 256, False, "one_pass"),
                             (4096, 256, True, "streaming")):
        if fwd:
            q = sds((1, 1, T, hd))
            _compile(v5e, functools.partial(px.pallas_sdpa_fwd, is_causal=True),
                     q, q, q)
        assert _bwd_rung_compiled(v5e, (1, 1, T, hd), bf16) == rung
    # mistral7b_train's own backward operands: the one-pass kernel stages
    # 24.00 MiB there, over Mosaic's default and under the limit it is
    # compiled with; the chip's compiler admits it before the chip is asked
    assert _bwd_rung_compiled(v5e, (4, 32, 4096, 128), bf16) == "one_pass"
    # the gate counts bytes: float32 there would stage 40 MiB and takes the
    # pair, whose dk/dv kernel stages 20.00 MiB at this batch x heads (it too
    # needs the raised limit; a grid of one batch·head is allocated less)
    assert _bwd_rung_compiled(v5e, (4, 32, 4096, 128), f32) == "pair"


def test_rowwise_kernels(v5e):
    x, w = sds((512, 256)), sds((256,))
    _compile(v5e, px.pallas_rms_norm, x, w)
    _compile(v5e, px.pallas_rms_norm_residual, x, x, w)
    _compile(v5e, px.pallas_ce_fwd, sds((512, 1024), f32), sds((512,), i32))


def test_linear_act_and_mlp_subblock_every_activation(v5e):
    x, w, b = sds((256, 256)), sds((384, 256)), sds((384,))
    wn, wg, wd = sds((256,)), sds((384, 256)), sds((256, 384))
    for act in sorted(px._ACT_IMPLS):       # exact GELU needs erf in-kernel
        _compile(v5e, functools.partial(px.pallas_linear_act, act=act), x, w, b)
        _compile(v5e, functools.partial(px.pallas_mlp_subblock, act=act),
                 x, x, wn, wg, wg, wd)


def _decode_operands(H, KV, S=8, D=256, hd=128, ps=16, npg=4, F=384,
                     pool_sharding=None, col=None, row=None):
    pool = sds((KV, S * npg + 1, ps, hd), sharding=pool_sharding)
    attn = (sds((S, 1, D)), sds((D,)), sds((H * hd, D), sharding=col),
            sds((KV * hd, D), sharding=col), sds((KV * hd, D), sharding=col),
            sds((D, H * hd), sharding=row), sds((S, 1, 1, hd // 2)),
            sds((S, 1, 1, hd // 2)), pool, pool, sds((S, npg), i32),
            sds((S,), i32), sds((S,), i32))
    mlp = (sds((D,)), sds((F, D), sharding=col), sds((F, D), sharding=col),
           sds((D, F), sharding=row))
    return attn, mlp


# the last case is mistral7b_serve_decode_sat's own shape (32 slots, GQA 8,
# a 2,048-token window of 16-token pages, bf16): a VMEM or tiling refusal of
# the cell's kernel shows here, before the chip
_SAT = dict(S=32, D=4096, hd=128, ps=16, npg=128, F=14336)


@pytest.mark.parametrize("H,KV,shape", [(2, 2, {}), (4, 2, {}), (32, 8, _SAT)],
                         ids=["mha", "gqa", "sat-cell"])
def test_serving_kernels(v5e, H, KV, shape):
    """The megakernels (their walk one KV head a grid step) and the per-op
    kernel (a group of heads a step)."""
    attn, mlp = _decode_operands(H, KV, **shape)
    _compile(v5e, px.pallas_attn_subblock, *attn)
    _compile(v5e, px.pallas_decode_layer, *attn, *mlp)
    q = sds((attn[0].shape[0], H, 1, 128))
    _compile(v5e, px.pallas_paged_decode_attention, q, attn[8], attn[9],
             attn[10], attn[11])


def test_fused_adamw_in_place_and_packed(v5e):
    """Aligned matrices update in place (no temporaries beyond the small
    packed remainder); the unaligned vector rides the slab."""
    shapes = [(512, 256), (64, 384), (256,)]
    p = tuple(sds(s) for s in shapes)
    v = tuple(sds(s, f32) for s in shapes)
    fn = functools.partial(px.pallas_fused_adamw, lr=1e-3, weight_decay=0.01)
    with jax.default_matmul_precision("default"):
        compiled = jax.jit(fn, donate_argnums=(0, 2, 3)).lower(
            *jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(
                    a.shape, a.dtype, sharding=SingleDeviceSharding(v5e[0])),
                (p, p, p, v, sds((), f32), sds((), f32)))).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 64 * 1024, mem   # only the 256-vector's slabs
    assert [px._adamw_inplace_view(s) is not None for s in shapes] == \
        [True, True, False]


def test_partitioning_plans_under_a_mesh(v5e):
    """Inside a program compiled over a GSPMD mesh a bare Mosaic call is
    refused by the lowering; the planned impls wrap themselves in a
    shard_map (Megatron layout) and compile with one all-reduce, the pool
    never gathered — and an impl WITHOUT a plan says so."""
    mesh = Mesh(np.array(v5e[:2]), ("tp",))
    ns = lambda *spec: NamedSharding(mesh, P(*spec))
    attn, mlp = _decode_operands(
        4, 2, F=512, pool_sharding=ns("tp", None, None, None),
        col=ns("tp", None), row=ns(None, "tp"))

    def under_mesh(fn):
        def run(*a):
            with px.gspmd_mesh(mesh):
                return fn(*a)
        return run

    hlo = _compile(v5e, under_mesh(px.pallas_attn_subblock), *attn,
                   sharding=ns()).as_text()
    assert hlo.count(" all-reduce(") + hlo.count(" all-reduce-start(") == 1
    assert "all-gather" not in hlo
    x = sds((8, 1, 256))
    hlo = _compile(v5e, under_mesh(px.pallas_mlp_subblock), x, x, *mlp,
                   sharding=ns()).as_text()
    assert hlo.count(" all-reduce(") + hlo.count(" all-reduce-start(") == 1
    assert "all-gather" not in hlo
    _compile(v5e, under_mesh(px.pallas_rms_norm), sds((512, 256)),
             sds((256,)), sharding=ns())
    with pytest.raises(NotImplementedError, match="no partitioning plan"):
        _compile(v5e, under_mesh(px.linear_act_op.python_impl.__wrapped__),
                 sds((512, 256)), sds((384, 256)), sharding=ns())
    # and WITHOUT the scope the lowering itself refuses, loudly
    with pytest.raises(NotImplementedError, match="automatically partitioned"):
        _compile(v5e, px.pallas_rms_norm, sds((512, 256)), sds((256,)),
                 sharding=ns())


@pytest.mark.parametrize("kind,pages,table,window", [
    ("window", 32 * 257 + 1, 257, 4096),
    ("global", 32 * 1024 + 1, 1024, None),
])
def test_window_page_walk_at_the_agent_cell_widths(v5e, kind, pages, table,
                                                   window):
    """``commandaplus_serve_agent_sat``'s decode attention: 32 slots, 128
    query heads over 8 KV heads x 128, pages of 16; a window layer's ring of
    257 pages and a global layer's 1,024-page table. A grid step walks all 8
    KV heads: one copy moves a page of every head (a strided source over
    the pool, Mosaic's to accept), 4 MB staged."""
    from thunder_tpu.observe import registry as obs

    pool = sds((8, pages, 16, 128))
    obs.enable(clear=True)
    try:
        _compile(v5e, functools.partial(px.pallas_paged_decode_attention,
                                        window=window),
                 sds((32, 128, 1, 128)), pool, pool, sds((32, table), i32),
                 sds((32,), i32))
        path, = [e for e in obs.snapshot()["events"]
                 if e["kind"] == "kernel_path"]
    finally:
        obs.disable()
        obs.reset()
    assert (path["heads_per_copy"], path["pages_per_block"],
            path["staged_bytes"]) == (8, 32, 4 << 20)
    assert path["rung"] == {"window": "ring_walk_257p_8h",
                            "global": "walk_8h"}[kind]


@pytest.mark.parametrize("chunk,keys,window", [
    (512, 4608, 4096),      # the ring's 256 pages in order + the chunk
    (512, 16384, None),     # a global layer's whole table
    (16, 4224, 4096),       # the smallest ladder rung, keys padded to 128
    (16, 16384, None),
])
def test_banded_flash_forward_at_the_agent_cell_widths(v5e, chunk, keys,
                                                       window):
    _compile(v5e, functools.partial(px.pallas_banded_attention, window=window),
             sds((128, chunk, 128)), sds((8, keys, 128)), sds((8, keys, 128)),
             sds((), i32), sds((), i32))


@pytest.mark.parametrize("rows", [16, 32, 512])
def test_grouped_expert_matmul_at_the_agent_cell_widths(v5e, rows):
    """16 held + 4 shared experts of 4096 x 4096 x 3; a ladder rung's rows,
    a decode step's 32 (one row tile, 512-wide feed-forward blocks: 24 MiB
    of tiles, inside the planned VMEM limit) and a chunk's 512 (256-row
    tiles); 8 picks + 4 shared + 1 keep-warm assignment a row."""
    w = sds((20, 4096, 4096))
    _compile(v5e, px.pallas_moe_experts, sds((rows, 4096)), w, w, w,
             sds((rows, 13), i32), sds((rows, 13), f32))


def _relayouts(hlo: str, shape: str) -> list[str]:
    """Instructions of ``hlo``'s entry computation that re-lay out a
    parameter of ``shape`` (``"bf16[16384,4096]"``), or what the program
    staged of it: a bitcast, an async slice or copy into VMEM and the
    concatenation of slices pass a weight on as it is."""
    passing = {"bitcast", "slice-start", "slice-done", "copy-start",
               "copy-done", "get-tuple-element"}
    insts = []
    for line in hlo[hlo.index("\nENTRY"):].splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = (.*)$", line)
        if m:
            op = re.search(r" ([a-z][a-z0-9\-]*)\(([^)]*)\)", m.group(2))
            insts.append((m.group(1), op.group(1),
                          set(re.findall(r"%([\w.\-]+)", op.group(2))),
                          m.group(2)))
    staged = {n for n, op, _, rest in insts
              if op == "parameter" and rest.startswith(shape + "{")}
    assert staged, f"no parameter {shape}"
    found, grew = [], True
    while grew:
        grew = False
        for n, op, args, rest in insts:
            if n in staged or n in found or not staged & args:
                continue
            if op in passing or 'custom_call_target="ConcatBitcast"' in rest:
                staged.add(n)
                grew = True
            elif op in ("reshape", "copy", "transpose"):
                found.append(n)
    return found


def test_routed_decode_reads_the_rotary_weights_as_held(v5e):
    """The decode program of ``commandaplus_serve_agent_sat``'s attention
    widths (4096 wide, 128 query heads over 8 KV heads x 128, 32 slots; a
    window layer and a global one, experts, vocabulary and context cut)
    reads a layer's ``wq`` and ``wk`` as they are held. Without the barrier
    in ``Cohere2MoeDescription._decode_attn`` the interleaved rotary's
    stride-2 split moves into the projection's weight, and every step copies
    the window layer's ``wq`` (134 MB) and ``wk`` to a tiling of row pairs
    (at the agent cell, three ``reshape``s of 0.52 ms a step on a v5e)."""
    from thunder_tpu.core import dtypes
    from thunder_tpu.models.cohere2_moe import Cohere2MoeConfig
    from thunder_tpu.serving.description import describe
    from thunder_tpu.serving.kv_cache import PageGeometry
    from thunder_tpu.serving.runner import PagedRunner

    S, D, H, KV, hd, ps, ctx = 32, 4096, 128, 8, 128, 16, 1024
    cfg = Cohere2MoeConfig(vocab_size=1024, dim=D, n_heads=H, n_kv_heads=KV,
                           head_dim=hd, window=256,
                           layer_types=("window", "full"), n_layers=2,
                           expert_dim=256, n_experts=8, top_k=2, n_held=2,
                           n_shared=1, max_seq_len=ctx, dtype=dtypes.bfloat16)
    desc = describe(cfg)
    geoms = tuple(
        PageGeometry(n_layers=desc.layer_kinds.count(k), kv_heads=KV,
                     head_dim=hd, page_size=ps,
                     num_pages=S * kind.pages_per_request(ctx, ps) + 1,
                     pages_per_request=kind.pages_per_request(ctx, ps),
                     window=kind.window)
        for k, kind in enumerate(desc.cache_kinds))
    n = cfg.n_held + cfg.n_shared
    layer = {"norm": sds((D,)), "wq": sds((H * hd, D)),
             "wk": sds((KV * hd, D)), "wv": sds((KV * hd, D)),
             "wo": sds((D, H * hd)), "router": sds((cfg.n_experts, D)),
             "w_gate": sds((n, cfg.expert_dim, D)),
             "w_up": sds((n, cfg.expert_dim, D)),
             "w_down": sds((n, D, cfg.expert_dim))}
    params = {"tok_embedding": sds((cfg.vocab_size, D)), "norm_f": sds((D,)),
              "layers": [layer, dict(layer)]}
    pools = [{name: sds((KV, geoms[k].num_pages, ps, hd)) for name in "kv"}
             for k in desc.layer_kinds]
    zeros = lambda *shape, dt=np.int32: np.zeros(shape, dt)
    args = (params, zeros(S, 1),
            tuple(zeros(S, g.pages_per_request) for g in geoms),
            np.ones((S,), np.int32), tuple(zeros(S) for _ in geoms), pools,
            zeros(S, dt=np.float32), zeros(S), np.ones((S,), np.float32),
            zeros(S, 2, dt=np.uint32))
    one = SingleDeviceSharding(v5e[0])
    with jax.default_matmul_precision("default"):
        entry = PagedRunner(desc, geoms).decode_jit.compile(*args)
        avals = jax.tree_util.tree_map(
            lambda a: sds(a.shape, a.dtype, sharding=one), entry.input_avals)
        hlo = entry.jit_obj.lower(*avals).compile().as_text()
    assert _relayouts(hlo, f"bf16[{H * hd},{D}]") == []        # wq
    assert _relayouts(hlo, f"bf16[{KV * hd},{D}]") == []       # wk, wv


@pytest.mark.parametrize("T", [512, 16])
def test_kda_chunk_compiles_at_the_cell_widths(v5e, T):
    """The delta rule's prefill chunk, 64 heads of 128, a full 512-token
    chunk (eight inner chunks of 64) and the ladder's shortest rung."""
    H, dk = 64, 128
    x = sds((H, T, dk), f32)
    _compile(v5e, lambda *a: px.pallas_kda_chunk(*a, chunk=64), x, x, x, x,
             sds((H, T), f32), sds((H, dk, dk), f32), sds((), i32))


def test_kda_decode_compiles_at_the_cell_shape(v5e):
    """``solaropen2_serve_reason_sat``'s decode kernel alone: 128 slots, 64
    heads of 128, the float32 state donated. A grid step holds the head
    group the rule picks, its staging within the VMEM the kernel is
    compiled with, and the pool is updated in place: no copy of it."""
    from thunder_tpu.core.cost_model import VMEM_LIMIT_BYTES
    from thunder_tpu.observe import registry as obs

    Sl, H, d = 128, 64, 128
    one = SingleDeviceSharding(v5e[0])
    vec = sds((Sl, H, d), f32, one)
    pool = sds((Sl, H, d, d), f32, one)
    obs.enable(clear=True)
    try:
        hlo = jax.jit(px.pallas_kda_decode, donate_argnums=(5,)).lower(
            vec, vec, vec, vec, sds((Sl, H), f32, one), pool,
            sds((Sl,), i32, one)).compile().as_text()
        paths = [e for e in obs.get_registry().events
                 if e["kind"] == "kernel_path"]
    finally:
        obs.disable()
        obs.reset()
    (e,) = paths
    hg = px._kda_heads_per_step(H, d, d)
    assert (e["op"], e["rung"], e["heads_per_step"]) == (
        "nn.kda_decode", f"heads_{hg}", hg)
    assert e["staged_bytes"] == px._kda_decode_staging(hg, d, d)
    assert e["staged_bytes"] <= VMEM_LIMIT_BYTES
    shape = f"f32[{Sl},{H},{d},{d}]"
    entry_hlo = hlo[hlo.index("\nENTRY"):]
    assert re.search(r"%pallas_kda_decode\S* = \(.*" + re.escape(shape),
                     entry_hlo)
    assert [line for line in entry_hlo.splitlines()
            if shape in line and re.search(r" (copy|copy-start)\(", line)] \
        == []


def test_solar_decode_updates_the_state_pool_in_place(v5e):
    """The decode program of ``solaropen2_serve_reason_sat``'s KDA widths (64
    heads of 128, the state float32; 32 slots; a GQA layer and a KDA layer,
    experts, vocabulary and context cut) runs ``pallas_kda_decode`` on the
    donated state pool and holds no whole copy of it: the kernel's output is
    aliased to its input, and a copy of the pool (134 MB here, 537 MB a
    layer at the cell's 128 slots) would be a step's whole state traffic
    again."""
    from thunder_tpu.core import dtypes
    from thunder_tpu.models.solar_open2 import SolarOpen2Config
    from thunder_tpu.serving.description import describe
    from thunder_tpu.serving.kv_cache import PageGeometry, StateGeometry
    from thunder_tpu.serving.runner import PagedRunner

    S, D, H, KV, hd, Hk, ps, ctx = 32, 1024, 16, 2, 128, 64, 16, 256
    cfg = SolarOpen2Config(vocab_size=1024, dim=D, n_layers=2,
                           layer_types=("gqa", "kda"), n_heads=H,
                           n_kv_heads=KV, head_dim=hd, kda_heads=Hk,
                           kda_head_dim=128, kda_rank=128, expert_dim=256,
                           n_experts=8, top_k=2, n_held=2, n_shared=1,
                           max_seq_len=ctx, dtype=dtypes.bfloat16)
    desc = describe(cfg)
    w = desc.cache_kinds[0].pages_per_request(ctx, ps)
    geoms = (PageGeometry(n_layers=1, kv_heads=KV, head_dim=hd, page_size=ps,
                          num_pages=S * w + 1, pages_per_request=w),
             StateGeometry.of(1, S, desc.state_shapes()))
    c, n = Hk * 128, cfg.n_held + cfg.n_shared
    moe = {"attn_norm": sds((D,)), "ffn_norm": sds((D,)),
           "router": sds((8, D)), "router_bias": sds((8,), f32),
           "w_gate": sds((n, 256, D)), "w_up": sds((n, 256, D)),
           "w_down": sds((n, D, 256))}
    gqa = dict(moe, wq=sds((H * hd, D)), wk=sds((KV * hd, D)),
               wv=sds((KV * hd, D)), wo=sds((D, H * hd)), wg=sds((H * hd, D)))
    kda = dict(moe, wq=sds((c, D)), wk=sds((c, D)), wv=sds((c, D)),
               wo=sds((D, c)), conv=sds((3 * c, 4)), wb=sds((Hk, D)),
               wf_a=sds((128, D)), wf_b=sds((c, 128)), wg_a=sds((128, D)),
               wg_b=sds((c, 128)), a_log=sds((Hk,)), dt_bias=sds((c,)),
               o_norm=sds((128,)))
    params = {"tok_embedding": sds((1024, D)), "lm_head": sds((1024, D)),
              "norm_f": sds((D,)), "layers": [gqa, kda]}
    pools = [{name: sds((KV, S * w + 1, ps, hd)) for name in "kv"},
             {"s": sds((S, Hk, 128, 128), f32), "conv": sds((S, 4, 3 * c))}]
    zeros = lambda *shape, dt=np.int32: np.zeros(shape, dt)
    args = (params, zeros(S, 1), (zeros(S, w), zeros(S, 1)),
            np.ones((S,), np.int32), (zeros(S), zeros(S)), pools,
            zeros(S, dt=np.float32), zeros(S), np.ones((S,), np.float32),
            zeros(S, 2, dt=np.uint32))
    one = SingleDeviceSharding(v5e[0])
    with jax.default_matmul_precision("default"):
        entry = PagedRunner(desc, geoms).decode_jit.compile(*args)
        avals = jax.tree_util.tree_map(
            lambda a: sds(a.shape, a.dtype, sharding=one), entry.input_avals)
        hlo = entry.jit_obj.lower(*avals).compile().as_text()
    entry_hlo = hlo[hlo.index("\nENTRY"):]
    pool = f"f32[{S},{Hk},128,128]"
    assert re.search(r"%pallas_kda_decode\S* = \(.*" + re.escape(pool),
                     entry_hlo)
    copies = [line for line in entry_hlo.splitlines()
              if pool in line and re.search(r" (copy|copy-start)\(", line)]
    assert copies == []
