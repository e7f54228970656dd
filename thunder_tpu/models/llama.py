"""Llama-family transformer (Llama 2 / Llama 3 / tiny configs), written
functionally against ``thunder_tpu.ops``.

Covers the reference's model-zoo role (``thunder/tests/llama2_model.py``,
``litgpt`` GPT in ``thunder/tests/litgpt_model.py``) with the BASELINE.md
configs: tiny-stories Llama (config 1), Llama-2-7B (configs 2-3),
Llama-3-8B with GQA (config 4). Pure functions over a params pytree — the
TPU-first shape: the whole train step (fwd+bwd+optimizer) compiles into one
XLA program, and the distributed transforms shard the params pytree.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field

from thunder_tpu import ops
from thunder_tpu.core import dtypes, prims
from thunder_tpu.core.baseutils import check


@dataclass(frozen=True)
class LlamaConfig:
    name: str = "tiny"
    vocab_size: int = 32000
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int | None = None  # GQA when < n_heads
    intermediate_size: int = 11008
    max_seq_len: int = 4096
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    dtype: dtypes.dtype = dtypes.float32
    head_dim_override: int | None = None  # set by tensor-parallel local configs

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads if self.n_kv_heads is not None else self.n_heads

    @property
    def head_dim(self) -> int:
        return self.head_dim_override or self.dim // self.n_heads


CONFIGS = {
    # llama2.c tiny-stories scale (BASELINE config 1)
    "tiny": LlamaConfig(name="tiny", vocab_size=512, dim=64, n_layers=4, n_heads=4,
                        intermediate_size=176, max_seq_len=256),
    "tiny-gqa": LlamaConfig(name="tiny-gqa", vocab_size=512, dim=64, n_layers=4, n_heads=4,
                            n_kv_heads=2, intermediate_size=176, max_seq_len=256),
    # 8-way tensor-parallel smoke scale: every sharded dim (heads, kv heads,
    # intermediate) divides by 8, so the CPU 8-device mesh splits it cleanly
    "tiny-tp": LlamaConfig(name="tiny-tp", vocab_size=512, dim=64, n_layers=4, n_heads=8,
                           n_kv_heads=8, intermediate_size=192, max_seq_len=256),
    "llama2-7b": LlamaConfig(name="llama2-7b", vocab_size=32000, dim=4096, n_layers=32,
                             n_heads=32, intermediate_size=11008, max_seq_len=4096,
                             dtype=dtypes.bfloat16),
    "llama2-7b-bench": LlamaConfig(name="llama2-7b-bench", vocab_size=32000, dim=4096,
                                   n_layers=32, n_heads=32, intermediate_size=11008,
                                   max_seq_len=2048, dtype=dtypes.bfloat16),
    "llama3-8b": LlamaConfig(name="llama3-8b", vocab_size=128256, dim=4096, n_layers=32,
                             n_heads=32, n_kv_heads=8, intermediate_size=14336,
                             max_seq_len=8192, rope_theta=500000.0, dtype=dtypes.bfloat16),
    # bench variant: same per-layer arithmetic (GQA 32/8 heads, MLP 14336);
    # vocab capped at 32k and seq at 2048 so a scaled-layer slice + full
    # AdamW state fits one 16GB chip (the 128k-vocab embed+head alone is
    # 1.05B params — the GQA attention/MLP geometry is what this measures)
    "llama3-8b-bench": LlamaConfig(name="llama3-8b-bench", vocab_size=32000, dim=4096,
                                   n_layers=32, n_heads=32, n_kv_heads=8,
                                   intermediate_size=14336, max_seq_len=2048,
                                   rope_theta=500000.0, dtype=dtypes.bfloat16),
}


def init_params(cfg: LlamaConfig, seed: int = 0, scale_layers: int | None = None):
    """Initialize a params pytree with jax (host-side; not traced)."""
    import jax
    import jax.numpy as jnp

    n_layers = scale_layers if scale_layers is not None else cfg.n_layers
    key = jax.random.PRNGKey(seed)
    jd = cfg.dtype.jax

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32) * (1.0 / math.sqrt(fan_in))).astype(jd)

    keys = iter(jax.random.split(key, 4 + n_layers * 7))
    params = {
        "tok_embedding": dense(next(keys), (cfg.vocab_size, cfg.dim), cfg.dim),
        "norm_f": jnp.ones((cfg.dim,), jd),
        "lm_head": dense(next(keys), (cfg.vocab_size, cfg.dim), cfg.dim),
        "layers": [],
    }
    kv_dim = cfg.kv_heads * cfg.head_dim
    for _ in range(n_layers):
        params["layers"].append({
            "attn_norm": jnp.ones((cfg.dim,), jd),
            "wq": dense(next(keys), (cfg.dim, cfg.dim), cfg.dim),
            "wk": dense(next(keys), (kv_dim, cfg.dim), cfg.dim),
            "wv": dense(next(keys), (kv_dim, cfg.dim), cfg.dim),
            "wo": dense(next(keys), (cfg.dim, cfg.dim), cfg.dim),
            "mlp_norm": jnp.ones((cfg.dim,), jd),
            "w_gate": dense(next(keys), (cfg.intermediate_size, cfg.dim), cfg.dim),
            "w_up": dense(next(keys), (cfg.intermediate_size, cfg.dim), cfg.dim),
            "w_down": dense(next(keys), (cfg.dim, cfg.intermediate_size), cfg.intermediate_size),
        })
        # wq..w_down consumed 5 keys; gate/up/down 3 more handled above
    return params


def _rope_tables(cfg: LlamaConfig, pos, dtype):
    """cos/sin for an arbitrary POSITION TENSOR: ``pos`` (any shape, any
    numeric dtype) -> tables of shape ``pos.shape + (hd/2,)``. The ONE
    owner of the rope frequency math — `_rope_cos_sin` (contiguous ranges)
    and the serving runner's per-request decode positions both build on it,
    so a future rope change (scaling, theta handling) cannot diverge
    between training, prefill, and paged decode."""
    hd = cfg.head_dim
    posf = ops.convert_element_type(pos, dtypes.float32)
    idx = ops.convert_element_type(ops.arange(hd // 2), dtypes.float32)  # (hd/2,)
    inv_freq = ops.pow(cfg.rope_theta, ops.true_divide(ops.mul(idx, -2.0), float(hd)))
    angles = ops.mul(ops.unsqueeze(posf, -1), inv_freq)  # pos.shape + (hd/2,)
    cos = ops.convert_element_type(ops.cos(angles), dtype)
    sin = ops.convert_element_type(ops.sin(angles), dtype)
    return cos, sin


def _rope_cos_sin(cfg: LlamaConfig, T: int, dtype, pos_offset=None):
    """cos/sin tables built from iota (fully fusible, no host constants).
    ``pos_offset`` shifts positions (context parallelism: local chunk start)."""
    pos = ops.convert_element_type(ops.arange(T), dtypes.float32)  # (T,)
    if pos_offset is not None:
        pos = ops.add(pos, ops.convert_element_type(pos_offset, dtypes.float32))
    return _rope_tables(cfg, pos, dtype)


def _apply_rope(x, cos, sin):
    """x: (B, H, T, hd); GPT-NeoX half-rotation."""
    hd = x.shape[-1]
    x1 = x[..., : hd // 2]
    x2 = x[..., hd // 2:]
    # cos/sin: (T, hd/2) -> broadcast over (B, H)
    rx1 = ops.sub(ops.mul(x1, cos), ops.mul(x2, sin))
    rx2 = ops.add(ops.mul(x2, cos), ops.mul(x1, sin))
    return ops.cat([rx1, rx2], -1)


def _project_qkv(x, layer, cfg: LlamaConfig, cos, sin):
    """RoPE'd q/k/v heads from a normed hidden state: q (B, n_heads, T, hd);
    k, v keep kv_heads (GQA expansion is the attention path's business)."""
    B, T = x.shape[0], x.shape[1]
    hd = cfg.head_dim
    q = ops.transpose(ops.reshape(ops.linear(x, layer["wq"]),
                                  (B, T, cfg.n_heads, hd)), (0, 2, 1, 3))
    k = ops.transpose(ops.reshape(ops.linear(x, layer["wk"]),
                                  (B, T, cfg.kv_heads, hd)), (0, 2, 1, 3))
    v = ops.transpose(ops.reshape(ops.linear(x, layer["wv"]),
                                  (B, T, cfg.kv_heads, hd)), (0, 2, 1, 3))
    return _apply_rope(q, cos, sin), _apply_rope(k, cos, sin), v


def _mlp(h, layer, cfg: LlamaConfig):
    """Residual SwiGLU MLP sub-block."""
    x = ops.rms_norm(h, layer["mlp_norm"], eps=cfg.norm_eps)
    gate = ops.silu(ops.linear(x, layer["w_gate"]))
    up = ops.linear(x, layer["w_up"])
    return ops.add(h, ops.linear(ops.mul(gate, up), layer["w_down"]))


def _block(h, layer, cfg: LlamaConfig, cos, sin):
    """One decoder layer: RMSNorm → GQA attention → RMSNorm → SwiGLU MLP."""
    B, T = h.shape[0], h.shape[1]
    n_rep = cfg.n_heads // cfg.kv_heads
    hd = cfg.head_dim

    x = ops.rms_norm(h, layer["attn_norm"], eps=cfg.norm_eps)
    q, k, v = _project_qkv(x, layer, cfg, cos, sin)
    if n_rep > 1:  # GQA: repeat kv heads
        k = ops.reshape(ops.expand(ops.unsqueeze(k, 2), (B, cfg.kv_heads, n_rep, T, hd)),
                        (B, cfg.n_heads, T, hd))
        v = ops.reshape(ops.expand(ops.unsqueeze(v, 2), (B, cfg.kv_heads, n_rep, T, hd)),
                        (B, cfg.n_heads, T, hd))
    attn = ops.scaled_dot_product_attention(q, k, v, is_causal=True)
    # width is n_heads*hd (== dim/tp_size under tensor parallelism)
    attn = ops.reshape(ops.transpose(attn, (0, 2, 1, 3)), (B, T, cfg.n_heads * hd))
    h = ops.add(h, ops.linear(attn, layer["wo"]))
    return _mlp(h, layer, cfg)


def forward_hidden(params, tokens, cfg: LlamaConfig, remat: bool = False):
    """tokens: (B, T) int32 -> final hidden states (B, T, D) (pre-lm_head).

    ``remat=True`` wraps every transformer block in ``tt.checkpoint``:
    the backward recomputes each block from its input instead of saving
    intermediates — per-layer activation memory drops from ~dozens of
    (B,T,*) tensors to one, which is what lets deep 7B-geometry stacks
    train on a single 16 GB chip (reference analog: litgpt
    benchmark's activation checkpointing flag)."""
    B, T = tokens.shape
    h = ops.embedding(tokens, params["tok_embedding"])  # (B, T, D)
    from thunder_tpu.distributed import current_cp

    cp = current_cp()
    pos_offset = None
    if cp is not None:  # sequence sharded: positions start at my_chunk * T_local
        from thunder_tpu.distributed import prims as dist_prims

        pos_offset = ops.mul(dist_prims.axis_index(cp[0]), T)
    cos, sin = _rope_cos_sin(cfg, T, h.dtype, pos_offset)
    n_rep = cfg.n_heads // cfg.kv_heads
    hd = cfg.head_dim

    if remat:
        from thunder_tpu.core.rematerialization import checkpoint as _ckpt

        block = _ckpt(lambda x, lyr: _block(x, lyr, cfg, cos, sin))
        for layer in params["layers"]:
            h = block(h, layer)
    else:
        for layer in params["layers"]:
            h = _block(h, layer, cfg, cos, sin)

    return ops.rms_norm(h, params["norm_f"], eps=cfg.norm_eps)


def forward(params, tokens, cfg: LlamaConfig, remat: bool = False):
    """tokens: (B, T) int32 -> logits (B, T, vocab)."""
    return ops.linear(forward_hidden(params, tokens, cfg, remat=remat),
                      params["lm_head"])


def loss_fn(params, tokens, targets, cfg: LlamaConfig, remat: bool = False):
    logits = forward(params, tokens, cfg, remat=remat)
    B, T, V = logits.shape
    logits = ops.convert_element_type(ops.reshape(logits, (B * T, V)), dtypes.float32)
    return ops.cross_entropy(logits, ops.reshape(targets, (B * T,)))


def fused_loss_fn(params, tokens, targets, cfg: LlamaConfig, chunk: int = 8192,
                  remat: bool = False):
    """Chunked-vocab loss: lm_head projection fused into the cross-entropy
    (``nn.fused_linear_cross_entropy``) — the (B*T, vocab) logits are never
    materialized. Drop-in for ``loss_fn`` when activation memory is the
    constraint (large vocab / long sequence)."""
    from thunder_tpu.ops import nn as tnn

    h = forward_hidden(params, tokens, cfg, remat=remat)
    B, T, D = h.shape
    loss, _lse = tnn.fused_linear_cross_entropy(
        ops.reshape(h, (B * T, D)), params["lm_head"],
        ops.reshape(targets, (B * T,)), chunk=chunk)
    return loss


def stack_layers(params):
    """Convert the per-layer list-of-dicts into stacked arrays with a leading
    layer dim — the layout pipeline parallelism shards across the ``pp``
    axis (each device receives a contiguous layer chunk)."""
    import jax.numpy as jnp

    stacked = dict(params)
    layers = params["layers"]
    stacked["layers"] = {k: jnp.stack([l[k] for l in layers]) for k in layers[0]}
    return stacked


def pipeline_fns(cfg: LlamaConfig):
    """(embed_fn, stage_fn, head_loss_fn) for
    ``thunder_tpu.distributed.make_pipeline_loss``. ``stage_fn`` reads its
    layer-chunk length from the local (sharded) stacked shape, so the same
    trace works for any pp degree."""

    def embed_fn(params, tokens):
        return ops.embedding(tokens, params["tok_embedding"])

    def stage_fn(params, h):
        T = h.shape[1]
        cos, sin = _rope_cos_sin(cfg, T, h.dtype)
        n_local = params["layers"]["attn_norm"].shape[0]
        for i in range(n_local):
            layer = {k: v[i] for k, v in params["layers"].items()}
            h = _block(h, layer, cfg, cos, sin)
        return h

    def head_loss_fn(params, h, targets):
        h = ops.rms_norm(h, params["norm_f"], eps=cfg.norm_eps)
        logits = ops.linear(h, params["lm_head"])
        B, T, V = logits.shape
        logits = ops.convert_element_type(ops.reshape(logits, (B * T, V)), dtypes.float32)
        return ops.cross_entropy(logits, ops.reshape(targets, (B * T,)))

    return embed_fn, stage_fn, head_loss_fn


PP_STAGE_PATTERNS = (r"\['layers'\]",)


def tp_config(cfg: LlamaConfig, tp_size: int) -> LlamaConfig:
    """Local (per-shard) config for Megatron-style tensor parallelism:
    heads and MLP width divided across the tp axis (reference
    ``thunder/distributed/tensor_parallel/``: the consumer-rewrite visitor;
    here the model is shape-polymorphic so a local config suffices)."""
    import dataclasses

    check_ok = (cfg.n_heads % tp_size == 0 and cfg.kv_heads % tp_size == 0
                and cfg.intermediate_size % tp_size == 0)
    if not check_ok:
        raise ValueError(f"config {cfg.name} not divisible by tp={tp_size}")
    return dataclasses.replace(
        cfg,
        n_heads=cfg.n_heads // tp_size,
        n_kv_heads=cfg.kv_heads // tp_size,
        intermediate_size=cfg.intermediate_size // tp_size,
        head_dim_override=cfg.head_dim,
    )


TP_COLUMN_PATTERNS = (r"\['wq'\]", r"\['wk'\]", r"\['wv'\]", r"\['w_gate'\]", r"\['w_up'\]")
TP_ROW_PATTERNS = (r"\['wo'\]", r"\['w_down'\]")


def num_params(cfg: LlamaConfig, n_layers: int | None = None) -> int:
    n_layers = n_layers if n_layers is not None else cfg.n_layers
    kv_dim = cfg.kv_heads * cfg.head_dim
    per_layer = (2 * cfg.dim  # norms
                 + 2 * cfg.dim * cfg.dim  # wq, wo
                 + 2 * kv_dim * cfg.dim  # wk, wv
                 + 3 * cfg.dim * cfg.intermediate_size)  # gate/up/down
    return (2 * cfg.vocab_size * cfg.dim + cfg.dim + n_layers * per_layer)


def flops_per_token(cfg: LlamaConfig, seq_len: int, n_layers: int | None = None) -> float:
    """Model FLOPs per token for fwd+bwd (6N + attention terms)."""
    n = num_params(cfg, n_layers) - 2 * cfg.vocab_size * cfg.dim
    attn = 2 * 2 * (n_layers or cfg.n_layers) * cfg.dim * seq_len  # qk^T + pv per token
    return 6 * (n + cfg.vocab_size * cfg.dim) + 3 * attn


# ---------------------------------------------------------------------------
# KV-cache inference (autoregressive decoding)
# ---------------------------------------------------------------------------

def init_kv_cache(cfg: LlamaConfig, batch: int, max_len: int | None = None,
                  n_layers: int | None = None):
    """Per-layer K/V buffers (B, kv_heads, max_len, head_dim)."""
    import jax.numpy as jnp

    max_len = max_len or cfg.max_seq_len
    n = n_layers if n_layers is not None else cfg.n_layers
    shape = (batch, cfg.kv_heads, max_len, cfg.head_dim)
    return [{"k": jnp.zeros(shape, cfg.dtype.jax), "v": jnp.zeros(shape, cfg.dtype.jax)}
            for _ in range(n)]


def forward_step(params, tokens, cache, pos, cfg: LlamaConfig, last_idx=None):
    """Incremental forward: ``tokens`` (B, T) occupy positions
    [pos, pos+T) (prefill T>1 or decode T=1); ``pos`` is a traced scalar so
    one compiled program serves every decode step. Returns
    (logits (B, T, vocab), updated cache) — or (B, 1, vocab) when
    ``last_idx`` selects a single output row before the lm_head."""
    B, T = tokens.shape
    hd = cfg.head_dim
    n_rep = cfg.n_heads // cfg.kv_heads
    max_len = cache[0]["k"].shape[2]
    h = ops.embedding(tokens, params["tok_embedding"])
    cos, sin = _rope_cos_sin(cfg, T, h.dtype, pos_offset=pos)
    zero = ops.full((), 0, dtype=dtypes.int32)
    new_cache = []
    # validity of cache column j for local row i: j <= pos + i
    col = ops.arange(max_len)                                   # (max_len,)
    row = ops.add(ops.arange(T), pos)                           # (T,)
    valid = ops.le(ops.unsqueeze(col, 0), ops.unsqueeze(row, 1))  # (T, max_len)

    for layer, c in zip(params["layers"], cache):
        x = ops.rms_norm(h, layer["attn_norm"], eps=cfg.norm_eps)
        q, k, v = _project_qkv(x, layer, cfg, cos, sin)
        ck = prims.dynamic_update_slice(c["k"], k, (zero, zero, pos, zero))
        cv = prims.dynamic_update_slice(c["v"], v, (zero, zero, pos, zero))
        new_cache.append({"k": ck, "v": cv})
        # grouped-query attention WITHOUT materializing the expanded cache:
        # fold the group dim into q's row dim — q (B, H, T, hd) becomes
        # (B, kv_heads, n_rep*T, hd) and matmuls run against the unexpanded
        # (B, kv_heads, max_len, hd) cache
        qg = ops.reshape(q, (B, cfg.kv_heads, n_rep * T, hd))
        qf = ops.convert_element_type(qg, dtypes.float32)
        kf = ops.convert_element_type(ck, dtypes.float32)
        scores = ops.mul(ops.matmul(qf, kf.mT), 1.0 / math.sqrt(hd))
        scores = ops.reshape(scores, (B, cfg.n_heads, T, max_len))
        neg = ops.full((), float("-inf"), dtype=dtypes.float32)
        scores = ops.where(valid, scores, neg)
        attn_w = ops.convert_element_type(ops.softmax(scores, -1), h.dtype)
        attn = ops.matmul(ops.reshape(attn_w, (B, cfg.kv_heads, n_rep * T, max_len)), cv)
        attn = ops.reshape(attn, (B, cfg.n_heads, T, hd))
        attn = ops.reshape(ops.transpose(attn, (0, 2, 1, 3)), (B, T, cfg.n_heads * hd))
        h = ops.add(h, ops.linear(attn, layer["wo"]))
        h = _mlp(h, layer, cfg)

    h = ops.rms_norm(h, params["norm_f"], eps=cfg.norm_eps)
    if last_idx is not None:
        # logits only at row ``last_idx`` (traced 0-d index): the lm_head
        # projection runs on (B, 1, dim), not (B, T, dim) — for a Tp=512
        # prefill that is 512x less lm_head work and no (B, T, vocab)
        # materialization (measured r4: the whole prefill gap to the
        # hand-written baseline was this projection)
        zero = ops.full((), 0, dtype=dtypes.int32)
        h = prims.dynamic_slice(h, (zero, last_idx, zero), (B, 1, cfg.dim))
    return ops.linear(h, params["lm_head"]), new_cache


# shared decode/prefill step cache: tt.jit functions cache per input shape
# internally, so one entry per (config, n_layers) bounds compilations across
# generate() calls — a bucketed prefill (prefill_buckets) then compiles at
# most len(buckets) prefill programs total
_step_fns: dict = {}


def _get_step_fns(cfg: LlamaConfig, n_layers):
    import thunder_tpu as tt

    key = (repr(cfg), n_layers)
    if key in _step_fns:
        return _step_fns[key]

    def _step(p, t, c, pos):
        T = t.shape[1]
        last = ops.full((), T - 1, dtype=dtypes.int32)
        logits, nc = forward_step(p, t, c, pos, cfg, last_idx=last)
        return ops.squeeze(logits, 1), nc

    def _prefill(p, t, c, pos, true_len):
        # padded prefill: logits at the LAST REAL position (true_len - 1),
        # a traced 0-d index sliced BEFORE the lm_head — the compiled
        # program is shared by every prompt length in the bucket and never
        # materializes (B, T, vocab)
        logits, nc = forward_step(p, t, c, pos, cfg,
                                  last_idx=ops.sub(true_len, 1))
        return ops.squeeze(logits, 1), nc

    fns = (tt.jit(_step, donate_argnums=(2,)), tt.jit(_prefill, donate_argnums=(2,)))
    _step_fns[key] = fns
    return fns


def generate(params, cfg: LlamaConfig, prompt, max_new_tokens: int,
             temperature: float = 0.0, key=None, max_len: int | None = None,
             n_layers: int | None = None, prefill_buckets=None):
    """Autoregressive decoding with a KV cache: prefill once, then one
    compiled decode step reused for every position (``pos`` is a traced
    array — no per-step recompilation). Greedy when ``temperature == 0``,
    else softmax sampling via Gumbel trick with the keyed functional RNG.

    ``prefill_buckets=(128, 512, ...)``: pad the prompt to a bucket ladder so
    ragged prompt lengths compile at most ``len(buckets)`` prefill programs
    (step functions are shared across ``generate`` calls per config). The
    pad positions write garbage K/V beyond ``Tp`` — harmless: the causal
    mask hides cols > row, and decode overwrites each position before it is
    first attended."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import thunder_tpu as tt

    if max_new_tokens <= 0:
        return jnp.zeros((len(prompt), 0), jnp.int32)
    prompt = jnp.asarray(prompt)
    B, Tp = prompt.shape
    prompt_in, Tpad = prompt, Tp
    if prefill_buckets is not None:
        from thunder_tpu.data import LengthBucketer

        bk = LengthBucketer(prefill_buckets)
        Tpad = bk.bucket_for(Tp)
        if Tpad != Tp:
            prompt_in = jnp.pad(prompt, ((0, 0), (0, Tpad - Tp)))
        if max_len is None:
            # bucket the KV-cache length too: the decode step's compiled
            # shape is (B, 1) tokens × (B, H, max_len, hd) cache, so an
            # un-bucketed max_len would recompile decode per prompt length
            align = bk.buckets[0]
            max_len = min(cfg.max_seq_len,
                          max(Tpad, -(-(Tp + max_new_tokens) // align) * align))
    max_len = max_len or max(Tp + max_new_tokens, Tpad)
    if Tpad > max_len:
        raise ValueError(
            f"prefill bucket {Tpad} (for prompt length {Tp}) exceeds the KV "
            f"cache length (max_len={max_len}); use a tighter bucket ladder "
            f"or a larger max_len")
    if Tp + max_new_tokens > max_len or max_len > cfg.max_seq_len:
        raise ValueError(
            f"prompt ({Tp}) + max_new_tokens ({max_new_tokens}) exceeds the "
            f"context window (max_len={max_len}, cfg.max_seq_len={cfg.max_seq_len})")
    cache = init_kv_cache(cfg, B, max_len, n_layers=n_layers)

    # the step returns only the LAST position's logits (prefill would
    # otherwise run lm_head over the whole prompt and ship (B, Tp, vocab)
    # to the host); the cache is donated so XLA updates it in place instead
    # of copying ~all of it every token
    step_fn, prefill_fn = _get_step_fns(cfg, n_layers)

    def pick(logits_last, key):
        if temperature == 0.0:
            return jnp.argmax(logits_last, -1).astype(jnp.int32)
        g = -jnp.log(-jnp.log(jax.random.uniform(key, logits_last.shape) + 1e-10) + 1e-10)
        return jnp.argmax(logits_last / temperature + g, -1).astype(jnp.int32)

    if prefill_buckets is not None:
        last, cache = prefill_fn(params, prompt_in, cache, jnp.int32(0), jnp.int32(Tp))
    else:
        last, cache = step_fn(params, prompt, cache, jnp.int32(0))
    if key is None:
        key = jax.random.PRNGKey(0)
    key, sub = jax.random.split(key)
    tok = pick(last, sub)
    out = [tok]
    for i in range(1, max_new_tokens):
        last, cache = step_fn(params, tok[:, None], cache, jnp.int32(Tp + i - 1))
        key, sub = jax.random.split(key)
        tok = pick(last, sub)
        out.append(tok)
    return jnp.stack(out, axis=1)  # (B, max_new_tokens)


def generate_fused(params, cfg: LlamaConfig, prompt, max_new_tokens: int,
                   max_len: int | None = None, n_layers: int | None = None):
    """Greedy decoding with the WHOLE decode loop compiled as one XLA
    program: ``lax.scan`` over the framework-traced step, so generation is
    a single device dispatch — no per-token host round-trips (the per-step
    ``generate`` loop pays one dispatch + one token fetch per token; this
    pays one total). The scanned body IS the compiled entry's
    computation (same trace, same executors) — not a reimplementation.
    Reference analog: litgpt's generate is a per-step Python loop; this is
    the TPU-native replacement."""
    import jax
    import jax.numpy as jnp

    from thunder_tpu.core.pytree import tree_flatten

    prompt = jnp.asarray(prompt)
    B, Tp = prompt.shape
    max_len = max_len or (Tp + max_new_tokens)
    check(Tp + max_new_tokens <= max_len <= cfg.max_seq_len,
          lambda: f"prompt ({Tp}) + max_new_tokens ({max_new_tokens}) "
                  f"exceeds max_len={max_len} / cfg.max_seq_len={cfg.max_seq_len}")
    cache = init_kv_cache(cfg, B, max_len, n_layers=n_layers)
    step_fn, _ = _get_step_fns(cfg, n_layers)

    last, cache = step_fn(params, prompt, cache, jnp.int32(0))
    tok = jnp.argmax(last, -1).astype(jnp.int32)[:, None]
    if max_new_tokens == 1:
        return tok

    # the compiled decode entry for (B, 1) tokens; its computation_fn is the
    # pure-jax callable the scan body invokes
    entry = step_fn.compile(params, tok, cache, jnp.int32(Tp))
    comp = entry.computation_fn
    t_idx = entry.tensor_indices

    def body(carry, _):
        tok, cache, pos = carry
        flat, _ = tree_flatten(((params, tok, cache, pos), {}))
        lastl, nc = comp(*[flat[i] for i in t_idx])
        ntok = jnp.argmax(lastl, -1).astype(jnp.int32)[:, None]
        return (ntok, nc, pos + 1), ntok[:, 0]

    @jax.jit
    def decode_all(tok, cache):
        (_, _, _), toks = jax.lax.scan(
            body, (tok, cache, jnp.int32(Tp)), None,
            length=max_new_tokens - 1)
        return jnp.swapaxes(toks, 0, 1)  # (B, n-1)

    rest = decode_all(tok, cache)
    return jnp.concatenate([tok, rest], axis=1)
