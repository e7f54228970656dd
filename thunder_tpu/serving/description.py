"""Model descriptions: what the serving engine needs to know of a model.

The engine (scheduler, page allocator, sampling, dispatch) is the same for
every model; a :class:`ModelDescription` is the model's side of the
contract:

- the KV cache kind of every layer (:class:`CacheKind`: ``full`` keeps the
  whole context, ``window(W)`` the last ``W`` positions in a ring of pages
  the host recycles, ``state`` a fixed-size recurrent state a slot and no
  pages at all) — the allocator gives each distinct paged kind its own pool
  and block table, and the state kind one row a slot
  (:meth:`ModelDescription.state_shapes`);
- the two traced step functions, ``decode`` (one token for every slot,
  sampling in-graph) and ``prefill`` (one chunk of one prompt, K/V writes
  only), over the parameter tree the model's ``init_params`` lays out;
- the tensor-parallel plan, where the model has one.

``ServingEngine(params, cfg)`` asks :func:`describe` for ``cfg``'s
description: a config object with a ``serving_description`` method gives its
own (``models/cohere2_moe.py``); any other is a Llama-family config and gets
:class:`LlamaDescription`. No argument selects a model.

Step-function arguments that exist once a cache kind — ``block_tables``,
``write_pos`` / ``page_writes`` — arrive as tuples in ``cache_kinds`` order
(the runner wraps a bare array, which is how a direct caller of a one-kind
model may still pass them); ``geoms`` likewise. ``pools`` stays one dict a
LAYER, each shaped by its layer's kind: ``{"k", "v"}`` pages, or a state
kind's arrays with the slot first.

A state kind rides the same tuples: its block table is one unused column;
at decode its ``write_pos`` is ``(S,)`` int32, 1 where the row takes its
token into the state (0 for an idle row, and for a slot's first row, which
re-feeds the last prompt token that prefill already took in); at prefill
its ``page_writes`` is ``[slot, tokens, carried]`` int32: the slot whose
row the chunk reads and writes, the chunk's prompt tokens (the rest is
padding and leaves the state as it is), and 0 on a request's first chunk,
which starts from a zero state.
"""

from __future__ import annotations

from dataclasses import dataclass

from thunder_tpu import ops
from thunder_tpu.core import dtypes, prims
from thunder_tpu.ops import nn as tnn
from thunder_tpu.serving.sampling import sample_tokens


@dataclass(frozen=True)
class CacheKind:
    """How one kind of layer keeps its K/V."""

    name: str                       # "full" | "window" | "state"
    window: int | None = None       # positions kept, the newest included

    @property
    def paged(self) -> bool:
        """False for the state kind: a row a slot, no pages."""
        return self.name != "state"

    def pages_per_request(self, max_context: int, page_size: int) -> int:
        """Block-table width: the whole context, or the ring
        ``ceil(W / page) + 1`` (a window that starts mid-page reaches one
        page more than it fills); a state kind's one unused column."""
        if not self.paged:
            return 1
        if self.window is None:
            return -(-max_context // page_size)
        return -(-self.window // page_size) + 1


FULL = CacheKind("full")
STATE = CacheKind("state")


def write_rows(pool, rows, flat_positions):
    """Scatter every slot's K/V row into a flattened page pool in ONE
    scatter op.

    ``pool``: (KV, P*ps, hd); ``rows``: (S, KV, 1, hd); ``flat_positions``:
    (S,) int32 of page*ps+offset. Replace semantics (``prims.scatter``) —
    freed pages hold stale values, so add-style scatters would corrupt.
    Idle slots all target position 0 (the reserved scratch page); duplicate
    indices there are benign (any write wins, nobody reads it). One scatter
    beats S chained dynamic_update_slices: XLA copies the input pool once
    either way, but the chain pays S update kernels.

    The op emission lives in ``ops.nn.decode_row_write`` — ONE owner shared
    with the ``nn.attn_subblock`` decomposition, so the block planner's
    chain matcher and the quarantine fallback always see the exact sequence
    the description traces."""
    return tnn.decode_row_write(pool, rows, flat_positions)


def write_pages(pool, rows, page_positions, ps: int):
    """Scatter a prefill chunk's K/V into its pages. ``rows``: (KV, C, hd)
    with C a multiple of ps; ``page_positions``: (C//ps,) int32 flat
    positions (page*ps) — chunks start page-aligned by construction."""
    zero = ops.full((), 0, dtype=dtypes.int32)
    C = rows.shape[1]
    for i in range(C // ps):
        pos = ops.getitem(page_positions, i)
        pool = prims.dynamic_update_slice(pool, ops.narrow(rows, 1, i * ps, ps),
                                          (zero, pos, zero))
    return pool


class ModelDescription:
    """Base of every description; see the module docstring."""

    cfg = None
    n_layers: int = 0
    cache_kinds: tuple = (FULL,)        # the distinct kinds, tables' order
    layer_kinds: tuple = ()             # per layer: index into cache_kinds

    # geometry of one layer's K/V, and the pools' dtype
    @property
    def kv_heads(self) -> int:
        return self.cfg.kv_heads

    @property
    def head_dim(self) -> int:
        return self.cfg.head_dim

    @property
    def dtype(self):
        return self.cfg.dtype.jax

    @property
    def max_seq_len(self) -> int:
        return self.cfg.max_seq_len

    def state_shapes(self) -> dict:
        """A state kind's arrays for ONE slot of one layer: ``{name:
        (shape, dtype)}``; the engine keeps them with a slot axis in
        front."""
        return {}

    def tp_mesh(self, mesh):
        """``mesh`` (None, an int tp degree or a ``TensorParallelMesh``)
        normalised and checked against the model, typed errors on a bad
        split. A model without a plan refuses any mesh."""
        if mesh is None:
            return None
        from thunder_tpu.serving.errors import ShardingGeometryError

        raise ShardingGeometryError(
            f"model {self.cfg.name}: no tensor-parallel serving plan",
            kv_heads=self.kv_heads, tp=getattr(mesh, "tp", int(mesh)))

    def decode(self, geoms, params, tokens, block_tables, lengths, write_pos,
               pools, temps, top_ks, top_ps, rng):
        """-> (token ids (S,), logits (S, V), pools[, aux dict of small
        arrays the engine hands to :meth:`on_decode_aux`])."""
        raise NotImplementedError

    def prefill(self, geoms, params, tokens, block_tables, lengths,
                page_writes, pools):
        """-> pools."""
        raise NotImplementedError

    def on_decode_aux(self, obs, aux: dict, step: int) -> None:
        """Record what a decode step returned beside its tokens (host
        arrays); called on delivery, while the registry is enabled."""


def _rope_tables_at(cfg, positions, dtype):
    """Per-request rotary tables: ``positions`` (S,) int32 -> cos/sin
    ``(S, 1, 1, hd/2)``, broadcasting over heads and the single decode row.
    The frequency math lives in ``models.llama._rope_tables`` — ONE owner
    shared with training and prefill, so rope changes can't silently break
    the engine's token-identity with ``generate()``."""
    from thunder_tpu.models.llama import _rope_tables

    cos, sin = _rope_tables(cfg, positions, dtype)     # (S, hd/2)
    shape = (positions.shape[0], 1, 1, cfg.head_dim // 2)
    return ops.reshape(cos, shape), ops.reshape(sin, shape)


class LlamaDescription(ModelDescription):
    """The dense GQA decoder (``models/llama.py``): every layer keeps its
    whole context; RMSNorm, half-rotation rotary, SwiGLU, untied head."""

    def __init__(self, cfg, n_layers: int | None = None):
        self.cfg = cfg
        self.n_layers = n_layers if n_layers is not None else cfg.n_layers
        self.layer_kinds = (0,) * self.n_layers

    def tp_mesh(self, mesh):
        if mesh is None:
            return None
        from thunder_tpu.distributed.gspmd import TensorParallelMesh
        from thunder_tpu.models.llama import (TP_COLUMN_PATTERNS,
                                              TP_ROW_PATTERNS)
        from thunder_tpu.serving.errors import ShardingGeometryError

        cfg = self.cfg
        if isinstance(mesh, int):
            mesh = TensorParallelMesh(tp=mesh,
                                      column_patterns=TP_COLUMN_PATTERNS,
                                      row_patterns=TP_ROW_PATTERNS)
        if mesh.tp <= 1:
            return None
        for name, n in (("n_heads", cfg.n_heads), ("kv_heads", cfg.kv_heads),
                        ("intermediate_size", cfg.intermediate_size)):
            if n % mesh.tp != 0:
                raise ShardingGeometryError(
                    f"config {cfg.name}: {name}={n} not divisible by "
                    f"tp={mesh.tp}", kv_heads=cfg.kv_heads, tp=mesh.tp)
        return mesh

    # -- traced bodies ------------------------------------------------------
    def _attn_block(self, h, layer, q, block_tables, lengths, pools_kv):
        """Shared attention tail: this step's K/V rows are already written
        into the pools; run paged attention and the residual + MLP."""
        cfg = self.cfg
        B, T = h.shape[0], h.shape[1]
        attn = tnn.paged_decode_attention(q, pools_kv["k"], pools_kv["v"],
                                          block_tables, lengths)
        attn = ops.reshape(ops.transpose(attn, (0, 2, 1, 3)),
                           (B, T, cfg.n_heads * cfg.head_dim))
        h = ops.add(h, ops.linear(attn, layer["wo"]))
        from thunder_tpu.models.llama import _mlp

        return _mlp(h, layer, cfg)

    def decode(self, geoms, params, tokens, block_tables, lengths, write_pos,
               pools, temps, top_ks, top_ps, rng):
        """One continuous-batching decode step for every slot.

        tokens (S, 1) int32; block_tables (S, npg) int32; lengths (S,) int32
        context length INCLUDING this token; write_pos (S,) int32 flat pool
        position of this token's K/V row (the scratch position 0 for replay
        rows, whose K/V already exists). Sampling inputs: temps (S,) f32,
        top_ks (S,) int32, top_ps (S,) f32, rng (S, 2) uint32 raw threefry
        keys. Returns (sampled token ids (S,) int32, logits (S, V), pools)
        — the logits output exists for parity tests and future logprob
        surfacing; the scheduler fetches only the token ids."""
        cfg = self.cfg
        (g,), (block_tables,), (write_pos,) = geoms, block_tables, write_pos
        h = ops.embedding(tokens, params["tok_embedding"])             # (S,1,D)
        cos, sin = _rope_tables_at(cfg, ops.sub(lengths, 1), h.dtype)
        new_pools = []
        flat = (g.kv_heads, g.num_pages * g.page_size, g.head_dim)
        paged = (g.kv_heads, g.num_pages, g.page_size, g.head_dim)
        for layer, kv in zip(params["layers"], pools):
            x = ops.rms_norm(h, layer["attn_norm"], eps=cfg.norm_eps)
            q, k, v = self._qkv(x, layer, cos, sin)
            kp = write_rows(ops.reshape(kv["k"], flat), k, write_pos)
            vp = write_rows(ops.reshape(kv["v"], flat), v, write_pos)
            kv = {"k": ops.reshape(kp, paged), "v": ops.reshape(vp, paged)}
            new_pools.append(kv)
            h = self._attn_block(h, layer, q, block_tables, lengths, kv)
        h = ops.rms_norm(h, params["norm_f"], eps=cfg.norm_eps)
        logits = ops.squeeze(ops.linear(h, params["lm_head"]), 1)      # (S,V)
        # in-graph sampling epilogue: one more fused tail on the program we
        # already dispatch once per token (greedy == temperature 0)
        toks = sample_tokens(logits, temps, top_ks, top_ps, rng)
        return toks, logits, new_pools

    def _qkv(self, x, layer, cos, sin):
        """RoPE'd q/k/v heads (decode layout: T == x.shape[1])."""
        from thunder_tpu.models.llama import _apply_rope

        cfg = self.cfg
        B, T = x.shape[0], x.shape[1]
        hd = cfg.head_dim
        q = ops.transpose(ops.reshape(ops.linear(x, layer["wq"]),
                                      (B, T, cfg.n_heads, hd)), (0, 2, 1, 3))
        k = ops.transpose(ops.reshape(ops.linear(x, layer["wk"]),
                                      (B, T, cfg.kv_heads, hd)), (0, 2, 1, 3))
        v = ops.transpose(ops.reshape(ops.linear(x, layer["wv"]),
                                      (B, T, cfg.kv_heads, hd)), (0, 2, 1, 3))
        return _apply_rope(q, cos, sin), _apply_rope(k, cos, sin), v

    def prefill(self, geoms, params, tokens, block_tables, lengths,
                page_writes, pools):
        """One prefill chunk of one request — K/V writes only, no logits.

        tokens (1, C) int32 (C from the bucket ladder, multiple of the page
        size; padded past the prompt tail); block_tables (1, npg); lengths
        (1,) int32 = chunk_start + C (context including the padded chunk);
        page_writes (C//ps,) int32 flat positions of the chunk's pages.
        Returns the updated pools. The first token is sampled by a decode
        REPLAY row of the same iteration's decode step, dispatched behind
        the final chunk, so prefill carries no lm_head work at all (the
        old last-row logits slice is gone with its host argmax)."""
        cfg = self.cfg
        (g,), (block_tables,), (page_writes,) = geoms, block_tables, page_writes
        C = tokens.shape[1]
        from thunder_tpu.models.llama import _project_qkv, _rope_cos_sin

        h = ops.embedding(tokens, params["tok_embedding"])             # (1,C,D)
        pos0 = ops.sub(ops.getitem(lengths, 0), C)
        cos, sin = _rope_cos_sin(cfg, C, h.dtype, pos_offset=pos0)
        new_pools = []
        flat = (g.kv_heads, g.num_pages * g.page_size, g.head_dim)
        paged = (g.kv_heads, g.num_pages, g.page_size, g.head_dim)
        for layer, kv in zip(params["layers"], pools):
            x = ops.rms_norm(h, layer["attn_norm"], eps=cfg.norm_eps)
            q, k, v = _project_qkv(x, layer, cfg, cos, sin)
            kp = write_pages(ops.reshape(kv["k"], flat), ops.squeeze(k, 0),
                             page_writes, g.page_size)
            vp = write_pages(ops.reshape(kv["v"], flat), ops.squeeze(v, 0),
                             page_writes, g.page_size)
            kv = {"k": ops.reshape(kp, paged), "v": ops.reshape(vp, paged)}
            new_pools.append(kv)
            h = self._attn_block(h, layer, q, block_tables, lengths, kv)
        return new_pools


def describe(model, n_layers: int | None = None) -> ModelDescription:
    """The description of ``model``: itself when it is one, its own
    (``model.serving_description``) when its config carries one, else the
    Llama family's."""
    if isinstance(model, ModelDescription):
        return model
    own = getattr(model, "serving_description", None)
    if own is not None:
        return own(n_layers=n_layers)
    return LlamaDescription(model, n_layers)
