"""Paged model runner: the compiled prefill/decode step functions the
serving engine dispatches.

Two traced programs per engine, both shape-stable for the life of the
process:

- ``decode``: ONE batched step over every slot — ``(S, 1)`` tokens against
  the shared page pools, ragged per-slot context lengths handled in-graph
  by ``nn.paged_decode_attention`` (claimed by the Pallas scalar-prefetch
  kernel on TPU; XLA decomposition otherwise), and SAMPLING fused in-graph
  as the epilogue: per-slot sampling-parameter rows + raw threefry keys
  ride in as plain arrays and the program returns sampled TOKEN IDS
  (:func:`~thunder_tpu.serving.sampling.sample_tokens`; greedy is the
  ``temperature == 0`` degenerate case, bit-identical to the host argmax
  it replaced). The scheduler reads tokens, not logits — the prerequisite
  for a fully device-side token loop. Dispatched through ``bind()`` — the
  serving fast path pays zero guard cost per step.
- ``prefill``: one CHUNK of one request's prompt — ``(1, C)`` tokens with
  ``C`` drawn from a ``LengthBucketer`` ladder (multiples of the page
  size), writing the chunk's K/V into the request's pages and attending
  the paged context so far. Ragged prompt lengths compile at most
  ``len(ladder)`` prefill programs, ever. Prefill emits NO logits at all:
  every request's first token comes from a decode REPLAY row (the
  scheduler re-feeds the last prompt token with the write redirected to
  the scratch page, in the decode step that follows the last chunk within
  the same engine iteration), so the lm_head matmul leaves the prefill program
  entirely and the first token is sampled on the exact same program path
  as every later one — which is what makes best-of-N forks and
  recompute-on-resume token-streams line up with the unforked path.

K/V writes address the pools through host-computed flat positions
(``page_id * page_size + offset``) — the host owns the block tables, so the
traced program never does page arithmetic; it just ``dynamic_update_slice``s
at traced scalar positions, which keeps one compiled decode program valid
for every allocation pattern.

Crash-recovery note: both programs take the page pools as DONATED
arguments, so a dispatch that fails mid-execution may leave them consumed
(deleted buffers). The runner's compiled cache entries are keyed on shapes
only and survive a supervisor restart unchanged — rebuilding after an
``EngineFault`` means fresh pools (same shapes) plus a re-``bind_decode``;
no recompilation. The *binding* is engine-owned state (the scheduler drops
and re-creates it), never stored here.
"""

from __future__ import annotations

from thunder_tpu.core import dtypes, prims
from thunder_tpu import ops
from thunder_tpu.ops import nn as tnn
from thunder_tpu.serving.sampling import sample_tokens


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


def _write_rows(pool, rows, flat_positions):
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
    this runner traces."""
    return tnn.decode_row_write(pool, rows, flat_positions)


def _write_pages(pool, rows, page_positions, ps: int):
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


class PagedLlamaRunner:
    """Builds + owns the compiled paged step functions for one engine."""

    def __init__(self, cfg, geometry, *, n_layers: int | None = None,
                 executors=None, block_fusion=None,
                 launch_budget_per_layer: float | None = None, mesh=None,
                 engine_id: str | None = None):
        import thunder_tpu as tt
        from thunder_tpu.observe import registry as _observe

        self.cfg = cfg
        self.geom = geometry
        self.mesh = mesh  # distributed.gspmd.TensorParallelMesh or None
        # owning engine's label: the runner's gauge/event emissions (decode
        # bind shape) must land in that engine's series, not a shared one
        self.engine_id = engine_id
        self.obs = (_observe.labeled(engine=engine_id)
                    if engine_id is not None else None)
        self.n_layers = n_layers if n_layers is not None else cfg.n_layers
        # decode-launch budget: when set (via census_context below), a
        # decode program dispatching more Pallas launches per layer per
        # token than the budget yields a typed `decode-launch-growth`
        # pessimization finding whenever its census is evaluated
        # (observe.census) — a megakernel falling back to its
        # decomposition becomes a finding, not just a throughput regression
        self.launch_budget_per_layer = launch_budget_per_layer
        # block planner passthrough: unset lets the decode cost model decide
        # (at T==1 serving shapes the launch-amortization objective plans the
        # whole-decode-layer megakernel whenever an executor claims it);
        # True/False force/disable — tests and A/Bs use both
        opts = {} if block_fusion is None else {"block_fusion": block_fusion}
        # tensor-parallel mesh: the step inputs (params, pools) arrive
        # COMMITTED to NamedShardings, so the whole-program jit compiles one
        # SPMD program around them. Pallas launches cannot auto-partition
        # under GSPMD, so the planner caps block fusion ONE rung below the
        # whole-decode-layer megakernel (attention/MLP sub-blocks still
        # plan) — never silently down to per-op XLA
        if mesh is not None and getattr(mesh, "tp", 1) > 1:
            opts["decode_tp_shards"] = int(mesh.tp)
        # one jitted fn each; distinct chunk shapes become distinct cache
        # entries inside the ThunderTPUFunction (bounded by the ladder)
        self.decode_jit = tt.jit(self._decode_fn, executors=executors,
                                 fn_name="serving_decode", donate_argnums=(5,),
                                 **opts)
        self.prefill_jit = tt.jit(self._prefill_fn, executors=executors,
                                  fn_name="serving_prefill", donate_argnums=(5,),
                                  **opts)
        # census context: lets observe.census derive launches-per-layer and
        # re-evaluate the decode-launch-growth finding whenever the decode
        # program's census is taken (explain(), postmortems), not only at
        # the bind-time publication below
        self.decode_jit._stats.census_context = {
            "decode_layers": self.n_layers,
            "decode_launches_per_layer_max": launch_budget_per_layer,
        }
        if mesh is not None and getattr(mesh, "tp", 1) > 1:
            from thunder_tpu.distributed.gspmd import mesh_descriptor

            md = mesh_descriptor(mesh)
            self.decode_jit._stats.census_context.update(md)
            self.prefill_jit._stats.census_context = dict(md)

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

    def _decode_fn(self, params, tokens, block_tables, lengths, write_pos,
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
        g = self.geom
        h = ops.embedding(tokens, params["tok_embedding"])             # (S,1,D)
        cos, sin = _rope_tables_at(cfg, ops.sub(lengths, 1), h.dtype)
        new_pools = []
        flat = (g.kv_heads, g.num_pages * g.page_size, g.head_dim)
        paged = (g.kv_heads, g.num_pages, g.page_size, g.head_dim)
        for layer, kv in zip(params["layers"], pools):
            x = ops.rms_norm(h, layer["attn_norm"], eps=cfg.norm_eps)
            q, k, v = self._qkv(x, layer, cos, sin)
            kp = _write_rows(ops.reshape(kv["k"], flat), k, write_pos)
            vp = _write_rows(ops.reshape(kv["v"], flat), v, write_pos)
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

    def _prefill_fn(self, params, tokens, block_tables, lengths, page_writes,
                    pools):
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
        g = self.geom
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
            kp = _write_pages(ops.reshape(kv["k"], flat), ops.squeeze(k, 0),
                              page_writes, g.page_size)
            vp = _write_pages(ops.reshape(kv["v"], flat), ops.squeeze(v, 0),
                              page_writes, g.page_size)
            kv = {"k": ops.reshape(kp, paged), "v": ops.reshape(vp, paged)}
            new_pools.append(kv)
            h = self._attn_block(h, layer, q, block_tables, lengths, kv)
        return new_pools

    # -- dispatch -----------------------------------------------------------
    def bind_decode(self, *args):
        """Compile the decode step for these inputs and bind it (zero-guard
        dispatch). The scheduler owns the bound callable and re-binds when
        the quarantine epoch moves (a containment event recompiled under a
        new cache entry; the stale binding would re-contain every call).
        Each (re)bind republishes the decode program's fusion shape to the
        observe registry, so a fallback to the unfused decode layer is
        visible as a launch-count move rather than only as a throughput
        regression."""
        bound = self.decode_jit.bind(*args)
        self._publish_decode_fusion_shape()
        return bound

    def _publish_decode_fusion_shape(self) -> None:
        """Gauges describing the compiled decode step's per-token launch
        shape, fed from the SAME census walk the per-compile observe
        surface uses (``observe.census.trace_census`` — one owner, so the
        serving gauges and ``CompileStats.last_census`` can never disagree):
        how many Pallas launches one decode step dispatches, and how many
        of them are whole-decode-layer megakernels. The fusion-shape
        acceptance test reads launches-per-layer from them."""
        import thunder_tpu as tt
        from thunder_tpu.observe import census as _census
        from thunder_tpu.observe import registry as _observe

        try:
            trc = tt.last_execution_trace(self.decode_jit)
        except Exception:
            return
        if trc is None:
            return
        tc = _census.trace_census(trc)
        launches = tc["pallas_launches"]
        layers = tc["decode_layer_fusions"]
        rec = self.obs if self.obs is not None else _observe
        rec.set_gauge("serving.decode_pallas_launches", launches)
        rec.set_gauge("serving.decode_layer_fusions", layers)
        # launch-budget enforcement lives in the census (the census_context
        # stashed at construction): the decode-launch-growth finding is
        # derived — ONCE — whenever the decode program's census is
        # evaluated (explain, postmortems, budget tests), while the
        # serving_decode_bind event below already lands the launch shape
        # in the flight ring at bind time. Recording the finding here too
        # would double-count compile.pessimizations for one condition.
        # lifecycle edge for the flight ring: WHICH program shape is now
        # serving (a postmortem wants to know if the megakernel or a
        # fallback rung was bound when the fault hit)
        rec.event("serving_decode_bind", launches=launches,
                  decode_layer_fusions=layers)
