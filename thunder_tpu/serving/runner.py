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

from thunder_tpu.serving.description import ModelDescription, describe


class PagedRunner:
    """Builds + owns the compiled paged step functions for one engine. The
    traced bodies are the model description's (``serving/description.py``);
    the runner compiles them, binds the decode step and publishes its
    fusion shape."""

    def __init__(self, model, geometry, *, n_layers: int | None = None,
                 executors=None, block_fusion=None,
                 launch_budget_per_layer: float | None = None, mesh=None,
                 engine_id: str | None = None):
        import thunder_tpu as tt
        from thunder_tpu.observe import registry as _observe

        # ``model``: a description, or a config ``describe`` knows;
        # ``geometry``: one PageGeometry a cache kind (a bare one for a
        # one-kind model)
        self.desc: ModelDescription = describe(model, n_layers)
        self.cfg = self.desc.cfg
        self.geoms = tuple(geometry) if isinstance(geometry, (tuple, list)) \
            else (geometry,)
        self.geom = self.geoms[0]
        self.mesh = mesh  # distributed.gspmd.TensorParallelMesh or None
        # owning engine's label: the runner's gauge/event emissions (decode
        # bind shape) must land in that engine's series, not a shared one
        self.engine_id = engine_id
        self.obs = (_observe.labeled(engine=engine_id)
                    if engine_id is not None else None)
        self.n_layers = self.desc.n_layers
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

    # -- traced bodies: the description's, with every per-kind argument as a
    # tuple in ``cache_kinds`` order -----------------------------------------
    def _decode_fn(self, params, tokens, block_tables, lengths, write_pos,
                   pools, temps, top_ks, top_ps, rng):
        return self.desc.decode(self.geoms, params, tokens,
                                _per_kind(block_tables), lengths,
                                _per_kind(write_pos), pools, temps, top_ks,
                                top_ps, rng)

    def _prefill_fn(self, params, tokens, block_tables, lengths, page_writes,
                    pools):
        return self.desc.prefill(self.geoms, params, tokens,
                                 _per_kind(block_tables), lengths,
                                 _per_kind(page_writes), pools)

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


def _per_kind(arg) -> tuple:
    return tuple(arg) if isinstance(arg, (tuple, list)) else (arg,)


PagedLlamaRunner = PagedRunner      # the name the first description shipped under
