"""Continuous (in-flight) batching scheduler over the paged KV cache.

The serving runtime ROADMAP item 1 calls for: many concurrent streams at
different sequence lengths served by ONE compiled decode program.

- **Admission**: queued requests join a free decode slot when the page pool
  can cover their first prefill chunk; otherwise the queue back-pressures
  (nothing crashes — pages are the capacity unit). Admission is
  priority-ordered (highest first, FIFO among equals), the queue is
  optionally bounded (``max_queue``: overflow sheds the lowest-priority
  queued request or rejects the newcomer, typed
  :class:`~thunder_tpu.serving.errors.AdmissionRejected`), and a request
  whose page demand exceeds the TOTAL pool fails at ``submit()`` with
  :class:`~thunder_tpu.serving.errors.InfeasibleRequest` instead of
  queueing forever.
- **Request SLOs**: ``submit(deadline_s=, priority=)``. Every engine
  iteration sheds expired queued requests and evicts expired residents
  with :class:`~thunder_tpu.serving.errors.DeadlineExceeded`
  (``serving.deadline_misses``); shedding of any kind counts
  ``serving.shed_requests`` and the rolling on-time completion ratio is
  the ``serving.slo_attainment`` gauge.
- **Prefill, then decode, with chunked prefill interleaving**: every engine
  iteration admits, then runs at most ONE prefill chunk of the head-of-line
  prefilling request while the decode batch is well filled (a burst while
  it is thin), then one batched decode step over all resident requests —
  the ones whose prompt just became resident included, so a request's
  first token comes from the decode step of the iteration that finished
  its prefill. Long prompts cannot stall in-flight decodes for more than a
  chunk, and residents keep their rank when pages are short: a decoding
  request's next page is taken before a newcomer's chunk allocates.
- **Continuous batching**: requests join and leave the decode batch
  mid-flight. Completion (or EOS) frees the request's pages immediately;
  the slot admits the next queued request on the same compiled program.
- **Preemption**: when the pool runs dry mid-decode, the lowest-priority
  newest resident request is evicted back to the queue (recompute-on-
  resume: its generated tokens re-prefill as prompt) —
  ``serving.preempted_requests`` counts these.
- **Dispatch**: the decode step is bound (``bind()``, zero-guard) and runs
  under the ``step`` + ``serving:decode`` fault domains with retry (prefill
  under ``serving:prefill``) — a transient injected or XLA fault re-runs
  the same step; kernel crashes still take the normal quarantine path
  inside the bound call. A failure that CONSUMED the donated page pools
  mid-execution (the ``serving:engine`` domain simulates this) escalates
  as :class:`~thunder_tpu.serving.errors.EngineFault`: in-place retry is
  impossible, and the :class:`~thunder_tpu.serving.supervisor
  .EngineSupervisor` restart — pool rebuild + re-prefill of every
  in-flight request via :meth:`ServingEngine.rebuild_after_fault` — is the
  engine-level fallback rung.

- **Always-on lifecycle tracing**: every request's phase chain (submitted
  → queued → admitted → prefill chunk(s) → decode residency → preempt /
  restart re-prefill → complete/shed) is recorded as spans + events
  through ``observe.registry`` — which feeds the bounded flight ring
  (``observe.flight``) even when the registry is disabled, so a fault
  leaves a black box. Each iteration also records scheduler spans
  (``schedule`` host work vs ``decode_dispatch``); the Perfetto exporter
  renders per-request tracks, a scheduler track, and counter tracks.
- **Spans** (every record carries ``id`` and ``parent``; spans of one
  iteration carry ``step``, spans of one request ``request``). With the
  registry enabled, a busy iteration is one tree whose leaves do not
  overlap and together cover it::

      engine_step                 root; args step, decoding (the rows the
                                  decode step ran), queued
        schedule                  deadlines, forks, admission, the decoding
                                  requests' next pages
        prefill_build             one chunk's arrays
        prefill_chunk             the chunk's dispatch (the device runs it
                                  behind the call: the ``decode_wait`` of
                                  the same iteration holds that time)
        prefill_deliver           the request's progress, its move to decode
                                  once resident, the admission that may free
        decode_build              the batch arrays
        decode_dispatch           ``live_pages`` of ``window_pages``: what
                                  the decode attention walks of the tables
                                  (``state_rows``: the rows whose state it
                                  reads and writes, every slot's, on a
                                  model with a state kind)
          decode_enqueue          the call into the bound program until it
                                  returns (``step:serving_decode`` inside)
          decode_wait             the device wait and the token fetch
            decode_ready          until the step's token ids are ready
            decode_fetch          until the ids (and, while the registry is
                                  on, the step's aux) are on the host
        decode_deliver            tokens to requests, finishes, page release

  ``schedule``, ``decode_dispatch`` and ``prefill_chunk`` feed the flight
  ring as well; the others exist only while the registry is enabled and
  cost nothing otherwise. A request's road to its first token reads from
  records that all close by then: ``queue`` = submit → the last admission
  before the token (every ``queued`` span adds up, and so does a residency
  that a preemption or a restart threw away: it gave the request nothing);
  ``prefill_wait`` = that admission → the start of its first
  ``prefill_chunk``; ``prefill`` = that start → the prompt resident
  (``resident_us`` of the ``serving_first_token`` event; a forked clone is
  resident when it forks); ``first_decode`` = resident → the event. The
  four add up to ``serving.ttft_ms``. The event's ``steps`` counts the
  iterations from that admission to the token: 1 when the prompt became
  resident in the iteration that admitted it (a burst under a thin batch,
  or a one-chunk prompt), one more for every further chunk under a
  well-filled batch.

- **In-graph sampling**: every request carries
  :class:`~thunder_tpu.serving.sampling.SamplingParams`; the compiled
  decode step samples temperature/top-k/top-p tokens IN-GRAPH (per-slot
  parameter rows + threefry keys, sort-free threshold masking, Gumbel-max
  draw) and the scheduler reads token ids, never logits. Greedy is the
  ``temperature == 0`` degenerate case of the same program — bit-identical
  to the host argmax it replaced, so token-identity-vs-``generate()`` pins
  hold. Every request's FIRST token comes from a decode *replay* row (the
  last prompt token re-fed with its K/V write redirected to the scratch
  page) of the decode step that follows its last chunk in the same
  iteration, so prefill carries no lm_head at all and first tokens ride
  the batched decode program like every other token.
- **Best-of-N via copy-on-write forks**: ``submit(best_of=N)`` prefills
  ONCE; when the primary's prompt is resident, N-1 clones fork its block
  table — full pages shared by refcount, only the partial tail page
  copied — and branch with independent RNG streams
  (``SamplingParams.fork``). A clone that can't fork yet (no free slot /
  no tail page) waits on the primary and spills to the ordinary queue if
  the primary terminates first.
- **Cross-request prefix cache** (``prefix_cache=True``): admission probes
  a page-granularity token trie
  (:class:`~thunder_tpu.serving.prefix_cache.PrefixCache`) with the
  prompt, prefill starts at the first uncached page, and completed
  requests donate their full prompt pages back. Cached pages are parked
  at refcount 0 — evicted oldest-first by the allocator under page
  pressure, so the cache can never starve live traffic. A warm hit
  collapses TTFT to one tail-chunk prefill
  (``serving.prefix_hit_rate`` / ``serving.cached_pages``).
- **Cache kinds**: the model's description (``serving/description.py``)
  names every layer's kind, and each distinct kind has its own pool,
  allocator and block table. A ``full`` kind's table spans the context; a
  ``window(W)`` kind's is a RING of ``ceil(W / page) + 1`` columns (logical
  page ``p`` in column ``p mod width``): ``schedule`` returns the page that
  fell wholly out of a request's window to the free list before the next
  one is taken (``kv.window_pages_recycled``; ``decode_dispatch`` then
  carries ``live_pages_full`` / ``live_pages_window``), and a prefill chunk
  gathers the ring BEFORE it writes, so its own pages take recycled
  columns. Admission, preemption and ``assert_quiescent`` count every
  kind; best-of forks and the prefix cache are refused
  (``InfeasibleRequest``) on a model with a window kind.
- **State kind**: a layer whose kind is ``state`` (a linear-attention
  layer's recurrent state) keeps one row a slot
  (:class:`~thunder_tpu.serving.kv_cache.SlotStateCache`) and no pages.
  A request's first prefill chunk starts its row from zero (``carried`` 0
  in the chunk's state control, counted in ``kv.state_resets``; the span
  ``prefill_chunk`` carries ``state_in``), each later chunk from what the
  one before left, and decode from the last chunk's. Decode takes the
  token into the rows that decode (``decode_dispatch`` carries
  ``state_rows``); a slot's replay row reads its state and leaves it. A
  freed slot needs no host work, and a preempted request re-prefills from
  zero. A snapshot of a state at a page boundary does not exist, so the
  prefix cache and best-of forks are refused on such a model too.
"""

from __future__ import annotations

import itertools
import time
from collections import deque
from dataclasses import dataclass, field

import jax
import numpy as np

from thunder_tpu.observe import registry as _observe
from thunder_tpu.runtime import faults as _faults
from thunder_tpu.runtime import quarantine as _quarantine
from thunder_tpu.runtime import retry as _retry
from thunder_tpu.serving.errors import (
    AdmissionRejected,
    DeadlineExceeded,
    EngineFault,
    EngineStallError,
    InfeasibleRequest,
    RestartState,
    ShardingGeometryError,
)
from thunder_tpu.serving.description import describe
from thunder_tpu.serving.kv_cache import (OutOfPages, PagedKVCache,
                                          PageGeometry, SlotStateCache,
                                          StateGeometry)
from thunder_tpu.serving.prefix_cache import PrefixCache
from thunder_tpu.serving.runner import PagedRunner
from thunder_tpu.serving.sampling import GREEDY, SamplingParams

QUEUED, PREFILL, DECODE, DONE, SHED = \
    "queued", "prefill", "decode", "done", "shed"

# request ids are PROCESS-unique (not per-engine): the flight recorder and
# the Perfetto per-request tracks key on the id, and a bench that builds a
# warm engine and a timed engine must not interleave two "request 0"s on
# one timeline
_REQUEST_IDS = itertools.count()

# process-unique engine ids ("e0", "e1", ...): the label value that keys
# every engine's metrics/events/spans so N engines in one process never
# clobber each other's series (the fleet-observatory contract)
_ENGINE_IDS = itertools.count()


@dataclass(eq=False)  # identity semantics: requests live in slot lists
class Request:
    """One generation request and its full lifecycle state."""

    prompt: np.ndarray                  # original prompt token ids (1-D int32)
    max_new_tokens: int
    request_id: int
    eos_id: int | None = None
    priority: int = 0                   # higher = more important (shed last)
    deadline_at: float | None = None    # absolute perf_counter deadline
    submitted_s: float = 0.0
    state: str = QUEUED
    error: BaseException | None = None  # set when state == SHED
    # allocated page ids a cache kind, in logical-page order; kind k's list
    # holds logical pages ``page_base[k] ..`` (a window kind's ring drops
    # the pages its window has left; a full kind's base stays 0)
    kind_pages: list = field(default_factory=lambda: [[]])
    page_base: list = field(default_factory=lambda: [0])
    prefilled: int = 0                  # work-prompt tokens written so far
    length: int = 0                     # context tokens written into the cache
    next_token: int | None = None       # sampled, not yet fed to decode
    generated: list = field(default_factory=list)
    ttft_s: float | None = None
    finished_s: float | None = None
    decode_start_s: float | None = None
    preemptions: int = 0
    restarts: int = 0                   # supervisor crash-recovery re-admits
    admit_seq: int = -1                 # admission order (preemption victim pick)
    admit_step: int = 0                 # engine iteration of the last admission
    pages_version: int = 0              # bumped when ``pages`` changes
    # in-graph sampling: per-request params + derived uint32 stream seed
    sampling: SamplingParams = GREEDY
    stream_seed: int = 0
    _replay: bool = False               # its next decode row re-feeds the last
    #                                     prompt token (write -> scratch) to
    #                                     sample the FIRST token in-graph
    # best-of-N copy-on-write forks
    fork_parent: "Request | None" = None
    fork_pending: list = field(default_factory=list)  # clones awaiting fork
    fork_group: list = field(default_factory=list)    # primary + clones
    # cross-request prefix cache
    prefix_hit_tokens: int = 0          # prompt tokens served from the trie
    # lifecycle tracing (flight recorder + Perfetto request tracks)
    submitted_us: float = 0.0           # observe-epoch submit timestamp
    queued_ms: float = 0.0              # total time spent queued (incl. resumes)
    prefill_chunks: int = 0             # prefill dispatches (incl. re-prefill)
    _phase: str = ""                    # open lifecycle phase span, if any
    _phase_t0_us: float = 0.0

    @property
    def pages(self) -> list:
        """The first cache kind's pages (the only kind of a one-kind
        model)."""
        return self.kind_pages[0]

    @pages.setter
    def pages(self, value: list) -> None:
        self.kind_pages[0] = value

    def drop_pages(self, n_kinds: int) -> None:
        """Forget every page (they were freed, or died with their pool)."""
        self.kind_pages = [[] for _ in range(n_kinds)]
        self.page_base = [0] * n_kinds
        self.pages_version += 1

    @property
    def work_prompt(self) -> np.ndarray:
        """What prefill must write: the original prompt plus any tokens
        generated before a preemption or engine restart
        (recompute-on-resume)."""
        if not self.generated:
            return self.prompt
        return np.concatenate(
            [self.prompt, np.asarray(self.generated, np.int32)])

    @property
    def done(self) -> bool:
        return self.state == DONE

    @property
    def failed(self) -> bool:
        """True when the engine shed this request (``error`` says why:
        ``DeadlineExceeded`` or ``AdmissionRejected``)."""
        return self.state == SHED

    def output(self) -> np.ndarray:
        return np.asarray(self.generated, np.int32)


class ServingEngine:
    """Continuous-batching serving runtime. ``cfg`` names the model: its
    description (``serving/description.py::describe``) brings the per-layer
    cache kinds and the two step functions; a config without one of its own
    is a Llama-family config.

    >>> eng = ServingEngine(params, cfg, max_slots=8, page_size=16,
    ...                     max_context=256, n_layers=2)
    >>> r = eng.submit([1, 2, 3], max_new_tokens=16, deadline_s=30.0)
    >>> eng.drain()
    >>> r.output()

    ``max_slots`` is the compiled decode batch width; ``num_pages`` sizes
    the shared pool (default: full residency for every slot — shrink it to
    exercise admission back-pressure and preemption); ``max_queue`` bounds
    the admission queue (``None`` = unbounded; overflow sheds by priority).
    """

    def __init__(self, params, cfg, *, max_slots: int = 8, page_size: int = 16,
                 num_pages: int | None = None, max_context: int | None = None,
                 prefill_chunk: int | None = None, n_layers: int | None = None,
                 max_queue: int | None = None, executors=None,
                 retry_policy=None, block_fusion=None,
                 prefix_cache: bool = False,
                 launch_budget_per_layer: float | None = None,
                 mesh=None, engine_id: str | None = None):
        # tensor-parallel serving (GSPMD): `mesh` is an int tp degree or a
        # distributed.gspmd.TensorParallelMesh. Params are committed to the
        # Megatron column/row plan, the paged pool is sharded by kv-head,
        # and the runner's jitted step compiles ONE SPMD program around the
        # committed shardings (donation preserved — in/out pool shardings
        # match). Step inputs stay host arrays (replicated).
        # engine identity first: every emission below this line is labeled
        self.engine_id = engine_id if engine_id is not None \
            else f"e{next(_ENGINE_IDS)}"
        self.obs = _observe.labeled(engine=self.engine_id)
        self.desc = describe(cfg, n_layers)
        self.mesh = self.desc.tp_mesh(mesh)
        if self.mesh is not None:
            from thunder_tpu.distributed.gspmd import shard_params

            params = shard_params(params, self.mesh)
        self.params = params
        self.cfg = cfg
        desc = self.desc
        max_context = int(max_context or desc.max_seq_len)
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        # prefill chunk ladder: powers-of-two multiples of the page size —
        # chunk starts stay page-aligned by construction, and ragged prompt
        # lengths compile at most len(ladder) prefill programs
        cap = int(prefill_chunk or min(max_context, 512))
        cap = max(page_size, (cap // page_size) * page_size)
        ladder, b = [], page_size
        while b < cap:
            ladder.append(b)
            b *= 2
        ladder.append(cap)
        from thunder_tpu.data import LengthBucketer

        self.chunker = LengthBucketer(ladder)
        self.max_chunk = ladder[-1]
        # align the context window to the chunk ladder top so a fully
        # chunk-padded prefill can never outrun the block table
        max_context = -(-max_context // self.max_chunk) * self.max_chunk
        self.max_context = max_context
        # one pool and one block table a cache KIND (description.CacheKind):
        # a full kind's table spans the context, a window kind's is the ring
        # ``ceil(W / page) + 1``. ``num_pages`` sizes the first kind's pool,
        # or every kind's by name ({"full": n, "window": m}); default: full
        # residency for every slot (+ the reserved page 0)
        self.kinds = desc.cache_kinds
        sized = num_pages if isinstance(num_pages, dict) \
            else {self.kinds[0].name: num_pages}
        geoms = []
        for k, kind in enumerate(self.kinds):
            if not kind.paged:
                geoms.append(StateGeometry.of(desc.layer_kinds.count(k),
                                              int(max_slots),
                                              desc.state_shapes()))
                continue
            width = kind.pages_per_request(max_context, page_size)
            n = sized.get(kind.name)
            geoms.append(PageGeometry(
                n_layers=desc.layer_kinds.count(k), kv_heads=desc.kv_heads,
                head_dim=desc.head_dim, page_size=page_size,
                num_pages=int(n if n is not None else max_slots * width + 1),
                pages_per_request=width, window=kind.window))
        self.geoms = tuple(geoms)
        self.geom = geometry = geoms[0]
        self._windowed = any(kind.window is not None for kind in self.kinds)
        self._stateful = not all(kind.paged for kind in self.kinds)
        # the typed restart state: everything a supervisor rebuild needs to
        # recreate the pool EXACTLY — geometry + dtype + mesh — carried on
        # every EngineFault so recovery is sharding-identical
        self._restart_state = RestartState(
            geometry=geometry if len(geoms) == 1 else self.geoms,
            dtype=desc.dtype, mesh=self.mesh)
        self.caches = self._new_caches(desc.dtype, self.mesh)
        self.cache = self.caches[0]
        # an engine of several kinds publishes its page gauge a kind too
        self._kind_obs = [
            _observe.labeled(engine=self.engine_id, kind=kind.name)
            for kind in self.kinds] if len(geoms) > 1 else []
        # cross-request prefix cache (opt-in): completed prompts donate
        # their full pages into a token trie; admission probes it. A window
        # ring recycles the very pages a later prompt would want to reuse:
        # refused, typed, like a best-of fork of one (``submit``)
        if prefix_cache and (self._windowed or self._stateful):
            why = ("the ring recycles the pages of a prompt's head"
                   if self._windowed else "a recurrent state has no "
                   "snapshot at a page boundary to resume from")
            raise InfeasibleRequest(
                f"model {cfg.name}: prefix reuse over a "
                f"{'window ring' if self._windowed else 'state kind'} is "
                f"not supported ({why})", engine_id=self.engine_id)
        self.prefix = PrefixCache(self.cache) if prefix_cache else None
        self.runner = PagedRunner(
            desc, self.geoms, executors=executors,
            block_fusion=block_fusion,
            launch_budget_per_layer=launch_budget_per_layer, mesh=self.mesh,
            engine_id=self.engine_id)
        if self.mesh is not None:
            from thunder_tpu.distributed.gspmd import mesh_descriptor

            md = mesh_descriptor(self.mesh)
            self.obs.set_gauge("serving.tp_degree", md["tp_degree"])
            self.obs.event("serving_mesh", phase="build", **md)
        self.max_slots = int(max_slots)
        self.max_queue = max_queue
        self.slots: list[Request | None] = [None] * self.max_slots
        self.queue: deque[Request] = deque()
        self.completed: list[Request] = []
        self.shed: list[Request] = []
        self.admitting = True           # stop_admissions() flips this
        self._admits = itertools.count()
        self._step_count = 0
        self._step_args = {"step": 0}
        self._slo_attained = 0          # on-time completions
        self._slo_total = 0             # terminal requests (done + shed)
        self._slo_resets = 0            # reset_slo_window() generation
        self.decode_rebinds = 0         # quarantine-forced re-binds (health
        #                                 reads this registry-independently)
        # serving is latency-sensitive: quick retries, no long backoff
        self._retry_policy = retry_policy or _retry.RetryPolicy(
            max_attempts=3, base_delay_s=0.05, max_delay_s=1.0)
        self._decode_bound = None
        self._bound_epoch = -1
        self.last_decode_logits = None  # (S, V) device array of the last step
        # persistent decode-step input buffers: rebuilt rows only for slots
        # whose state changed (the block-table row is cached per request) —
        # per-step host work stays O(active), not O(slots * table width)
        S = self.max_slots
        self._np_tokens = np.zeros((S, 1), np.int32)
        self._np_bts = [np.zeros((S, g.pages_per_request), np.int32)
                        for g in geoms]
        self._np_bt = self._np_bts[0]
        self._np_len = np.ones(S, np.int32)
        self._np_wps = [np.zeros(S, np.int32) for _ in geoms]
        self._recycled = 0              # window pages recycled, this step
        self._bt_slot_version: list = [None] * S
        # per-slot sampling rows fed to the in-graph sampler: temperature /
        # top-k / top-p plus a raw threefry key [stream_seed, counter].
        # Idle slots are greedy rows on the zero key (their token is
        # computed and discarded)
        self._np_temp = np.zeros(S, np.float32)
        self._np_topk = np.zeros(S, np.int32)
        self._np_topp = np.ones(S, np.float32)
        self._np_rng = np.zeros((S, 2), np.uint32)

    # -- public API ---------------------------------------------------------
    def submit(self, prompt, max_new_tokens: int, *,
               eos_id: int | None = None, deadline_s: float | None = None,
               priority: int = 0, sampling: SamplingParams | None = None,
               best_of: int = 1) -> Request:
        """Enqueue a request. ``deadline_s`` is the SLO budget from now
        (expiry sheds the request with ``DeadlineExceeded``); ``priority``
        orders admission and shedding (higher survives longer).

        ``sampling`` selects the in-graph sampler's per-request config
        (default greedy). ``best_of=N`` runs N branches over ONE prefill:
        the primary prefills normally and N-1 clones fork its block table
        copy-on-write once the prompt is resident, each on an independent
        RNG stream (``sampling.fork``). Returns the primary; the whole
        group is ``request.fork_group``. Clones bypass the admission
        queue (they ride the primary) but count as ordinary requests
        everywhere else — slots, pages, SLO accounting, shedding.

        Raises ``InfeasibleRequest`` when the request could never run on
        this engine (capacity contract, checked up front — an infeasible
        prompt must not queue forever and wedge ``drain()``) and
        ``AdmissionRejected`` when admissions are stopped or the bounded
        queue sheds it."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if best_of < 1:
            raise ValueError(f"best_of must be >= 1, got {best_of}")
        sampling = GREEDY if sampling is None else sampling
        total = int(prompt.size) + int(max_new_tokens)
        if total > self.max_context:
            raise InfeasibleRequest(
                f"prompt ({prompt.size}) + max_new_tokens ({max_new_tokens}) "
                f"exceeds the engine context window ({self.max_context})",
                engine_id=self.engine_id)
        # worst-case page footprint: the larger of the final context and the
        # chunk-PADDED prefill high-water mark (the last chunk rounds up to
        # a ladder size, which can transiently need more pages than the
        # final context — e.g. a 33-token prompt prefills as one 64 chunk)
        worst = max(total, self._padded_prefill_len(total))
        for kind, g, cache in zip(self.kinds, self.geoms, self.caches):
            if not kind.paged:
                continue                    # a state row a slot: no pages
            need = min(g.pages_for(worst), g.pages_per_request)
            if need > cache.pages_total:
                raise InfeasibleRequest(
                    f"request needs up to {need} KV pages; the pool only "
                    f"has {cache.pages_total} — enlarge num_pages",
                    engine_id=self.engine_id)
        if best_of > 1 and (self._windowed or self._stateful):
            why = ("the clones' rings would recycle shared pages"
                   if self._windowed else "a recurrent state cannot be "
                   "shared copy-on-write")
            raise InfeasibleRequest(
                f"best_of={best_of}: a copy-on-write fork of a "
                f"{'window ring' if self._windowed else 'state kind'} is "
                f"not supported ({why})", engine_id=self.engine_id)
        now = time.perf_counter()

        def new_request(sp: SamplingParams, parent=None) -> Request:
            r = Request(prompt=prompt, max_new_tokens=int(max_new_tokens),
                        request_id=next(_REQUEST_IDS), eos_id=eos_id,
                        priority=int(priority),
                        deadline_at=None if deadline_s is None
                        else now + float(deadline_s),
                        submitted_s=now, submitted_us=_observe._now_us(),
                        sampling=sp, fork_parent=parent)
            r.stream_seed = sp.stream_seed(r.request_id)
            # lifecycle edge 1: always in the flight ring, registry on/off
            self.obs.event("serving_submitted", request=r.request_id,
                           prompt_tokens=int(prompt.size),
                           max_new_tokens=int(max_new_tokens),
                           priority=r.priority, deadline_s=deadline_s,
                           best_of=best_of if parent is None else None,
                           fork_of=None if parent is None
                           else parent.request_id)
            self._phase_begin(r, QUEUED)
            return r

        req = new_request(sampling)
        if best_of > 1:
            req.fork_pending = [new_request(sampling.fork(i), parent=req)
                                for i in range(1, best_of)]
            req.fork_group = [req, *req.fork_pending]
        if not self.admitting:
            err = AdmissionRejected(
                f"request {req.request_id} rejected: engine is draining, "
                f"admissions are stopped", request_id=req.request_id,
                engine_id=self.engine_id)
            self._shed(req, err)
            raise err
        if self.max_queue is not None and len(self.queue) >= self.max_queue:
            # max_queue=0 is a legal admit-or-reject config: no queued
            # victim exists, so the newcomer is always the one rejected
            victim = min(self.queue,
                         key=lambda r: (r.priority, -r.request_id)) \
                if self.queue else None
            if victim is None or victim.priority >= req.priority:
                err = AdmissionRejected(
                    f"request {req.request_id} rejected: admission queue "
                    f"full ({self.max_queue}) and every queued request has "
                    f"priority >= {req.priority}", request_id=req.request_id,
                    engine_id=self.engine_id)
                self._shed(req, err)
                raise err
            self._shed(victim, AdmissionRejected(
                f"request {victim.request_id} (priority {victim.priority}) "
                f"shed from the full admission queue for higher-priority "
                f"request {req.request_id}", request_id=victim.request_id,
                engine_id=self.engine_id))
        self.queue.append(req)
        self._gauges()
        return req

    def step(self) -> bool:
        """One engine iteration: expire deadlines, admit, prefill, one
        batched decode step. Returns whether any scheduling progress was
        made (False = idle — and ``drain()`` treats a no-progress step with
        work remaining as a stall, not as quiet completion).

        Prefill before decode, chunked prefill interleaving: a prompt that
        becomes resident in this iteration rides this iteration's decode
        step as its replay row, so its first token is there when ``step()``
        returns. With a well-filled decode batch, prefill advances ONE chunk
        per iteration (a long prompt can only add one bounded chunk of
        latency between decode steps); with a thin batch, prefill bursts so
        arriving requests reach the decode batch quickly instead of
        trickling in one chunk per decode step. Residents keep their rank
        over newcomers when pages are short: every decoding request's next
        page is taken (``_reserve_decode_pages``) before a chunk allocates."""
        self._step_count += 1
        busy = bool(self.queue) or self.active_requests > 0
        # one dict for every span of this iteration that carries the step only
        self._step_args = {"step": self._step_count}
        # the iteration as a span tree (module docstring, "Spans"): one root
        # over leaves that do not overlap and together cover it
        with self.obs.span("engine_step", "serving:sched", ring=False) as root:
            # host-scheduling part of the iteration: deadlines, forks,
            # admission, the residents' next pages
            with self.obs.span("schedule", "serving:sched",
                               self._step_args) as sched:
                worked = self._expire_deadlines()
                # pending best-of forks take slots before fresh admissions
                # (they are older traffic, and forking is cheaper than a
                # prefill)
                for r in self.slots:
                    if r is not None and r.fork_pending:
                        worked = self._materialize_forks(r) or worked
                worked = self._admit() or worked
                self._recycled = 0
                self._reserve_decode_pages()
                if self._windowed and sched.live:
                    sched.args = {**self._step_args,
                                  "window_pages_recycled": self._recycled}
                if not busy:
                    # idle polling steps stay out of the flight ring — a
                    # long idle stretch must not flush the last incident's
                    # history out of the bounded ring
                    sched.cancel()
            # the chunk budget reads how full the decode batch was BEFORE
            # this iteration's prefill added to it
            resident = sum(1 for r in self.slots
                           if r is not None and r.state == DECODE)
            budget = 1 if resident > self.max_slots // 2 else self.max_slots
            for _ in range(budget):
                if not self._prefill_one():
                    break
                worked = True
            decoding = self._decode_step()
            worked = bool(decoding) or worked
            if busy or worked:
                # gauges are unchanged on a no-op idle step, and set_gauge
                # feeds the always-on flight ring — publishing them anyway
                # would let an idle polling loop flush the last incident's
                # history out of the bounded ring (same rule as the schedule
                # span above; every real transition path publishes its own)
                self._gauges()
                if root.live:
                    root.args = {"step": self._step_count,
                                 "decoding": decoding,
                                 "queued": len(self.queue)}
            else:
                root.cancel()
        return worked

    def drain(self, max_steps: int = 1_000_000) -> list[Request]:
        """Run until every submitted request reaches a terminal state
        (completed or shed). Returns the completed requests in completion
        order. A step that makes NO progress (nothing admitted, prefilled,
        decoded, or shed) while requests remain raises
        ``EngineStallError`` naming the stuck requests — as does burning
        ``max_steps`` — instead of returning silently with work wedged."""
        for _ in range(max_steps):
            if self.idle:
                break
            if not self.step():
                raise self._stall_error("no-progress step")
        else:
            if not self.idle:
                raise self._stall_error(f"no completion in {max_steps} steps")
        return self.completed

    def stop_admissions(self) -> None:
        """Graceful-drain entry: every later ``submit()`` raises
        ``AdmissionRejected``; resident and queued requests keep running."""
        self.admitting = False

    def shed_outstanding(self, reason: str) -> list[Request]:
        """Shed every queued and resident request with ``DeadlineExceeded``
        (the graceful-drain wall-clock bound expired). Pages return to the
        free list; outputs produced so far stay readable on the request."""
        victims = list(self.queue) + [r for r in self.slots if r is not None]
        for req in victims:
            self._shed(req, DeadlineExceeded(
                f"request {req.request_id} shed: {reason}",
                request_id=req.request_id, engine_id=self.engine_id))
        return victims

    def rebuild_after_fault(self, restart_state: RestartState | None = None) \
            -> list[Request]:
        """Crash recovery (the supervisor's restart rung): discard the
        consumed device pools, build fresh ones, drop the stale decode
        binding, and re-queue every in-flight request for recompute-on-
        resume re-prefill — the same discipline as ``_preempt``, so
        surviving outputs stay token-identical to a fault-free run. The
        compiled prefill/decode programs survive (same shapes, same cache
        entries); only the pools and the binding are rebuilt.

        ``restart_state`` (the typed record the fault carried) must match
        this engine's own — the supervisor passes it back so a rebuild is
        provably SHARDING-identical, not just shape-identical; a mismatch
        is a lifecycle bug and raises ``ShardingGeometryError``."""
        if restart_state is not None \
                and restart_state != self._restart_state:
            raise ShardingGeometryError(
                "restart state mismatch: the fault's recorded pool spec "
                f"{restart_state.describe()} != this engine's "
                f"{self._restart_state.describe()}; rebuilding from it "
                "would not be sharding-identical")
        residents = sorted((r for r in self.slots if r is not None),
                           key=lambda r: r.admit_seq, reverse=True)
        for req in residents:
            self.slots[self.slots.index(req)] = None
            self._phase_end(req, reason="engine_restart")
            req.drop_pages(len(self.kinds))  # the pools they lived in are gone
            req.prefilled = 0
            req.length = 0
            req.next_token = None
            req._replay = False
            req.state = QUEUED
            req.restarts += 1
            self.queue.appendleft(req)  # reverse admit order -> FIFO resume
            self._phase_begin(req, QUEUED)
        # rebuild from the typed restart state — geometry, dtype, AND mesh —
        # so a tensor-parallel engine's fresh pools come back committed to
        # the same NamedShardings the compiled SPMD step was built around
        # (geometry alone would rebuild an unsharded pool and the next
        # dispatch would recompile or crash)
        rs = self._restart_state
        self.caches = self._new_caches(rs.dtype, rs.mesh)
        self.cache = self.caches[0]
        if self.mesh is not None:
            from thunder_tpu.distributed.gspmd import mesh_descriptor

            self.obs.event("serving_mesh", phase="rebuild",
                           **mesh_descriptor(self.mesh))
        if self.prefix is not None:
            # the trie's pages died with the consumed pools: start a fresh
            # cache attached to the rebuilt allocator (re-donation refills
            # it as recovered requests complete)
            self.prefix = PrefixCache(self.cache)
        self._decode_bound = None
        self._bound_epoch = -1
        for bt in self._np_bts:
            bt[:] = 0
        self._bt_slot_version = [None] * self.max_slots
        self._gauges()
        return residents

    def assert_quiescent(self) -> None:
        """Leak audit: the engine must be idle with every KV page back on
        the free list and every block-table row pointing only at the
        scratch page (see ``PagedKVCache.assert_quiescent``)."""
        busy = [r.request_id for r in self.slots if r is not None]
        if busy or self.queue:
            raise AssertionError(
                f"engine not idle: resident {busy}, "
                f"queued {[r.request_id for r in self.queue]}")
        for cache, bt in zip(self.caches, self._np_bts):
            cache.assert_quiescent(bt)

    def reset_slo_window(self) -> None:
        """Restart SLO-attainment accounting (benchmarks: exclude warmup)."""
        self._slo_attained = 0
        self._slo_total = 0
        self._slo_resets += 1

    @property
    def active_requests(self) -> int:
        return sum(s is not None for s in self.slots)

    @property
    def idle(self) -> bool:
        return not self.queue and not any(s is not None for s in self.slots)

    def request_state(self, req: Request) -> list:
        """The recurrent state a resident request's slot holds: one dict of
        host arrays a layer of a state kind, in layer order. Between steps
        it has taken in the prompt and every generated token but the last,
        which the next decode step feeds."""
        slot = self.slots.index(req)
        return [{name: np.asarray(a[slot]) for name, a in pool.items()}
                for cache, kind in zip(self.caches, self.kinds)
                if not kind.paged for pool in cache.pools]

    def describe_state(self) -> dict:
        """Plain-dict engine/cache state summary — what a postmortem bundle
        embeds: slot occupancy, queue, page accounting, block-table
        liveness, and the ``assert_quiescent`` findings (the finding TEXT
        when not quiescent — during a fault that is the interesting part)."""
        try:
            self.assert_quiescent()
            quiescence = "quiescent"
        except AssertionError as e:
            quiescence = str(e)
        return {
            "engine_id": self.engine_id,
            "step": self._step_count,
            "admitting": self.admitting,
            "slots": [{"slot": i, "request": r.request_id, "state": r.state,
                       "pages": len(r.pages), "prefilled": r.prefilled,
                       "length": r.length, "generated": len(r.generated),
                       "priority": r.priority, "preemptions": r.preemptions,
                       "restarts": r.restarts}
                      for i, r in enumerate(self.slots) if r is not None],
            "queued": [r.request_id for r in self.queue],
            "completed": len(self.completed),
            "shed": len(self.shed),
            "pages_free": self.cache.pages_free,
            "pages_total": self.cache.pages_total,
            "peak_pages_used": self.cache.peak_pages_used,
            "pools_alive": self._pools_alive(),
            "cache_kinds": [
                {"kind": kind.name, "window": kind.window,
                 "layers": g.n_layers,
                 **({"pages_free": c.pages_free, "pages_total": c.pages_total}
                    if kind.paged else {"state_bytes": c.nbytes})}
                for kind, g, c in zip(self.kinds, self.geoms, self.caches)],
            "cached_pages": self.cache.cached_pages,
            "cow_copies": self.cache.cow_copies,
            "prefix_hit_rate": (round(self.prefix.hit_rate(), 4)
                                if self.prefix is not None else None),
            "block_table_rows_live": int((self._np_bt != 0).any(1).sum()),
            "quiescence": quiescence,
            "slo": {"attained": self._slo_attained, "total": self._slo_total},
            "mesh": self._restart_state.describe(),
        }

    # -- scheduling internals -----------------------------------------------
    def _phase_begin(self, req: Request, phase: str) -> None:
        req._phase = phase
        req._phase_t0_us = _observe._now_us()

    def _phase_end(self, req: Request, **args) -> None:
        """Close the request's open lifecycle phase as a span on its
        Perfetto track (queued / prefill / decode; always in the flight
        ring). Queued time accumulates on the request for the timeline
        report and the bench's queue-time percentiles."""
        if not req._phase:
            return
        dur_us = _observe._now_us() - req._phase_t0_us
        if req._phase == QUEUED:
            req.queued_ms += dur_us / 1e3
        self.obs.record_span(req._phase, "serving:request", req._phase_t0_us,
                             dur_us, {"request": req.request_id, **args})
        req._phase = ""

    def _close_request_span(self, req: Request) -> None:
        """The terminal umbrella span: one bar covering submit -> terminal
        on the request's track, phases nested inside it."""
        self.obs.record_span(
            f"request {req.request_id}", "serving:request", req.submitted_us,
            _observe._now_us() - req.submitted_us,
            {"request": req.request_id, "state": req.state,
             "tokens": len(req.generated), "queued_ms": round(req.queued_ms, 3),
             "prefill_chunks": req.prefill_chunks,
             "preemptions": req.preemptions, "restarts": req.restarts})

    def _stall_error(self, why: str) -> EngineStallError:
        stuck = [(r.request_id, r.state) for r in self.queue]
        stuck += [(r.request_id, r.state)
                  for r in self.slots if r is not None]
        return EngineStallError(
            f"engine stalled ({why}) with {len(stuck)} request(s) "
            f"outstanding: {stuck} — free pages "
            f"{self.cache.pages_free}/{self.cache.pages_total}", stuck=stuck)

    def _gauges(self) -> None:
        self.obs.set_gauge("serving.queue_depth", len(self.queue))
        self.obs.set_gauge("serving.active_requests", self.active_requests)
        self.obs.set_gauge("serving.kv_pages_free", self.cache.pages_free)
        for obs, cache, kind in zip(self._kind_obs, self.caches, self.kinds):
            if kind.paged:
                obs.set_gauge("serving.kv_pages_free", cache.pages_free)
            else:
                obs.set_gauge("serving.state_bytes", cache.nbytes)
        if self.prefix is not None:
            self.obs.set_gauge("serving.cached_pages", self.cache.cached_pages)
        if self._slo_total:
            self.obs.set_gauge("serving.slo_attainment",
                               self._slo_attained / self._slo_total)

    def _expire_deadlines(self) -> bool:
        """Shed expired queued requests and evict expired residents —
        deadline-aware scheduling's enforcement point, once per step."""
        now = time.perf_counter()
        expired = [r for r in self.queue
                   if r.deadline_at is not None and now > r.deadline_at]
        expired += [r for r in self.slots
                    if r is not None and r.deadline_at is not None
                    and now > r.deadline_at]
        # pending fork clones expire too (they ride a resident primary)
        expired += [c for r in self.slots if r is not None
                    for c in r.fork_pending
                    if c.deadline_at is not None and now > c.deadline_at]
        for req in expired:
            self._shed(req, DeadlineExceeded(
                f"request {req.request_id} missed its deadline "
                f"({req.deadline_at - req.submitted_s:.3f}s) in state "
                f"{req.state}", request_id=req.request_id,
                deadline_s=req.deadline_at - req.submitted_s,
                engine_id=self.engine_id))
        return bool(expired)

    def _shed(self, req: Request, error: BaseException) -> None:
        """Terminal removal with a typed error: from the queue, from a
        slot (pages freed through the refcount path, block-table row
        zeroed), from a primary's pending-fork list, or pre-admission.
        Pending clones die with their primary (they can't fork from a
        terminal request and were never independently queued)."""
        if req.state in (DONE, SHED):   # cascades can re-reach a terminal
            return
        shed_from = req.state           # the state it was shed FROM
        if req in self.queue:
            self.queue.remove(req)
        elif req in self.slots:
            self._release_slot(req)
        elif req.fork_parent is not None and \
                req in req.fork_parent.fork_pending:
            req.fork_parent.fork_pending.remove(req)
        for clone in list(req.fork_pending):
            kind = DeadlineExceeded if isinstance(error, DeadlineExceeded) \
                else AdmissionRejected
            self._shed(clone, kind(
                f"request {clone.request_id} shed with its fork primary "
                f"{req.request_id} ({type(error).__name__})",
                request_id=clone.request_id, engine_id=self.engine_id))
        req.fork_pending = []
        self._phase_end(req, reason=type(error).__name__)
        req.state = SHED
        req.error = error
        req.finished_s = time.perf_counter()
        self._close_request_span(req)
        self.shed.append(req)
        self._slo_total += 1
        self.obs.inc("serving.shed_requests")
        if isinstance(error, DeadlineExceeded):
            self.obs.inc("serving.deadline_misses")
        self.obs.event("serving_shed", request=req.request_id,
                       priority=req.priority, state=shed_from,
                       reason=type(error).__name__,
                       generated=len(req.generated))
        self._gauges()

    def _release_slot(self, req: Request) -> None:
        """Return a resident request's pages and zero its block-table row
        (the quiescence invariant: idle rows reference only page 0)."""
        slot = self.slots.index(req)
        for kind, cache, pages in zip(self.kinds, self.caches, req.kind_pages):
            if kind.paged:
                cache.free(pages)
        req.drop_pages(len(self.kinds))
        self.slots[slot] = None
        for bt in self._np_bts:
            bt[slot] = 0
        self._bt_slot_version[slot] = None

    def _new_caches(self, dtype, mesh) -> list:
        return [PagedKVCache(g, dtype, sharding=mesh) if kind.paged
                else SlotStateCache(g)
                for kind, g in zip(self.kinds, self.geoms)]

    def _admit(self) -> bool:
        admitted = False
        while self.queue:
            slot = next((i for i, s in enumerate(self.slots) if s is None), None)
            if slot is None:
                break
            # priority-ordered admission: highest priority first, FIFO among
            # equals (all-default-priority traffic keeps the old strict FIFO)
            req = max(self.queue, key=lambda r: r.priority)
            wp = req.work_prompt
            # prefix-cache probe (sizing pass, nothing retained yet):
            # prefill starts at the first uncached page, so a hit shrinks
            # both the first chunk and the fresh-page demand
            hit = self.prefix.lookup(wp) if self.prefix is not None else []
            hit_tokens = len(hit) * self.geom.page_size
            first_chunk = self._chunk_size(len(wp) - hit_tokens)
            need_new = (hit_tokens + first_chunk) // self.geom.page_size \
                - len(hit)
            # availability check: hit pages parked at rc 0 are about to be
            # claimed, so they must not double-count as evictable headroom
            parked_hits = sum(1 for p in hit if self.cache.refcount(p) == 0)
            if self.cache.pages_free + self.cache.cached_pages \
                    - parked_hits < need_new:
                break   # page back-pressure: wait for completions/evictions
            # the other kinds' pages are taken chunk by chunk
            # (``_prefill_one``); admission only waits until each pool
            # could cover the first chunk
            first = self._chunk_pages(0, min(len(wp), first_chunk),
                                      first_chunk)
            if any(self.kinds[k].paged
                   and not self.caches[k].can_alloc(len(first[k]))
                   for k in range(1, len(self.kinds))):
                break
            try:
                _faults.maybe_fail("serving:admission", step=self._step_count)
            except _faults.InjectedFault as e:
                # contained: the request stays queued and this step's
                # admission round aborts; the next step retries it. The
                # deferral COUNTS as progress — drain() must read it as
                # "the engine deliberately waited", not as a stall (a
                # permanent admission fault still bounds out via max_steps)
                self.obs.event("serving_admission_fault", error=repr(e),
                               request=req.request_id)
                admitted = True
                break
            self.queue.remove(req)
            # commit: claim the probed chain FIRST (retained pages can't be
            # evicted out from under us by the alloc below), then the fresh
            # pages for the first uncached chunk
            chain = self.prefix.probe(wp, req.request_id, chain=hit) \
                if self.prefix is not None else []
            req.drop_pages(len(self.kinds))
            if self.kinds[0].window is None:
                req.pages = chain + self.cache.alloc(need_new)
            req.prefilled = len(chain) * self.geom.page_size
            req.prefix_hit_tokens = req.prefilled
            req.length = 0
            req.state = PREFILL
            req.admit_seq = next(self._admits)
            req.admit_step = self._step_count
            self.slots[slot] = req
            self._phase_end(req)            # close "queued"
            self.obs.event("serving_admitted", request=req.request_id,
                           slot=slot, preemptions=req.preemptions,
                           restarts=req.restarts,
                           prefix_hit_tokens=req.prefilled)
            self._phase_begin(req, PREFILL)
            admitted = True
        return admitted

    def _chunk_size(self, remaining: int) -> int:
        return self.max_chunk if remaining >= self.max_chunk \
            else self.chunker.bucket_for(remaining)

    def _padded_prefill_len(self, n: int) -> int:
        """Context length at the end of prefilling ``n`` tokens, including
        the final chunk's ladder padding."""
        full = (n // self.max_chunk) * self.max_chunk
        rem = n - full
        return full + (self.chunker.bucket_for(rem) if rem else 0)

    def _block_table(self, req: Request, k: int = 0,
                     out: np.ndarray | None = None) -> np.ndarray:
        """Kind ``k``'s table row: logical page ``p`` in column ``p`` (full)
        or ``p mod width`` (a window kind's ring); the rest point at the
        scratch page."""
        width = self.geoms[k].pages_per_request
        bt = np.zeros(width, np.int32) if out is None else out
        pages, base = req.kind_pages[k], req.page_base[k]
        bt[:] = 0
        if not self.kinds[k].paged:
            pass                            # the state kind's unused column
        elif self.kinds[k].window is None:
            bt[:len(pages)] = pages
        elif pages:
            bt[(base + np.arange(len(pages))) % width] = pages
        return bt

    def _first_kept_page(self, k: int, ln: int) -> int:
        """The lowest logical page kind ``k`` still needs for a row whose
        context, itself included, is ``ln`` tokens."""
        w = self.kinds[k].window
        return 0 if w is None else max(ln - w, 0) // self.geoms[k].page_size

    def _chunk_pages(self, pos0: int, real: int, C: int) -> list:
        """For each kind, the logical pages of chunk ``[pos0, pos0 + C)``
        (``real`` prompt tokens, the rest ladder padding) it writes. A full
        kind writes them all; a window kind only those that hold prompt
        tokens AND that the window still reaches once the chunk is in —
        the others go to the scratch page."""
        ps = self.geom.page_size
        a, b = pos0 // ps, (pos0 + C) // ps
        out = []
        for k, kind in enumerate(self.kinds):
            if not kind.paged:
                out.append(range(0))
            elif kind.window is None:
                out.append(range(a, b))
            else:
                n = pos0 + real
                out.append(range(max(a, self._first_kept_page(k, n)),
                                 min(b, -(-n // ps))))
        return out

    def _ring_drop(self, req: Request, k: int, below: int) -> None:
        """Return kind ``k``'s pages below logical page ``below`` to the
        free list: they lie wholly outside the window."""
        pages = req.kind_pages[k]
        n = min(max(below - req.page_base[k], 0), len(pages))
        if not n:
            return
        self.caches[k].free(pages[:n])
        del pages[:n]
        req.page_base[k] += n
        req.pages_version += 1
        self._recycled += n
        self.obs.inc("kv.window_pages_recycled", n)

    def _ring_extend(self, req: Request, k: int, first: int, n: int) -> bool:
        """Append logical pages ``first .. first + n - 1`` to kind ``k``'s
        list (contiguous with what it holds)."""
        pages = req.kind_pages[k]
        if not pages:
            req.page_base[k] = first
        assert req.page_base[k] + len(pages) == first, \
            (req.page_base[k], len(pages), first)
        return n == 0 or self._grow_pages(req, n, k)

    def _pools(self) -> list:
        """The pools as the step functions take them: one a layer."""
        if len(self.caches) == 1:
            return self.cache.pools
        each = [iter(c.pools) for c in self.caches]
        return [next(each[k]) for k in self.desc.layer_kinds]

    def _store_pools(self, pools) -> None:
        if len(self.caches) == 1:
            self.cache.update_pools(pools)
            return
        for k, cache in enumerate(self.caches):
            cache.update_pools([kv for kv, lk in zip(pools,
                                                     self.desc.layer_kinds)
                                if lk == k])

    def _pools_alive(self) -> bool:
        return all(c.pools_alive() for c in self.caches)

    def _per_kind(self, arrays):
        """Step-function argument of one array a kind: bare for a one-kind
        model (its programs keep the signature they always had)."""
        return arrays[0] if len(arrays) == 1 else tuple(arrays)

    def _dispatch_guarded(self, dispatch, domain: str):
        """Run a pool-donating dispatch under retry. A retryable failure
        that consumed the donated pools mid-execution escalates FATAL (a
        blind re-run would crash on deleted buffers every attempt), and any
        failure that leaves the pools dead surfaces as ``EngineFault`` —
        the supervisor's restart signal."""
        def classify(exc):
            kind = _retry.classify(exc)
            if kind == _retry.RETRYABLE and not self._pools_alive():
                return _retry.FATAL
            return kind

        try:
            return _retry.call_with_retry(dispatch, domain=domain,
                                          policy=self._retry_policy,
                                          classify_fn=classify)
        except (KeyboardInterrupt, SystemExit, GeneratorExit):
            raise
        except BaseException as e:
            if not self._pools_alive():
                raise EngineFault(
                    f"{domain} dispatch consumed the donated page pools; "
                    f"in-place retry is impossible — supervisor restart "
                    f"(pool rebuild + re-prefill) required", domain=domain,
                    restart_state=self._restart_state,
                    engine_id=self.engine_id) from e
            raise

    def _prefill_one(self) -> bool:
        """Advance the head-of-line prefilling request by ONE chunk."""
        req = min((r for r in self.slots
                   if r is not None and r.state == PREFILL),
                  key=lambda r: r.admit_seq, default=None)
        if req is None:
            return False
        # the two registry-only leaves around the chunk's dispatch share
        # their args, built only while the registry is on
        leaf_args = {"step": self._step_count, "request": req.request_id} \
            if _observe.is_enabled() else None
        with self.obs.span("prefill_build", "serving:sched", leaf_args,
                           ring=False):
            g = self.geom
            wp = req.work_prompt
            remaining = len(wp) - req.prefilled
            C = self._chunk_size(remaining)
            pos0 = req.prefilled                    # chunk/page aligned
            real = min(remaining, C)
            first_page = pos0 // g.page_size
            block_tables, page_writes = [], []
            for k, keep in enumerate(self._chunk_pages(pos0, real, C)):
                if not self.kinds[k].paged:
                    # the state control: slot, prompt tokens, carried
                    block_tables.append(np.zeros((1, 1), np.int32))
                    page_writes.append(np.asarray(
                        [self.slots.index(req), real, int(pos0 > 0)],
                        np.int32))
                    continue
                pages = req.kind_pages[k]
                ring = self.kinds[k].window is not None
                need = keep.stop - len(pages)
                if not ring and need > 0 and \
                        not self._grow_pages(req, need, k):
                    return False                    # preempted or must wait
                # a ring's table as it stands is what the chunk's gather
                # reads; the pages the window leaves behind are recycled
                # for the chunk's own (the program gathers before it writes)
                block_tables.append(self._block_table(req, k)[None])
                if ring:
                    self._ring_drop(
                        req, k, self._first_kept_page(k, pos0 + real))
                    if not self._ring_extend(req, k, keep.start, len(keep)):
                        return False
                base = req.page_base[k]
                page_writes.append(np.asarray(
                    [pages[p - base] * g.page_size if p in keep else 0
                     for p in range(first_page, first_page + C // g.page_size)],
                    np.int32))
            chunk = np.zeros((1, C), np.int32)
            chunk[0, :real] = wp[pos0:pos0 + real]
            lengths = np.asarray([pos0 + C], np.int32)
            block_table = self._per_kind(block_tables)
            page_writes = self._per_kind(page_writes)

        def dispatch():
            # the fault hook fires BEFORE the device dispatch, so a retried
            # injected fault re-runs on unconsumed inputs
            _faults.maybe_fail("serving:prefill", step=self._step_count)
            return self.runner.prefill_jit(
                self.params, chunk, block_table, lengths, page_writes,
                self._pools())

        chunk_args = {"request": req.request_id, "chunk": C, "pos0": pos0,
                      "step": self._step_count}
        if self._stateful:
            # 0: the chunk starts the slot's state from zero
            chunk_args["state_in"] = int(pos0 > 0)
            if not pos0:
                self.obs.inc("kv.state_resets")
        # the chunk's dispatch on the request's own lifecycle track (the
        # device runs the chunk behind it: this iteration's ``decode_wait``
        # holds that time); per-chunk ``serving.prefill_ms`` is this span's
        # length
        with self.obs.span("prefill_chunk", "serving:request", chunk_args,
                           histogram="serving.prefill_ms"):
            pools = self._dispatch_guarded(dispatch, "serving:prefill")
            self._store_pools(pools)
        with self.obs.span("prefill_deliver", "serving:sched", leaf_args,
                           ring=False):
            req.prefill_chunks += 1
            self.obs.event("serving_prefill_chunk", request=req.request_id,
                           chunk=C, pos0=pos0, real=real)
            req.prefilled += real
            if req.prefilled == len(wp):            # prompt fully resident
                # no logits left prefill: the FIRST token comes from this
                # iteration's batched decode step as a REPLAY — re-feed the
                # last prompt token (its K/V row already exists; the write
                # goes to the scratch page) and sample in-graph on the same
                # program path as every later token
                req.length = len(wp)
                req.next_token = int(wp[-1])
                req._replay = True
                req.state = DECODE
                self._phase_end(req)                # close "prefill"
                self._phase_begin(req, DECODE)
                if req.decode_start_s is None:      # survive preempt-resume:
                    # decode_ms stays first-token -> completion, as documented
                    req.decode_start_s = time.perf_counter()
                if req.fork_pending:
                    # the prompt is resident: best-of clones can fork it now
                    self._materialize_forks(req)
            self._admit()  # a completed prefill may free queue back-pressure
        return True

    def _grow_pages(self, req: Request, n: int, k: int = 0) -> bool:
        """Allocate ``n`` more pages of kind ``k`` for ``req``, preempting
        the lowest-priority newest resident request (possibly ``req``
        itself) while that kind's pool is dry."""
        while not self.caches[k].can_alloc(n):
            victim = min((r for r in self.slots
                          if r is not None and r.state in (DECODE, PREFILL)
                          and r is not req),
                         key=lambda r: (r.priority, -r.admit_seq),
                         default=None)
            if victim is None or victim.priority > req.priority:
                # nothing else to evict, or every other resident OUTRANKS
                # the grower ("higher survives longer" — evicting one would
                # be a priority inversion): requeue req itself and wait
                self._preempt(req)
                return False
            self._preempt(victim)
        req.kind_pages[k].extend(self.caches[k].alloc(n))
        req.pages_version += 1
        return True

    def _preempt(self, req: Request) -> None:
        """Evict a resident request back to the queue head (recompute-on-
        resume). Its pages return to the free list immediately."""
        self._release_slot(req)
        self._phase_end(req, reason="preempt")
        req.prefilled = 0
        req.length = 0
        req.next_token = None
        req._replay = False
        req.state = QUEUED
        req.preemptions += 1
        self.queue.appendleft(req)
        self._phase_begin(req, QUEUED)
        self.obs.inc("serving.preempted_requests")
        self.obs.event("serving_preempt", request=req.request_id,
                       generated=len(req.generated))

    def _reserve_decode_pages(self) -> None:
        """Page capacity for this iteration's decode step, taken BEFORE any
        prefill chunk allocates: residents outrank newcomers when pages are
        short. May preempt (the newest lowest-priority resident, a request
        admitted a moment ago included), so no chunk is computed for a
        request this iteration's decode would evict."""
        g = self.geom
        for req in list(self.slots):
            if req is None or req.state != DECODE:
                continue
            # a replay row writes nothing (scratch page): it only needs its
            # existing context pages, not the next append page yet — so a
            # prompt that turns resident in this iteration's prefill needs
            # no pass of its own
            ln = req.length if req._replay else req.length + 1
            for k in range(len(self.kinds)):
                if req.state != DECODE:
                    break                   # a grow below evicted it
                if not self.kinds[k].paged:
                    continue
                if self.kinds[k].window is not None:
                    # the page that fell wholly out of the window goes back
                    # to the free list before the next one is taken
                    self._ring_drop(req, k, self._first_kept_page(k, ln))
                have = req.page_base[k] + len(req.kind_pages[k])
                need = -(-ln // g.page_size)
                if have < need:
                    self._ring_extend(req, k, have, need - have)

    def _decode_step(self) -> int:
        """One batched decode step over every resident DECODE request, the
        ones this iteration's prefill made resident included (their pages
        were reserved by ``_reserve_decode_pages``; a resident that a
        chunk's page growth evicted since is not in the batch). Returns the
        number of rows it ran."""
        with self.obs.span("decode_build", "serving:sched", self._step_args,
                           ring=False) as build:
            g = self.geom
            active = [(i, r) for i, r in enumerate(self.slots)
                      if r is not None and r.state == DECODE]
            if not active:
                build.cancel()
                return 0
            tokens, bt = self._np_tokens, self._np_bt
            lengths, bts, wps = self._np_len, self._np_bts, self._np_wps
            temps, topk = self._np_temp, self._np_topk
            topp, rng = self._np_topp, self._np_rng
            for i in range(self.max_slots):
                r = self.slots[i]
                if r is None or r.state != DECODE:
                    # idle slots attend + scribble on the reserved page 0 only
                    # (their block-table row is zeroed when the slot is
                    # released, so the documented invariant holds exactly:
                    # idle slots never read a live request's pages); their
                    # sampling row is greedy on the zero key
                    tokens[i, 0] = 0
                    lengths[i] = 1
                    for wp in wps:
                        wp[i] = 0
                    temps[i] = 0.0
                    topk[i] = 0
                    topp[i] = 1.0
                    rng[i] = 0
                    if self._bt_slot_version[i] is not None:
                        for t in bts:
                            t[i] = 0
                        self._bt_slot_version[i] = None
            for i, r in active:
                tokens[i, 0] = r.next_token
                key = (r.request_id, r.pages_version)
                if self._bt_slot_version[i] != key:     # pages changed (rare)
                    for k, t in enumerate(bts):
                        self._block_table(r, k, out=t[i])
                    self._bt_slot_version[i] = key
                if r._replay:
                    # first-token replay: the fed token's K/V row already
                    # exists at position length-1 (prefill wrote it, or the
                    # fork copied it), so the context length is unchanged and
                    # the recomputed row is discarded on the scratch page —
                    # shared COW pages are never written
                    lengths[i] = r.length
                    for wp in wps:
                        wp[i] = 0
                else:
                    lengths[i] = r.length + 1
                    page, off = divmod(r.length, g.page_size)
                    for k, wp in enumerate(wps):
                        # a state kind's entry: 1, the row takes its token
                        wp[i] = r.kind_pages[k][page - r.page_base[k]] \
                            * g.page_size + off if self.kinds[k].paged else 1
                sp = r.sampling
                temps[i] = sp.temperature
                topk[i] = sp.top_k
                topp[i] = sp.top_p
                rng[i, 0] = r.stream_seed
                rng[i, 1] = len(r.generated)    # counter: tokens sampled so far
            # what the decode attention walks of what the block tables span
            # (an idle slot's one scratch page included: the kernel walks it)
            top = -(-lengths // g.page_size)
            walk = {"live_pages": int(top.sum()), "window_pages": bt.size}
            if self._windowed:
                # by kind: a window kind's walk starts at the first page
                # its window still reaches
                walk["live_pages_full"] = walk["live_pages_window"] = 0
                for kind in self.kinds:
                    if not kind.paged:
                        continue
                    if kind.window is None:
                        walk["live_pages_full"] += int(top.sum())
                    else:
                        low = np.maximum(lengths - kind.window, 0) \
                            // g.page_size
                        walk["live_pages_window"] += int((top - low).sum())
            if self._stateful:
                # the rows whose state the step reads and writes: every
                # slot's, idle ones too (the kernel walks the whole pool
                # and writes an idle row back as it was)
                walk["state_rows"] = self.max_slots
            bt_arg, wp_arg = self._per_kind(bts), self._per_kind(wps)

        def dispatch():
            # injected faults fire BEFORE the device dispatch, so a retried
            # transient re-runs on unconsumed inputs (`step` is the legacy
            # domain; `serving:decode` the serving-layer one)
            _faults.maybe_fail("step", step=self._step_count)
            _faults.maybe_fail("serving:decode", step=self._step_count)
            try:
                _faults.maybe_fail("serving:engine", step=self._step_count)
            except _faults.InjectedFault:
                # the engine domain simulates the REAL fatal failure mode —
                # a mid-execution accelerator fault that consumed the
                # donated page pools — so the supervisor's restart rung is
                # exercisable deterministically on CPU
                self.cache.consume_pools()
                raise
            # a quarantine containment inside a previous bound call
            # recompiled under a NEW cache entry (epoch bump); re-bind so
            # the fallback program serves — the stale bound entry would
            # re-enter containment (clear + recompile) on EVERY step
            ep = _quarantine.epoch()
            if self._decode_bound is None or self._bound_epoch != ep:
                if self._decode_bound is not None:
                    # the epoch MOVED under a live binding: a kernel was
                    # quarantined and the decode program is about to fall
                    # back (e.g. the decode-layer megakernel to its per-op
                    # form). Log it — a silent fallback would only show up
                    # as a throughput regression; the counter renders in
                    # explain()'s serving section, the event carries the
                    # epochs, and the rebind republishes the launch gauges.
                    self.decode_rebinds += 1
                    self.obs.inc("serving.decode_rebinds")
                    self.obs.event("serving_decode_rebind",
                                   old_epoch=self._bound_epoch, epoch=ep,
                                   quarantined=sorted(
                                       _quarantine.get_quarantine().ids()))
                self.obs.set_gauge("serving.quarantine_epoch", ep)
                self._decode_bound = self.runner.bind_decode(
                    self.params, tokens, bt_arg, lengths, wp_arg,
                    self._pools(), temps, topk, topp, rng)
                self._bound_epoch = ep
            return self._decode_bound(self.params, tokens, bt_arg, lengths,
                                      wp_arg, self._pools(),
                                      temps, topk, topp, rng)

        # the dispatch half of the iteration, on the scheduler track, and its
        # two parts: the call into the bound program until it returns, and
        # the wait for the device
        with self.obs.span("decode_dispatch", "serving:sched",
                           {"step": self._step_count, "batch": len(active),
                            **walk}):
            with self.obs.span("decode_enqueue", "serving:sched",
                               self._step_args, ring=False):
                tok_ids, self.last_decode_logits, pools, *aux = \
                    self._dispatch_guarded(dispatch, "serving:decode")
                self._store_pools(pools)
            # tokens were sampled IN-GRAPH; the wait is the device's part of
            # the step (``decode_ready``) and then the (S,) ids' landing on
            # the host (``decode_fetch``): device idle under the first is the
            # program's, under the second the tail after it ended. The copy
            # is asked for first, so it follows the program as a plain fetch
            # would. What the step returned beside its tokens (small arrays,
            # read only while the registry is on) rides the same fetch. The
            # (S, V) logits output stays on device, unread (the handle is
            # kept for parity checks: chip_smoke.py reads one slot's row)
            fetched = (tok_ids, aux[0] if aux and _observe.is_enabled()
                       else None)
            with self.obs.span("decode_wait", "serving:sched",
                               self._step_args, ring=False):
                for x in jax.tree_util.tree_leaves(fetched):
                    x.copy_to_host_async()
                with self.obs.span("decode_ready", "serving:sched",
                                   self._step_args, ring=False):
                    jax.block_until_ready(tok_ids)
                with self.obs.span("decode_fetch", "serving:sched",
                                   self._step_args, ring=False):
                    toks, aux = jax.device_get(fetched)
        with self.obs.span("decode_deliver", "serving:sched",
                           self._step_args, ring=False):
            if aux is not None:
                self.desc.on_decode_aux(self.obs, aux, self._step_count)
            for i, r in active:
                if r._replay:
                    r._replay = False   # context length unchanged; row existed
                else:
                    r.length += 1
                self._on_token(r, int(toks[i]))
        return len(active)

    def _on_token(self, req: Request, tok: int) -> None:
        req.generated.append(tok)
        req.next_token = tok
        if req.ttft_s is None:
            req.ttft_s = time.perf_counter() - req.submitted_s
            self.obs.observe_value("serving.ttft_ms", req.ttft_s * 1e3)
            # the open lifecycle phase is "decode", begun when the prompt
            # became resident (after the last admission): with it a reader
            # has the request's road to this token from `request` alone
            # `steps`: engine iterations from the last admission to this
            # token, 1 = the admission's own iteration (the prompt was
            # resident, and its replay row decoded, before step() returned)
            self.obs.event("serving_first_token", request=req.request_id,
                           ttft_ms=round(req.ttft_s * 1e3, 3),
                           resident_us=req._phase_t0_us,
                           steps=self._step_count - req.admit_step + 1)
        if (len(req.generated) >= req.max_new_tokens
                or (req.eos_id is not None and tok == req.eos_id)):
            self._finish(req)

    def _materialize_forks(self, primary: Request) -> bool:
        """Fork pending best-of clones off a resident primary whose prompt
        is fully resident: full prompt pages SHARED by refcount (zero bytes
        moved), only a partial tail page copied (``serving.cow_copies``).
        Each clone takes a free slot and enters decode in replay mode — its
        first token samples from the prompt's last-position logits on its
        own RNG stream, exactly like an independently-submitted request
        would. Clones that can't fork yet (no free slot, no page for the
        tail copy) stay pending and retry next step; the primary's terminal
        transition spills any remainder to the ordinary queue."""
        g = self.geom
        L = len(primary.prompt)
        n_ctx = g.pages_for(L)
        if primary.state != DECODE or len(primary.pages) < n_ctx:
            return False
        # priority-ordered slot acquisition applies to clones too: a
        # strictly higher-priority queued request gets the free slot (via
        # the admission pass that follows); equal priority favors the
        # clone — it is older traffic and forking is cheaper than prefill
        top_queued = max((r.priority for r in self.queue), default=None)
        worked = False
        while primary.fork_pending:
            if top_queued is not None and \
                    top_queued > primary.fork_pending[0].priority:
                break
            slot = next((i for i, s in enumerate(self.slots) if s is None),
                        None)
            if slot is None:
                break
            clone = primary.fork_pending[0]
            cow_before = self.cache.cow_copies
            try:
                pages = self.cache.fork(primary.pages, L)
            except OutOfPages:
                break       # tail copy can't allocate; retry under less load
            primary.fork_pending.pop(0)
            # the allocator owns the copy decision; read the count back
            # rather than re-deriving it (the two can't drift)
            copied = self.cache.cow_copies - cow_before
            if copied:
                self.obs.inc("serving.cow_copies", copied)
            clone.pages = pages
            clone.pages_version += 1
            clone.prefilled = L
            clone.length = L
            clone.next_token = int(clone.prompt[-1])
            clone._replay = True
            clone.state = DECODE
            clone.admit_seq = next(self._admits)
            clone.admit_step = self._step_count
            self.slots[slot] = clone
            self._phase_end(clone)          # close "queued" (fork-pending)
            self.obs.event("serving_fork", request=clone.request_id,
                           parent=primary.request_id, slot=slot,
                           shared_pages=len(pages) - copied, copied=copied)
            self._phase_begin(clone, DECODE)
            if clone.decode_start_s is None:
                clone.decode_start_s = time.perf_counter()
            worked = True
        return worked

    def _finish(self, req: Request) -> None:
        if self.prefix is not None and req.pages:
            # donate the full prompt pages back BEFORE freeing: the
            # registration is what parks them (K/V preserved) when the
            # release below drops their last reference
            self.prefix.donate(req.work_prompt, req.pages)
        for clone in list(req.fork_pending):   # _shed mutates the list
            # never-forked clones fall back to the ordinary queue (full
            # prefill — which may now prefix-hit the donated prompt), but
            # the bounded-admission contract still applies: spill only up
            # to max_queue and shed the overflow typed, so best_of can't
            # grow the queue past the overload bound submit() enforces
            if self.max_queue is not None and \
                    len(self.queue) >= self.max_queue:
                self._shed(clone, AdmissionRejected(
                    f"request {clone.request_id} shed: fork primary "
                    f"{req.request_id} finished before the clone could "
                    f"fork and the admission queue is full "
                    f"({self.max_queue})", request_id=clone.request_id,
                    engine_id=self.engine_id))
            else:
                self.queue.appendleft(clone)
        req.fork_pending = []
        self._release_slot(req)
        self._phase_end(req)            # close "decode"
        req.state = DONE
        req.finished_s = time.perf_counter()
        self._close_request_span(req)
        if req.decode_start_s is not None:
            # per-request decode-phase duration (first token -> completion)
            self.obs.observe_value(
                "serving.decode_ms", (req.finished_s - req.decode_start_s) * 1e3)
        self.completed.append(req)
        self._slo_total += 1
        if req.deadline_at is None or req.finished_s <= req.deadline_at:
            self._slo_attained += 1
        else:
            # completed, but late: an SLO miss even though tokens shipped
            self.obs.inc("serving.deadline_misses")
        self.obs.event("serving_complete", request=req.request_id,
                       generated=len(req.generated),
                       preemptions=req.preemptions, restarts=req.restarts)
