"""Production serving runtime: continuous batching over a paged KV cache,
supervised for survival under fire.

The layers (ROADMAP item 1 + the serving containment story):

- :mod:`thunder_tpu.serving.kv_cache` — block-allocated page pool +
  free-list + per-request block tables (requests at any mix of sequence
  lengths share one device allocation, one compiled decode shape), with
  per-page REFCOUNTS (copy-on-write ``fork`` shares full pages, copies
  only the partial tail) and the refcount-aware
  :meth:`~kv_cache.PagedKVCache.assert_quiescent` leak audit.
- :mod:`thunder_tpu.serving.sampling` — in-graph sampling:
  :class:`~sampling.SamplingParams` per request, sort-free top-k/top-p
  threshold masking + Gumbel-max draw fused into the decode program
  (greedy == ``temperature 0``; the scheduler reads tokens, not logits).
- :mod:`thunder_tpu.serving.prefix_cache` — cross-request prefix cache: a
  page-granularity token trie; admission probes it, completed requests
  donate their prompt pages, the allocator evicts parked pages under
  pressure (the cache can never starve live traffic).
- :mod:`thunder_tpu.serving.description` — the model's side of the engine:
  a :class:`~description.ModelDescription` names every layer's cache kind
  (``full``, or ``window(W)`` kept as a ring of pages the scheduler
  recycles) and brings the traced decode and prefill functions. The Llama
  family's is the first; ``models/cohere2_moe.py`` brings a second
  (window and global layers, a parallel attention + expert block).
- :mod:`thunder_tpu.serving.runner` — the compiled paged prefill/decode
  step programs (``bind()``-dispatched decode; ``LengthBucketer``-laddered
  prefill chunks; ragged attention via ``nn.paged_decode_attention``,
  Pallas-claimed on TPU; sampling as the decode epilogue — prefill carries
  no lm_head, first tokens ride a replay row of the decode step that
  follows the last chunk in the same iteration).
- :mod:`thunder_tpu.serving.scheduler` — admission (priority-ordered,
  optionally bounded, infeasibility-checked), deadline-aware continuous
  batching with chunked prefill interleaving (an iteration is schedule,
  prefill, decode), mid-flight join/evict,
  page-pressure preemption, load shedding with typed errors
  (:mod:`~thunder_tpu.serving.errors`), ``serving:*``-domain retry, and
  the ``serving.*`` observe metrics.
- :mod:`thunder_tpu.serving.supervisor` — the engine-level fallback rung:
  crash recovery (pool rebuild + re-prefill of in-flight requests, charged
  to a sliding-window :class:`~thunder_tpu.runtime.retry.RestartBudget`),
  graceful ``drain()``/``shutdown()``, and a heartbeat watchdog.
- :mod:`thunder_tpu.serving.health` — the fleet plane: every engine's
  telemetry is labeled with its process-unique ``engine_id``;
  :class:`~health.EngineHealth` scores it into a typed
  HEALTHY/DEGRADED/DRAINING/DEAD machine with hysteresis, and a
  :class:`~health.FleetObservatory` aggregates N supervised engines
  (fleet SLO, merged explain section, cross-engine postmortems, statusz
  directory aggregation).
- :mod:`thunder_tpu.serving.router` — one ``submit()``/``step()`` surface
  over N supervised engines: health-gated, cache-affine, least-loaded
  placement through a composable policy chain
  (:class:`~router.FleetRouter`), a decision log for every placement,
  failover re-admission of in-flight requests off dead engines
  (token-identical, recompute-on-resume), and drain-time
  :meth:`~router.FleetRouter.rebalance`.

>>> from thunder_tpu.serving import EngineSupervisor, ServingEngine
>>> eng = ServingEngine(params, cfg, max_slots=8, page_size=16,
...                     max_context=256, n_layers=2)
>>> sup = EngineSupervisor(eng, max_restarts=3, restart_window_s=600.0)
>>> req = sup.submit(prompt_ids, max_new_tokens=32, deadline_s=30.0)
>>> sup.drain(); req.output()

The benchmark's serving cells (``benchmark/run.py --workload
mistral7b_serve_decode_sat`` / ``mistral7b_serve_chat``) drive this engine:
output tokens/s at full slots, TTFT and inter-token latency under an open
Poisson loop.
"""

from thunder_tpu.serving.events import EVENT_KINDS  # noqa: F401
from thunder_tpu.serving.errors import (  # noqa: F401
    AdmissionRejected,
    DeadlineExceeded,
    EngineFault,
    EngineStallError,
    InfeasibleRequest,
    RestartBudgetExceeded,
    RestartState,
    ServingError,
    ShardingGeometryError,
)
from thunder_tpu.serving.health import (  # noqa: F401
    DEAD,
    DEGRADED,
    DRAINING,
    HEALTH_STATES,
    HEALTHY,
    EngineHealth,
    FleetObservatory,
    HealthPolicy,
)
from thunder_tpu.serving.kv_cache import (  # noqa: F401
    OutOfPages,
    PagedKVCache,
    PageGeometry,
)
from thunder_tpu.serving.prefix_cache import (  # noqa: F401
    PrefixCache,
    content_key,
)
from thunder_tpu.serving.router import (  # noqa: F401
    FleetRouter,
    HealthGate,
    LeastLoaded,
    PrefixAffinity,
    RandomPlacement,
    RoutingPolicy,
)
from thunder_tpu.serving.description import (  # noqa: F401
    CacheKind,
    LlamaDescription,
    ModelDescription,
    describe,
)
from thunder_tpu.serving.runner import PagedLlamaRunner, PagedRunner  # noqa: F401
from thunder_tpu.serving.sampling import (  # noqa: F401
    GREEDY,
    SamplingParams,
    sample_tokens,
)
from thunder_tpu.serving.scheduler import Request, ServingEngine  # noqa: F401
from thunder_tpu.serving.supervisor import EngineSupervisor  # noqa: F401
