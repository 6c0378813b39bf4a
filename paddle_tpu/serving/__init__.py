"""paddle_tpu.serving — continuous-batching inference engine with a
block-paged KV cache (reference capability: Paddle's serving stack —
paddle.inference at scale / FastDeploy — and the vLLM/TPU
ragged-paged-attention design, PAPERS.md).

Layers:
- :mod:`kv_cache`   — paged K/V pool: free-list allocator, per-sequence
  page tables, refcounted copy-on-fork (n>1 sampling), budget sizing;
  round 10: radix-tree prefix cache (full-prompt-page reuse, LRU leaf
  eviction, uncached-only accounting) behind ``prefix_cache=True``.
- :mod:`sampling`   — fused on-device sampler (round 10): greedy/
  temperature/top-k/top-p with per-lane counter RNG inside the compiled
  step; the per-step host fetch is [B] ids + [B] logprobs, not [B, V]
  logits (host numpy oracle behind PADDLE_TPU_SERVING_HOST_SAMPLE=1).
- :mod:`attention`  — paged attention: jax gather reference path
  (oracle-parity with the contiguous static cache) + ONE unified
  ragged Pallas kernel gated behind ``PADDLE_TPU_PAGED_KERNEL``
  (interpret-mode only; round 22 folded the decode-only stub into it —
  ``ragged_paged_attention`` is the token-packed mixed-batch entry).
- :mod:`scheduler`  — continuous batching: watermark admission, chunked
  prefill, decode-priority iteration, deadlines, LIFO preemption.
- :mod:`engine`     — ONE token-packed fixed-shape compiled step
  (decode lanes, verify lanes and the prefill chunk in one program of
  at most two token capacities; weights as arguments) +
  :mod:`metrics` (TTFT / inter-token / occupancy JSON +
  Prometheus exposition). Round 9: per-token ``on_event`` streaming,
  ``cancel()`` (pages freed, queues purged), ``drain()`` mode,
  env-gated fault injection at the step boundary, failure-path page
  release. Round 12: batched speculative decoding
  (``draft_model=``/``speculative_k=`` — fused k+1-step draft-propose
  scan, then each lane rides the step with k+1 tokens and
  deterministic-sample acceptance: greedy AND seeded-sampled streams
  token-exact vs the plain engine; accounting-only rollback via
  ``PagedKVCache.free_tail``; admission reserves the verify burst).
- :mod:`frontend`   — thread-safe request bridge: lock-serialized
  engine loop thread, per-request token streams, reservation-based
  load shedding (429) and graceful drain (503).
- :mod:`server`     — stdlib OpenAI-compatible HTTP front-end:
  /v1/completions + /v1/chat/completions (SSE streaming), /healthz,
  /metrics; disconnect-driven cancellation; round 11: SSE keepalive
  pings (bounded disconnect detection) + X-Request-Id propagation.
- :mod:`replica` / :mod:`router` — the multi-replica tier (round 11):
  ``ServingRouter`` fronts N replicas (in-process frontends or remote
  HTTP servers) behind the same front-end surface, with round-robin /
  least-loaded / prefix-cache-aware routing, token-exact mid-stream
  failover (determinism-backed stream splicing), aggregated 429
  admission, rolling drain + weight-reload re-admit, and a merged
  ``replica``-labelled /metrics.

- :mod:`disagg` / :mod:`pagewire` / :mod:`autoscale` — the
  disaggregated tier (round 14): ``DisaggRouter`` routes admissions to
  prefill-role replicas (``prefill_only`` requests hold their pages at
  the first token), migrates the KV page chain to a decode-role
  replica (radix tree as transfer index — only the uncached suffix
  moves; in-process array handoff or the ``/v1/_pages`` wire format),
  and splices the streams token-exactly; ``FleetAutoscaler`` grows the
  fleet from a replica factory and shrinks it through the rolling
  drain, driven by reserved-page load + TTFT histogram windows.

- :mod:`trace` — serving-wide observability (round 16): an always-on
  capped span timeline per request (queued/prefill/decode/spec/
  preempt/recompute/prefix-hit/migration/failover-splice/held, emitted
  under the existing locks) + a per-engine flight recorder ring
  (step composition/wall, admissions, sheds, preemptions, faults,
  drain, loop errors — dumped to the structured log on loop failure);
  ``/debug/trace?request_id=`` and ``/debug/flight`` expose both as
  JSON, router-merged across replicas like /metrics; completed
  timelines export as chrome://tracing JSON in the
  ``paddle_tpu.profiler`` event format (``bench_serving.py
  --trace-out``).

- Fleet-wide prefix cache (round 18): the router's affinity radix
  tree doubles as a KV-page TRANSFER INDEX (``prefix_fleet=True`` /
  ``PADDLE_TPU_SERVING_PREFIX_FLEET=1``) — on a prefix miss at the
  routed replica but a hit anywhere in the fleet, the cached prefix
  pages ship over the pagewire path (in-process array handoff or
  ``/v1/_pages/prefix``) instead of being recomputed; the target
  chunk-prefills only the uncovered suffix.  Donor liveness and
  eviction races resolve through the PrefixDrift/GeometryMismatch
  bounce into a recompute fallback (never a failed request), the
  router consults the ``/healthz``-advertised ``cache_dtype`` so
  dtype-skewed fleets skip doomed ships up front, and
  ``prefix_max_owners`` dedups hot prefixes across replicas
  (router-driven ``drop_prefix`` eviction pressure).

- :mod:`chaos` — the robustness layer (round 17): ONE seeded
  deterministic fault schedule (``ChaosConfig`` — the legacy FAULT_*
  knobs alias in) over 15 registered fault points (engine step
  fault/latency, allocator-pressure spikes, migration export/import/
  transfer failures, HTTP connect/EOF/slow-read, replica crash during
  drain/readmit/shrink, prefix-ship donor-gone/eviction-race/
  torn-payload), the injected sleeper every serving sleep
  routes through (graftlint ``serving-raw-sleep``), bounded
  exponential-backoff retries (migration + idempotent HTTP hops),
  per-replica circuit breakers (``/healthz``-advertised, /metrics
  counted, flight-dumped on open), held-page release on deadline
  expiry, and the global recovery invariants the chaos fuzz
  (``tools/chaos_fuzz.py``) asserts after every convulsion.

- :mod:`fleet` / :mod:`fleet_worker` — the crash-survivable fleet
  control plane (round 19): ``ProcessReplicaBackend`` provisions REAL
  replica server processes for the autoscaler (ephemeral ports,
  bounded ``/healthz`` readiness, liveness supervision with
  restart-backoff under a per-replica budget, every process reaped on
  every exit path incl. a parent-death self-reap watchdog in the
  worker); ``RouterJournal`` (CRC-framed append-only JSONL, torn
  records skipped on replay, bounded rotation) + one ``/healthz``
  sweep make EVERY piece of routing state rebuildable — a cold router
  (``ServingRouter.recover``) converges to a never-crashed router's
  decisions within one sweep; ``RouterSupervisor`` runs primary +
  warm standby with idempotent takeover — accepted streams survive
  the router's own death token-exactly via the client-side splice,
  and the autoscaler's pressure signal now also reads breaker state
  and shed/failover deltas (browning-out fleets grow BEFORE the SLOs
  blow; flapping replicas rotate out via drain-by-health).  Proof at
  scale: ``tools/fleet_harness.py`` (bursty/diurnal traffic + seeded
  concurrent chaos, SLO-gated, ``BENCH_serving_fleet.json``).

- :mod:`kvtier` — hierarchical KV-cache tiers (round 20): a
  byte-budgeted LRU ``HostPagePool`` (``PADDLE_TPU_SERVING_HOST_POOL_
  MB``) with an optional file-backed ``DiskPagePool`` under it, bound
  behind ``PagedKVCache`` via ``attach_tier``.  rc-0 cached pages
  evicted by allocation pressure spill their pagewire payload (int8
  codes+scales ride intact) to the host tier at step boundaries; a
  prefix probe that misses device pages but hits the tier restores
  them through the same fused gather/scatter import path as a remote
  ship (pages re-enter CACHED at rc==0, so the shed gate's
  probe-based accounting covers them with no new case).  Probe order:
  local device → local host tier → remote donor → recompute.
  Strictly best-effort: spill/restore failures, dtype/geometry skew,
  CRC-caught bit-rot (the pagewire payload checksum), and capacity
  sheds all degrade to the recompute the engine would have done
  anyway.  The autoscaler pre-warms freshly grown replicas from the
  hottest spilled chains (``prewarm_prefix``).

- :mod:`deploy` / :mod:`distill` — versioned live weight deployment +
  online draft distillation (round 21): a ``WeightRegistry`` (monotonic
  version ids across named weight sets — "target"/"draft" — in-memory
  handles with atomic npz spill-to-disk) and a ``RollingDeployer`` that
  hot-swaps one replica at a time: router-level drain (in-flight
  streams FINISH on the version they started on), a one-step quiesce
  under the engine lock (weights are ARGUMENTS of the compiled step —
  the swap is a pytree write, zero recompile), stale-weight K/V flush
  (``clear_prefix`` detaches + invalidates the spilled tiers too),
  ``/healthz``-advertised ``weight_version``, re-admit.  Routers PIN
  every stream to the version it started on (failover re-placement
  skips version-skewed replicas; prefix ships skip version-skewed
  donors) so no stream ever splices tokens from two versions.  The
  swap itself (``engine.set_weights`` — the graftlint
  ``weight-swap-lock`` blessed mutation site) validates the payload
  all-or-nothing, so a torn push degrades to serving the old version.
  ``DraftDistiller`` closes the training↔serving loop: the speculative
  verify step logs (history, target-token) pairs for free, a
  background trainer distills the draft on them, and refreshed draft
  weights roll out through the same deployer fully live (the draft
  only PROPOSES — the target's verify decides every emitted token, so
  a mid-stream draft refresh moves acceptance rate, never output).
  Proof: ``tools/deploy_harness.py`` (rolling deploy under SLO-gated
  traffic + chaos, ``BENCH_serving_deploy.json``).

- :mod:`tp` — tensor-parallel SPMD serving (round 23):
  ``ServingEngine(mesh=...)`` / ``tp_degree=k`` runs the whole
  token-packed step as ONE GSPMD program over a device mesh —
  weights committed to mesh shardings (last-output-dim splits composed
  on top of fleet dist_specs via ``_add_sharding``, never returned
  verbatim), KV page pools sharded on the head axis (one allocator,
  replicated page tables), paged attention pinned to the jnp gather
  path (``pallas_call`` has no GSPMD rule — the kernel knob demotes
  loudly: log + ``tp_kernel_fallbacks``), and fused sampling still
  in-program with the partial (vocab-column-sliced) logits
  all-gathered only at the sampled lane.  Because only non-contracting
  dims shard, every matmul keeps its full contraction local — a TP=k
  replica streams token-exact vs TP=1 (greedy AND seeded, across
  preemption/recompute).  ``/healthz`` advertises
  ``tp_degree``/``tp_mesh``, pagewire payloads grow per-shard lists
  (scales ride every shard), and tp-skewed transfers bounce to the
  re-prefill fallback exactly like dtype skew.

Drivers: ``bench_serving.py`` (repo root) replays a Poisson trace —
offline through the engine, or over real sockets with ``--server`` —
and emits the BENCH_serving artifacts. Docs: ``docs/SERVING.md``.
"""
from .attention import (paged_attention, paged_attention_ref,  # noqa: F401
                        ragged_paged_attention)
from .autoscale import FleetAutoscaler  # noqa: F401
from .chaos import (FAULT_POINTS, Backoff, ChaosConfig,  # noqa: F401
                    ChaosInjector, CircuitBreaker)
from .deploy import (DeployError, RollingDeployer,  # noqa: F401
                     WeightRegistry, snapshot_weights)
from .disagg import DisaggRouter, DisaggStream  # noqa: F401
from .distill import (DistillBuffer, DraftDistiller,  # noqa: F401
                      distill_buffer_from_env)
from .engine import (EngineDraining, FaultInjected,  # noqa: F401
                     ServingEngine)
from .fleet import (ProcessReplica, ProcessReplicaBackend,  # noqa: F401
                    ReplicaSpec, RouterCrashed, RouterJournal,
                    RouterSupervisor, SubprocessLauncher,
                    ThreadLauncher)
from .frontend import (Rejected, RequestStream,  # noqa: F401
                       ServingFrontend, Unavailable)
from .kv_cache import (SCRATCH_PAGE, GeometryMismatch,  # noqa: F401
                       OutOfPages, PagedKVCache, PrefixDrift)
from .kvtier import (DiskPagePool, HostPagePool,  # noqa: F401
                     KVTier, chain_key, host_pool_from_env)
from .metrics import (Counter, Gauge, Histogram,  # noqa: F401
                      LabeledCounter, ServingMetrics)
from .pagewire import (WireFormatError, deserialize_pages,  # noqa: F401
                       serialize_pages)
from .replica import (HTTPReplica, InProcessReplica,  # noqa: F401
                      ReplicaFailed)
from .router import RouterStream, ServingRouter  # noqa: F401
from .sampling import fused_sample  # noqa: F401
from .scheduler import (Request, RequestState, Scheduler,  # noqa: F401
                        SchedulerOutput)
from .server import ServingServer  # noqa: F401
from .tp import TP_AXIS, TPContext, resolve_tp  # noqa: F401
from .trace import (FlightRecorder, RequestTrace,  # noqa: F401
                    ServingTrace, chrome_trace_events,
                    export_chrome_trace)

__all__ = [
    "PagedKVCache", "OutOfPages", "SCRATCH_PAGE",
    "paged_attention", "paged_attention_ref", "ragged_paged_attention",
    "fused_sample",
    "Scheduler", "SchedulerOutput", "Request", "RequestState",
    "ServingEngine", "EngineDraining", "FaultInjected",
    "ServingMetrics", "Counter", "Gauge", "Histogram", "LabeledCounter",
    "ServingFrontend", "RequestStream", "Rejected", "Unavailable",
    "ServingServer",
    "ServingRouter", "RouterStream", "InProcessReplica", "HTTPReplica",
    "ReplicaFailed",
    "DisaggRouter", "DisaggStream", "FleetAutoscaler",
    "GeometryMismatch", "PrefixDrift", "WireFormatError",
    "serialize_pages", "deserialize_pages",
    "ServingTrace", "RequestTrace", "FlightRecorder",
    "chrome_trace_events", "export_chrome_trace",
    "ChaosConfig", "ChaosInjector", "Backoff", "CircuitBreaker",
    "FAULT_POINTS",
    "ProcessReplica", "ProcessReplicaBackend", "ReplicaSpec",
    "RouterCrashed", "RouterJournal", "RouterSupervisor",
    "SubprocessLauncher", "ThreadLauncher",
    "DiskPagePool", "HostPagePool", "KVTier", "chain_key",
    "host_pool_from_env",
    "DeployError", "RollingDeployer", "WeightRegistry",
    "snapshot_weights",
    "DistillBuffer", "DraftDistiller", "distill_buffer_from_env",
    "TPContext", "resolve_tp", "TP_AXIS",
]
