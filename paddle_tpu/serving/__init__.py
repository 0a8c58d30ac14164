"""paddle_tpu.serving — continuous-batching inference engine over a
paged (block-pool) KV cache.

The north-star workload is "serve heavy traffic from millions of
users"; ``generation.generate`` is one request at a time, whole-batch
lockstep. This package is the request-level layer above the same
static-shape decode substrate:

- ``engine``:     ``ServingEngine`` — a fixed pool of decode slots whose
                  KV lives in a shared pool of device blocks addressed
                  through per-slot traced block tables (capacity bounded
                  by tokens in flight, not slots * worst-case length);
                  chunked prefill, ref-counted copy-on-write prefix
                  sharing, preemption-by-recompute under pool pressure,
                  ONE jitted decode step for the whole pool (per-slot
                  positions/params/keys/block tables as traced arrays),
                  slots freed on EOS/max-tokens and refilled
                  immediately.
                  ``draft_model=`` adds SPECULATIVE DECODING: a small
                  draft proposes ``spec_k`` tokens per slot, the target
                  scores the whole bundle in one paged flash-decode
                  call, and each slot advances by its own accept length
                  through the block tables — outputs stay bit-identical
                  to plain decode (greedy and sampled), speculation only
                  moves throughput.
- ``block_pool``: host-side KV block allocator (free list + refcounts,
                  exhaustion/double-free errors, fragmentation stats)
                  and the exact-prefix LRU cache behind prefix sharing
                  (eviction-callback hook feeding the tier below).
- ``kv_tier``:    hierarchical KV under the block pool
                  (``ServingConfig(kv_tier=True)``): prefix-cache
                  eviction victims and preempted requests' blocks
                  demote device->host instead of being freed, a
                  returning prefix re-admits via one jitted host->HBM
                  splice instead of prefill chunks (cost model:
                  transfer bytes vs the perf ledger's measured
                  recompute rate), and an optional disk tier
                  (``kv_tier_path``) persists the prefix cache across
                  engine restarts with atomic-commit crash safety.
- ``scheduler``:  FCFS admission, max-queue-depth backpressure
                  (``QueueFullError``), deadlines, cancellation,
                  front-of-queue requeue for preempted requests.
- ``request``:    ``Request`` handles — blocking ``result()``, streaming
                  ``stream()`` iterator, per-token callbacks.
- ``metrics``:    requests/tokens counters, queue-depth + slot-occupancy
                  + KV-block gauges, prefix-cache/COW/preemption
                  counters, TTFT/TPOT histograms in the shared
                  observability registry (registered at import so
                  scrapes always show serving state).
- ``http``:       opt-in stdlib HTTP front end (``ServingHTTPServer`` /
                  ``start_serving_http_server``) with split /healthz 503
                  states (crashed/draining/saturated/stalled) and
                  digest-derived Retry-After.
- ``router``:     multi-replica layer: ``Router`` spreads requests over
                  N engine replicas (``LocalReplica``/``HTTPReplica``)
                  with load-aware admission, health-gated failover
                  (probe ejection + warmup-gated readmission),
                  deadline-aware retries whose failover outputs are
                  bit-identical to a single engine, optional TTFT
                  hedging, and graceful drain.
- ``router_http``: the router's HTTP front end (``RouterHTTPServer``)
                  + SIGTERM -> fleet drain.
- ``supervisor``: self-healing layer over one engine
                  (``EngineSupervisor``): warm in-process restart after
                  a decode-loop crash (fresh pools, zero-compile
                  warmup, innocent queued+running requests requeued on
                  the seed-deterministic replay — same handles, same
                  bytes), a crash-loop breaker, and poison-request
                  quarantine (``PoisonedRequestError``) whose
                  fingerprint blacklist the router propagates
                  fleet-wide via /stats and the retry path.
- ``chaos``:      deterministic fault injection (``ChaosEngine``,
                  ``ChaosReplica``, restart-surviving
                  ``SupervisedChaos`` with fingerprint-targeted poison
                  faults) powering the router chaos suite.

Quick start::

    from paddle_tpu import serving
    eng = serving.ServingEngine(model, max_slots=8, max_len=512)
    eng.start()                      # background loop (or drive step())
    req = eng.submit(prompt_ids, max_new_tokens=64, eos_token_id=2)
    for tok in req.stream():         # tokens as the decode lands them
        ...
"""

from __future__ import annotations

from . import metrics  # registers the serving gauges at import
from .block_pool import (BlockPool, BlockPoolError, PoolExhaustedError,
                         PrefixCache)
from .chaos import ChaosEngine, ChaosError, ChaosReplica, SupervisedChaos
from .engine import (EngineDrainingError, EngineStoppedError, ServingConfig,
                     ServingEngine)
from .http import (ServingHTTPServer, start_serving_http_server,
                   stop_serving_http_server)
from .kv_tier import DiskPrefixStore, KVTier, TierCostModel
from .request import (PRIORITY_CLASSES, Request, RequestStatus,
                      SamplingParams, request_fingerprint)
from .router import (HTTPReplica, LocalReplica, NoReplicaError, ReplicaState,
                     Router, RouterConfig, RouterRequest)
from .router_http import (RouterHTTPServer, install_sigterm_drain,
                          uninstall_sigterm_drain)
from .scheduler import DeadlineInfeasibleError, QueueFullError, Scheduler
from .supervisor import EngineSupervisor, PoisonedRequestError

__all__ = [
    "ServingConfig", "ServingEngine", "SamplingParams", "Request",
    "RequestStatus", "Scheduler", "QueueFullError",
    "DeadlineInfeasibleError", "PRIORITY_CLASSES", "request_fingerprint",
    "EngineSupervisor", "PoisonedRequestError",
    "EngineStoppedError", "EngineDrainingError",
    "BlockPool", "PrefixCache", "PoolExhaustedError", "BlockPoolError",
    "KVTier", "TierCostModel", "DiskPrefixStore",
    "ServingHTTPServer", "start_serving_http_server",
    "stop_serving_http_server",
    "Router", "RouterConfig", "RouterRequest", "ReplicaState",
    "LocalReplica", "HTTPReplica", "NoReplicaError",
    "RouterHTTPServer", "install_sigterm_drain", "uninstall_sigterm_drain",
    "ChaosEngine", "ChaosReplica", "ChaosError", "SupervisedChaos",
]
