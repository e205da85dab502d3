"""paddle_tpu.serving — dynamic-batching inference on a closed compile set.

The serving stack turns the framework's AOT inference artifacts and
KV-cache model paths into an online engine:

* :mod:`~paddle_tpu.serving.bucketing` — shape buckets; every request is
  padded to the smallest fitting bucket so XLA compiles exactly one
  executable per bucket (the *closed compile set*), never one per
  observed request shape.
* :mod:`~paddle_tpu.serving.batcher` — request queue + micro-batcher
  (``max_batch_size`` / ``max_queue_delay_ms``), with load shedding,
  per-request deadlines and graceful drain.
* :mod:`~paddle_tpu.serving.engine` — :class:`InferenceEngine`: bucketed
  AOT predictors over an exported ``save_inference_model`` artifact, with
  hot weight-swap from a ``.pdiparams`` side-file.
* :mod:`~paddle_tpu.serving.generation` — :class:`GenerationEngine`:
  prefill/decode greedy generation for a paged causal LM
  (``models.GPTForCausalLM``, ``models.LatentMoEForCausalLM``; one
  decode executable total) under slot-level continuous batching: a
  persistent decode loop admits/evicts individual requests at
  decode-step granularity, so a stalled long request holds one slot,
  never the batch.  The KV state is one shared page pool behind a
  slot→page-table indirection (PagedAttention): pages allocate on
  demand, shared-prefix pages are reused copy-on-write, eviction is a
  host table edit, and an n-gram proposer drives speculative decoding —
  all token-identical to uncached greedy on the same closed compile
  set.
* :mod:`~paddle_tpu.serving.paging` — :class:`PagePool`: the host-side
  page accounting behind the engine — refcounts, the free list, CoW
  copy scheduling and the shared-prefix registry.
* :mod:`~paddle_tpu.serving.metrics` — :class:`ServingMetrics`: queue
  depth, batch occupancy, p50/p99 latency, tokens/s, the continuous
  batching slot-scheduler family (admitted/evicted/starved counters,
  per-step occupancy gauges) and the paged-KV page-accounting family
  (``kv_pages_free``/``kv_pages_shared`` gauges, ``cow_copies``),
  published as ``("serving", <name>)`` events on
  ``framework.trace_events`` (consumed by ``analysis`` rules
  S601/S603/S604).
* :mod:`~paddle_tpu.serving.router` / :mod:`~paddle_tpu.serving.replica`
  — :class:`Router`: the multi-replica control plane — health-checked
  (active probes + per-replica circuit breaker) least-outstanding/p2c
  balancing over N engine replicas, transparent failover, optional
  hedged requests, zero-downtime drain and rolling weight swap
  (consumed by ``analysis`` rule S602), plus dynamic fleet membership
  (``add_replica`` / ``remove_replica`` — replicas join through the
  half-open probe/admit path and retire through graceful drain).
* :mod:`~paddle_tpu.serving.pool` — :class:`ReplicaPool`: the replica
  lifecycle actuator closing the autoscaling loop — consumes
  ``SloEngine`` scale signals, cold-starts warmed replicas off the
  serving path, retires them via drain, with hysteresis / cooldown /
  bounds / sequence-ordering guards (consumed by ``analysis`` rule
  S605); and :class:`DisaggServer`: the prefill/decode-disaggregated
  front-end piping :class:`~paddle_tpu.serving.generation.KVHandoff`
  page hand-offs from prefill-role to decode-role targets.
* :mod:`~paddle_tpu.serving.scenarios` — deterministic open-loop
  traffic scenarios (diurnal ramps, flash crowds, heavy-tail budgets,
  poison requests, noisy-neighbor tenant floods) and the
  :func:`run_scenario` harness that drives a serving stack through them
  with zero-loss accounting.
* :mod:`~paddle_tpu.serving.tenancy` — :class:`TenantScheduler`:
  multi-tenant admission control in front of the continuous-batching
  loop — weighted-fair (stride) ordering, per-tenant token budgets with
  deterministic budget preemption, default LoRA adapter slots and
  per-tenant SLO objectives (consumed by ``analysis`` rule S607).
"""
from .batcher import MicroBatcher, Request
from .bucketing import Bucket, BucketSet, as_bucket
from .engine import InferenceEngine
from .generation import GenerationEngine, KVHandoff
from .metrics import ServingMetrics
from .paging import PagePool
from .pool import DisaggServer, ReplicaPool
from .remote import EngineServer, RemoteEngineProxy
from .replica import Replica
from .router import Router
from .scenarios import (Scenario, ScenarioRequest, diurnal, flash_crowd,
                        heavy_tail, noisy_neighbor, poison, run_scenario)
from .tenancy import TenantScheduler, TenantSpec

__all__ = [
    "Bucket",
    "BucketSet",
    "as_bucket",
    "MicroBatcher",
    "Request",
    "InferenceEngine",
    "GenerationEngine",
    "KVHandoff",
    "ServingMetrics",
    "PagePool",
    "Replica",
    "Router",
    "EngineServer",
    "RemoteEngineProxy",
    "ReplicaPool",
    "DisaggServer",
    "Scenario",
    "ScenarioRequest",
    "diurnal",
    "flash_crowd",
    "heavy_tail",
    "noisy_neighbor",
    "poison",
    "run_scenario",
    "TenantScheduler",
    "TenantSpec",
]
