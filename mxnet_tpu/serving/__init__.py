"""Production serving tier: continuous batching, multi-tenant
front-end, SLO-aware admission.

The first user-facing subsystem above the training stack — live
traffic in, predictions out:

- :mod:`~mxnet_tpu.serving.admission` — typed, HTTP-mappable shedding:
  bounded queues (429), deadlines checked twice (504), drain mode
  (503).
- :mod:`~mxnet_tpu.serving.registry` — multi-tenant model registry;
  ``Predictor`` and ``deploy.ExportedModel`` behind one ``Backend``
  protocol, atomic checkpoint hot-reload between dispatch windows.
- :mod:`~mxnet_tpu.serving.scheduler` — the continuous-batching
  dispatch engine: pack waiting requests, pad to a bucket, zero
  steady-state recompiles.
- :mod:`~mxnet_tpu.serving.replication` — replica groups + failover
  router; accepted requests are never dropped, new load sheds typed.
- :mod:`~mxnet_tpu.serving.generation` — the autoregressive lane:
  prefill/decode split, iteration-level batching, paged KV cache
  (:mod:`~mxnet_tpu.ops.kv_cache`), streamed tokens.
- :mod:`~mxnet_tpu.serving.frontend` — the stdlib HTTP surface
  (``/v1/predict``, ``/v1/generate``, ``/v1/models``, ``/healthz``,
  ``/readyz``).
- :mod:`~mxnet_tpu.serving.tenancy` — multi-tenant fairness: weighted
  fair queuing (deficit round-robin) and per-tenant token-bucket
  quotas shared by both scheduler lanes.
- :mod:`~mxnet_tpu.serving.routing` — KV-affinity routing for
  generation sessions: stay on the replica holding your KV blocks,
  spill with re-prefill on imbalance or death.

Quickstart (one replica)::

    from mxnet_tpu import predict, serving

    sched = serving.Scheduler()
    sched.register("mlp", predict.load("model", 3,
                                       input_shapes={"data": (8, 6)}))
    sched.warmup("mlp")                      # pre-bind every bucket
    fe = serving.start_frontend(sched)       # POST {fe.url}/v1/predict

See ``docs/how_to/serving.md`` for the batching model, SLO knobs, and
the brownout story.
"""

from . import (admission, frontend, generation, registry, replication,
               routing, scheduler, tenancy)
from .admission import (AdmissionController, CacheExhaustedError,
                        DeadlineExceededError, InvalidDeadlineError,
                        QuotaExceededError, ReplicaDeadError,
                        ServerDrainingError, ServerOverloadedError,
                        ServingError, UnknownModelError, deadline_from_ms,
                        default_deadline_ms)
from .frontend import ServingFrontend, start_frontend
from .generation import (GenerationRequest, GenerationScheduler,
                         LMBackend, RecurrentStateHazard)
from .registry import (Backend, ExportedBackend, ModelRegistry,
                       PredictorBackend, as_backend, default_buckets)
from .replication import ReplicaGroup, ServingRouter
from .routing import KVAffinityRouter
from .scheduler import InferenceRequest, Scheduler
from .tenancy import (DEFAULT_TENANT, FairQueue, TenantPolicy,
                      TokenBucket, clean_tenant)

__all__ = [
    "AdmissionController", "Backend", "CacheExhaustedError",
    "DEFAULT_TENANT", "DeadlineExceededError", "ExportedBackend",
    "FairQueue", "GenerationRequest", "GenerationScheduler",
    "InferenceRequest", "InvalidDeadlineError", "KVAffinityRouter",
    "LMBackend", "RecurrentStateHazard", "ModelRegistry", "PredictorBackend",
    "QuotaExceededError", "ReplicaDeadError", "ReplicaGroup",
    "Scheduler", "ServerDrainingError", "ServerOverloadedError",
    "ServingError", "ServingFrontend", "ServingRouter", "TenantPolicy",
    "TokenBucket", "UnknownModelError", "admission", "as_backend",
    "clean_tenant", "deadline_from_ms", "default_buckets",
    "default_deadline_ms", "frontend", "generation", "registry",
    "replication", "routing", "scheduler", "start_frontend", "tenancy",
]
