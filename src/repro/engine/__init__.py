"""repro.engine — continuous-batching serving engine with a paged,
SP-sharded KV cache (see docs/SERVING.md).

Public surface:
  Request                — one serving request (prompt, budget, sampling)
  Engine / EngineConfig  — add_request / step / collect / run driver;
                           constructed from a kind='decode' ExecutionPlan
                           (mesh, arrangement, decode_batch/page_size and
                           the paged-decode kernel_impl all come from it)
  build_engine           — convenience constructor: resolves a serve plan
                           (plan.make_serve_plan) over the local mesh
  paged_cache            — SP-sharded page-pool layout + island helpers;
                           PagePool, the ref-counted free list that makes
                           pages shareable (repro.gateway's prefix cache)
  sampling               — vocab-parallel greedy/temperature/top-k/top-p
  scheduler              — FIFO continuous-batching slot/page bookkeeping
                           (prefix-cache-aware admission when a
                           repro.gateway.PrefixCache is attached)
"""

from repro.engine.engine import (Engine, EngineConfig, EngineMetrics,
                                 build_engine)
from repro.engine.paged_cache import PagePool
from repro.engine.scheduler import (Rejection, Request, Scheduler, SlotState,
                                    bucket_pow2)

__all__ = [
    "Engine", "EngineConfig", "EngineMetrics", "build_engine", "PagePool",
    "Rejection", "Request", "Scheduler", "SlotState", "bucket_pow2",
]
