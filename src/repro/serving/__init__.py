"""Serverless serving surface (generation-first).

  api      Request / GenerateSpec / RequestClass / Response / stats +
           typed errors (UnknownModelError, CacheOverflowError)
  autoscale  SLO autoscaler: arrival-rate slope + queue depth drive
           pool prewarm / scale-in (repro.metrics is the signal source)
  decode   DecodeScheduler: continuous-batching decode over KV pages
           + the serial reference_generate oracle
  policy   keep-alive eviction policies (TTL, never-evict)
  pool     FunctionInstance (owns a DecodeScheduler when live) +
           per-model InstancePool (exclusive + shared-generation holds)
  router   thread-safe Router: admission control, priority dispatch,
           generation requests join running decode batches
  engine   ServerlessPlatform (trace replay on the Router, one-shot or
           generation)
  trace    bursty Azure-like invocation workload generator

The node-local WeightCache (repro.store.cache) is re-exported here:
one cache per platform makes scale-out cold starts reuse resident
weights and single-flight store reads.
"""
from repro.serving.api import (AdmissionError, CacheOverflowError,  # noqa: F401
                               GenerateSpec, PoolStats, Request,
                               RequestClass, Response, RouterStats,
                               UnknownModelError)
from repro.serving.decode import (DecodeScheduler, GenResult,  # noqa: F401
                                  reference_generate)
from repro.serving.autoscale import Autoscaler  # noqa: F401
from repro.serving.policy import (EvictionPolicy, KeepAliveTTL,  # noqa: F401
                                  NeverEvict, make_policy)
from repro.serving.pool import FunctionInstance, InstancePool  # noqa: F401
from repro.serving.router import Router  # noqa: F401
from repro.serving.engine import ServerlessPlatform  # noqa: F401
from repro.store.cache import CacheStats, WeightCache  # noqa: F401
