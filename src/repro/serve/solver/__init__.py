"""Dynamic-batching IVP serving front-end (the ROADMAP "heavy traffic"
tier): admission queue -> shape bucket -> trace cache -> warm-start
continuation.

Layers (one module each, composed by :class:`SolverServer`):

* :mod:`repro.serve.solver.queue` — admission and dynamic batching:
  requests are bucketed by (problem family, shape n, method, tolerance
  class, dtype) and flushed on a max-batch-or-max-wait policy, padded
  to fixed bucket sizes, with bounded-depth backpressure
  (:class:`RetryAfter` instead of unbounded queue growth).
* :mod:`repro.serve.solver.trace_cache` — the shape-bucketed jit/trace
  cache keyed on (bucket shape, method, ExecPolicy fingerprint):
  steady-state traffic never recompiles; hit/miss/evict counters
  surface through ``Context.dispatch_report()``.
* :mod:`repro.serve.solver.server` — the synchronous-core,
  async-facade driver: pumps bundles through ``IVP.integrate``,
  resolves per-request futures, reports queue depth, batch occupancy,
  and p50/p99 latency.

Warm-start continuation rides :class:`repro.core.batched.SolverSession`
(exported/consumed by ``ensemble_bdf``): responses carry a session
handle, and resubmitting with it re-enters the BDF loop at the
terminal order/step instead of the cold order-1 restart.
"""
from repro.core.batched import SolverSession  # re-export: the warm-start handle

from .queue import (AdmissionQueue, Bundle, BucketKey, IVPRequest,
                    RetryAfter, bucket_key, tolerance_class)
from .server import ProblemFamily, SolverServer
from .trace_cache import TraceCache, TraceKey

__all__ = [
    "AdmissionQueue", "Bundle", "BucketKey", "IVPRequest", "RetryAfter",
    "bucket_key", "tolerance_class",
    "ProblemFamily", "SolverServer", "SolverSession",
    "TraceCache", "TraceKey",
]
