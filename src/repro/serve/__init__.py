"""repro.serve — anytime, deadline-aware serving of AccurateML workloads.

Design (request -> deadline -> (r, eps) -> anytime response)
============================================================

The paper's two-stage algorithm is an *anytime* algorithm: stage 1 answers
from aggregated points in O(N/r), stage 2 spends eps*N more work refining
the top-correlated buckets toward the exact answer.  Offline, (r, eps) are
static job knobs; this subsystem turns them into per-request serving knobs
driven by each request's latency SLO.

Life of a request::

    submit(kind, payload, deadline_s)
        |                                  repro.serve.scheduler
        v
    [ ContinuousBatcher ]  heterogeneous queue; emits kind-homogeneous,
        |                  SLO-class-compatible batches padded to a bounded
        |                  set of shapes (one jit signature per shape)
        v
    [ DeadlineController ] repro.serve.deadline — maps the batch's tightest
        |                  remaining budget through CostModel.solve_eps and
        |                  BudgetPolicy into a Grant(compression_ratio, eps):
        |                  load degrades eps, never correctness; below the
        |                  eps floor it escalates (should_reexecute) to a
        |                  relaxed-deadline full-eps re-execution
        v
    [ AggregateCache ]     repro.serve.cache — stage-1 aggregates built once
        |                  per (dataset shard, LSHConfig), LRU + hit metering;
        |                  misses delegate to repro.store.AggregateStore:
        |                  new compression ratios merge the shard's resident
        |                  pyramid level (coarsened_hits) and snapshots
        |                  warm-start restarted servers (restored_hits)
        v
    [ Servable.run ]       the workload's two-stage map + combine on the
        |                  MapReduce engine (shuffle bytes metered); stage 1
        |                  executes first and its answers are released
        |                  immediately (on_stage1), stage 2 only if granted
        v
    Response(stage1, refined, eps_granted, stage1/total latency, ...)
        |
    [ ServeMetrics ]       repro.serve.metrics — p50/p99 of both anytime
                           latencies, granted-eps stats, deadline-met rate,
                           cache hit rate, shuffle bytes, and the stage-1 vs
                           refined accuracy proxy — bounded reservoirs on a
                           repro.obs.MetricsRegistry (flat memory, labeled
                           series, Prometheus/JSON export)

Observability: pass ``tracer=repro.obs.Tracer()`` to ``Server`` and every
batch records a span tree (batcher wait -> grant -> cache lookup -> per-shard
map -> refine -> respond); see ``repro.obs`` and
``examples/observe_serving.py``.  The spans share the profiler's clock (each
is a ``host.*`` annotation in a device trace) and never block, so a tracer
adds no sync to the serving loop.

Workloads implement the small ``Servable`` protocol (repro.serve.request);
``repro.apps.knn.KNNServable``, ``repro.apps.cf.CFServable``, and
``repro.serve.lm.LMServable`` (aggregated-KV LM decoding: the bucketed KV
cache is the "dataset shard", a decode step the query, and the granted
eps is the per-step ``refine_frac``) are the shipped instances.

Robustness: ``repro.serve.frontdoor.FrontDoor`` puts admission control in
front of this loop — per-tenant token-bucket quotas, a bounded admission
queue, and a load-shed ladder that degrades eps fleet-wide before the
first typed ``Overloaded`` refusal; ``repro.runtime.shards`` fans each
batch over N failure domains (see ``Response.partial_shards``).
"""
from repro.serve.cache import AggregateCache
from repro.serve.deadline import DeadlineController, Grant
from repro.serve.frontdoor import (
    FrontDoor, LoadShedLadder, TenantSpec, TokenBucket,
)
from repro.serve.metrics import ServeMetrics, percentile
from repro.serve.request import Overloaded, Request, Response, Servable
from repro.serve.scheduler import ContinuousBatcher, ScheduledBatch
from repro.serve.server import Server

__all__ = [
    "AggregateCache",
    "ContinuousBatcher",
    "DeadlineController",
    "FrontDoor",
    "Grant",
    "LoadShedLadder",
    "Overloaded",
    "Request",
    "Response",
    "ScheduledBatch",
    "Servable",
    "ServeMetrics",
    "Server",
    "TenantSpec",
    "TokenBucket",
    "percentile",
]
