"""Unified observability layer: tracing, metrics, online recall.

Three pillars over the serving stack (docs/observability.md):

* ``obs.trace`` — deterministic request-span tracer on the injectable
  monotonic clock (queue-wait / batch-assembly / cache-lookup /
  device-dispatch with enqueue and device-wait / result-merge /
  device_get / collector-pause spans per batch and request, also
  marked as ``raft_tpu::serve.*`` profiler ranges), exportable as JSON
  and Chrome trace-event format;
* ``obs.registry`` — ``MetricsRegistry`` (counters / gauges /
  histograms with labels, Prometheus text exposition + JSON snapshot)
  and the ``*Collector`` adapters unifying ``ServeStats``,
  ``ShardHealth``, ``Compactor``, ``ResultCache``, index epoch /
  tombstone state, and per-engine merge dispatch volume onto one
  scrape;
* ``obs.recall`` — ``RecallProbe``, a deterministic shadow sampler
  that exact-scans served queries off the hot path and publishes
  realized-recall gauges plus the query-aware drift flag the
  ``Compactor`` trigger consumes.

Everything is disabled-by-default and zero-cost when off: no tracer,
registry, or probe is created unless wired in, and none of them add
operands or host syncs to any compiled program (the sanitized lane in
tests/test_obs.py proves instrumented steady-state serving runs with
zero implicit transfers and zero recompiles).
"""

from raft_tpu.obs.recall import RecallProbe
from raft_tpu.obs.registry import (
    BreakerCollector,
    CacheCollector,
    CompactorCollector,
    Counter,
    DegradeCollector,
    ElasticCollector,
    Gauge,
    HedgeCollector,
    Histogram,
    MergeDispatchCollector,
    MetricsRegistry,
    RoutingCollector,
    SearcherCollector,
    ServeStatsCollector,
    ShardHealthCollector,
    WalCollector,
)
from raft_tpu.obs.trace import NULL_SPAN, NULL_TRACER, Span, Tracer

__all__ = [
    "Span", "Tracer", "NULL_SPAN", "NULL_TRACER",
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "ServeStatsCollector", "ShardHealthCollector", "CacheCollector",
    "CompactorCollector", "SearcherCollector", "MergeDispatchCollector",
    "RoutingCollector", "WalCollector", "ElasticCollector",
    "HedgeCollector", "BreakerCollector", "DegradeCollector",
    "RecallProbe",
]
