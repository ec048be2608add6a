"""Serving layer: the fault-tolerant distributed continuous-batching engine
(see serve.engine's module docstring and docs/serving.md).

One engine, `PagedServeEngine`, on a paged/block KV cache with per-page
checksums (serve.paged_kv); an SLO-aware scheduler with admission control
+ aging (serve.scheduler); and the deterministic load harness behind the
SLO-under-fault numbers (serve.traffic).
"""
from repro.serve.engine import (EngineStats, PagedServeEngine, Request,
                                ScrubEvent, SDCEvent)
from repro.serve.paged_kv import PagedKVCache, PagedStats
from repro.serve.scheduler import SchedPolicy, SchedStats, SLOScheduler
from repro.serve.traffic import (TraceItem, TrafficConfig, TrafficReport,
                                 compare, make_trace, run_trace)

__all__ = [
    "Request", "PagedServeEngine", "EngineStats", "SDCEvent", "ScrubEvent",
    "PagedKVCache", "PagedStats",
    "SLOScheduler", "SchedPolicy", "SchedStats",
    "TrafficConfig", "TraceItem", "TrafficReport", "make_trace",
    "run_trace", "compare",
]
