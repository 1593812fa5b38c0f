"""repro.obs — the observability layer: metrics, traces, profiling.

The paper's headline claims are runtime properties (throughput at equal
recall, fewer I/O hops, drift recovery), and the ROADMAP's next perf
items (async I/O, shard rebalancing, hot/cold tiering) are all *driven
by measurement* — Quake rebalances from measured query distribution,
GoVector admits cache entries from measured access patterns.  This
package is the measurement substrate:

* ``metrics``  — counters / gauges / fixed-bucket latency histograms
                 (p50/p95/p99) in a ``MetricsRegistry`` with
                 Prometheus-text and JSON exporters; near-zero overhead
                 when disabled.  Surfaced as ``db.metrics()``.
* ``trace``    — per-query ``TraceRecorder`` spans threaded through the
                 search lifecycle (route → fetch → rerank → merge) on
                 every tier; surfaced as
                 ``db.search(..., explain=True) -> SearchTrace``.
* ``window``   — the serving frontend's rolling window (QPS, batch
                 occupancy, flush p99).
* ``profiler`` — ``span``: host spans (``catapultdb.<layer>.<phase>``)
                 on the profiler's clock, always on, ~1 µs each with no
                 capture running; ``profile_trace`` captures them with
                 the device trace.

See docs/OBSERVABILITY.md for metric names, the trace schema, and a
Prometheus scrape example.
"""
from repro.obs.metrics import (DEFAULT_MS_EDGES, Counter, Gauge, Histogram,
                               MetricsRegistry, NULL_INSTRUMENT)
from repro.obs.profiler import profile_trace, span
from repro.obs.trace import (STAGES, SearchTrace, Span, TraceRecorder,
                             build_search_trace)
from repro.obs.window import RollingWindow

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "NULL_INSTRUMENT",
    "DEFAULT_MS_EDGES", "RollingWindow", "STAGES", "SearchTrace", "Span",
    "TraceRecorder", "build_search_trace", "profile_trace", "span",
]
