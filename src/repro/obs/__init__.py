"""Low-overhead observability for the serving stack.

``trace``   span/instant/async/counter events -> Chrome trace-event JSON
            (Perfetto-loadable), injectable clock, ``NullTracer`` no-op
            default.
``stats``   streaming counters/gauges/log-histograms with O(1)-memory
            windowed percentiles.
``export``  periodic JSONL snapshots and Prometheus text exposition.
"""
from .export import MetricsExporter, prometheus_text
from .stats import Counter, Gauge, LogHistogram, Registry
from .trace import (FakeClock, NULL_TRACER, NullTracer, PREFIX, Tracer,
                    count_events, phase, select_events, tracks_of)

__all__ = [
    "Counter", "FakeClock", "Gauge", "LogHistogram", "MetricsExporter",
    "NULL_TRACER", "NullTracer", "PREFIX", "Registry", "Tracer",
    "count_events", "phase", "prometheus_text", "select_events",
    "tracks_of",
]
