"""rafiki-tpu observability plane (dependency-free).

One metrics core (counters / gauges / fixed-bucket histograms /
StatsMaps + Prometheus text exposition), one tracing core (trace IDs +
a bounded ring of request records, and ``SPANS``, the process's bounded
ring of phase spans on the profiler's clock), and the HTTP surfacing
that mounts ``GET /metrics``, ``GET /debug/requests`` and
``GET /debug/spans`` on every service. See
``docs/observability.md`` for the metric catalog and how the pieces
join across processes.
"""

from .http import (DEBUG_REQUESTS_DEFAULT_N, ObsServer, debug_spans,
                   mount_obs_routes)
from .metrics import (DEFAULT_LATENCY_BUCKETS_S, PROM_CONTENT_TYPE,
                      Counter, Gauge, Histogram, MetricsRegistry,
                      StatsMap)
from .trace import (SPANS, SpanRing, TraceBuffer, mint_trace_id,
                    sanitize_trace_id)

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "StatsMap",
    "DEFAULT_LATENCY_BUCKETS_S", "PROM_CONTENT_TYPE",
    "TraceBuffer", "mint_trace_id", "sanitize_trace_id",
    "SpanRing", "SPANS",
    "ObsServer", "mount_obs_routes", "debug_spans",
    "DEBUG_REQUESTS_DEFAULT_N",
]
