"""HTTP surfacing for the obs plane: ``/metrics``, ``/debug/requests``
and ``/debug/spans``.

Two entry points:

- :func:`mount_obs_routes` adds the three routes to an EXISTING
  :class:`~rafiki_tpu.utils.http.JsonHttpService` (admin app, predictor
  service — processes that already listen).
- :class:`ObsServer` is a standalone single-purpose server for
  processes that had no HTTP surface at all (the inference and train
  workers): the worker loop stays a queue consumer; scrapes and
  timeline pulls ride a daemon-threaded sidecar on an ephemeral port.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

from ..utils.http import JsonHttpService, RawResponse
from .metrics import PROM_CONTENT_TYPE, MetricsRegistry
from .trace import SPANS, TraceBuffer, span_as_dict

#: default /debug/requests page size (override with ?n=K)
DEBUG_REQUESTS_DEFAULT_N = 32
#: default /debug/spans page size: a few decode turns with their phases
DEBUG_SPANS_DEFAULT_N = 256


def _page_size(m: dict, default: int) -> Tuple[Optional[int], Any]:
    """``?n=K`` of a debug route: ``(n, None)``, or ``(None, reply)``
    with the 400 to send."""
    try:
        n = int(m.get("n", default))
    except (TypeError, ValueError):
        return None, (400, {"error": "n must be an integer"})
    if n < 0:
        return None, (400, {"error": "n must be >= 0"})
    return n, None


def debug_spans(m: dict) -> Tuple[int, Any]:
    """``GET /debug/spans?n=K``: the newest ``K`` records of the
    process's phase-span ring, newest first — "what was the host doing
    while the chip sat idle just now"."""
    n, bad = _page_size(m, DEBUG_SPANS_DEFAULT_N)
    if bad:
        return bad
    recs = SPANS.snapshot()[-n:] if n else []
    return 200, {"spans": [span_as_dict(r) for r in reversed(recs)],
                 "count": len(recs)}


def mount_obs_routes(http: JsonHttpService, registry: MetricsRegistry,
                     traces: Optional[TraceBuffer] = None) -> None:
    """Mount ``GET /metrics`` (Prometheus text),
    ``GET /debug/requests?n=K`` (JSON trace records, newest first) and
    ``GET /debug/spans?n=K`` (JSON phase spans, newest first)."""

    def _metrics(_m, _b, _h) -> Tuple[int, Any]:
        return 200, RawResponse(
            registry.render_prometheus().encode("utf-8"),
            PROM_CONTENT_TYPE)

    def _debug_requests(m, _b, _h) -> Tuple[int, Any]:
        n, bad = _page_size(m, DEBUG_REQUESTS_DEFAULT_N)
        if bad:
            return bad
        recs = traces.recent(n) if traces is not None else []
        return 200, {"requests": recs, "count": len(recs)}

    http.route("GET", "/metrics", _metrics)
    http.route("GET", "/debug/requests", _debug_requests)
    http.route("GET", "/debug/spans", lambda m, _b, _h: debug_spans(m))


class ObsServer:
    """Sidecar observability endpoint for HTTP-less processes.

    Serves exactly ``/metrics``, ``/debug/requests``, ``/debug/spans``
    and a trivial ``/health`` on a daemon-threaded stdlib server; the
    owning loop never blocks on it and ``stop()`` is idempotent.
    """

    def __init__(self, registry: MetricsRegistry,
                 traces: Optional[TraceBuffer] = None,
                 host: str = "127.0.0.1", port: int = 0) -> None:
        self.registry = registry
        self.traces = traces
        # the sidecar instruments its own scrapes too (http_requests_
        # total on a worker IS the scrape count — a cheap liveness probe)
        self.http = JsonHttpService(host, port, registry=registry)
        mount_obs_routes(self.http, registry, traces)
        self.http.route("GET", "/health",
                        lambda _m, _b, _h: (200, {"ok": True}))
        self._started = False

    def start(self) -> Tuple[str, int]:
        host, port = self.http.start()
        self._started = True
        return host, port

    @property
    def port(self) -> int:
        return self.http.port

    def stop(self) -> None:
        if self._started:
            self.http.stop()
            self._started = False
