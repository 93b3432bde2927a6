"""Request tracing (trace IDs + a bounded ring of per-request records)
and phase spans (a bounded ring of timed spans on the profiler's clock).

One trace ID is minted at the predictor (or honored from an inbound
``X-Rafiki-Trace-Id`` header), rides in the scatter payload to the
workers, and every process appends its own span records — queued,
admitted, prefill, per-N decode-step marks, first_token,
done/expired/preempted — into its local :class:`TraceBuffer`. Each
service exposes its buffer as ``GET /debug/requests?n=K``; joining the
outputs on the trace ID answers "where did this request's 900 ms go?"
across predictor and worker without any central collector.

Timestamps are **monotonic process uptime seconds** (``uptime_s`` at
record level, ``t`` per span): durations within one process are exact,
wall-clock steps can't corrupt them, and cross-process alignment happens
by trace ID, not by clock. Each record also carries ``t0_unix_ns``, the
``time.time_ns()`` at which it was opened, so a request's timeline can be
laid beside the phase spans below.

Phase spans (:class:`SpanRing`, one process-level instance
:data:`SPANS`) say what the HOST was doing: the decode engine's turn and
the train loop tile themselves with named spans stamped by
``time.time_ns()`` — the clock a ``jax.profiler`` trace is on once its
``profile_start_time`` is subtracted — so every stretch in which the
device sat idle can be named by the phase that covers it
(docs/observability.md "Phase spans").
"""

from __future__ import annotations

import collections
import itertools
import threading
import time
import uuid
from time import time_ns
from typing import Any, Dict, Iterable, List, Optional, Tuple

#: inbound trace ids are untrusted header bytes: bound the length and
#: alphabet so a hostile client can't stuff the ring with megabyte ids
_TRACE_ID_OK = set("abcdefghijklmnopqrstuvwxyz"
                   "ABCDEFGHIJKLMNOPQRSTUVWXYZ0123456789_.:-")
_TRACE_ID_MAX = 128


def mint_trace_id() -> str:
    return uuid.uuid4().hex


def sanitize_trace_id(trace_id: Optional[str]) -> str:
    """A safe trace id: the inbound one when it is well-formed, else
    empty (caller mints). Never raises — a garbage header must degrade
    to a fresh id, not 500 the request."""
    if not isinstance(trace_id, str):
        return ""
    tid = trace_id.strip()
    if not tid or len(tid) > _TRACE_ID_MAX or \
            any(c not in _TRACE_ID_OK for c in tid):
        return ""
    return tid


class TraceBuffer:
    """Bounded ring of request trace records (newest win; churn evicts
    oldest). O(1) span append via a trace-id index; every read returns
    JSON-safe copies so HTTP handlers never alias live mutable state."""

    def __init__(self, maxlen: int = 256) -> None:
        self.maxlen = max(1, int(maxlen))
        self._lock = threading.Lock()
        self._ring: "collections.deque[Dict[str, Any]]" = \
            collections.deque()
        self._index: Dict[str, Dict[str, Any]] = {}
        self._t0 = time.monotonic()

    def _now(self) -> float:
        return time.monotonic() - self._t0

    def start(self, trace_id: str, request_id: str = "",
              span: str = "queued", **attrs: Any) -> str:
        """Open a record for ``trace_id`` with its first span. Returns
        the trace id (convenience for ``start(mint_trace_id(), ...)``
        call sites)."""
        now = self._now()
        rec = {"trace_id": str(trace_id),
               "request_id": str(request_id),
               "uptime_s": now,
               "t0_unix_ns": time_ns(),
               "spans": [dict(attrs, name=span, t=now)]}
        with self._lock:
            if len(self._ring) >= self.maxlen:
                old = self._ring.popleft()
                # only unindex if the slot still points at the evictee
                if self._index.get(old["trace_id"]) is old:
                    del self._index[old["trace_id"]]
            self._ring.append(rec)
            self._index[rec["trace_id"]] = rec
        return rec["trace_id"]

    def add_span(self, trace_id: str, name: str, **attrs: Any) -> None:
        """Append a span to ``trace_id``'s record, creating the record
        if it was evicted (late spans under churn must not be lost —
        a fragment beats nothing when debugging)."""
        with self._lock:
            rec = self._index.get(str(trace_id))
        if rec is None:
            self.start(str(trace_id), span=name, **attrs)
            return
        span = dict(attrs, name=name, t=self._now())
        with self._lock:
            rec["spans"].append(span)

    def get(self, trace_id: str) -> Optional[Dict[str, Any]]:
        with self._lock:
            rec = self._index.get(str(trace_id))
            return None if rec is None else _copy(rec)

    def recent(self, n: int = 32) -> List[Dict[str, Any]]:
        """The most recent ``n`` records, newest first (the
        ``/debug/requests`` payload)."""
        n = max(0, int(n))
        with self._lock:
            tail = list(self._ring)[-n:] if n else []
        return [_copy(r) for r in reversed(tail)]

    def __len__(self) -> int:
        with self._lock:
            return len(self._ring)


def _copy(rec: Dict[str, Any]) -> Dict[str, Any]:
    out = dict(rec)
    out["spans"] = [dict(s) for s in rec["spans"]]
    return out


#: one phase-span record: ``(name, t0_ns, t1_ns, parent_seq, seq, key,
#: attrs)``. Both stamps are ``time.time_ns()``; an instant has
#: ``t1 == t0``. ``seq`` is the ring's own counter (from 1),
#: ``parent_seq`` the span that was open on the same thread when this one
#: began (0: none), ``key`` a request id for request records, else None.
SpanRecord = Tuple[str, int, int, int, int, Any, Optional[Dict[str, Any]]]

#: records the ring holds: ~12 a decode turn at 18 turns a second is over
#: two minutes, enough for a profiled stretch to be read after it ended
SPAN_RING_MAXLEN = 32768


class _Span:
    """One open span: the context manager :meth:`SpanRing.span` hands
    out. Its record is appended when it closes (so a parent follows its
    children in the ring); ``t0`` / ``t1`` / ``seq`` stay readable."""

    __slots__ = ("_ring", "_stack", "name", "attrs", "t0", "t1", "seq",
                 "parent_seq")

    def __init__(self, ring: "SpanRing", name: str,
                 attrs: Optional[Dict[str, Any]]) -> None:
        self._ring = ring
        self.name = name
        self.attrs = attrs
        self.t1 = 0

    def set(self, **attrs: Any) -> None:
        """Attributes known only once the work is under way."""
        if self.attrs is None:
            self.attrs = attrs
        else:
            self.attrs.update(attrs)

    def __enter__(self) -> "_Span":
        ring = self._ring
        self._stack = stack = ring._stack()
        self.parent_seq = stack[-1] if stack else 0
        self.seq = seq = next(ring._seq)
        stack.append(seq)
        self.t0 = time_ns()
        return self

    def __exit__(self, *_exc: Any) -> bool:
        self.t1 = t1 = time_ns()
        self._stack.pop()
        self._ring._ring.append((self.name, self.t0, t1, self.parent_seq,
                                 self.seq, None, self.attrs))
        return False


class SpanRing:
    """Bounded ring of phase spans and instants (newest win).

    Always on: a span costs two ``time_ns()`` reads, a tuple and one
    ``deque.append`` (atomic under the GIL, so writers take no lock);
    nesting is tracked per thread. ``time.time_ns()`` is CLOCK_REALTIME
    and can be stepped: over the seconds a profile lasts that is
    accepted, not engineered around."""

    def __init__(self, maxlen: int = SPAN_RING_MAXLEN) -> None:
        self._ring: "collections.deque[SpanRecord]" = \
            collections.deque(maxlen=max(1, int(maxlen)))
        self._seq = itertools.count(1)
        self._local = threading.local()

    def _stack(self) -> List[int]:
        try:
            return self._local.stack
        except AttributeError:
            stack = self._local.stack = []
            return stack

    def span(self, name: str, **attrs: Any) -> _Span:
        """``with ring.span("engine.admit"): ...`` — nests under the span
        open on this thread."""
        return _Span(self, name, attrs or None)

    def instant(self, name: str, key: Any = None,
                parent_seq: Optional[int] = None, **attrs: Any) -> int:
        """A point event. ``parent_seq`` defaults to the span open on
        this thread. Returns the record's ``seq``."""
        if parent_seq is None:
            stack = self._stack()
            parent_seq = stack[-1] if stack else 0
        seq = next(self._seq)
        t = time_ns()
        self._ring.append((name, t, t, parent_seq, seq, key,
                           attrs or None))
        return seq

    def snapshot(self, since_ns: int = 0,
                 until_ns: Optional[int] = None) -> List[SpanRecord]:
        """The records that overlap ``[since_ns, until_ns]`` (ended at or
        after ``since_ns``, began at or before ``until_ns``), oldest
        first by ``seq``."""
        recs = list(self._ring)  # one C call: atomic against appends
        if since_ns or until_ns is not None:
            hi = float("inf") if until_ns is None else until_ns
            recs = [r for r in recs if r[2] >= since_ns and r[1] <= hi]
        recs.sort(key=lambda r: r[4])
        return recs

    @staticmethod
    def self_time(records: Iterable[SpanRecord]) -> Dict[int, int]:
        """``{seq: ns}``: each record's duration minus what its children
        among ``records`` cover (children of one span never overlap: they
        ran one after another on its thread)."""
        records = list(records)
        out = {r[4]: r[2] - r[1] for r in records}
        for r in records:
            if r[3] in out:
                out[r[3]] -= r[2] - r[1]
        return out

    def __len__(self) -> int:
        return len(self._ring)


def span_as_dict(rec: SpanRecord) -> Dict[str, Any]:
    """A record as the JSON object ``GET /debug/spans`` serves."""
    name, t0, t1, parent_seq, seq, key, attrs = rec
    return {"name": name, "t0_unix_ns": t0, "t1_unix_ns": t1,
            "seq": seq, "parent_seq": parent_seq,
            "key": None if key is None else str(key),
            "attrs": attrs or {}}


#: the process's phase-span ring: the engine's turn and the train loop
#: write here, ``/debug/spans`` and the benchmark's readers read it
SPANS = SpanRing()
