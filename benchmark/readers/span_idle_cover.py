"""Of the seconds the device sat idle INSIDE the traced stretch, the share
that lies inside a named leaf span of the program: what the host was doing
while the chip waited.

The program stamps its phase spans (``rafiki_tpu.obs.SPANS``) with
``time.time_ns()``; the trace carries ``profile_start_time`` in the same
domain and every device event's start relative to it (``session_bounds``),
so spans minus that start are on the trace's clock. Whether the two really are one clock is TESTED on every run,
never assumed — the bracket test: the k-th execution of the program matching
``module_pattern`` has to start no earlier than the k-th ``dispatch`` span
opens and (where ``wait`` names the span of the output sync) end no later
than the k-th ``wait`` span closes. The profiler lays the device's plane on
the host's clock only to a few tenths of a millisecond (PR 28 read a program
starting 0.16 and 0.73 ms BEFORE its launch opened), so the device's events
are moved by the smallest offset under which every bracket holds — none when
they hold as they stand — and the line says which (``clock``,
``fitted_offset_us``, ``clock_slack_us``). Where no offset satisfies both
brackets, or the one that does is beyond the 1 ms tolerance, the clocks are
not one clock and the reader says nothing — never a wrong share.

Interior idle: the complement of the union of the ``XLA Ops`` events over
the stretch from the first to the last of {device events, program spans
wholly inside the session}. What ``trace_idle`` counts beyond that — the
profiler's own start and stop — is ``edge_idle_s``: no span can explain it.
A leaf is a span with no span inside it; idle inside a span that has
children but inside none of them is ``unnamed_idle_s`` (a hole in the
tiling), idle inside no program span at all is ``caller_idle_s``.

One earlier line ``host_spans`` says, for every span name, ``count``,
``total_s``, ``self_s`` and ``idle_s`` (device idle inside the spans' self
time); the leaves' ``idle_s`` + ``unnamed_idle_s`` + ``caller_idle_s`` is
``interior_idle_s``.
"""

from __future__ import annotations

import bisect
import glob
import os
from typing import Any, Dict, List, Optional, Sequence, Tuple

from benchmark import harness
from benchmark.readers import span_stat
from benchmark.trace_reduce import Event, find_xplane, module_calls

Interval = Tuple[float, float]
BRACKET_TOLERANCE_NS = 1e6


def find_run_xplane() -> Optional[str]:
    """The one trace a run has: ``.bench_work/<cell>/trace`` (the newest,
    should an interrupted run have left another behind)."""
    found = []
    for trace_dir in glob.glob(os.path.join(harness.ROOT, ".bench_work",
                                            "*", "trace")):
        try:
            found.append(find_xplane(trace_dir))
        except FileNotFoundError:
            continue
    return max(found, key=os.path.getmtime) if found else None


def session_bounds(path: str) -> Optional[Tuple[int, int]]:
    """``(profile_start_time, profile_stop_time)`` of a trace file, in
    Unix nanoseconds; every event's ``start_ns`` is relative to the first."""
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        if plane.name == "Task Environment":
            stats = dict(plane.stats)
            if "profile_start_time" in stats \
                    and "profile_stop_time" in stats:
                return (int(stats["profile_start_time"]),
                        int(stats["profile_stop_time"]))
    return None


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for lo, hi in sorted(i for i in intervals if i[1] > i[0]):
        if out and lo <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], hi))
        else:
            out.append((lo, hi))
    return out


def complement(merged: Sequence[Interval], lo: float, hi: float
               ) -> List[Interval]:
    """[lo, hi] minus a sorted disjoint union."""
    out, cur = [], lo
    for a, b in merged:
        if b <= cur:
            continue
        if a >= hi:
            break
        if a > cur:
            out.append((cur, min(a, hi)))
        cur = max(cur, b)
    if cur < hi:
        out.append((cur, hi))
    return out


class Measure:
    """Length of a sorted disjoint union inside any [a, b], by bisection
    (a trace has tens of thousands of gaps between ops)."""

    def __init__(self, merged: Sequence[Interval]) -> None:
        self.lo = [a for a, _b in merged]
        self.hi = [b for _a, b in merged]
        self.cum = [0.0]
        for a, b in merged:
            self.cum.append(self.cum[-1] + (b - a))

    def below(self, x: float) -> float:
        i = bisect.bisect_right(self.hi, x)  # intervals wholly below x
        part = x - self.lo[i] if i < len(self.lo) and self.lo[i] < x \
            else 0.0
        return self.cum[i] + part

    def inside(self, intervals: Sequence[Interval]) -> float:
        return sum(self.below(b) - self.below(a) for a, b in intervals)


def bracket(dispatches: Sequence[Interval], waits: Sequence[Interval],
            execs: Sequence[Event]) -> Dict[str, Any]:
    """The bracket test. Returns ``offset_ns`` to add to device times — the
    value nearest 0 under which every bracket holds (0: the clocks agree as
    they stand) — or None where none does; with it the worst violation at
    offset 0, the residual ``max(device_end - wait.t1)`` and the slack."""
    if not dispatches or len(dispatches) != len(execs) \
            or (waits and len(waits) != len(dispatches)):
        return {"offset_ns": None, "why": "dispatch spans and program "
                f"executions do not pair up ({len(dispatches)} spans, "
                f"{len(waits)} waits, {len(execs)} executions)"}
    # offset + device_start >= dispatch.t0 ; offset + device_end <= wait.t1
    lower = max(d[0] - e[1] for d, e in zip(dispatches, execs))
    upper = min((w[1] - (e[1] + e[2]) for w, e in zip(waits, execs)),
                default=float("inf"))
    out = {"bracket_violation_us": max(lower, -upper, 0.0) / 1e3,
           "clock_residual_us": None if not waits else -upper / 1e3,
           # how far the device's events could be moved, earlier and
           # later, before a bracket breaks: what the split of idle time
           # between neighbouring phases cannot resolve
           "clock_slack_us": [lower / 1e3,
                              None if not waits else upper / 1e3],
           "dispatches": len(dispatches)}
    offset = (lower + upper) / 2 if lower > upper \
        else min(max(0.0, lower), upper)
    if lower > upper + BRACKET_TOLERANCE_NS \
            or abs(offset) > BRACKET_TOLERANCE_NS:
        # no offset satisfies both brackets, or only one beyond what the
        # profiler's own alignment explains
        return {**out, "offset_ns": None, "clock": "unaligned",
                "why": "the brackets hold under no offset within the "
                "tolerance: spans and device events are not on one clock"}
    if offset == 0:
        return {**out, "offset_ns": 0.0, "clock": "same_origin"}
    return {**out, "offset_ns": offset, "clock": "fitted",
            "fitted_offset_us": offset / 1e3}


def cover(records: Sequence[span_stat.Record], ops: Sequence[Event],
          modules: Sequence[Event], params: Dict[str, Any],
          session_ns: float, window_s: Optional[float] = None
          ) -> Tuple[Optional[float], Dict[str, Any]]:
    """The share and the ``host_spans`` line, from program records already
    shifted to the trace's clock (0 = ``profile_start_time``), the device's
    op and module events, and the session's length."""
    from rafiki_tpu.obs.trace import SpanRing

    prefix = params["prefix"]
    spans = [r for r in records if r[0].startswith(prefix) and r[2] > r[1]
             and r[1] >= 0 and r[2] <= session_ns]
    line: Dict[str, Any] = {"prefix": prefix, "spans_in_session": len(spans)}
    ops = [e for e in ops if e[2] > 0]
    if not spans or not ops:
        return None, {**line, "why": "no spans or no device events"}

    def named(name):
        return sorted((r[1], r[2]) for r in spans if r[0] == name)

    execs = sorted(module_calls(modules, params["module_pattern"]),
                   key=lambda e: e[1])
    waits = named(params["wait"]) if params.get("wait") else []
    fit = bracket(named(params["dispatch"]), waits, execs)
    offset = fit.pop("offset_ns")
    line.update(fit)
    if offset is None:
        return None, line

    busy = union([(s + offset, s + d + offset) for _n, s, d in ops])
    lo = min(busy[0][0], min(r[1] for r in spans))
    hi = max(busy[-1][1], max(r[2] for r in spans))
    idle = Measure(complement(busy, lo, hi))
    interior = idle.cum[-1]

    children: Dict[int, List[Interval]] = {}
    for r in spans:
        children.setdefault(r[3], []).append((r[1], r[2]))
    parents = set(children)
    self_ns = SpanRing.self_time(spans)
    table: Dict[str, Dict[str, Any]] = {}
    leaf_cover: List[Interval] = []
    any_cover: List[Interval] = []
    for r in spans:
        row = table.setdefault(r[0], {"count": 0, "total_s": 0.0,
                                      "self_s": 0.0, "idle_s": 0.0,
                                      "leaf": r[4] not in parents})
        row["count"] += 1
        row["total_s"] += (r[2] - r[1]) / 1e9
        row["self_s"] += self_ns[r[4]] / 1e9
        any_cover.append((r[1], r[2]))
        if r[4] not in parents:
            leaf_cover.append((r[1], r[2]))
            row["idle_s"] += idle.inside([(r[1], r[2])]) / 1e9
        else:  # a parent: idle inside it and inside none of its children
            own = complement(union(children[r[4]]), r[1], r[2])
            row["idle_s"] += idle.inside(own) / 1e9
    in_leaf = idle.inside(union(leaf_cover))
    in_any = idle.inside(union(any_cover))
    busy_ns = sum(b - a for a, b in busy)
    line.update(
        interior_s=(hi - lo) / 1e9, interior_idle_s=interior / 1e9,
        named_idle_s=in_leaf / 1e9,
        unnamed_idle_s=(in_any - in_leaf) / 1e9,
        caller_idle_s=(interior - in_any) / 1e9,
        edge_idle_s=None if window_s is None
        else window_s - busy_ns / 1e9 - interior / 1e9,
        session_s=session_ns / 1e9, spans=table)
    if interior <= 0:
        return None, {**line, "why": "no interior idle"}
    return 100.0 * in_leaf / interior, line


def read(spec: Dict[str, Any], run: Dict[str, Any]) -> Optional[float]:
    trace = run.get("trace")
    if trace is None or not trace.lines:
        return None
    path = find_run_xplane()
    bounds = session_bounds(path) if path else None
    if bounds is None:
        return None
    start, stop = bounds
    records = span_stat.program_records(start, stop)
    if not records:
        return None
    shifted = [(r[0], r[1] - start, r[2] - start) + tuple(r[3:])
               for r in records]
    chip = trace.chips[0]
    value, line = cover(shifted, trace.ops(chip), trace.modules(chip),
                        spec["params"], stop - start, trace.window_s)
    harness.emit("host_spans", metric=spec["name"], **line)
    return value
