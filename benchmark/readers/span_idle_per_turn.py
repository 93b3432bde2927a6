"""Device-idle milliseconds a turn that the engine's own host work leaves
the chip: of the idle INSIDE the traced stretch, what lies inside the
engine's leaf spans other than the ones named ``exclude`` (the output
sync's wait for the chip, ``engine.sync_ready``: the program's own launch
and in-program idle), over the ``turn`` spans of the stretch.

The clock is ``span_idle_cover``'s (spans minus ``profile_start_time``) and
is tested the same way, by brackets, but with the tightest upper end the
host has: the k-th execution of ``module_pattern`` starts at or after the
k-th ``dispatch`` span opens and ends at or before the k-th ``ready`` span
closes — the span that closes when the chip's program has finished, before
any copy to the host. Where the ``prefill_dispatch`` spans and the
executions of ``prefill_pattern`` pair up, they add lower brackets (which
can only tighten the fit). The device's events are moved by the offset
nearest 0 under which every bracket holds; where none within
``BRACKET_TOLERANCE_NS`` does, the clocks are not one and the reader says
nothing.

One earlier line ``turn_idle``: the clock (``offset_us``, and ``slack_us``:
how far the device's events could move earlier and later before a bracket
breaks), ``turns``, and in ms a turn the idle inside each leaf name
(``ready`` among them), inside a span but none of its children
(``unnamed``), inside no span of the engine (``caller``) and in all
(``interior``): leaves + unnamed + caller = interior.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

from benchmark import harness
from benchmark.readers import span_stat
from benchmark.readers.span_idle_cover import (BRACKET_TOLERANCE_NS,
                                               Measure, complement,
                                               find_run_xplane,
                                               session_bounds, union)
from benchmark.trace_reduce import Event, module_calls

Interval = Tuple[float, float]


def fit(lowers: Sequence[Tuple[Interval, Event]],
        uppers: Sequence[Tuple[Interval, Event]]) -> Dict[str, Any]:
    """The offset to add to device times: nearest 0 in [max lower, min
    upper], where ``lowers`` pair a span that must open before its
    execution starts and ``uppers`` one that must close after it ends.
    Returns ``offset_ns`` (None: no fit) with the slack either way."""
    lower = max(s[0] - e[1] for s, e in lowers)
    upper = min(s[1] - (e[1] + e[2]) for s, e in uppers)
    if lower > upper + BRACKET_TOLERANCE_NS:
        return {"offset_ns": None, "clock": "unaligned",
                "bracket_gap_us": (lower - upper) / 1e3}
    offset = (lower + upper) / 2 if lower > upper \
        else min(max(0.0, lower), upper)
    if abs(offset) > BRACKET_TOLERANCE_NS:
        return {"offset_ns": None, "clock": "unaligned",
                "needed_offset_us": offset / 1e3}
    return {"offset_ns": offset,
            "clock": "same_origin" if offset == 0 else "fitted",
            "offset_us": offset / 1e3,
            "slack_us": [(offset - lower) / 1e3, (upper - offset) / 1e3]}


def per_turn(records: Sequence[span_stat.Record], ops: Sequence[Event],
             modules: Sequence[Event], params: Dict[str, Any],
             session_ns: float) -> Tuple[Optional[float], Dict[str, Any]]:
    """The value and the ``turn_idle`` line, from program records already
    on the trace's clock (0 = ``profile_start_time``), the device's op and
    module events, and the session's length."""
    prefix = params["prefix"]
    spans = [r for r in records if r[0].startswith(prefix) and r[2] > r[1]
             and r[1] >= 0 and r[2] <= session_ns]
    ops = [e for e in ops if e[2] > 0]
    line: Dict[str, Any] = {"prefix": prefix}
    turns = sum(r[0] == params["turn"] for r in spans)
    if not turns or not ops:
        return None, {**line, "why": "no turns or no device events"}

    def named(name):
        return sorted((r[1], r[2]) for r in spans if r[0] == name)

    def calls(pattern):
        return sorted(module_calls(modules, pattern), key=lambda e: e[1])

    dispatches, readies = named(params["dispatch"]), named(params["ready"])
    execs = calls(params["module_pattern"])
    if not dispatches or not len(dispatches) == len(readies) == len(execs):
        return None, {**line, "why": "dispatch and ready spans and program "
                      f"executions do not pair up ({len(dispatches)} "
                      f"dispatches, {len(readies)} readies, {len(execs)} "
                      "executions)"}
    lowers = list(zip(dispatches, execs))
    prefills = named(params["prefill_dispatch"])
    prefill_execs = calls(params["prefill_pattern"])
    if prefills and len(prefills) == len(prefill_execs):
        lowers += zip(prefills, prefill_execs)
    line["brackets"] = {"lower": len(lowers), "upper": len(readies)}
    clock = fit(lowers, list(zip(readies, execs)))
    offset = clock.pop("offset_ns")
    line.update(clock)
    if offset is None:
        return None, line

    busy = union([(s + offset, s + d + offset) for _n, s, d in ops])
    lo = min(busy[0][0], min(r[1] for r in spans))
    hi = max(busy[-1][1], max(r[2] for r in spans))
    idle = Measure(complement(busy, lo, hi))
    parents = {r[3] for r in spans}
    leaves: Dict[str, List[Interval]] = {}
    for r in spans:
        if r[4] not in parents:
            leaves.setdefault(r[0], []).append((r[1], r[2]))
    ms = 1e6 * turns  # ns -> ms a turn
    in_leaf = {n: idle.inside(union(v)) / ms for n, v in leaves.items()}
    named_ns = idle.inside(union([i for v in leaves.values() for i in v]))
    any_ns = idle.inside(union([(r[1], r[2]) for r in spans]))
    value = sum(v for n, v in in_leaf.items()
                if n not in params["exclude"])
    line.update(
        turns=turns,
        leaf_idle_ms_per_turn=dict(sorted(in_leaf.items(),
                                          key=lambda kv: -kv[1])),
        unnamed_idle_ms_per_turn=(any_ns - named_ns) / ms,
        caller_idle_ms_per_turn=(idle.cum[-1] - any_ns) / ms,
        interior_idle_ms_per_turn=idle.cum[-1] / ms,
        host_exposed_ms_per_turn=value)
    return value, line


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
    value, line = per_turn(shifted, trace.ops(chip), trace.modules(chip),
                           spec["params"], stop - start)
    harness.emit("turn_idle", metric=spec["name"], **line)
    return value
