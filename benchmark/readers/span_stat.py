"""A statistic of the program's own phase spans (``rafiki_tpu.obs.SPANS``)
over a stretch that the ring itself bounds: the durations of one span name,
or the gaps between a pair of request instants joined on the request's key.
Host stamps need no device clock, so no trace is opened here.

``params``: ``span`` (a span name) or ``from`` + ``to`` (two instant names:
each ``to`` inside the stretch is paired with the latest earlier ``from`` of
its key); ``stat`` (``mean``, ``sum``, ``p50``, ``p95``); optionally ``per``
(divide a sum by the count of another span) and ``scale`` (durations are
nanoseconds); and ``stretch``, the name of the record that bounds it:

- an instant — ``engine.stats_reset``, which the engine writes when the
  driver zeroes its counters where the measured window opens: the stretch
  runs from it for ``run["window_s"]`` seconds, the WHOLE window. A run with
  a traced stretch resets once more where that opens, so there the window's
  instant is the last but one; where the ring no longer holds it (a window
  longer than the ring) the reader says nothing.
- a span — ``train.epoch``: the newest one, start to end (the traced epoch
  of a traced run, the one the trace's own metrics read).

A pair's samples, median, p95 and mean go on an earlier line ``span_stat``.
Fewer than ten samples for a percentile, no such record, or a program
without the ring -> says nothing.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

from benchmark import harness

#: a program record, as ``SpanRing`` keeps it
Record = Tuple[str, int, int, int, int, Any, Optional[Dict[str, Any]]]
MIN_PERCENTILE_SAMPLES = 10


def program_records(since_ns: int = 0, until_ns: Optional[int] = None
                    ) -> Optional[List[Record]]:
    """The program's ring, or None where the program has none (a parent
    commit from before the spans)."""
    try:
        from rafiki_tpu.obs import SPANS
    except ImportError:
        return None
    return SPANS.snapshot(since_ns, until_ns)


def stretch(records: Sequence[Record], name: str, run: Dict[str, Any]
            ) -> Optional[Tuple[int, int]]:
    """``(lo, hi)`` in Unix nanoseconds: see the module's docstring."""
    marks = sorted((r[1], r[2]) for r in records if r[0] == name)
    if not marks:
        return None
    if marks[-1][1] > marks[-1][0]:  # a span: the newest, start to end
        return marks[-1]
    resets = 2 if run.get("traced") else 1
    if len(marks) < resets:
        return None
    lo = marks[-resets][0]
    return lo, lo + int(float(run["window_s"]) * 1e9)


def durations_ns(records: Sequence[Record], name: str, lo: int, hi: int
                 ) -> List[int]:
    """Durations of the spans called ``name`` lying wholly in [lo, hi]."""
    return [r[2] - r[1] for r in records
            if r[0] == name and r[1] >= lo and r[2] <= hi and r[2] > r[1]]


def gaps_ns(records: Sequence[Record], name_from: str, name_to: str,
            lo: int, hi: int) -> List[int]:
    """For every ``name_to`` instant in [lo, hi]: its distance from the
    latest earlier ``name_from`` instant of the same key (each ``from`` is
    used once, so a re-admission without a fresh submit gives no sample)."""
    last: Dict[Any, int] = {}
    out = []
    for r in sorted(records, key=lambda r: (r[1], r[4])):
        if r[5] is None:
            continue
        if r[0] == name_from:
            last[r[5]] = r[1]
        elif r[0] == name_to and r[5] in last:
            t0 = last.pop(r[5])
            if lo <= r[1] <= hi:
                out.append(r[1] - t0)
    return out


def statistic(samples: Sequence[float], stat: str) -> Optional[float]:
    if not samples:
        return None
    if stat == "sum":
        return float(sum(samples))
    if stat == "mean":
        return float(sum(samples)) / len(samples)
    if stat in ("p50", "p95"):
        if len(samples) < MIN_PERCENTILE_SAMPLES:
            return None
        return harness.percentile(list(samples), float(stat[1:]))
    raise ValueError(f"unknown stat {stat!r}")


def samples_of(records: Sequence[Record], params: Dict[str, Any], lo: int,
               hi: int) -> List[int]:
    if "span" in params:
        return durations_ns(records, params["span"], lo, hi)
    return gaps_ns(records, params["from"], params["to"], lo, hi)


def stat_of(records: Sequence[Record], params: Dict[str, Any], lo: int,
            hi: int) -> Optional[float]:
    """The number, from records and a stretch on one clock."""
    value = statistic(samples_of(records, params, lo, hi), params["stat"])
    if value is None:
        return None
    if "per" in params:
        n = len(durations_ns(records, params["per"], lo, hi))
        if n == 0:
            return None
        value /= n
    return float(params.get("scale", 1.0)) * value


def read(spec: Dict[str, Any], run: Dict[str, Any]) -> Optional[float]:
    # a ``from`` may lie before the stretch: take the whole ring
    records = program_records()
    if not records:
        return None
    params = spec["params"]
    bounds = stretch(records, params["stretch"], run)
    if bounds is None:
        return None
    if "from" in params:
        scale = float(params.get("scale", 1.0))
        gaps = [scale * g for g in samples_of(records, params, *bounds)]
        harness.emit("span_stat", metric=spec["name"], unit=spec["unit"],
                     pair=f"{params['from']}->{params['to']}",
                     stretch_s=(bounds[1] - bounds[0]) / 1e9, n=len(gaps),
                     p50=statistic(gaps, "p50"), p95=statistic(gaps, "p95"),
                     mean=statistic(gaps, "mean"))
    return stat_of(records, params, *bounds)
