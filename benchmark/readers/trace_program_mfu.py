"""One compiled program's share of the chip's peak while it runs: the
operations its useful work needs (``work_counter`` / ``calls_counter`` of
the traced stretch's own counters, a call, times the cost function named
in the file) over the mean device time of the executions of the modules
matching ``module_pattern``. Rows a program computes for nobody count as
time and not as work, so padding reads low here."""

from __future__ import annotations

from typing import Any, Dict, Optional

from benchmark import costs
from benchmark.trace_reduce import module_calls


def read(spec: Dict[str, Any], run: Dict[str, Any]) -> Optional[float]:
    p = spec["params"]
    trace, peaks = run.get("trace"), run.get("peaks")
    if trace is None or peaks is None or not trace.lines:
        return None
    stats = run.get("traced", {}).get("stats") or {}
    work, calls = stats.get(p["work_counter"]), stats.get(p["calls_counter"])
    execs = module_calls(trace.modules(trace.chips[0]), p["module_pattern"])
    busy_s = sum(d for _n, _s, d in execs) / 1e9
    if not work or not calls or busy_s <= 0:
        return None
    per_unit = getattr(costs, p["cost_function"])(run["config"])
    return 100.0 * (work / calls) * len(execs) * per_unit / busy_s \
        / float(peaks["bf16_flops_per_s"])
