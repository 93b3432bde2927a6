"""A kernel's share of its roofline over the traced stretch: the least
time the chip could take for the calls made — the larger of operations
over peak FLOP/s and bytes over peak bytes/s, from ``costs.py`` — over the
device time of the kernel's events in the trace. The kernel's events are
the ops whose text matches ``op_pattern`` inside executions of the modules
matching ``module_pattern`` (the Pallas calls carry no name of their own
yet). Finds nothing -> says nothing: never 0."""

from __future__ import annotations

from typing import Any, Dict, Optional

from benchmark import costs
from benchmark.trace_reduce import kernel_time_ns, module_calls


def read(spec: Dict[str, Any], run: Dict[str, Any]) -> Optional[float]:
    p = spec["params"]
    trace, peaks = run.get("trace"), run.get("peaks")
    if trace is None or peaks is None or not trace.lines:
        return None
    chip = trace.chips[0]
    ops, mods = trace.ops(chip), trace.modules(chip)
    t_ns, n_events = kernel_time_ns(ops, mods, p["module_pattern"],
                                    p["op_pattern"])
    n_calls = len(module_calls(mods, p["module_pattern"]))
    if t_ns <= 0 or n_events == 0 or n_calls == 0:
        return None
    # the cost of ONE program execution's kernel calls, from the run's own
    # facts (mean live tokens a dispatch, the batch) and the configuration
    # (or a constant the metric's file states)
    consts = p.get("constants", {})
    facts = {k: consts.get(k, run.get("traced", {}).get(k, run.get(k)))
             for k in p.get("facts", [])}
    if any(v is None for v in facts.values()):
        return None
    cost = getattr(costs, p["cost_function"])(run["config"], **facts)
    per_exec = float(p.get("kernel_calls_per_execution", 1))
    if p.get("calls_scale_with_layers"):
        per_exec *= int(run["config"]["num_hidden_layers"])
    least_s = max(cost["flops"] / float(peaks["bf16_flops_per_s"]),
                  cost["bytes"] / float(peaks["hbm_bytes_per_s"]))
    return 100.0 * least_s * per_exec * n_calls / (t_ns / 1e9)
