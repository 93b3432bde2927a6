"""A kernel's share of its roofline over the traced stretch, for a cost
module the metric's file NAMES (``params.cost_module``) and for facts that
are RATIOS OF THE PROGRAM'S COUNTERS over that stretch.

The kernel's events are the ops whose text matches ``op_pattern`` inside
executions of the modules matching ``module_pattern``; their summed device
time is the denominator. The numerator is the least time the chip could
take for ONE kernel call — the larger of operations over peak FLOP/s and
bytes over peak bytes/s, from ``<cost_module>.<cost_function>(config,
**facts)`` — times the calls made: ``kernel_calls_per_execution`` (times
the configuration's layers with ``calls_scale_with_layers``) times the
module executions in the trace. A "call" may be several events (the three
grouped products of one expert layer are one call of ``moe_grouped_cost``).

``facts``: plain facts of the traced stretch (``live_tokens``), as
``trace_kernel_roofline`` takes them. ``counter_facts``: ``{name:
{"numerator": [...], "denominator": [...]}}`` — products of the traced
stretch's counters, or of the configuration's own numbers, divided: the
mean a call. A counter the program does not have (a parent commit) ->
nothing to read -> ``None``, never 0."""

from __future__ import annotations

import importlib
from typing import Any, Dict, Optional

from benchmark.trace_reduce import kernel_time_ns, module_calls


def _product(names, stats: Dict[str, Any], config: Dict[str, Any]
             ) -> Optional[float]:
    out = 1.0
    for n in names:
        v = stats.get(n, config.get(n))
        if v is None or isinstance(v, (dict, list, str)):
            return None
        out *= float(v)
    return out


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
    traced = run.get("traced") or {}
    facts = {k: traced.get(k, run.get(k)) for k in p.get("facts", [])}
    stats = traced.get("stats") or {}
    for name, ratio in (p.get("counter_facts") or {}).items():
        num = _product(ratio["numerator"], stats, run["config"])
        den = _product(ratio["denominator"], stats, run["config"])
        facts[name] = None if num is None or not den else num / den
    if any(v is None for v in facts.values()):
        return None
    costs = importlib.import_module(f"benchmark.{p['cost_module']}")
    cost = getattr(costs, p["cost_function"])(run["config"], **facts)
    per_exec = float(p.get("kernel_calls_per_execution", 1))
    if p.get("calls_scale_with_layers"):
        per_exec *= int(run["config"]["num_hidden_layers"])
    least_s = max(cost["flops"] / float(peaks["bf16_flops_per_s"]),
                  cost["bytes"] / float(peaks["hbm_bytes_per_s"]))
    return 100.0 * least_s * per_exec * n_calls / (t_ns / 1e9)
