"""``flops_share`` for a cost module the metric's file NAMES
(``params.cost_module``, a module under ``benchmark/``): the operations
the window's work needs (``work`` facts of the run times
``<cost_module>.<cost_function>(config)``) over the window's seconds and
the peak of all chips used. ``readers/flops_share.py`` reads ``costs.py``
alone; a configuration of another architecture brings its own."""

from __future__ import annotations

import importlib
from typing import Any, Dict, Optional


def read(spec: Dict[str, Any], run: Dict[str, Any]) -> Optional[float]:
    p = spec["params"]
    units = [run.get(k) for k in p["work"]]
    if run.get("peaks") is None or any(u is None for u in units) \
            or not run.get("window_s"):
        return None
    costs = importlib.import_module(f"benchmark.{p['cost_module']}")
    per_unit = getattr(costs, p["cost_function"])(run["config"])
    peak = float(run["peaks"]["bf16_flops_per_s"]) * int(run["chips"])
    return 100.0 * sum(units) * per_unit / run["window_s"] / peak
