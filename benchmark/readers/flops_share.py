"""The whole step's share of the chip's peak: the operations the window's
work needs (``work`` facts of the run times the cost function named in the
file, from shapes) over the window's seconds and the peak of all chips
used. Bandwidth-bound work reads low here by design."""

from __future__ import annotations

from typing import Any, Dict, Optional

from benchmark import costs


def read(spec: Dict[str, Any], run: Dict[str, Any]) -> Optional[float]:
    p = spec["params"]
    units = [run.get(k) for k in p["work"]]
    if run.get("peaks") is None or any(u is None for u in units) \
            or not run.get("window_s"):
        return None
    per_unit = getattr(costs, p["cost_function"])(run["config"])
    peak = float(run["peaks"]["bf16_flops_per_s"]) * int(run["chips"])
    return 100.0 * sum(units) * per_unit / run["window_s"] / peak
