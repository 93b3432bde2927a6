"""``numerator / denominator`` of the engine's own counters over the
measured window, times ``scale``. Either may be a product of counter names
and of plain facts of the run (``max_slots``)."""

from __future__ import annotations

from typing import Any, Dict, Optional


def _product(names, run: Dict[str, Any]) -> Optional[float]:
    out = 1.0
    for n in names:
        v = run.get("stats", {}).get(n, run.get(n))
        if v is None:
            return None
        out *= float(v)
    return out


def read(spec: Dict[str, Any], run: Dict[str, Any]) -> Optional[float]:
    p = spec["params"]
    num, den = _product(p["numerator"], run), _product(p["denominator"], run)
    if num is None or den is None or den == 0:
        return None
    return float(p.get("scale", 1.0)) * num / den
