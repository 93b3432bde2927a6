"""Share of the traced stretch in which no operation ran on the device:
1 - (union of the device's op intervals) / (traced seconds), averaged over
chips."""

from __future__ import annotations

from typing import Any, Dict, Optional


def read(spec: Dict[str, Any], run: Dict[str, Any]) -> Optional[float]:
    trace = run.get("trace")
    if trace is None or not trace.lines or not trace.window_s:
        return None
    busy = trace.busy_s()
    if busy <= 0:
        return None
    return 100.0 * (1.0 - busy / trace.window_s)
