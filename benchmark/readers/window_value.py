"""A number the driver worked out over the measured window on the host's
clock and put on its ``window`` line: a tail that swings too widely from
seed to seed to carry a bound stands here, beside the end-to-end metric it
moves. Nothing there -> says nothing."""

from __future__ import annotations

from typing import Any, Dict, Optional


def read(spec: Dict[str, Any], run: Dict[str, Any]) -> Optional[float]:
    v = run.get("window", {}).get(spec["params"]["key"])
    return None if v is None else float(v)
