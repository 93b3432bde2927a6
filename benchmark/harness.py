"""What every run of the benchmark does, whatever the cell: find the cell's
files by name, refuse a machine without the chips, count compilations,
read the device's memory, read the per-layer metrics, print the lines.

Nothing here knows a configuration, a traffic mix or a metric by name:
``BENCHMARK.json`` names them and each is a file of its own —
``configs/<config>.json``, ``traffic/<traffic>.json``,
``metrics/<metric>.json`` read by ``readers/<kind>.py``, driven by
``drivers/<kind>.py``.
"""

from __future__ import annotations

import importlib
import json
import os
import sys
import time
from typing import Any, Callable, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

#: rehearsal cells (``--rehearse``): tiny, CPU, never ``correct``
REHEARSAL_CELLS = {
    "tiny-lm.tiny-chat": {"name": "tiny-lm.tiny-chat", "config": "tiny-lm",
                          "traffic": "tiny-chat", "chips": 1},
    "tiny-vit.tiny-images": {"name": "tiny-vit.tiny-images",
                             "config": "tiny-vit", "traffic": "tiny-images",
                             "chips": 1},
}


def load_json(*parts: str) -> Dict[str, Any]:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_manifest() -> Dict[str, Any]:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def emit(kind: str, **fields: Any) -> None:
    """One earlier line of standard output: a JSON object."""
    print(json.dumps({"line": kind, **fields}), flush=True)


def percentile(values: List[float], q: float) -> float:
    """Linear interpolation between closest ranks (numpy's default)."""
    v = sorted(values)
    if not v:
        raise ValueError("percentile of nothing")
    k = (len(v) - 1) * q / 100.0
    lo = int(k)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (k - lo)


class Phases:
    """Set-up phases with their seconds, on one clock from process start."""

    def __init__(self, t0: float) -> None:
        self.t0 = t0
        self.rows: List[List] = []
        self._last = t0

    def mark(self, name: str) -> None:
        now = time.monotonic()
        self.rows.append([name, round(now - self._last, 3)])
        self._last = now

    def since_start(self) -> float:
        return time.monotonic() - self.t0


class CompileMonitor:
    """Compilations, cache hits and misses as ``jax.monitoring`` reports
    them; ``fence()`` starts the count of those INSIDE the window."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"
    MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self) -> None:
        import jax.monitoring as monitoring

        self.compiles = 0
        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0
        self._fence: Optional[int] = None
        self._unfence: Optional[int] = None
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **_kw: Any) -> None:
        if event == self.COMPILE:
            self.compiles += 1
            self.compile_s += secs

    def _on_event(self, event: str, **_kw: Any) -> None:
        if event == self.HIT:
            self.hits += 1
        elif event == self.MISS:
            self.misses += 1

    def fence(self) -> None:
        self._fence = self.compiles

    def unfence(self) -> None:
        self._unfence = self.compiles

    @property
    def in_window(self) -> int:
        if self._fence is None:
            return 0
        end = self.compiles if self._unfence is None else self._unfence
        return end - self._fence

    def report(self) -> Dict[str, Any]:
        return {"compilations": self.compiles,
                "compile_seconds": round(self.compile_s, 3),
                "cache_hits": self.hits, "cache_misses": self.misses,
                "compilations_in_window": self.in_window}


def memory_peak() -> Dict[str, int]:
    """``memory_peak_bytes``: the runtime's own ``peak_bytes_in_use`` on
    the fullest chip, read by the driver once its window has closed and
    before its reference runs (a process's peak never falls again).
    ``peak_bytes_reserved`` goes on the same earlier line for information
    only: a running program's scratch shows there and not in the peak in
    use (PR 27, ViT-B/16 at batch 128: 2.4 GB in use, 9.36 GB reserved)."""
    import jax

    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    in_use = max((int(s.get("peak_bytes_in_use", 0)) for s in stats),
                 default=0)
    reserved = max((int(s.get("peak_bytes_reserved", 0)) for s in stats),
                   default=0)
    return {"memory_peak_bytes": in_use, "peak_bytes_reserved": reserved}


class Tracer:
    """The profiler around a short stretch of the run, host tracing OFF:
    at the default level a two-second trace is 120 MB of futex events, and
    even at level 1 the host events of the ViT cell's 77 MB batches made a
    229 MB trace and stretched the traced epoch from 2.62 s to 4.7 s; at
    level 0 it is 16 MB and 2.62 s (PR 27). A program that adds
    ``TraceAnnotation`` spans will need level 1 back, and a fresh look at
    what that costs."""

    def __init__(self, trace_dir: str) -> None:
        self.trace_dir = trace_dir
        self.t_start: Optional[float] = None
        self.window_s: Optional[float] = None

    def start(self) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.host_tracer_level = 0
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self.t_start = time.monotonic()

    def stop(self) -> None:
        import jax

        self.window_s = time.monotonic() - self.t_start
        jax.profiler.stop_trace()

    @property
    def active(self) -> bool:
        return self.t_start is not None and self.window_s is None


def device_facts() -> Dict[str, Any]:
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def load_peaks(kind: str) -> Dict[str, Any]:
    peaks = load_json("peaks.json")
    if kind not in peaks:
        raise KeyError(f"device kind {kind!r} is not in benchmark/peaks.json"
                       f" (known: {sorted(peaks)}): add it with its source,"
                       " there is no default")
    return peaks[kind]


def load_driver(kind: str) -> Callable:
    return importlib.import_module(f"benchmark.drivers.{kind}").run


def load_reference(cfg: Dict[str, Any]):
    """The plain reference module a configuration names."""
    return importlib.import_module(f"benchmark.reference.{cfg['reference']}")


def cell_metrics(manifest: Dict[str, Any], cell: str, group: str
                 ) -> List[Dict[str, Any]]:
    """The ``end_to_end`` or ``per_layer`` entries that this cell reports:
    those that list it, and those that list no cells at all (for a
    per-layer metric: where the cell reports the metric it moves)."""
    e2e = {m["name"] for m in manifest["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]}
    out = []
    for m in manifest[group]:
        if "workloads" in m:
            if cell in m["workloads"]:
                out.append(m)
        elif group == "end_to_end" or m["moves"] in e2e:
            out.append(m)
    return out


def read_per_layer(names: List[str], run: Dict[str, Any]
                   ) -> Dict[str, Dict[str, Any]]:
    """Each metric through the reader its file names. A reader that finds
    nothing to read returns ``None`` and the metric is left out."""
    out: Dict[str, Dict[str, Any]] = {}
    for name in names:
        spec = load_json("metrics", f"{name}.json")
        reader = importlib.import_module(
            f"benchmark.readers.{spec['reader']}").read
        value = reader(spec, run)
        if value is not None:
            out[name] = {"value": float(value), "unit": spec["unit"]}
    return out


def print_checks(checks: List[Dict[str, Any]]) -> None:
    """Each number compared beside its limit, as the last lines of
    standard error."""
    sys.stderr.flush()
    for c in checks:
        print(f"check {c['name']}: {c['value']!r} (limit {c['limit']!r}, "
              f"{'ok' if c['ok'] else 'FAILED'})", file=sys.stderr)
    sys.stderr.flush()
