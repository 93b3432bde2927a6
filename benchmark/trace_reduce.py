"""From a profiler trace (``*.xplane.pb``) to numbers.

What one TPU trace looks like (read by hand, PR 27): each chip is a plane
``/device:TPU:<n>`` with the lines ``Steps``, ``XLA Modules`` (one event a
program execution, named ``jit_<fn>(<fingerprint>)``), ``XLA Ops`` (one
event an HLO instruction, named by the instruction's text, e.g.
``%fusion.12 = bf16[...] fusion(...)``; a Pallas kernel is a
``custom-call`` whose text carries ``tpu_custom_call``) and ``Async XLA
Ops`` (copies and slices that overlap compute — not counted as busy
twice). Times are nanoseconds on the device's clock.

Everything here works on plain ``(name, start_ns, duration_ns)`` tuples so
the arithmetic can be tested without a trace file.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Sequence, Tuple

Event = Tuple[str, float, float]  # name, start_ns, duration_ns

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
#: ops whose event spans the events of a body: a scan is ONE ``while`` event
#: over all its steps' ops (PR 27: summed durations 4.2 s, union 2.8 s)
CONTAINERS = ("while", "conditional", "call")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return files[-1]


def load_device_lines(path: str) -> Dict[int, Dict[str, List[Event]]]:
    """``{chip: {line name: [events]}}`` for every TPU plane of the file."""
    from jax.profiler import ProfileData

    out: Dict[int, Dict[str, List[Event]]] = {}
    for plane in ProfileData.from_file(path).planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        lines = {}
        for line in plane.lines:
            if line.name in (OPS_LINE, MODULES_LINE):
                lines[line.name] = [
                    (e.name, float(e.start_ns), float(e.duration_ns))
                    for e in line.events]
        out[int(m.group(1))] = lines
    return out


def busy_union_ns(events: Iterable[Event]) -> float:
    """Length of the union of the events' intervals."""
    spans = sorted((s, s + d) for _, s, d in events if d > 0)
    total, cur_lo, cur_hi = 0.0, None, None
    for lo, hi in spans:
        if cur_hi is None or lo > cur_hi:
            if cur_hi is not None:
                total += cur_hi - cur_lo
            cur_lo, cur_hi = lo, hi
        else:
            cur_hi = max(cur_hi, hi)
    if cur_hi is not None:
        total += cur_hi - cur_lo
    return total


def op_kind(name: str) -> str:
    """A short stable name for an HLO instruction's text:
    ``%fusion.12 = ...`` -> ``fusion``; a Pallas kernel ->
    ``custom-call:tpu_custom_call``."""
    m = re.match(r"^%?([A-Za-z_\-]+?)(?:[._]\d+)*(?:\s*=|$)", name.strip())
    kind = m.group(1) if m else name.split(" ")[0][:40]
    if kind.startswith("custom-call") or " custom-call(" in name:
        return "custom-call:tpu_custom_call" if "tpu_custom_call" in name \
            else "custom-call"
    return kind


def attribute_ops(ops: Sequence[Event], modules: Sequence[Event]
                  ) -> List[Tuple[str, Event]]:
    """Each op with the name of the module execution it ran inside
    (``jit_step_fn`` without the fingerprint), by one merge pass."""
    mods = sorted(modules, key=lambda e: e[1])
    out: List[Tuple[str, Event]] = []
    j = 0
    for ev in sorted(ops, key=lambda e: e[1]):
        while j < len(mods) and mods[j][1] + mods[j][2] < ev[1]:
            j += 1
        owner = ""
        if j < len(mods) and mods[j][1] <= ev[1] <= mods[j][1] + mods[j][2]:
            owner = mods[j][0].split("(")[0]
        out.append((owner, ev))
    return out


def kernel_time_ns(ops: Sequence[Event], modules: Sequence[Event],
                   module_pattern: str, op_pattern: str
                   ) -> Tuple[float, int]:
    """Summed device time and count of the ops whose text matches
    ``op_pattern`` inside executions of modules matching
    ``module_pattern``."""
    mre, ore = re.compile(module_pattern), re.compile(op_pattern)
    total, n = 0.0, 0
    for owner, (name, _s, d) in attribute_ops(ops, modules):
        if mre.search(owner) and ore.search(name):
            total += d
            n += 1
    return total, n


def module_calls(modules: Sequence[Event], module_pattern: str
                 ) -> List[Event]:
    mre = re.compile(module_pattern)
    return [e for e in modules if mre.search(e[0].split("(")[0])]


def top_device_ops(ops: Sequence[Event], modules: Sequence[Event],
                   n: int = 10) -> List[List]:
    """The ``n`` (module, op kind) groups with most device time, as
    ``[name, seconds]``."""
    agg: Dict[str, float] = {}
    for owner, (name, _s, d) in attribute_ops(ops, modules):
        if op_kind(name) in CONTAINERS:
            continue  # its body's ops are events of their own
        key = f"{owner or '?'}/{op_kind(name)}"
        agg[key] = agg.get(key, 0.0) + d
    rows = sorted(agg.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in rows]


def longest_gaps(ops: Sequence[Event], modules: Sequence[Event],
                 n: int = 10) -> List[List]:
    """The idle time between program executions, grouped by which program
    ran NEXT (what the host was getting ready), as ``[name, seconds]``.
    Host spans would name the host's activity; the program records none
    yet, so the next program's name is the best label there is."""
    mods = sorted(modules, key=lambda e: e[1])
    agg: Dict[str, float] = {}
    for prev, nxt in zip(mods, mods[1:]):
        gap = nxt[1] - (prev[1] + prev[2])
        if gap > 0:
            key = f"before:{nxt[0].split('(')[0]}"
            agg[key] = agg.get(key, 0.0) + gap
    rows = sorted(agg.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in rows]


class TraceSummary:
    """What the readers under ``benchmark/readers/`` get to look at."""

    def __init__(self, lines: Dict[int, Dict[str, List[Event]]],
                 window_s: float) -> None:
        self.lines = lines
        self.window_s = float(window_s)

    @classmethod
    def from_dir(cls, trace_dir: str, window_s: float) -> "TraceSummary":
        return cls(load_device_lines(find_xplane(trace_dir)), window_s)

    @property
    def chips(self) -> List[int]:
        return sorted(self.lines)

    def ops(self, chip: int) -> List[Event]:
        return self.lines[chip].get(OPS_LINE, [])

    def modules(self, chip: int) -> List[Event]:
        return self.lines[chip].get(MODULES_LINE, [])

    def busy_s(self) -> float:
        """Seconds with an operation on the device, averaged over chips."""
        if not self.lines:
            return 0.0
        return sum(busy_union_ns(self.ops(c)) for c in self.chips) \
            / 1e9 / len(self.chips)

    def breakdown(self) -> Dict[str, List[List]]:
        c = self.chips[0]
        return {"device_ops": top_device_ops(self.ops(c), self.modules(c)),
                "idle_gaps": longest_gaps(self.ops(c), self.modules(c))}
