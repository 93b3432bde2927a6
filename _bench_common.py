"""Shared harness for the deadline-bounded benches (bench.py,
bench_extra.py).

The pattern both use: a parent that never imports jax owns the clock;
accelerator work runs in a child that appends one JSON record per
completed stage to a scratch file (fsynced, parsed per-line so a
mid-write kill can't discard finished stages); if the accelerator child
produced no useful records, a CPU-pinned rerun spends the remaining
budget so the driver always gets a labeled number.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from typing import Callable, List, Optional


def record(out_path: str, rec: dict) -> None:
    """Append one stage record; fsync so the parent sees it even if the
    child is killed right after."""
    with open(out_path, "a") as f:
        f.write(json.dumps(rec) + "\n")
        f.flush()
        os.fsync(f.fileno())


def read_records(out_path: str) -> List[dict]:
    """Per-line parse: a partial trailing line (child killed mid-write)
    must not discard completed, fsynced records before it."""
    records: List[dict] = []
    try:
        with open(out_path) as f:
            for line in f:
                try:
                    records.append(json.loads(line))
                except ValueError:
                    continue
    except OSError:
        pass
    return records


def run_child(script: str, out_path: str, budget: float,
              env: dict, extra_args: Optional[List[str]] = None
              ) -> "subprocess.Popen":
    """Run ``script --child out_path <child_budget> [extra]`` with a hard
    wall-clock timeout; the child's own soft budget is a bit shorter so
    it can skip late stages instead of being killed mid-stage. An
    overdue child is killed and reaped."""
    args = [sys.executable, os.path.abspath(script), "--child", out_path,
            str(max(10.0, budget - 15.0))] + list(extra_args or ())
    proc = subprocess.Popen(args, env=env, stdout=subprocess.DEVNULL,
                            stderr=subprocess.DEVNULL)
    try:
        proc.wait(timeout=budget)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait()
    return proc


def run_with_cpu_fallback(script: str, out_path: str, deadline: float,
                          now: Callable[[], float], t0: float,
                          fallback_reserve: float,
                          need_rerun: Callable[[List[dict]], bool],
                          extra_args: Optional[List[str]] = None,
                          ) -> tuple:
    """Accelerator child first, CPU-pinned rerun if it produced nothing
    useful. Returns (records, fallback_used). The CPU rerun writes to
    its own file; records merge from both."""
    cpu_path = out_path + ".cpu"
    for p in (out_path, cpu_path):
        try:
            os.unlink(p)
        except OSError:
            pass
    run_child(script, out_path, max(30.0, deadline - fallback_reserve),
              dict(os.environ), extra_args)
    records = read_records(out_path)
    fallback_used = False
    if need_rerun(records):
        left = deadline - (now() - t0) - 5.0
        if left > 20:
            fallback_used = True
            env = dict(os.environ)
            env["JAX_PLATFORMS"] = "cpu"
            run_child(script, cpu_path, left, env, extra_args)
            records = records + read_records(cpu_path)
    for p in (out_path, cpu_path):
        try:
            os.unlink(p)
        except OSError:
            pass
    return records, fallback_used


def collect_errors(records: List[dict], limit: int = 3) -> List[str]:
    """Error strings the child fsynced (child_error / *_error stages)."""
    return [str(r.get("error", r.get("stage")))[:200] for r in records
            if "error" in r or str(r.get("stage", "")).endswith("_error")
            ][:limit]
