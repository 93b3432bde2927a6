#!/usr/bin/env python3
"""The benchmark's one command.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, on the machine it is started on. It finds the cell in
``BENCHMARK.json``, the cell's configuration, traffic mix and per-layer
metrics as files under ``benchmark/`` by their names, sets up (weights from
the seed, every shape warmed — all of it ``setup_s``), measures for
``--seconds``, compares what the timed path produced with the plain
reference, and prints one JSON object a line: earlier lines for set-up
phases, the compile cache and counters, and LAST the result
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` also ``breakdown``, and ``checks``: each number compared
beside its limit).

Without a TPU (or with fewer chips than the cell asks for) it prints no
result and exits 3. ``--rehearse`` walks the same code at the tiny
configurations on whatever backend there is and always says
``"correct": false``: a CPU's number never passes for a chip's.
``--control <precision>`` puts the control in the program's place in the
comparison, through the same checks: such a run has to say ``false`` too.
"""

from __future__ import annotations

import time

T0 = time.monotonic()  # process start, near enough: before any import

import argparse
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default="",
                    help="directory to copy the trace's .xplane.pb into, "
                    "for looking at one by hand")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny configurations, any backend, never correct")
    ap.add_argument("--control", default="",
                    help="put the CONTROL in the program's place in the "
                    "comparison: the reference computed in this lower "
                    "precision (int8, fp8, bfloat16). The run is otherwise "
                    "the same and has to come out \"correct\": false")
    args = ap.parse_args(argv)

    from benchmark import harness

    manifest = harness.load_manifest()
    cells = {w["name"]: w for w in manifest["workloads"]}
    if args.rehearse:
        cells = harness.REHEARSAL_CELLS
    if args.workload not in cells:
        print(f"unknown workload {args.workload!r}; known: {sorted(cells)}",
              file=sys.stderr)
        return 2
    cell = cells[args.workload]
    config = harness.load_json("configs", f"{cell['config']}.json")
    traffic = harness.load_json("traffic", f"{cell['traffic']}.json")

    # the compile cache, where every process of the program keeps it:
    # $JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache
    from rafiki_tpu.utils.platform import apply_platform_env, \
        compile_cache_path

    apply_platform_env()
    import jax

    facts = harness.device_facts()
    if not args.rehearse and (facts["platform"] != "tpu"
                              or facts["count"] < int(cell["chips"])):
        print(f"this cell needs {cell['chips']} TPU chip(s); jax found "
              f"{facts}: no result", file=sys.stderr)
        return 3
    peaks = None if args.rehearse else harness.load_peaks(facts["kind"])

    phases = harness.Phases(T0)
    monitor = harness.CompileMonitor()
    work_dir = os.path.join(ROOT, ".bench_work", args.workload)
    shutil.rmtree(work_dir, ignore_errors=True)
    os.makedirs(work_dir)
    tracer = harness.Tracer(os.path.join(work_dir, "trace")) \
        if args.trace else None
    phases.mark("imports_and_device")
    harness.emit("start", workload=args.workload, seed=args.seed,
                 seconds=args.seconds, trace=args.trace,
                 rehearse=args.rehearse, device=facts,
                 compile_cache=compile_cache_path(),
                 jax=jax.__version__, control=args.control)

    try:
        run = harness.load_driver(config["kind"])(dict(
            cell=cell, config=config, traffic=traffic, seed=args.seed,
            seconds=args.seconds, rehearse=args.rehearse, phases=phases,
            monitor=monitor, tracer=tracer, work_dir=work_dir,
            peaks=peaks, control=args.control or None))
    finally:
        if tracer is not None and tracer.active:
            tracer.stop()
    mem = run["memory"]  # read by the driver before its reference ran

    harness.emit("setup_phases", phases=phases.rows)
    harness.emit("compile_cache", **monitor.report(),
                 directory=compile_cache_path())
    harness.emit("memory", **mem)
    harness.emit("counters", **run.get("counters", {}))
    harness.emit("window", **run.get("window", {}))

    checks = list(run["checks"])
    checks.append({"name": "compilations_in_window",
                   "value": monitor.in_window, "limit": 0,
                   "ok": monitor.in_window == 0})
    correct = all(c["ok"] for c in checks)
    if args.rehearse:
        checks.append({"name": "platform_is_tpu", "value": facts["platform"],
                       "limit": "tpu", "ok": False})
        correct = False

    device = {**facts, "memory_peak_bytes": mem["memory_peak_bytes"]}
    result = {"correct": bool(correct), "attempted": int(run["attempted"]),
              "failed": int(run["failed"])}
    if args.trace:
        from benchmark.trace_reduce import TraceSummary, find_xplane

        summary = TraceSummary.from_dir(tracer.trace_dir, tracer.window_s)
        if args.keep_trace:
            os.makedirs(args.keep_trace, exist_ok=True)
            shutil.copy(find_xplane(tracer.trace_dir), args.keep_trace)
        run.update(trace=summary, peaks=peaks, config=config,
                   chips=int(cell["chips"]))
        if args.rehearse:  # no cell of the manifest: every metric of the kind
            names = sorted(
                f[:-5] for f in os.listdir(os.path.join(
                    harness.HERE, "metrics"))
                if config["kind"] in harness.load_json(
                    "metrics", f).get("kinds", []))
        else:
            names = [m["name"] for m in harness.cell_metrics(
                manifest, cell["name"], "per_layer")]
        result["metrics"] = harness.read_per_layer(names, run)
        device.update(busy_s=summary.busy_s(), window_s=summary.window_s)
        result["device"] = device
        if summary.lines:
            result["breakdown"] = summary.breakdown()
        result["end_to_end_traced"] = run["end_to_end"]
    else:
        units = {m["name"]: m["unit"] for m in manifest["end_to_end"]}
        result["metrics"] = {
            k: {"value": float(v), "unit": units.get(k, "")}
            for k, v in run["end_to_end"].items()}
        result["device"] = device
    if args.control:
        result["control"] = args.control
    result["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                        for c in checks}
    shutil.rmtree(work_dir, ignore_errors=True)
    harness.print_checks(checks)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
