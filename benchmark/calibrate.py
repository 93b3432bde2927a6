#!/usr/bin/env python3
"""Read, on the chip, what a cell's limits are set from (PERF.md §2 gives
the readings and the limits): for each seed the program's numbers against
the plain reference, and with ``--control`` also the control's — the
reference computed in the nearest precision below the configuration's — and
the faults a training cell can have. One process for all seeds, because
set-up is most of a run. The benchmark's own runs never call this.

    python benchmark/calibrate.py --workload <cell> --seeds 1,2,3 \\
        --seconds 8 [--control int8] [--rehearse]
"""

from __future__ import annotations

import argparse
import importlib
import os
import shutil
import sys
import time

T0 = time.monotonic()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--control", default="")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    from benchmark import harness

    cells = harness.REHEARSAL_CELLS if args.rehearse else {
        w["name"]: w for w in harness.load_manifest()["workloads"]}
    cell = cells[args.workload]
    config = harness.load_json("configs", f"{cell['config']}.json")
    traffic = harness.load_json("traffic", f"{cell['traffic']}.json")
    from rafiki_tpu.utils.platform import apply_platform_env

    apply_platform_env()
    facts = harness.device_facts()
    if not args.rehearse and facts["platform"] != "tpu":
        print(f"needs a TPU; jax found {facts}", file=sys.stderr)
        return 3
    work_dir = os.path.join(ROOT, ".bench_work", "calibrate")
    shutil.rmtree(work_dir, ignore_errors=True)
    os.makedirs(work_dir)
    harness.emit("start", workload=args.workload, device=facts,
                 control=args.control)
    driver = importlib.import_module(f"benchmark.drivers.{config['kind']}")
    driver.calibrate(dict(
        cell=cell, config=config, traffic=traffic, seconds=args.seconds,
        rehearse=args.rehearse, phases=harness.Phases(T0),
        monitor=harness.CompileMonitor(), work_dir=work_dir),
        [int(s) for s in args.seeds.split(",")], args.control)
    shutil.rmtree(work_dir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
