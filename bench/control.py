#!/usr/bin/env python3
"""Readings of the control and of the planted faults, on the chip.

    python bench/control.py --workload <cell> --seeds 1,2,3 --seconds 5 \\
        --faults none,control,frozen,half,altered_draw

Runs the cell once per (fault, seed) in this one process, each with the
timed path broken as ``bench/harness/faults.py`` describes (``none``
leaves it sound), and prints one JSON line per run with the numbers
compared for ``correct``. These readings set the limits in the
configuration files (PERF.md gives them); the benchmark's own runs never
plant a fault.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import run as bench_run  # noqa: E402
from bench.harness import faults, spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--faults", default="none,control")
    ap.add_argument("--rehearse", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    kind = cell.traffic["kind"]
    for fault in args.faults.split(","):
        for seed in args.seeds.split(","):
            run_args = argparse.Namespace(workload=args.workload, seed=int(seed),
                                          seconds=args.seconds, trace=0)

            def hook(drive, fault=None if fault == "none" else fault):
                def broken(ctx):
                    with faults.planted(fault, cell.model, ctx.config, kind):
                        return drive(ctx)
                return broken

            try:
                result = bench_run.run_cell(run_args, rehearse=args.rehearse,
                                            driver_hook=hook)
                out = {"correct": result["correct"], "checks": result["checks"]}
            except Exception as e:  # noqa: BLE001 — a crash is a failed run
                out = {"correct": False, "error": f"{type(e).__name__}: {e}"}
            print(json.dumps({"workload": args.workload, "fault": fault,
                              "seed": int(seed), **out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
