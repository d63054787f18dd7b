#!/usr/bin/env python3
"""Find the knee of an open-loop cell: run it at each of several fixed
rates and report, per rate, the p95 latency from the due time, the growth
of the backlog over the second half of the window, and the generator's lag.

    python bench/sweep.py --workload <cell> --rates 60,90,120 --seconds 10

The knee is the highest rate whose backlog does not grow; the cell's
traffic file then fixes its rate at about four fifths of it. One process,
one chip; the sweep's numbers are not metrics.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import run as bench_run  # noqa: E402
from bench.harness import spec  # noqa: E402
from bench.harness.stats import percentile  # noqa: E402


def backlog(due: np.ndarray, done: np.ndarray, t: float) -> int:
    """Requests due by ``t`` and not finished by then."""
    return int(np.sum(due <= t) - np.sum(done <= t))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    base = spec.load_cell(args.workload)
    for rate in (float(r) for r in args.rates.split(",")):
        cell = dataclasses.replace(base, traffic={**base.traffic, "rate_per_s": rate})
        rec = {}

        def keep(drive):
            def wrapped(ctx):
                rec.update(drive(ctx))
                return rec
            return wrapped

        run_args = argparse.Namespace(workload=args.workload, seed=args.seed,
                                      seconds=args.seconds, trace=0)
        result = bench_run.run_cell(run_args, driver_hook=keep, cell=cell)
        due, lat = rec["due_s"], rec["latency_s"]
        done = due + lat
        half, end = args.seconds / 2, args.seconds
        print(json.dumps({
            "rate_per_s": rate, "requests": int(due.size),
            "query_p95_ms": 1e3 * percentile(lat, 95),
            "backlog_growth": backlog(due, done, end) - backlog(due, done, half),
            "generator_lag_p95_ms": 1e3 * percentile(rec["generator_lag_s"], 95),
            "correct": result["correct"], "failed": result["failed"],
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
