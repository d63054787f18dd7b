#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip, and print one JSON line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's configuration, traffic mix, model reference and metric readers
are found by the names in ``BENCHMARK.json`` (see ``bench/harness/spec.py``).
The run loads and warms up (``setup_s``), measures for ``--seconds``,
then checks what the timed path produced against the plain reference.
With ``--trace 0`` it reports the cell's end-to-end metrics; with
``--trace 1`` it profiles the window and reports the per-layer metrics,
the device's busy and window time, and a breakdown.

The last line of stdout is the result; the numbers compared for
``correct`` are the last lines of stderr and the last key of the result.
Without a TPU (or with fewer chips than the cell asks for) it exits 2 and
prints no result. ``--rehearse`` is for the tests under ``bench/tests``
only: it runs the cell at the configuration's tiny ``rehearsal`` sizes on
the CPU and reports its numbers under ``rehearsal``, never as metrics.
"""
from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH_DIR.parent / "src"))
sys.path.insert(0, str(BENCH_DIR.parent))

from bench.harness import refresh, serve, spec  # noqa: E402
from bench.harness.context import TRACE_DIR, RunContext  # noqa: E402

DRIVERS = {"refresh": refresh.run, "open_loop": serve.run}
#: Host spans that label the device's idle gaps, most specific first.
GAP_LABELS = ("bench", "query", "refresh", "generator")


def _merge(base: dict, over: dict) -> dict:
    out = copy.deepcopy(base)
    for k, v in over.items():
        out[k] = _merge(out[k], v) if isinstance(v, dict) and isinstance(out.get(k), dict) else v
    return out


def _number(v: float):
    """JSON has no infinity; an infinite reading prints as a string."""
    return v if math.isfinite(v) else str(v)


def run_cell(args, *, rehearse: bool = False, driver_hook=None, cell=None) -> dict:
    """One run of one cell; returns the result object. ``driver_hook``
    wraps the traffic driver (a test breaks the timed path with it);
    ``cell`` replaces the cell that ``args.workload`` names (the sweep
    varies the rate with it)."""
    import jax

    from bench.harness import device

    cell = cell or spec.load_cell(args.workload)
    devs = jax.devices()
    want = "cpu" if rehearse else "tpu"
    if devs[0].platform != want or len(devs) < cell.chips:
        raise SystemExit(f"bench: cell {cell.name} needs {cell.chips} {want} "
                         f"device(s); JAX found {len(devs)} {devs[0].platform}")
    from repro import compile_cache

    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    config = cell.config
    if rehearse:
        config = _merge(config, config["rehearsal"])
    # The precision in which the configuration asks JAX to run float32
    # matrix products (see PERF.md, Open questions: at the chip's default,
    # the refresh kernel computes its products in bfloat16).
    jax.config.update("jax_default_matmul_precision", config["matmul_precision"])
    ctx = RunContext(cell=cell, seed=args.seed, seconds=float(args.seconds),
                     trace=bool(args.trace), t_process=T_PROCESS, config=config)
    drive = DRIVERS[cell.traffic["kind"]]
    if driver_hook is not None:
        drive = driver_hook(drive)
    rec = drive(ctx)
    dev = device.describe(cell.chips)
    dev["memory_peak_bytes"] = rec["peak_bytes"]
    rec["device_kind"] = dev["kind"]
    rec["config"] = config
    if ctx.trace and not rehearse:
        from bench.harness import trace as trace_mod

        kernels = {m.name: m.reader.KERNELS for m in cell.per_layer
                   if hasattr(m.reader, "KERNELS")}
        planes = trace_mod.load_planes(str(TRACE_DIR))
        rec["trace"] = trace_mod.reduce_trace(planes, GAP_LABELS, kernels)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        dev["busy_s"] = rec["trace"]["busy_s"]
        dev["window_s"] = rec["trace"]["window_s"]

    metrics = {}
    for m in (cell.per_layer if ctx.trace else cell.end_to_end):
        value = m.reader.read(rec)
        if value is not None:
            metrics[m.name] = {"value": _number(float(value)), "unit": m.entry["unit"]}
    limits = config["limits"][cell.traffic["kind"]]
    checks = {name: {"value": _number(float(v)), "limit": limits[name]}
              for name, v in rec["checks"].items()}
    correct = all(math.isfinite(v) and v <= limits[name]
                  for name, v in rec["checks"].items())
    result = {"correct": correct, "attempted": rec["attempted"],
              "failed": rec["failed"]}
    if rehearse:
        result["metrics"] = {}
        result["rehearsal"] = {f"cpu_rehearsal.{k}": v["value"] for k, v in metrics.items()}
    else:
        result["metrics"] = metrics
    result["device"] = dev
    if ctx.trace and not rehearse:
        result["breakdown"] = {"device_ops": rec["trace"]["device_ops"],
                               "idle_gaps": rec["trace"]["idle_gaps"]}
    result["checks"] = checks
    if "diagnostics" in rec:
        print(f"diagnostics {json.dumps(rec['diagnostics'])}", file=sys.stderr)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args, rehearse=args.rehearse)
    except SystemExit as e:
        print(e, file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']} (limit {c['limit']})", file=sys.stderr)
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
